"""The judge of a decoder whose expert layer is top-1 behind a router that
may choose no expert at all, and whose every layer keeps a slot's tails
beside its K/V.  Two sets of rows:

* the served tokens, by two numbers that are steady from seed to seed: the
  MEAN of how far each lies under the reference's top logit at its position
  (``logit_deficit_mean_limit``; the widest of 128 is one heavy-tailed draw
  and is printed, not judged) and the share of positions where it IS the top
  (``argmax_agree_min``);
* a block's parts on the program's own inputs at the timed sizes (the kind's
  ``mechanism``: a prompt of ``state_probe_tokens`` real tokens in its padded
  rung, then ``state_probe_steps`` decode steps of one slot from the tails it
  left), judged wherever the configuration states ``state_probe_tokens``: the
  relative errors of the projections (``q``, ``k``, ``v`` as the pool holds
  them, the router's depth state), of the prompt's attention, of the steps'
  read and of the expert sublayer under the program's own choice, the
  absolute error of the router's 17 probabilities, how far under the
  reference's best a served choice may lie where the two differ, the share
  of a block's token-layers where they do, and the ENGINE'S ENTRY FUNCTIONS
  on a cache of the graph's own slots and blocks held to that composition
  (``entry_*``: block 0's worst row, the early blocks' largest median row,
  what the prompt's program hands the first step's, and the bookkeeping of
  the slots that sat still) (``*_limit``)."""

import frame

PARTS = ("projection_rel_err", "attention_rel_err", "decode_read_rel_err",
         "router_prob_abs_err", "choice_deficit", "choice_differs_share",
         "expert_rel_err", "entry_first_block_rel_err",
         "entry_early_blocks_median_rel_err", "entry_handoff_rel_err",
         "entry_bookkeeping_faults")


def compared(found: dict, limits: dict) -> list[tuple]:
    """(number, what was found, "<=" or ">=", its limit), one row each."""
    rows = [
        ("logit_deficit_mean", found["logit_deficit_mean"], "<=",
         limits["logit_deficit_mean_limit"]),
        ("argmax_agree_share", found["argmax_agree_share"], ">=", limits["argmax_agree_min"]),
    ]
    if limits.get("state_probe_tokens"):
        rows += [
            (name + "_max", found[name + "_max"], "<=", limits[name + "_limit"])
            for name in PARTS
        ]
    return rows


def judge(found: dict, limits: dict) -> bool:
    return frame.all_hold(compared(found, limits))
