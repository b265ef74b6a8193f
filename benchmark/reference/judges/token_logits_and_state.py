"""The judge of a decoder most of whose layers keep a state a SLOT and not a
row a token (a selective state-space mixer).  Two sets of rows:

* the served tokens, held as ``token_logits`` holds them: ``logit_margin``,
  ``argmax_agree_min``;
* a state-space layer's parts on the program's own inputs at the timed sizes
  (the kind's ``mechanism``: a prompt of ``state_probe_tokens`` real tokens in
  its padded rung, then ``state_probe_steps`` decode steps), judged wherever
  the configuration states ``state_probe_tokens``: the relative errors of the
  projections (the convolution's output, ``B``, ``C``, ``D_t``), of the
  prompt's ``y``, of the STATE after the prompt and after the steps, and of
  the steps' ``y`` (``*_rel_err_limit``)."""

import frame

PARTS = ("projection_rel_err", "scan_rel_err", "state_rel_err", "decode_rel_err")


def compared(found: dict, limits: dict) -> list[tuple]:
    """(number, what was found, "<=" or ">=", its limit), one row each."""
    rows = [
        ("logit_deficit_max", found["logit_deficit_max"], "<=", limits["logit_margin"]),
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
