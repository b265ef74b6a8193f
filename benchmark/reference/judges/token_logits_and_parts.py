"""The judge of a decoder whose prompt and decode step attend by two
formulations of one mathematics (latent attention: expanded and absorbed).
Two sets of rows:

* the served tokens, held as ``token_logits`` holds them: ``logit_margin``,
  ``argmax_agree_min``;
* a layer's parts on the program's own inputs at a context the probes do not
  reach (the kind's ``mechanism``), judged wherever the configuration states
  ``parts_probe_tokens``: the relative errors of the projections, of the
  prompt's attention and of the decode read (``*_rel_err_limit``)."""

import frame

PARTS = ("projection_rel_err", "attention_rel_err", "decode_read_rel_err")


def compared(found: dict, limits: dict) -> list[tuple]:
    """(number, what was found, "<=" or ">=", its limit), one row each."""
    rows = [
        ("logit_deficit_max", found["logit_deficit_max"], "<=", limits["logit_margin"]),
        ("argmax_agree_share", found["argmax_agree_share"], ">=", limits["argmax_agree_min"]),
    ]
    if limits.get("parts_probe_tokens"):
        rows += [
            (name + "_max", found[name + "_max"], "<=", limits[name + "_limit"])
            for name in PARTS
        ]
    return rows


def judge(found: dict, limits: dict) -> bool:
    return frame.all_hold(compared(found, limits))
