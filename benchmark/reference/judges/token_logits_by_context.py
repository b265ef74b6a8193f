"""The judge of a decoder whose attention selects keys by content past a
context of ``topk`` (learned sparse attention).  Three sets of rows:

* the served tokens at positions where every key is still attended
  (``_dense``: all that the harness's own probes reach), held as
  ``token_logits`` holds them: ``logit_margin``, ``argmax_agree_min``;
* the served tokens at positions where the model selects (``_selecting``:
  ``long_probe.py``, by hand): ``selecting_argmax_agree_min`` alone — with
  random weights the logits at the end of the layers are ill-conditioned in
  the keys selected (the configuration's ``reference.why``), so the widest
  gap of a logit is held by no limit;
* the selection itself, part by part on the program's own inputs (the
  kind's ``mechanism``; ``selection_rows``): keys of the reference's set
  the program left out (``selection_swaps_limit``: a count, of one row),
  and the relative errors of the projections, the attention and the decode
  read (``*_rel_err_limit``).

A kind gives ``positions``, ``logit_deficit_max`` and ``argmax_agree_share``
under the suffixes ``_dense`` and ``_selecting``; a set with no position is
not judged, and the selection's rows are judged wherever the configuration
states ``selection_swaps_limit``."""

import frame

SELECTION = ("selection_swaps", "projection_rel_err", "attention_rel_err",
             "decode_read_rel_err")


def selection_rows(found: dict, limits: dict) -> list[tuple]:
    return [
        (name + "_max", found[name + "_max"], "<=", limits[name + "_limit"])
        for name in SELECTION
    ]


def compared(found: dict, limits: dict) -> list[tuple]:
    """(number, what was found, "<=" or ">=", its limit), one row each."""
    rows = []
    if found.get("positions_dense", 0) > 0:
        rows += [
            ("logit_deficit_max_dense", found["logit_deficit_max_dense"],
             "<=", limits["logit_margin"]),
            ("argmax_agree_share_dense", found["argmax_agree_share_dense"],
             ">=", limits["argmax_agree_min"]),
        ]
    if found.get("positions_selecting", 0) > 0:
        rows.append(
            ("argmax_agree_share_selecting", found["argmax_agree_share_selecting"],
             ">=", limits["selecting_argmax_agree_min"])
        )
    if "selection_swaps_limit" in limits:
        rows += selection_rows(found, limits)
    return rows


def judge(found: dict, limits: dict) -> bool:
    rows = compared(found, limits)
    return bool(rows) and frame.all_hold(rows)
