"""The decoder's judge: every served token within ``logit_margin`` of the
reference's top logit at its position, and the reference's arg-max at
``argmax_agree_min`` of the positions or more."""

import frame


def compared(found: dict, limits: dict) -> list[tuple]:
    """(number, what was found, "<=" or ">=", its limit), one row each."""
    return [
        ("logit_deficit_max", found["logit_deficit_max"], "<=", limits["logit_margin"]),
        ("argmax_agree_share", found["argmax_agree_share"], ">=", limits["argmax_agree_min"]),
    ]


def judge(found: dict, limits: dict) -> bool:
    return frame.all_hold(compared(found, limits))
