"""The encoder's judge: every class probability within ``prob_abs_tol`` of
the reference's."""

import frame


def compared(found: dict, limits: dict) -> list[tuple]:
    """(number, what was found, "<=" or ">=", its limit), one row each."""
    return [
        ("prob_abs_err_max", found["prob_abs_err_max"], "<=", limits["prob_abs_tol"]),
    ]


def judge(found: dict, limits: dict) -> bool:
    return frame.all_hold(compared(found, limits))
