"""The share of the rows the window's prefill programs ran that were padding,
in %: 100 x (padded - real) / padded.  From the model's count of prefill rows,
``/stats/summary`` after, less before
(``breakdown.generation.<unit>.prefill_rows``): ``real`` is the tokens of the
prompts and suffixes prefilled, ``padded`` the sum of the ladder's rungs they
ran in (``by_rung`` says which).  A prompt is padded to the next rung of the
ladder, so this is what the ladder's spacing costs on this traffic.  A program
without the counter (one from before ISSUE 36) gives None."""


def _rows(snapshot: dict) -> dict | None:
    units = (snapshot.get("breakdown") or {}).get("generation") or {}
    for unit in units.values():
        found = unit.get("prefill_rows") if isinstance(unit, dict) else None
        if found:
            return found
    return None


def read(run):
    after = _rows(run.after)
    if after is None:
        return None
    before = _rows(run.before) or {}
    real, padded = (int(after.get(k, 0)) - int(before.get(k, 0)) for k in ("real", "padded"))
    return 100.0 * (padded - real) / padded if padded > 0 else None
