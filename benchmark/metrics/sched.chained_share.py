"""The share of the window's decode blocks that were dispatched from the
device carry (``step_k_continue``: no host turn-round before them), in %.
From the scheduler's count of block boundaries by outcome, ``/stats/summary``
after, less before (``breakdown.generation.<unit>.block_boundaries``): every
block ends in one boundary, which chained the next block (``chained_*``: when
this one was dispatched, when it was about to end, or with its tokens in
hand), went to a sync point for a cause, or dispatched nothing.  A program without the
counter (one from before ISSUE 31) gives None."""


def _boundaries(snapshot: dict) -> dict | None:
    units = (snapshot.get("breakdown") or {}).get("generation") or {}
    for unit in units.values():
        found = unit.get("block_boundaries") if isinstance(unit, dict) else None
        if found:
            return found
    return None


def _counts(found: dict | None) -> tuple[int, int]:
    """(chained, all boundaries)."""
    if not found:
        return 0, 0
    chained = sum(int(n) for k, n in found.items() if k.startswith("chained_"))
    rest = int(found.get("idle", 0)) + sum(int(n) for n in (found.get("sync") or {}).values())
    return chained, chained + rest


def read(run):
    after = _boundaries(run.after)
    if after is None:
        return None
    chained, total = (a - b for a, b in zip(_counts(after), _counts(_boundaries(run.before))))
    return 100.0 * chained / total if total > 0 else None
