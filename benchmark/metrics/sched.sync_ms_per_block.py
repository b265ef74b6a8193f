"""What a decode block pays for sync points, in ms: the time the window's
sync points took (the ``sync-point`` stage histogram, after - before: each
bucket's count times the bucket's geometric middle, good to one bucket, 3 %)
over ALL the window's block boundaries (``breakdown.generation.<unit>.
block_boundaries``, after - before: chained, sync by cause, idle).  A program
without the stage (one from before ISSUE 40) gives None."""
import math

import metriclib as ml


def _boundaries(snapshot: dict) -> int | None:
    units = (snapshot.get("breakdown") or {}).get("generation") or {}
    for unit in units.values():
        found = unit.get("block_boundaries") if isinstance(unit, dict) else None
        if found:
            return sum(
                sum(int(n) for n in v.values()) if isinstance(v, dict) else int(v)
                for v in found.values()
            )
    return None


def read(run):
    delta = ml.stage(run, "sync-point")
    after = _boundaries(run.after)
    if after is None:
        return None
    if delta is None:
        # the stage is there and the window met no sync point, or no stage
        return 0.0 if "sync-point" in run.after.get("stage_hist", {}) else None
    blocks = after - (_boundaries(run.before) or 0)
    if blocks <= 0:
        return None
    edges = run.stats.BUCKET_EDGES
    seconds = sum(
        n * (edges[0] if i == 0 else edges[-1] if i >= len(edges)
             else math.sqrt(edges[i - 1] * edges[i]))
        for i, n in enumerate(delta) if n
    )
    return seconds * 1e3 / blocks
