"""Arithmetic the benchmark's numbers rest on: percentiles of a list of
samples, the per-request token gap, and the reading of the engine's
cumulative stage histograms (``/stats/summary`` -> ``stage_hist``).

Kept here, under the benchmark's own directory, so that no later change to
the program can move a metric by changing how it is reduced.
"""

from __future__ import annotations

import math

# The program's histogram grid (obs/history.py::BUCKET_EDGES at the commit
# this benchmark was defined on): 50 us .. 50 s, 40 buckets a decade, 241
# edges, 242 counting slots with the overflow slot.  A copy, on purpose: a
# traced run fails if the engine's vectors have another length, so a change
# to the grid cannot move a per-layer metric in silence.
BUCKET_EDGES: tuple[float, ...] = tuple(
    5e-5 * 10.0 ** (i / 40.0) for i in range(241)
)
HIST_SLOTS = len(BUCKET_EDGES) + 1


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of ``values`` by linear interpolation
    between the two nearest ranks (numpy's default), on a sorted copy."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    rank = (q / 100.0) * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def tpot_s(t_first: float, t_done: float, n_tokens: int) -> float | None:
    """Mean gap between a stream's tokens as its reader feels it:
    (time of the done event - time of the first token) / (tokens - 1).
    Tokens reach the client in blocks, so single gaps are bimodal; the
    per-request mean is what the percentile is taken over."""
    if n_tokens < 2:
        return None
    return (t_done - t_first) / (n_tokens - 1)


class HistogramGridChanged(Exception):
    """The engine's stage histograms are not on the grid this benchmark
    copied."""


def hist_delta(before: dict, after: dict, stage: str) -> list[int] | None:
    """Counts recorded in ``stage`` between two ``stage_hist`` snapshots, or
    None where the stage recorded nothing in between."""
    b, a = before.get(stage), after.get(stage)
    if a is None:
        return None
    if len(a) != HIST_SLOTS or (b is not None and len(b) != HIST_SLOTS):
        raise HistogramGridChanged(
            f"stage_hist[{stage!r}] has {len(a)} slots, the benchmark's copy "
            f"of the grid has {HIST_SLOTS}"
        )
    d = [int(x) - (int(b[i]) if b is not None else 0) for i, x in enumerate(a)]
    if any(x < 0 for x in d):
        raise HistogramGridChanged(f"stage_hist[{stage!r}] went backwards")
    return d if sum(d) else None


def hist_count(delta: list[int] | None) -> int:
    return sum(delta) if delta else 0


def hist_percentile_s(delta: list[int] | None, q: float) -> float | None:
    """Percentile of a bucket-count vector, in seconds: the geometric middle
    of the bucket that holds the rank.  Good to one bucket, about 3 %."""
    if not delta:
        return None
    total = sum(delta)
    rank = q / 100.0 * total
    seen = 0
    for i, c in enumerate(delta):
        seen += c
        if c and seen >= rank:
            if i == 0:
                return BUCKET_EDGES[0]
            if i >= len(BUCKET_EDGES):
                return BUCKET_EDGES[-1]
            return math.sqrt(BUCKET_EDGES[i - 1] * BUCKET_EDGES[i])
    return BUCKET_EDGES[-1]


def union_seconds(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
