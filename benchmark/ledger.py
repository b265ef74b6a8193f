"""What the ``ledger.*`` readers share: the generation scheduler's own
ledger of the device's time (``/stats/summary`` after the window,
``breakdown.generation.<unit>.device``; ``seldon_core_tpu/obs/device.py``):
busy seconds by kind, decode steps and idle seconds by the part of the run
loop the host was in, a row a second on ``time.perf_counter`` — the clock of
``run.w0`` and ``run.w1`` too, one ``CLOCK_MONOTONIC`` for both processes.
An interval is shared over the seconds it covers by overlap, so a second's
``busy_decode_s / decode_steps`` is the step time of the blocks that ran in
it, whichever edge cut them.  A program without the ledger gives None, and
every reader then gives None."""

from __future__ import annotations

import statistics

PARK = "idle-park"  # idle for want of demand, not for the host's doing


def device(snapshot: dict) -> dict | None:
    units = (snapshot.get("breakdown") or {}).get("generation") or {}
    for unit in units.values():
        found = unit.get("device") if isinstance(unit, dict) else None
        if found:
            return found
    return None


def seconds(run) -> list[dict] | None:
    """The rows of the window, ``run.w0 <= t < run.w1``, by column name; the
    seconds in which a profiler trace ran or was being collected left out."""
    dev = device(run.after)
    if dev is None:
        return None
    rows = (dict(zip(dev["columns"], r)) for r in dev["seconds"])
    return [r for r in rows if run.w0 <= r["t"] < run.w1 and not r["profiler"]]


def _busy(row: dict) -> float:
    return row["busy_decode_s"] + row["busy_prefill_s"] + row["busy_other_s"]


def decode_step_ms(run) -> float | None:
    """The median over the window's seconds of a decode step's device time."""
    rows = seconds(run)
    steps = [1e3 * r["busy_decode_s"] / r["decode_steps"]
             for r in rows or () if r["decode_steps"] >= 1]
    return statistics.median(steps) if steps else None


def prefill_share(run) -> float | None:
    """Prompts' share of the window's busy seconds, in %."""
    rows = seconds(run)
    busy = sum(_busy(r) for r in rows or ())
    return 100.0 * sum(r["busy_prefill_s"] for r in rows) / busy if busy > 0 else None


def idle_share(run) -> float | None:
    """The share of the window's seconds, the parked ones left out, in which
    the device stood idle for the host, in %."""
    rows = seconds(run)
    if not rows:
        return None
    idle = sum(v for r in rows for k, v in r["idle_s"].items() if k != PARK)
    wall = idle + sum(_busy(r) for r in rows)
    return 100.0 * idle / wall if wall > 0 else None


def idle_vs_trace_pts(run) -> float | None:
    """| the ledger's idle share of the stretch the profiler traced - the
    device plane's |, in points: a dispatch site the ledger does not hear of
    shows here."""
    dev, trace = device(run.after), run.trace
    traced = (dev or {}).get("traced")
    if not traced or not trace or not trace.get("window_s") or not traced["wall_s"]:
        return None
    mine = 100.0 * sum(traced["idle_s"].values()) / traced["wall_s"]
    return abs(mine - 100.0 * (1.0 - trace["busy_s"] / trace["window_s"]))
