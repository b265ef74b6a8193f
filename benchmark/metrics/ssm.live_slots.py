"""How many of the slots a decode step carries: ``ssm.slot_steps`` over
``ssm.steps``, from the program's device counters.  The harness reads them
before the first request and after the last (``moe_counters.delta``), so
this is the average over the lead-in, the window AND the drain: a closed
loop's callers stop at the window's end and the slots empty over the drain,
which pulls the number under the window's own (109 read where the window
holds about 120; ``PERF.md`` §5).  Every live slot is its whole state read and
written.  A program without the counters gives nothing."""
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("ssm.steps", 0) <= 0 or "ssm.slot_steps" not in d:
        return None
    return d["ssm.slot_steps"] / d["ssm.steps"]
