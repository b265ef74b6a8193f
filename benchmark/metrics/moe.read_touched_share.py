"""The share of the expert weights a decode step streamed that some token
needed: 100 x experts touched / experts read over the window's decode steps,
from the program's counters (``moe.experts_touched`` / ``moe.experts_read``,
summed on the device over layers and steps).  100 means the step read only
the experts its tokens chose (the touched-only kernel engaged in every
step); a step that runs every held expert densely reads held x layers x
steps, and the share is what the routing happened to touch.  A program
without the counter (one from before ISSUE 39) gives nothing."""
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("moe.experts_read", 0) <= 0:
        return None
    return 100.0 * d["moe.experts_touched"] / d["moe.experts_read"]
