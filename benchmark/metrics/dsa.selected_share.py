"""How much of the context the traffic lets the selection leave unread:
100 x keys attended / keys scored over the window's decode steps, from the
program's counters (``dsa.keys_selected`` / ``dsa.keys_scored``, summed on
the device over layers, slots and steps).  100 means the mechanism was idle
(every context at or under ``index_topk``); a program without the counters
gives nothing."""
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("dsa.keys_scored", 0) <= 0:
        return None
    return 100.0 * d["dsa.keys_selected"] / d["dsa.keys_scored"]
