"""The share of a decode step's token-layers that chose the router's no-op
and skipped the expert sublayer (mixture of depths): 100 x
``moe.tokens_skipped`` / ``moe.pairs_routed`` over the window's decode steps,
from the program's counters (top-1: a routed pair is a live token on a
block; the no-op's are among the routed and not among the held).  One
choice in 17 is 5.9 % on an even router.  A program without the counters
gives nothing."""
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("moe.pairs_routed", 0) <= 0 or "moe.tokens_skipped" not in d:
        return None
    return 100.0 * d["moe.tokens_skipped"] / d["moe.pairs_routed"]
