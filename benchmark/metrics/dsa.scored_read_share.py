"""The share of the index keys a decode step's program read that it had to
score: 100 x keys scored / (index-key blocks read x tokens a block) over the
window's decode steps, from the program's counters (``dsa.keys_scored``: the
keys up to each live slot's position; ``dsa.key_blocks_read``: the pool
blocks of index keys the step's selection read, as it counts them itself
(the kernel its awaited copies, the XLA lines the window they gather); both
summed on the device
over layers, slots and steps) and the graph's ``kv_block_size``.  Near 100
means the selection read only each slot's live blocks (the decode kernel of
ISSUE 42: a context rounds up to its last block); a step that gathers the
whole static window of every slot reads window x slots, and the share is
what the contexts happen to fill of it.  A program without the counter (one
from before ISSUE 42) gives nothing."""
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("dsa.key_blocks_read", 0) <= 0:
        return None
    block = int(run.config["graph"]["parameters"]["kv_block_size"])
    return 100.0 * d["dsa.keys_scored"] / (d["dsa.key_blocks_read"] * block)
