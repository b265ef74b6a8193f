"""How much of what a decode step moves is the K/V it attends: 100 x (1,024 B
a block for every row the live slots' positions say it attends:
``attn.rows_live``) over the step's bytes by the same costs (that, the dense
weights and the head once, 25.17 MB for every expert the step read:
``moe.experts_read``, and the live slots' writes and tails:
``costs_zaya.decode_step_bytes``), from the program's device counters over
the load's decode steps (``zaya.steps``: lead-in, window and drain).  Near the
18 % of the configuration's arithmetic it says the contexts are the cell's;
the compressed latent leaves a row an eighth of what 8 key-value heads
would.  A program without the counters gives nothing."""
import costs_zaya as cz
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d:
        return None
    g = run.config["graph"]["parameters"]
    found = cz.counted(g, d)
    if found is None:
        return None
    _, _, read_, rows_live, slots_live = found
    need = cz.decode_step_bytes(g, read_, rows_live, slots_live)
    return 100.0 * cz.decode_kv_bytes(g, rows_live) / need
