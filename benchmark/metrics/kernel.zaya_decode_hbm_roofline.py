"""Share of the HBM roofline a decode step of the ZAYA1 model reaches: the
bytes the step HAS to move whatever implements it — every block's weights
outside its experts and the tied head once, 25.17 MB for every expert some
token chose (``moe.experts_touched`` over ``zaya.steps``: not all that are
held, so a program that reads an untouched expert cannot read over 100 %),
1,024 B a block for every K/V row the live slots' positions say it attends
(``attn.rows_live``) and writes, a live slot's tails in and out
(``costs_zaya.decode_step_bytes``, from shapes and the program's counters) —
over the chip's peak bandwidth, over the measured device time of a step: the
MEDIAN over the traced decode blocks of a block's device time over its steps
(``kernel.ssm_decode_hbm_roofline`` has the reason).  The trace gives program
times, not a kernel's own, so the share is of the WHOLE step: the small ops
of the CCA chain and the router between the reads count against it.  Bound:
memory.  The counters' averages are the whole load's (lead-in, window and
drain) while the traced blocks lie inside the window, where more slots are
live: the share is understated by the difference, never overstated.  A
program without the counters gives nothing."""
import statistics

import costs_zaya as cz
import metriclib as ml
import moe_counters


def read(run):
    blocks = ml.programs(run, "decode_k:")
    d = moe_counters.delta(run)
    if not blocks or run.peaks is None or not d:
        return None
    g = run.config["graph"]["parameters"]
    found = cz.counted(g, d)
    if found is None:
        return None
    _, touched, _, rows_live, slots_live = found
    step_s = statistics.median(
        p["device_s"] / int(p["label"].split(":")[1][1:]) for p in blocks
    )
    need = cz.decode_step_bytes(g, touched, rows_live, slots_live)
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
