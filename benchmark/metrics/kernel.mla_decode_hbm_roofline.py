"""Share of the HBM roofline a decode step of the latent-attention model
reaches: the bytes the step has to read whatever implements it — every
layer's attention weights, the dense layer's MLP, the shared experts, routers
and norms, the head's slice, the held experts its tokens TOUCHED
(``moe.experts_touched`` over ``moe.steps``, not all that are held), and
1,152 B for every latent row the live slots' positions say the step attends
(``mla.rows_live`` over ``moe.steps``: counted from the positions, NOT the
read's own ``mla.rows_read``, so a read that fetches more blocks than it
needs cannot raise its own share), from shapes and the program's counters
(``costs_kimi_k2.decode_step_bytes``) — over the chip's peak bandwidth, over
the measured device time of a step: the MEDIAN over the traced decode blocks
of a block's device time over its steps (a slice of 3 s holds five or six
blocks between prompts, and one cut by the slice's edge counts its 16 steps
for a part of its time: the mean of ``metriclib.decode_step_s`` then reads a
step a tenth short and this share a tenth high, PERF.md 7).  The trace gives
program times, not a kernel's own, so the share is of the step, as
``kernel.moe_decode_hbm_roofline`` is.  Bound: memory.  A program that reads
every held expert, or every row twice, reads under 100 %: the numerator
counts what was touched and each needed row once.  A program without the
counters gives nothing."""
import statistics

import costs_kimi_k2 as ck
import metriclib as ml
import moe_counters


def read(run):
    blocks = ml.programs(run, "decode_k:")
    d = moe_counters.delta(run)
    if not blocks or run.peaks is None or not d:
        return None
    step_s = statistics.median(
        p["device_s"] / int(p["label"].split(":")[1][1:]) for p in blocks
    )
    steps = d.get("moe.steps", 0)
    if steps <= 0 or "mla.rows_live" not in d:
        return None
    need = ck.decode_step_bytes(
        run.config["graph"]["parameters"], d["moe.experts_touched"] / steps,
        d["mla.rows_live"] / steps,
    )
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
