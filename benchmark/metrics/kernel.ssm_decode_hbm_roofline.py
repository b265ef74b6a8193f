"""Share of the HBM roofline a decode step of the state-space model reaches:
the bytes the step HAS to move whatever implements it — every layer's
weights and the tied head once, 18.6 MB for every live slot (its 26 layers'
state and convolution tails read and written: ``ssm.slot_steps`` over
``ssm.steps``) and 512 B for every K/V row the live slots' positions say the
two attention layers attend (``attn.rows_live`` over ``ssm.steps``), from
shapes and the program's counters (``costs_jamba.decode_step_bytes``) — over
the chip's peak bandwidth, over the measured device time of a step: the
MEDIAN over the traced decode blocks of a block's device time over its steps
(a block cut by the slice's edge counts its 16 steps for a part of its time:
``kernel.mla_decode_hbm_roofline`` has the reason).  The trace gives program
times, not a kernel's own, so the share is of the whole step.  Bound: memory.
A program that read the state twice, or all 128 slots' when 100 were live,
reads under 100 %: the numerator counts each live slot's state once in and
once out.  The counters' averages are the whole load's (lead-in, window and
drain: see ``ssm.live_slots``) while the traced blocks lie inside the window,
where more slots are live than on that average: the share is understated by
the difference (2 to 3 points at 109 against 120 slots), never overstated.
A program without the counters gives nothing."""
import statistics

import costs_jamba as cj
import metriclib as ml
import moe_counters


def read(run):
    blocks = ml.programs(run, "decode_k:")
    d = moe_counters.delta(run)
    if not blocks or run.peaks is None or not d:
        return None
    steps = d.get("ssm.steps", 0)
    if steps <= 0 or "ssm.slot_steps" not in d or "attn.rows_live" not in d:
        return None
    step_s = statistics.median(
        p["device_s"] / int(p["label"].split(":")[1][1:]) for p in blocks
    )
    need = cj.decode_step_bytes(
        run.config["graph"]["parameters"], d["ssm.slot_steps"] / steps,
        d["attn.rows_live"] / steps,
    )
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
