"""Share of the HBM roofline a decode step of the learned-sparse-attention
model reaches: the bytes the step has to read whatever implements it —
every layer's attention, indexer, router and norms, the head, the experts
its tokens TOUCHED (``moe.experts_touched`` over ``moe.steps``), 128 B for
every key scored and 2,048 B for every key selected (``dsa.keys_scored``,
``dsa.keys_selected`` over ``moe.steps``), from shapes and the program's
counters (``costs_keye_vl2.decode_step_bytes``) — over the chip's peak
bandwidth, over the measured device time of a step.  The trace gives
program times, not a kernel's own, so the share is of the step, as
``kernel.moe_decode_hbm_roofline`` is.  Bound: memory.  A program without
the counters gives nothing."""
import costs_keye_vl2 as ck
import metriclib as ml
import moe_counters


def read(run):
    step_s = ml.decode_step_s(run)
    d = moe_counters.delta(run)
    if step_s is None or run.peaks is None or not d:
        return None
    steps = d.get("moe.steps", 0)
    if steps <= 0 or "dsa.keys_scored" not in d:
        return None
    need = ck.decode_step_bytes(
        run.config["graph"]["parameters"], d["moe.experts_touched"] / steps,
        d["dsa.keys_scored"] / steps, d["dsa.keys_selected"] / steps,
    )
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
