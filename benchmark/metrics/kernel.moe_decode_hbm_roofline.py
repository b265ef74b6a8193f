"""Share of the HBM roofline a decode step of the expert-layer model
reaches: the bytes the step has to read — every layer's dense part
(attention, shared experts, router, norm) and the head's vocabulary slice,
the experts its tokens TOUCHED (the program's counter ``moe.experts_touched``
over ``moe.steps``, not all that are held: a program that skips untouched
experts cannot read over 100 %), and the K/V inside each layer's window (a
full layer a slot's whole context, a sliding layer at most its window), from
shapes (``costs_cohere2_moe.decode_step_bytes``) — over the chip's peak
bandwidth, over the measured device time of a step.  Bound: memory."""
import costs_cohere2_moe as cm
import metriclib as ml
import moe_counters


def weighted_contexts(run) -> list[tuple[float, float]]:
    """(context, slots at it) of a saturated step, from the traffic's
    shapes: each request of the mix at 8 evenly spaced points of its output,
    weighted by how long it holds its slot, scaled to ``n_slots`` slots."""
    mix = run.mix
    n = 64
    prompts = run.traffic.stratified(mix["prompt_len"], n)
    outputs = run.traffic.stratified(mix["output_len"], n)
    # the two lengths are drawn independently: every pair of quantiles
    points, weights = [], []
    for p in prompts:
        for o in outputs[::8]:
            for j in range(8):
                points.append(p + o * (j + 0.5) / 8)
                weights.append(o)
    slots = int(run.config["graph"]["parameters"]["n_slots"])
    total = sum(weights)
    return [(t, slots * w / total) for t, w in zip(points, weights)]


def read(run):
    step_s = ml.decode_step_s(run)
    d = moe_counters.delta(run)
    if step_s is None or run.peaks is None or not d or d.get("moe.steps", 0) <= 0:
        return None
    g = run.config["graph"]["parameters"]
    touched = d["moe.experts_touched"] / d["moe.steps"]
    # kv_tokens_read is linear in the slots at a context: weight each point
    kv = sum(w * cm.kv_tokens_read(g, [t]) for t, w in weighted_contexts(run))
    need = (
        cm.decode_step_bytes(g, [], touched)
        + kv * cm.kv_bytes_per_token_layer(g)
    )
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
