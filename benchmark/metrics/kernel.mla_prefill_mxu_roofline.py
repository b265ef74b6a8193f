"""Share of the MXU's peak the prompt programs of the latent-attention model
reach in the traced slice: the FLOPs a prompt needs whatever implements it —
the projections (the up-projection ``c Wkvb`` among them), every causal
pair's score (192 wide) and its product with V (128 wide), the dense layer's
MLP, the shared experts, the router, the routed experts at the pairs the
window's prompts really brought to the held ones
(``moe.prefill_pairs_held`` over ``moe.prefill_tokens``), from shapes and the
program's counters (``costs_kimi_k2.prefill_flops``) — over the chip's
bfloat16 peak, over the device time of a traced ``prefill:b<rung>`` program:
the (lower) MEDIAN of the programs' own shares (three slices in four cut a
prompt program at an edge, and a cut program counts its whole prompt for a
part of its time, so it only ever reads high; a sum over the slice read four
points high for it, PERF.md 6).
The trace's labels carry a program's rung and not its prompt's
length, so a traced program's REAL rows are taken as its rung's times the
window's real share of the rows its prompt programs expanded (``n_layers`` x
``moe.prefill_tokens`` over ``mla.prefill_rows_expanded``): padding is work
done and not work needed, and counts for nothing here.  Bound: compute.  A
program computes every row of its rung and whole tiles on the diagonal, so
the share reads under 100 % whatever the kernel.  Suffix programs are left
out (another count of pairs; this traffic has none).  A program without the
counters gives nothing."""
import re
import statistics

import costs_kimi_k2 as ck
import metriclib as ml
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    prompts = [
        (int(m.group(1)), p["device_s"])
        for p in ml.programs(run, "prefill:")
        if (m := re.match(r"prefill:b(\d+)", p["label"])) and p["device_s"] > 0
    ]
    if not prompts or run.peaks is None or not d:
        return None
    tokens, expanded = d.get("moe.prefill_tokens", 0), d.get("mla.prefill_rows_expanded", 0)
    if tokens <= 0 or expanded <= 0:
        return None
    g = run.config["graph"]["parameters"]
    real = min(1.0, g["n_layers"] * tokens / expanded)
    pairs = d["moe.prefill_pairs_held"] / (tokens * ck.expert_layers(g))
    rate = statistics.median_low(
        ck.prefill_flops(g, rung * real, pairs) / s for rung, s in prompts
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])
