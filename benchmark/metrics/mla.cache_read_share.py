"""How much of what a decode step has to read is the latent cache: 100 x
(1,152 B x ``mla.rows_live``) / (that + the weights the step has to read:
every layer's attention, the dense layer's MLP, the shared experts, routers,
norms, the head's slice and the held experts TOUCHED), over the window's
decode steps, from the program's counters and shapes
(``costs_kimi_k2``: the numerator of ``kernel.mla_decode_hbm_roofline``; the
rows are those the live slots' positions say a step attends).  Near 0 means
the traffic has stopped working the mechanism (short contexts, few live
slots).  A program without the counter gives nothing."""
import costs_kimi_k2 as ck
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("moe.steps", 0) <= 0 or "mla.rows_live" not in d:
        return None
    g = run.config["graph"]["parameters"]
    steps = d["moe.steps"]
    latents = ck.latent_read_bytes(g, d["mla.rows_live"] / steps)
    weights = ck.decode_weight_bytes(g, d["moe.experts_touched"] / steps)
    return 100.0 * latents / (latents + weights)
