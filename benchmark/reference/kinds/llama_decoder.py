"""Reference kind ``llama_decoder``: the served weights of ``models/llama.py``
remade from the seed, and the engine's probe tokens held, teacher-forced,
against the plain forward pass of ``../llama_decoder.py``."""

from __future__ import annotations

JUDGE = "token_logits"  # unless the configuration names another


def check(config: dict, graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import jax
    import numpy as np

    from seldon_core_tpu.models import llama

    import frame
    import llama_decoder

    fields = ("vocab_size", "hidden", "n_layers", "n_heads", "n_kv_heads",
              "ffn", "max_seq", "rope_theta", "norm_eps")
    cfg = llama.Config(**{k: graph[k] for k in fields if k in graph})
    dtype = frame.served_dtype(graph.get("dtype", "float32"))

    def init(key):
        return jax.tree.map(
            lambda a: a.astype(dtype), llama.init_params(key, cfg)
        )

    frame.lap("import")
    devices = frame.layer_devices(cfg.n_layers, chips)
    frame.lap("backend")
    params = frame.init_spread(init, jax.random.PRNGKey(seed), devices)
    frame.lap("weights")

    def layers():
        for local in frame.local_stacks(params["layers"], len(devices)):
            yield from llama_decoder.layers_of(local)

    head = jax.tree.map(
        lambda a: jax.device_put(a, devices[0]),
        {k: params[k] for k in ("tok_emb", "ln_f", "head")},
    )
    deficits, agree, n = [], 0, 0
    for pr in probes["probes"]:
        prompt, toks = pr["prompt"], pr["tokens"]
        lg = np.asarray(llama_decoder.logits(
            head, prompt + toks[:-1], n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, layers=layers(),
        ))[len(prompt) - 1:]
        for row, t in zip(lg, toks):
            deficits.append(float(row.max() - row[t]))
            agree += int(row.argmax() == t)
            n += 1
    top = sorted(deficits)
    frame.lap("forward")
    return {
        "kind": "llama_decoder", "positions": n,
        "argmax_agree_share": agree / n,
        "logit_deficit_max": top[-1],
        "logit_deficit_p99": top[min(n - 1, int(0.99 * n))],
    }
