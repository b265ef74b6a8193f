"""Reference kind ``cohere2_moe_decoder``: the served weights of
``models/cohere2_moe.py`` remade from the seed (in the served dtype, by the
program's own init with the same key), and the engine's probe tokens held,
teacher-forced, against the plain forward pass of
``../cohere2_moe_decoder.py`` given the same ``experts_held``."""

from __future__ import annotations

JUDGE = "token_logits"  # unless the configuration names another

FIELDS = ("vocab_size", "hidden", "n_layers", "n_heads", "n_kv_heads",
          "head_dim", "ffn", "n_experts", "experts_per_tok",
          "n_shared_experts", "experts_held", "sliding_window",
          "layer_pattern", "max_seq", "rope_theta", "norm_eps", "logit_scale")


def model(graph: dict, seed: int, chips: int):
    """(cfg, head weights, a function that yields the layers' weights one by
    one, the reference's keyword arguments) for a configuration's graph."""
    import jax

    from seldon_core_tpu.models import cohere2_moe

    import cohere2_moe_decoder as ref
    import frame

    cfg = cohere2_moe.Config(**{k: graph[k] for k in FIELDS if k in graph})
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    devices = frame.layer_devices(cfg.n_layers, chips)
    frame.lap("backend")
    params = frame.init_spread(
        lambda key: cohere2_moe.init_params(key, cfg, dtype),
        jax.random.PRNGKey(seed), devices,
    )
    frame.lap("weights")

    def layers():
        for local in frame.local_stacks(params["layers"], len(devices)):
            yield from ref.layers_of(local)

    head = jax.tree.map(
        lambda a: jax.device_put(a, devices[0]),
        {k: params[k] for k in ("tok_emb", "ln_f")},
    )
    kw = dict(
        pattern=cfg.layer_pattern, theta=cfg.rope_theta, eps=cfg.norm_eps,
        window=cfg.sliding_window, top_k=cfg.experts_per_tok, held=cfg.held,
        logit_scale=cfg.logit_scale,
    )
    return cfg, head, layers, kw


def deficits(ref_logits, tokens) -> tuple[list[float], int]:
    """How far each served token lies under the reference's top logit at its
    position, and at how many positions it IS the top."""
    out, agree = [], 0
    for row, t in zip(ref_logits, tokens):
        out.append(float(row.max() - row[t]))
        agree += int(row.argmax() == t)
    return out, agree


def check(config: dict, graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import numpy as np

    import cohere2_moe_decoder as ref
    import frame

    frame.lap("import")
    _, head, layers, kw = model(graph, seed, chips)
    found, agree, n = [], 0, 0
    for pr in probes["probes"]:
        prompt, toks = pr["prompt"], pr["tokens"]
        # only the rows that are judged leave the last layer: a prompt of
        # thousands of tokens times the vocabulary is gigabytes
        rows = range(len(prompt) - 1, len(prompt) + len(toks) - 1)
        lg = np.asarray(ref.logits(
            head, prompt + toks[:-1], layers=layers(), rows=list(rows), **kw
        ))
        d, a = deficits(lg, toks)
        found += d
        agree += a
        n += len(toks)
    top = sorted(found)
    frame.lap("forward")
    return {
        "kind": "cohere2_moe_decoder", "positions": n,
        "argmax_agree_share": agree / n,
        "logit_deficit_max": top[-1],
        "logit_deficit_p99": top[min(n - 1, int(0.99 * n))],
    }
