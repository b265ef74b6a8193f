"""Reference kind ``bert_encoder``: the served weights of ``models/bert.py``
remade from the seed, and the engine's class probabilities for the probe
rows held against the plain forward pass of ``../bert_encoder.py``."""

from __future__ import annotations

JUDGE = "class_probs"  # unless the configuration names another


def check(config: dict, graph: dict, seed: int, chips: int, probe: dict) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from seldon_core_tpu.models import bert

    import bert_encoder
    import frame

    names = {f.name for f in dataclasses.fields(bert.Config)}
    # preset "base" is the published sizes, models/bert.py::Config's defaults
    cfg = bert.Config(**{k: v for k, v in graph.items() if k in names})
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    params = jax.tree.map(
        lambda a: a.astype(dtype), bert.init_params(jax.random.PRNGKey(seed), cfg)
    )
    want = np.asarray(bert_encoder.probabilities(
        params, np.asarray(probe["tokens"], np.int32), n_layers=cfg.n_layers,
        pad_id=cfg.pad_id,
    ))
    got = np.asarray(probe["outputs"], np.float32)
    return {
        "kind": "bert_encoder", "rows": int(got.shape[0]),
        "prob_abs_err_max": float(np.abs(got - want).max()),
    }
