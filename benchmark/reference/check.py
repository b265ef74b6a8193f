"""The reference child: a process of its own, started after the engine has
exited (so the chip is free), that makes the served weights again from the
seed and holds the engine's probe outputs against the plain reference.

    python benchmark/reference/check.py --config benchmark/configs/X.json --seed N < probes.json

It reads ONE line of JSON from its standard input (the probes: what the
engine was asked and what it answered) and prints one line of JSON with
what it found.  It judges nothing: the margins are the configuration's,
applied by the caller.  It runs on whatever JAX serves on here — the chip
on a chip run (float32 at ``highest`` matmul precision), the CPU in a
rehearsal — and spreads a model that one device cannot hold over the
configuration's ``chips`` along the layer axis, running one layer after the
other where its weights are.

The weights come from the program's own init call with the same key
(``PRNGKey(seed)``), cast the way the engine casts them — they ARE the
system under test's weights, and the only way to have them here; every
line of the forward pass is the benchmark's own (``llama_decoder.py``,
``bert_encoder.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


CLOCK: dict[str, float] = {}
_T = [__import__("time").perf_counter()]


def lap(name: str) -> None:
    """Where the child's time went: kept beside what it found."""
    import time

    now = time.perf_counter()
    CLOCK[name] = CLOCK.get(name, 0.0) + now - _T[0]
    _T[0] = now


def served_dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def llama_check(graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from seldon_core_tpu.models import llama

    import llama_decoder

    fields = ("vocab_size", "hidden", "n_layers", "n_heads", "n_kv_heads",
              "ffn", "max_seq", "rope_theta", "norm_eps")
    cfg = llama.Config(**{k: graph[k] for k in fields if k in graph})
    dtype = served_dtype(graph.get("dtype", "float32"))

    def init(key):
        # the key is an argument: one compiled program for every seed
        return jax.tree.map(
            lambda a: a.astype(dtype), llama.init_params(key, cfg)
        )

    lap("import")
    devices = jax.devices()[:chips]
    lap("backend")
    if cfg.n_layers % len(devices):
        devices = devices[:1]
    mesh = Mesh(np.asarray(devices), ("layers",))
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(init, key)
    shardings = {
        k: jax.tree.map(
            lambda _: NamedSharding(mesh, P("layers") if k == "layers" else P()), v
        )
        for k, v in shapes.items()
    }
    params = jax.jit(init, out_shardings=shardings)(key)
    jax.block_until_ready(params)
    lap("weights")

    def layers():
        # each device's own layers, in order: single-device arrays
        per_dev = {
            k: sorted(v.addressable_shards, key=lambda s: s.index[0].start or 0)
            for k, v in params["layers"].items()
        }
        for d in range(len(devices)):
            local = {k: shards[d].data for k, shards in per_dev.items()}
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
    lap("forward")
    return {
        "kind": "llama_decoder", "positions": n,
        "argmax_agree_share": agree / n,
        "logit_deficit_max": top[-1],
        "logit_deficit_p99": top[min(n - 1, int(0.99 * n))],
    }


def bert_check(graph: dict, seed: int, chips: int, probe: dict) -> dict:
    import jax
    import numpy as np

    from seldon_core_tpu.models import bert

    import bert_encoder

    import dataclasses

    names = {f.name for f in dataclasses.fields(bert.Config)}
    # preset "base" is the published sizes, models/bert.py::Config's defaults
    cfg = bert.Config(**{k: v for k, v in graph.items() if k in names})
    dtype = served_dtype(graph.get("dtype", "float32"))
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    from seldon_core_tpu.utils.device import configure_compile_cache

    configure_compile_cache()  # the reference's programs are cached too
    with open(args.config) as f:
        config = json.load(f)
    graph = config["graph"]["parameters"]
    kind = config["reference"]["kind"]
    check = {"llama_decoder": llama_check, "bert_encoder": bert_check}[kind]
    probes = json.loads(sys.stdin.readline())
    found = check(graph, args.seed, int(config.get("chips", 1)), probes)
    found["child_seconds"] = {k: round(v, 2) for k, v in CLOCK.items()}
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
