"""Where a decode step's and a prompt's device time goes BY NAMED SCOPE, for a
cell whose family names its scopes (``jax.named_scope``), without the engine:
the family's entry functions, jitted as ``executor/generation.py`` jits them
on a cache of the graph's own slots and blocks, every slot live at
``--context`` tokens; twenty steps and five prompts a rung are traced, and
every device operation is given the scope its HLO metadata names (a Pallas
call is named by its scope already).  ``benchmark/trace.py`` keeps a run's
ten longest operations by name; this keeps all of them, by scope.

    python3 benchmark/scope_probe.py --workload <cell> [--seed N]
        [--context 2050] [--prompts 2048:2000,1024:1024] [--rehearse-cpu]

Prints one JSON object as the last line of standard output: for ``decode``
and each ``prefill<rung>``, milliseconds a call by scope, operations a call
by scope, the device's busy milliseconds a call, the host's milliseconds a
call and the twelve longest operations.  Needs a TPU unless
``--rehearse-cpu`` (which has no device plane and says so).  PERF.md
section 5 quotes it for ``zaya1-8b-l20.reasoning-closed``.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import re
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE, os.path.join(HERE, "reference")]

import run  # noqa: E402  (the harness's own manifest lookup; no jax)

# the scopes ``models/zaya.py`` names (docs/OBSERVABILITY.md); another family's
# cell brings its own here
SCOPES = ("cca.qk", "cca.conv", "cca.mix", "cca.v", "attn.prompt", "attn.paged",
          "cca.out", "router.down", "router.mlp", "moe.route", "moe.experts",
          "res.scale", "head")


def scope_of(hlo: str, scopes) -> dict:
    """Each HLO instruction's scope: the innermost of ``scopes`` in its
    ``op_name``, else ``other``."""
    by = {}
    for line in hlo.splitlines():
        m = re.match(r'\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"', line)
        if m:
            hits = [(m.group(2).rfind(s), s) for s in scopes if s in m.group(2)]
            by[m.group(1)] = max(hits)[1] if hits else "other"
    return by


def by_scope(trace_dir: str, by: dict, scopes, n: int) -> dict:
    import trace as tr

    pd = tr.load(trace_dir)
    plane = next((p for p in pd.planes if tr.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        return {"no_device_plane": True}
    spent, count, by_op = (collections.Counter() for _ in range(3))
    for start, end, name in tr.events_of(plane, "XLA Ops"):
        if tr.CONTAINER.match(name):
            continue
        scope = next((s for s in scopes if name.startswith(s + ".")), by.get(name, "other"))
        spent[scope] += end - start
        count[scope] += 1
        by_op[name] += end - start
    return {
        "per_call_ms": {k: 1e3 * v / n for k, v in spent.most_common()},
        "ops_per_call": {k: count[k] / n for k in spent},
        "busy_ms_per_call": 1e3 * sum(spent.values()) / n,
        "top_ops_ms": [(k, 1e3 * v / n) for k, v in by_op.most_common(12)],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--context", type=int, default=2050)
    ap.add_argument("--prompts", default="2048:2000", help="rung:real tokens, ...")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    manifest = run.load_json("BENCHMARK.json")
    cell = run.named(manifest["workloads"], args.workload, "workload")
    path = run.named(manifest["configs"], cell["config"], "configuration")["file"]
    if args.rehearse_cpu:
        path = os.path.join("benchmark", "rehearsal", os.path.basename(path))
    config = run.load_json(path)
    graph = config["graph"]["parameters"]
    scopes = SCOPES

    import jax
    import jax.numpy as jnp
    import numpy as np

    import frame

    from seldon_core_tpu.utils.device import configure_compile_cache

    configure_compile_cache()
    fam = importlib.import_module(f"seldon_core_tpu.models.{graph['family']}")
    kind = frame.named_module("kinds", config["reference"]["kind"])
    cfg, params, _ = kind.model(graph, args.seed)
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    S, bs, nb = int(graph["n_slots"]), int(graph["kv_block_size"]), int(graph["kv_blocks"])
    per = min(cfg.max_seq // bs, (nb - 1) // S)
    cache = fam.init_paged_cache(cfg, S, nb, bs, dtype)
    table = np.zeros((S, cfg.max_seq // bs), np.int32)
    table[:, :per] = 1 + np.arange(S * per).reshape(S, per)
    cache["table"] = jnp.asarray(table)
    here = jnp.full((S,), min(args.context, per * bs - 32), jnp.int32)
    kernel = graph.get("decode_kernel", jax.default_backend() != "cpu")
    rng = np.random.default_rng([args.seed, 0x5C0])
    out = {}

    def traced(compiled, call, n):
        """``call((logits, cache)) -> (logits, cache)`` ``n`` times under the
        profiler: the logits are kept, so the head is not compiled away."""
        by = scope_of(compiled.as_text(), scopes)
        carry = call(call((None, cache_box[0])))
        jax.block_until_ready(carry)
        with tempfile.TemporaryDirectory(dir=run.CACHE if os.path.isdir(run.CACHE) else None) as d:
            jax.profiler.start_trace(d)
            t0 = time.perf_counter()
            for _ in range(n):
                carry = call(carry)
            jax.block_until_ready(carry)
            wall = time.perf_counter() - t0
            jax.profiler.stop_trace()
            found = by_scope(d, by, scopes, n)
        cache_box[0] = carry[1]
        return dict(found, host_ms_per_call=1e3 * wall / n)

    cache_box = [cache]
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, S), jnp.int32)
    active = jnp.ones((S,), bool)
    step = jax.jit(
        lambda p, t, c, a: fam.decode_slots_paged(p, t, c, a, cfg, window=cfg.max_seq,
                                                  kernel=kernel),
        donate_argnums=2,
    ).lower(params, tokens, cache, active).compile()
    out["decode"] = dict(
        traced(step, lambda c: step(params, tokens, dict(c[1], pos=here + 0), active), 20),
        context=int(here[0]), slots=S,
    )
    for item in args.prompts.split(","):
        rung, length = (int(v) for v in item.split(":"))
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, rung)), jnp.int32)
        fixed = (toks, jnp.int32(length), jnp.int32(3), jnp.asarray(table[3]))
        prefill = jax.jit(
            lambda p, t, n, s, row, c: fam.prefill_slot_paged(
                p, t, n, s, row, c, cfg, seq_impl=graph.get("seq_impl", "dense")),
            donate_argnums=5,
        ).lower(params, *fixed, cache_box[0]).compile()
        out[f"prefill{rung}"] = dict(
            traced(prefill, lambda c: prefill(params, *fixed, c[1]), 5), real_tokens=length,
        )
    print(json.dumps({"cell": cell["name"], "seed": args.seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
