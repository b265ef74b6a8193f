"""The state-space layers of a ``jamba`` cell at the timed sizes, without
the engine: the configuration's reference kind runs a prompt of
``reference.state_probe_tokens`` real tokens in its padded rung and then
``reference.state_probe_steps`` decode steps through the served program's own
layer functions, in this process, and holds each part of a state-space layer
(projections, the prompt's recurrence, the state, the steps) to the plain
reference given the same inputs — the unit check every run's ``correct``
makes too, on the committed graph (it is not the engine's compiled programs:
``kinds/jamba_decoder.py`` says what it is not).  The controls a limit must
refuse are graphs that are never served:

    python3 benchmark/state_probe.py --workload <cell> --seed <n>
        [--controls] [--graph-param NAME=JSON ...] [--rehearse-cpu]

``--controls`` then runs, on the same weights, the state kept in bfloat16
(``ssm_state_dtype``: the nearest precision below the one the configuration
states), the recurrence's products in bfloat16 (``ssm_product_dtype``), the
rung's padding rows allowed to move the state (``ssm_padding``), the
convolution's tail taken at the rung's end (``conv_tail_at``) and ``b_dt``
left out of ``D_t`` (``dt_bias``).  Prints what it found as the last line of
standard output; the exit code is 1 where the served graph's rows do not
hold, or with ``--controls`` where a control is NOT refused.  Needs a TPU
unless ``--rehearse-cpu``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE, os.path.join(HERE, "reference")]

import run  # noqa: E402  (the harness's own manifest lookup; no jax)

CONTROLS = [
    ("ssm_state_dtype=bfloat16", {"ssm_state_dtype": "bfloat16"}),
    ("ssm_product_dtype=bfloat16", {"ssm_product_dtype": "bfloat16"}),
    ("ssm_padding=moves", {"ssm_padding": "moves"}),
    ("conv_tail_at=rung", {"conv_tail_at": "rung"}),
    ("dt_bias=off", {"dt_bias": "off"}),
]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--graph-param", action="append", default=[], metavar="NAME=JSON")
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
    graph = dict(config["graph"]["parameters"])
    for item in args.graph_param:
        name, _, value = item.partition("=")
        graph[name] = json.loads(value)

    import dataclasses

    import frame

    from seldon_core_tpu.utils.device import configure_compile_cache

    configure_compile_cache()
    limits = config["reference"]
    kind = frame.named_module("kinds", limits["kind"])
    judge = frame.named_module("judges", limits.get("judge") or kind.JUDGE)
    cfg, params, _ = kind.model(graph, args.seed)
    out, ok = {}, True
    for name, control in [("served", {})] + (CONTROLS if args.controls else []):
        found = kind.mechanism(
            dataclasses.replace(cfg, **control), graph, params, args.seed,
            int(limits["state_probe_tokens"]), int(limits.get("state_probe_steps", 64)),
        )
        rows = [
            (part + "_max", found[part + "_max"], "<=", limits[part + "_limit"])
            for part in judge.PARTS
        ]
        holds = frame.all_hold(rows)
        ok = ok and (holds if not control else not holds)
        for row in rows:
            print(f"{name}: compared {row[0]}: {row[1]} {row[2]} {row[3]}", file=sys.stderr)
        out[name] = {
            "holds": holds, "refused": not holds,
            "refused_by": [r[0] for r in rows if not frame.all_hold([r])],
            "found": {k: v for k, v in found.items() if not k.endswith("_by_layer")},
        }
    print(json.dumps({
        "ok": ok, "cell": cell["name"], "seed": args.seed, "runs": out,
        "seconds": {k: round(v, 2) for k, v in frame.CLOCK.items()},
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
