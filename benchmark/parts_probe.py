"""The layer functions of a ``kimi_k2`` cell past the probes' 96 tokens,
without the engine: the configuration's reference kind runs a prompt of
``reference.parts_probe_tokens`` through the served program's own layer
functions, in this process, and holds each part of a layer (projections, the
prompt's expanded attention, the absorbed decode read over a pool of the
prompt's rows) to the plain reference given the same inputs — the unit check
every run's ``correct`` makes too, on the committed graph (it is not the
engine's compiled programs: ``kinds/kimi_k2_decoder.py`` says what it is
not).  The controls a limit must refuse are graphs that are never served:

    python3 benchmark/parts_probe.py --workload <cell> --seed <n>
        [--controls] [--graph-param NAME=JSON ...] [--rehearse-cpu]

``--controls`` then runs, on the same weights, the rotary part left out of
the decode score (``decode_rope``), ``sigma`` without ``m^2``
(``softmax_mscale``), the decode read's scores in bfloat16
(``decode_score_dtype``), the prompt's scores in bfloat16
(``prompt_score_dtype``) and a wrong YaRN ramp under the projections
(``rope_beta_slow`` 4 for 1: the reference keeps the graph's).  Prints what it found as the last line of standard
output; the exit code is 1 where the served graph's rows do not hold.  A
control that the limits do not refuse is SAID (``"refused": false``), not
failed: the configuration's ``reference.why`` says which are seen by what.
Needs a TPU unless ``--rehearse-cpu``.
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
    ("decode_rope=off", {"decode_rope": "off"}),
    ("softmax_mscale=off", {"softmax_mscale": "off"}),
    ("decode_score_dtype=bfloat16", {"decode_score_dtype": "bfloat16"}),
    ("prompt_score_dtype=bfloat16", {"prompt_score_dtype": "bfloat16"}),
    ("rope_beta_slow=4", {"rope_beta_slow": 4.0}),
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
    n_tokens = int(limits["parts_probe_tokens"])
    out, ok = {}, True
    for name, control in [("served", {})] + (CONTROLS if args.controls else []):
        found = kind.mechanism(
            dataclasses.replace(cfg, **control), graph, params, args.seed, n_tokens
        )
        rows = [
            (part + "_max", found[part + "_max"], "<=", limits[part + "_limit"])
            for part in judge.PARTS
        ]
        holds = frame.all_hold(rows)
        if not control:
            ok = ok and holds
        for row in rows:
            print(f"{name}: compared {row[0]}: {row[1]} {row[2]} {row[3]}", file=sys.stderr)
        out[name] = {"holds": holds, "refused": not holds, "found": found,
                     "compared": [list(r) for r in rows]}
    print(json.dumps({
        "ok": ok, "cell": cell["name"], "seed": args.seed, "runs": out,
        "seconds": {k: round(v, 2) for k, v in frame.CLOCK.items()},
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
