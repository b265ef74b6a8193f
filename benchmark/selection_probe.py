"""The selection of a learned-sparse-attention cell at work, without the
engine: the configuration's reference kind runs a prompt of
``reference.selection_probe_tokens`` through the served program's own layer
functions, in this process, and holds each part of a layer (projections,
keys selected, attention, decode read) to the plain reference given the
same inputs — what every run's ``correct`` holds too, since the harness's
own probes end under ``topk`` — and, with ``--chain``, counts layer by
layer how far the program and the reference part on their OWN hidden
states.  The controls a limit must refuse are graphs that are never served:

    python3 benchmark/selection_probe.py --workload <cell> --seed <n>
        [--chain] [--controls] [--float32] [--rehearse-cpu]

``--controls`` then runs, on the same weights, the index scores in bfloat16
(``index_dtype``) and the selection switched off (``select``); ``--float32``
then serves in float32 at the highest matmul precision with ``--chain``: it
shows whether the two part by rounding alone.  Prints what it found as the
last line of standard output; exit code 1 where the served graph's rows do
not hold or a control's do.  Needs a TPU unless ``--rehearse-cpu``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(HERE, "reference")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chain", action="store_true")
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--float32", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    path = next(c for c in manifest["configs"] if c["name"] == cell["config"])["file"]
    if args.rehearse_cpu:
        path = os.path.join("benchmark", "rehearsal", os.path.basename(path))
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    graph = config["graph"]["parameters"]

    import dataclasses

    import frame

    from seldon_core_tpu.utils.device import configure_compile_cache

    configure_compile_cache()
    limits = config["reference"]
    kind = frame.named_module("kinds", limits["kind"])
    cfg, head, layers, _ = kind.model(graph, args.seed, int(config.get("chips", 1)))
    judge = frame.named_module("judges", limits.get("judge") or kind.JUDGE)
    n_tokens = int(limits["selection_probe_tokens"])
    runs = [("served", {}, dict(chain=args.chain))]
    if args.controls:
        runs += [("index_dtype=bfloat16", {"index_dtype": "bfloat16"}, {}),
                 ("select=off", {"select": "off"}, {})]
    if args.float32:
        runs.append(("served in float32", {}, dict(chain=True, float32=True)))
    out, ok = {}, True
    for name, control, how in runs:
        found = kind.mechanism(
            dataclasses.replace(cfg, **control), graph, head, layers, args.seed,
            n_tokens, **how,
        )
        rows = judge.selection_rows(found, limits)
        holds = frame.all_hold(rows)
        ok = ok and holds == (not control)  # a control has to be refused
        for row in rows:
            print(f"{name}: compared {row[0]}: {row[1]} {row[2]} {row[3]}", file=sys.stderr)
        out[name] = {"holds": holds, "found": found, "compared": [list(r) for r in rows]}
    print(json.dumps({
        "ok": ok, "cell": cell["name"], "seed": args.seed, "runs": out,
        "seconds": {k: round(v, 2) for k, v in frame.CLOCK.items()},
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
