"""Runs of one cell in a row, each with another seed, and the spread of each
metric over them: how a bound is measured (see PERF.md section 2).

    python3 benchmark/measure_sets.py --workload <cell> --runs 6 --sets 2 \\
        [--seconds S] [--trace-runs 1] [--out chiprun_out/<name>.json]

A set is ``--runs`` runs with seeds ``--seed0, --seed0 + 1, ...``; every set
uses the same seeds.  The spread is the distance between the first and the
third quartile (``statistics.quantiles(values, n=4)``) over the median.
This process never imports jax: each run is ``benchmark/run.py``, a new
process, one at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one_run(workload: str, seed: int, seconds: float, trace: int, extra: list[str]) -> dict:
    t0 = time.time()
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace), *extra],
        cwd=ROOT, capture_output=True, text=True,
    )
    lines = done.stdout.strip().splitlines()
    if done.returncode != 0 or not lines:
        sys.stderr.write(done.stderr[-4000:])
        raise SystemExit(f"run failed rc={done.returncode}")
    result = json.loads(lines[-1])
    result["wall_s"] = time.time() - t0
    result["info"] = [json.loads(l[2:]) for l in lines[:-1] if l.startswith("# ")]
    return result


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seed0", type=int, default=2147483700)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace-runs", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("extra", nargs="*", help="passed through to run.py after --")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    report = {"workload": args.workload, "seconds": seconds, "sets": [], "traced": []}

    def save() -> None:
        """After every run: a call cut at its limit keeps what it had."""
        if args.out:
            os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
            with open(os.path.join(ROOT, args.out), "w") as f:
                json.dump(report, f)

    for k in range(args.sets):
        runs = []
        report["sets"].append(runs)
        for i in range(args.runs):
            r = one_run(args.workload, args.seed0 + i, seconds, 0, args.extra)
            runs.append(r)
            save()
            print(f"set {k} run {i} seed {args.seed0 + i}: correct={r['correct']} "
                  f"failed={r['failed']}/{r['attempted']} wall={r['wall_s']:.0f}s "
                  + " ".join(f"{m}={v['value']:.4f}" for m, v in r["metrics"].items()),
                  flush=True)
            for line in r["info"]:
                if "requests_in_window" in line or "stall_s" in line:
                    print("   ", json.dumps(line)[:1200], flush=True)
                if "reference" in line:
                    print("    reference:", json.dumps(line["reference"])[:300], flush=True)
    for i in range(args.trace_runs):
        r = one_run(args.workload, args.seed0 + i, seconds, 1, args.extra)
        report["traced"].append(r)
        save()
        print(f"traced run {i}: correct={r['correct']} "
              + " ".join(f"{m}={v['value']:.4f}" for m, v in r["metrics"].items())
              + f" busy_s={r['device'].get('busy_s')} window_s={r['device'].get('window_s')}",
              flush=True)
        print("  breakdown:", json.dumps(r.get("breakdown")), flush=True)
    if args.runs >= 2:
        for m in report["sets"][0][0]["metrics"]:
            per_set = [[r["metrics"][m]["value"] for r in runs] for runs in report["sets"]]
            # setup_s: each set's first run in a fresh checkout compiles
            meds = [statistics.median(v) for v in per_set]
            sp = [spread(v) for v in per_set]
            print(f"{m}: medians {[round(x, 4) for x in meds]} spreads "
                  f"{[round(x, 5) for x in sp]} widest {max(sp):.5f} "
                  f"-> five times: {5 * max(sp):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
