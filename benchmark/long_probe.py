"""Long-context agreement of a decoder cell, which the runs' own probes (64
tokens in, 32 out) cannot show: prompts of about 5,000 tokens and 64 new
tokens each through ``/predictions/stream`` of the engine at the cell's own
sizes, then — the engine gone, the chip free — the configuration's plain
reference, teacher-forced on the served tokens and computed in blocks of
queries so that it fits, judged by the configuration's own judge and limits.
Every position judged lies past the 4,096 window, on both layer kinds.

    python3 benchmark/long_probe.py --workload <cell> --seed <n> [--prompts 4990,5040]

Prints what it found as the last line of standard output; exit code 1 where
the judge does not hold.  Needs a TPU (``--rehearse-cpu``: the rehearsal's
sizes on the CPU, prompts a tenth as long).  This process never imports jax.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "reference")]

import loadgen  # noqa: E402
import run  # noqa: E402  (the harness's own manifest lookup and judge)
from engine import BenchFailure, Engine, graph_of  # noqa: E402

NEW_TOKENS = 64


async def ask(base: str, prompts: list[list[int]], vocab: int) -> list[dict]:
    import aiohttp

    out = []
    timeout = aiohttp.ClientTimeout(total=None, sock_read=600)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        for prompt in prompts:
            s = loadgen.Sample(0, 0.0, time.perf_counter(), asked=NEW_TOKENS)
            toks: list[int] = []
            body = loadgen.stream_body({"tokens": prompt, "max_new": NEW_TOKENS}, 0.0)
            await loadgen.stream_request(session, base, body, s, vocab, toks)
            if not s.ok:
                raise BenchFailure(f"long probe failed: {s.error}")
            out.append({"prompt": prompt, "tokens": toks})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompts", default="4990,5040", help="prompt lengths")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--graph-param", action="append", default=[], metavar="NAME=JSON",
                    help="control only: the engine's graph with this parameter set")
    args = ap.parse_args()

    manifest = run.load_json("BENCHMARK.json")
    cell = run.named(manifest["workloads"], args.workload, "workload")
    cfg_path = run.named(manifest["configs"], cell["config"], "configuration")["file"]
    if args.rehearse_cpu:
        cfg_path = os.path.join("benchmark", "rehearsal", os.path.basename(cfg_path))
    config = run.load_json(cfg_path)
    served = json.loads(json.dumps(config))
    for item in args.graph_param:
        name, _, value = item.partition("=")
        served["graph"]["parameters"][name] = json.loads(value)
    vocab = int(config["vocab_size"])
    platform = "cpu" if args.rehearse_cpu else "tpu"
    lengths = [int(n) // (10 if args.rehearse_cpu else 1) for n in args.prompts.split(",")]

    import numpy as np

    rng = np.random.default_rng([args.seed, 0x10F6])
    prompts = [rng.integers(1, vocab, size=n).tolist() for n in lengths]
    run_dir = os.path.join(ROOT, ".benchmark_cache", "runs", f"long_probe.{os.getpid()}")
    os.makedirs(run_dir, exist_ok=True)
    engine = Engine(graph_of(served, args.seed), platform,
                    os.path.join(run_dir, "engine.log"), {})
    try:
        engine.wait_ready(1100.0)
        dev = engine.warmup()["device"]
        if dev["platform"] != platform:
            raise BenchFailure(f"engine serves on {dev['platform']}, not {platform}")
        t0 = time.perf_counter()
        probes = asyncio.run(ask(engine.base, prompts, vocab))
        served_s = time.perf_counter() - t0
        compiles = engine.warmup()["device"]["xla_compiles_since_ready"]
    except BaseException:
        engine.stop()
        sys.stderr.write(f"--- engine log tail ---\n{engine.log_tail()}\n---\n")
        raise
    engine.stop()

    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "reference", "check.py"),
         "--config", cfg_path, "--seed", str(args.seed)],
        env={**os.environ, "JAX_PLATFORMS": platform}, cwd=ROOT,
        input=json.dumps({"probes": probes}) + "\n",
        capture_output=True, text=True, timeout=3000,
    )
    if done.returncode != 0:
        raise BenchFailure(f"reference child failed: {done.stderr[-3000:]}")
    found = json.loads(done.stdout.strip().splitlines()[-1])
    holds, rows = run.judge(found, config["reference"])
    for name, value, op, limit in rows:
        print(f"compared {name}: {value} {op} {limit}", file=sys.stderr)
    print(json.dumps({
        "holds": holds and compiles == 0, "cell": cell["name"], "seed": args.seed,
        "prompt_tokens": lengths, "new_tokens": NEW_TOKENS,
        "first_position_judged": min(lengths) - 1, "served_s": served_s,
        "xla_compiles_since_ready": compiles, "found": found, "compared": rows,
        "device": {"platform": dev["platform"], "kind": dev["device_kind"]},
    }), flush=True)
    return 0 if holds and compiles == 0 else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"long probe FAILED: {e}", file=sys.stderr)
        sys.exit(1)
