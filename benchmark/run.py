"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the engine (``python -m seldon_core_tpu.engine.app``) as a child with
the cell's graph and weights from ``--seed``, waits for ``/ready``, drives
it over HTTP with the cell's traffic, reads the engine's ``/stats/*`` before
and after the window, stops the engine, and prints the result as the last
line of standard output.  Needs a TPU: an engine that came up on anything
else, or on fewer chips than the cell asks for, ends the run non-zero with
no result.  ``--rehearse-cpu`` walks the same path at the tiny configuration
of ``benchmark/rehearsal/`` on the CPU and prints no device metric.

Everything a cell is made of is found by name: the configuration's file and
the traffic file from BENCHMARK.json, each metric's reader at
``benchmark/metrics/<metric>.py``, the configuration's reference kind and
its judge at ``benchmark/reference/kinds|judges/<name>.py``; the arrival
process is a parameter of the traffic file.  This process never imports jax.
"""

from __future__ import annotations

T_COMMAND = __import__("time").perf_counter()

import argparse
import asyncio
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "reference")]

import costs  # noqa: E402
import frame  # noqa: E402
import loadgen  # noqa: E402
import peaks  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
from engine import BenchFailure, Engine, graph_of  # noqa: E402

CACHE = os.path.join(ROOT, ".benchmark_cache")
PROBE_PROMPT, PROBE_NEW, N_PROBES, PROBE_ROWS = 64, 32, 4, 8


def info(**kv) -> None:
    """An earlier line: for the reader, never parsed by the driver."""
    print("# " + json.dumps(kv), flush=True)


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchFailure(f"BENCHMARK.json has no {what} named {name!r}")


# ------------------------------------------------------------- reference


def reference_verdict(config_name: str, config_path: str, seed: int,
                      env: dict, probes: dict) -> dict:
    """The plain reference's word on the probes, once for each
    (configuration, seed): kept in a file in the checkout, and worked out in
    a child of its own after the engine has exited — never inside the
    window, never in set-up, and on the device the engine has let go of."""
    path = os.path.join(CACHE, "reference", f"{config_name}.{seed}.json")
    key = hashlib.sha256(json.dumps(probes, sort_keys=True).encode()).hexdigest()
    try:
        with open(path) as f:
            cached = json.load(f)
        if cached["probes_sha256"] == key:
            return {**cached["found"], "cached": True}
    except (OSError, ValueError, KeyError):
        pass
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "reference", "check.py"),
         "--config", config_path, "--seed", str(seed)],
        env=env, cwd=ROOT, input=json.dumps(probes) + "\n",
        capture_output=True, text=True, timeout=900,
    )
    if done.returncode != 0:
        raise BenchFailure(
            f"reference child failed rc={done.returncode}: {done.stderr[-3000:]}"
        )
    found = json.loads(done.stdout.strip().splitlines()[-1])
    found["seconds"] = time.perf_counter() - t0
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"probes_sha256": key, "found": found}, f)
    return {**found, "cached": False}


def judge(found: dict, reference: dict) -> tuple[bool, list]:
    """Hold what the reference found to the configuration's stated limits,
    by the judge the configuration names (``benchmark/reference/judges/
    <judge>.py``: ``judge(found, limits) -> bool``), or its kind's own.
    Beside the verdict, each number compared with its limit, where the
    judge says which they are (``compared(found, limits)``)."""
    try:
        mod = frame.named_module("judges", reference.get("judge") or found["judge"])
    except LookupError as e:
        raise BenchFailure(str(e)) from None
    rows = mod.compared(found, reference) if hasattr(mod, "compared") else []
    return bool(mod.judge(found, reference)), [list(r) for r in rows]


# ---------------------------------------------------------------- probes


def probe_prompts(vocab: int) -> list[list[int]]:
    return [
        [((7 + 6 * p) * i + 11 * p) % (vocab - 1) + 1 for i in range(PROBE_PROMPT)]
        for p in range(N_PROBES)
    ]


def probe_rows(vocab: int, seq: int):
    import numpy as np

    i = np.arange(PROBE_ROWS * seq, dtype=np.int64).reshape(PROBE_ROWS, seq)
    return ((31 * i + 7 * (i // seq)) % (vocab - 1) + 1).astype(np.int32)


async def run_probes(base: str, route: str, vocab: int, seq: int) -> dict:
    import aiohttp

    async with aiohttp.ClientSession() as session:
        if route == "stream":
            out = []
            for prompt in probe_prompts(vocab):
                s = loadgen.Sample(0, 0.0, time.perf_counter(), asked=PROBE_NEW)
                toks: list[int] = []
                body = loadgen.stream_body(
                    {"tokens": prompt, "max_new": PROBE_NEW}, 0.0
                )
                await loadgen.stream_request(session, base, body, s, vocab, toks)
                if not s.ok:
                    raise BenchFailure(f"probe stream failed: {s.error}")
                out.append({"prompt": prompt, "tokens": toks})
            return {"probes": out}
        rows = probe_rows(vocab, seq)
        s = loadgen.Sample(0, 0.0, time.perf_counter(), asked=PROBE_ROWS)
        keep: list = []
        await loadgen.predict_request(
            session, base, loadgen.predict_body(rows), s, keep
        )
        if not s.ok:
            raise BenchFailure(f"probe batch failed: {s.error}")
        return {"tokens": rows.tolist(), "outputs": keep[0].tolist()}


# ----------------------------------------------------------------- trace


async def trace_slice(base: str, out_dir: str, start_at: float, seconds: float):
    """Ask the engine for a profiler trace of ``seconds`` from ``start_at``."""
    import aiohttp

    await asyncio.sleep(max(0.0, start_at - time.perf_counter()))
    timeout = aiohttp.ClientTimeout(total=300)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async with session.post(base + "/profile/start", json={"dir": out_dir}) as r:
            if r.status != 200:
                raise BenchFailure(f"/profile/start: {r.status} {await r.text()}")
        await asyncio.sleep(seconds)
        async with session.post(base + "/profile/stop") as r:
            if r.status != 200:
                raise BenchFailure(f"/profile/stop: {r.status} {await r.text()}")


def reduce_trace(trace_dir: str) -> dict | None:
    """Reduce the run's ``.xplane.pb`` in a child pinned to the CPU (the
    engine has exited; this process stays off jax)."""
    out_path = os.path.join(trace_dir, "reduced.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace.py"), trace_dir, out_path],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    if done.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {done.stderr[-2000:]}")
    with open(out_path) as f:
        return json.load(f)


# --------------------------------------------------------------- metrics


def read_metric(name: str, run) -> float | None:
    try:
        return frame.named_module(os.path.join(HERE, "metrics"), name).read(run)
    except LookupError as e:
        raise BenchFailure(f"metric {name!r} has no reader: {e}") from None


def metrics_of(manifest: dict, group: str, cell: str, run) -> dict:
    out = {}
    for m in manifest[group]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = read_metric(m["name"], run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def summary(run) -> dict:
    """Medians and sample counts behind the percentiles: printed on an
    earlier line of every run, kept in PERF.md, deciding no PR."""
    import metriclib as ml

    out = {"requests_in_window": len(run.counted), "window_s": run.window_s}
    for name, xs in (("ttft_ms", ml.ttft_ms(run)), ("tpot_ms", ml.tpot_ms(run)),
                     ("latency_ms", ml.latency_ms(run)), ("late_ms", ml.late_ms(run))):
        if xs:
            out[name] = {"n": len(xs), "p50": stats.percentile(xs, 50),
                         "p95": stats.percentile(xs, 95), "max": max(xs)}
    # a growing backlog shows as more requests in flight at the window's end
    # than at its middle, and a later half that waits longer than the first
    mid = (run.w0 + run.w1) / 2
    for at, t in (("mid", mid), ("end", run.w1)):
        out[f"in_flight_at_{at}"] = sum(
            1 for s in run.samples
            if s.sent <= t and (s.done is None or s.done > t)
        )
    halves = [[], []]
    for s in run.counted:
        if s.first is not None:
            halves[s.due >= mid].append((s.first - s.due) * 1e3)
    if all(halves):
        out["ttft_ms_p50_by_half"] = [stats.percentile(h, 50) for h in halves]
    out.update(admission_groups(run))
    return out


def admission_groups(run) -> dict:
    """How the streams' slots are phased: first-token instants that lie
    within half a block interval of each other are one admission group.
    The scheduler admits at block boundaries, equal output lengths keep a
    grouping for the whole run, and the batch-wide decode window follows
    the slot that is furthest on, so runs that group differently do
    different work.  (Decode-closed admitted one group of 32 in every wave
    of every run, its slow runs too: PERF.md, Findings, PR 23.)"""
    import collections

    import metriclib as ml

    gaps = sorted(
        (b[0] - a[0], a[0]) for s in run.samples if s.ok
        for blocks in [ml.blocks_of(s.token_times)]
        for a, b in zip(blocks, blocks[1:])
    )
    longest_s, longest_from = gaps[-1] if gaps else (0.0, 0.0)
    # a stall of the engine (or of this process) stops every live stream at
    # once; one stream that waits alone was held back by the scheduler
    stalled = sum(1 for g, t in gaps if g > 1.0 and t < longest_from + longest_s
                  and t + g > longest_from)
    gaps = [g for g, _ in gaps]
    firsts = sorted(s.first for s in run.samples if s.ok and s.first is not None)
    if not gaps or not firsts:
        return {}
    block_s = gaps[len(gaps) // 2]
    sizes, n = [], 1
    for a, b in zip(firsts, firsts[1:]):
        if b - a > block_s / 2:
            sizes.append(n)
            n = 0
        n += 1
    sizes.append(n)
    count = collections.Counter(sizes)
    return {"block_interval_ms": block_s * 1e3,
            "longest_block_gap_ms": longest_s * 1e3,  # a stall shows here,
            "longest_block_gap_from_s": longest_from - run.w0,  # from the window's start
            "streams_stalled_with_it": stalled,  # gaps over 1 s that overlap it
            "admission_group_sizes": {str(k): count[k] for k in sorted(count)},
            "admission_groups_first": sizes[:12]}


# ------------------------------------------------------------------ main


def ensure_native_codec() -> None:
    """The wire codec is built, not committed: a checkout has none."""
    so = os.path.join(ROOT, "seldon_core_tpu", "_native", "libsctcodec.so")
    if os.path.exists(so) or not os.path.exists(os.path.join(ROOT, "Makefile")):
        return
    done = subprocess.run(
        ["make", "native"], cwd=ROOT, capture_output=True, text=True, timeout=300
    )
    if done.returncode != 0:
        info(native_codec_build="failed", stderr=done.stderr[-500:])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--rate", type=float, help="sweep only: another open-loop rate")
    ap.add_argument("--graph-param", action="append", default=[], metavar="NAME=JSON",
                    help="control only: the engine's graph with this parameter set "
                         "(a lower-precision path switched on); the reference keeps "
                         "the configuration as it is committed")
    args = ap.parse_args()

    manifest = load_json("BENCHMARK.json")
    cell = named(manifest["workloads"], args.workload, "workload")
    cfg_entry = named(manifest["configs"], cell["config"], "configuration")
    cfg_path = cfg_entry["file"]
    if args.rehearse_cpu:
        cfg_path = os.path.join("benchmark", "rehearsal", os.path.basename(cfg_path))
    config = load_json(cfg_path)
    mix = load_json(os.path.join("benchmark", "traffic", cell["traffic"] + ".json"))
    if args.rate:
        mix["rate_per_s"] = args.rate
    platform = "cpu" if args.rehearse_cpu else "tpu"
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if not args.rehearse_cpu and pinned not in ("", "tpu"):
        raise BenchFailure(
            f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} pins JAX away from "
            "the chip; the benchmark measures on a TPU or not at all"
        )
    extra_env = {}
    if args.rehearse_cpu and cell["chips"] > 1:
        extra_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}"
        )

    run_dir = os.path.join(CACHE, "runs", f"{cell['name']}.{os.getpid()}")
    os.makedirs(run_dir, exist_ok=True)
    ensure_native_codec()
    vocab, seq = int(config["vocab_size"]), int(mix.get("seq", 0))
    window_s = float(args.seconds)
    lead = float(mix.get("lead_in_s", 0.0))

    served = copy.deepcopy(config)  # the reference child reads the file as it is
    for item in args.graph_param:
        name, _, value = item.partition("=")
        served["graph"]["parameters"][name] = json.loads(value)
    engine = Engine(
        graph_of(served, args.seed), platform,
        os.path.join(run_dir, "engine.log"), extra_env,
    )
    trace_dir = os.path.join(run_dir, "trace")
    try:
        # the traffic is made while the engine boots: bodies are encoded
        # before the window, never inside it
        horizon = lead + window_s
        dues = None
        if traffic.open_loop(mix):
            dues = traffic.due_times(mix, horizon)
            n = len(dues)
        else:
            n = int(mix["pool"])
        requests = traffic.make_requests(mix, args.seed, vocab, n)
        if mix["route"] == "stream":
            bodies = [
                loadgen.stream_body(r, float(mix.get("temperature", 0.0)))
                for r in requests
            ]
        else:
            import numpy as np

            rng = np.random.default_rng([args.seed, 0xB0D1])
            by_rows = {
                r: loadgen.predict_body(
                    rng.integers(1, vocab, size=(r, seq), dtype=np.int32)
                )
                for r in sorted({q["rows"] for q in requests})
            }
            bodies = [by_rows[q["rows"]] for q in requests]

        engine.wait_ready(1100.0)
        ready_s = time.perf_counter() - T_COMMAND
        warm = engine.warmup()
        dev = warm["device"]
        info(platform=dev["platform"], device_kind=dev["device_kind"],
             device_count=dev["device_count"], native_codec=dev["native_codec"],
             ready_s=ready_s, warmup_s=warm["total_seconds"],
             programs=warm["programs"], cell=cell["name"], seed=args.seed)
        if dev["platform"] != platform or dev["device_count"] < cell["chips"]:
            raise BenchFailure(
                f"engine serves on {dev['platform']} x{dev['device_count']}; "
                f"the cell needs {platform} x{cell['chips']}"
            )
        chip_peaks = None if args.rehearse_cpu else peaks.peaks_of(dev["device_kind"])

        probes_before = asyncio.run(run_probes(engine.base, mix["route"], vocab, seq))
        before = engine.get_json("/stats/summary")

        async def offered():
            load = loadgen.Load(engine.base, mix, requests, bodies, vocab)
            jobs = [asyncio.create_task(load.run(window_s, dues))]
            if args.trace:
                slice_s = min(float(mix.get("trace_slice_s", 3.0)), window_s / 2)
                start = time.perf_counter() + lead + (window_s - slice_s) / 2
                jobs.append(asyncio.create_task(
                    trace_slice(engine.base, trace_dir, start, slice_s)
                ))
            done = await asyncio.gather(*jobs)
            return load, done[0]

        load, (w0, w1) = asyncio.run(offered())
        after = engine.get_json("/stats/summary")
        probes_after = asyncio.run(run_probes(engine.base, mix["route"], vocab, seq))
        warm_after = engine.warmup()["device"]
    except BaseException:
        engine.stop()
        sys.stderr.write(f"--- engine log tail ---\n{engine.log_tail()}\n---\n")
        raise
    engine.stop()

    found = reference_verdict(
        cfg_entry["name"] + (".cpu" if args.rehearse_cpu else ""), cfg_path,
        args.seed, {**os.environ, **extra_env, "JAX_PLATFORMS": platform},
        probes_before,
    )
    reduced = None
    if args.trace and os.path.isdir(trace_dir):
        reduced = reduce_trace(trace_dir)

    in_window = [s for s in load.samples if w0 <= s.due < w1]
    failed = [s for s in in_window if not s.ok]
    others_failed = [s for s in load.samples if not s.ok and not (w0 <= s.due < w1)]
    run = types.SimpleNamespace(
        cell=cell, config=config, mix=mix,
        window_s=w1 - w0, w0=w0, w1=w1, t_command=T_COMMAND,
        samples=load.samples, counted=[s for s in in_window if s.ok],
        before=before, after=after, trace=reduced,
        peaks=chip_peaks, chips=cell["chips"],
        stats=stats, costs=costs, traffic=traffic,
    )
    reference_ok, compared = judge(found, config["reference"])
    held = {  # every other check, as the number compared and its limit
        "platform": ["platform", dev["platform"], "==", platform],
        "no_compile_after_ready": ["xla_compiles_since_ready",
                                   warm_after["xla_compiles_since_ready"], "==", 0],
        "probes_repeat": ["probes_changed_over_window",
                          int(probes_before != probes_after), "==", 0],
        "no_failure_outside_window": ["failed_outside_window", len(others_failed), "==", 0],
    }
    checks = {name: frame.all_hold([row]) for name, row in held.items()}
    checks["reference"] = reference_ok
    compared += held.values()
    limits = {k: v for k, v in config["reference"].items() if k != "why"}
    info(checks=checks, reference=found, reference_limits=limits, compared=compared,
         xla_compiles_since_ready=warm_after["xla_compiles_since_ready"],
         errors=sorted({s.error for s in failed + others_failed})[:5])
    groups = {
        g: metrics_of(manifest, g, cell["name"], run)
        for g in ("end_to_end", "per_layer")
    }
    if args.rehearse_cpu:
        # the readers ran; a CPU's numbers are not shown under their names
        info(rehearsal=True, readers_ran={g: sorted(m) for g, m in groups.items()},
             admission_group_sizes=admission_groups(run).get("admission_group_sizes"))
    else:
        info(**{g: {k: v["value"] for k, v in m.items()} for g, m in groups.items()})
        seen = summary(run)
        info(**seen)
        if seen.get("longest_block_gap_ms", 0.0) > 1000.0:
            info(stall_s=seen["longest_block_gap_ms"] / 1e3,
                 engine_log_tail=engine.log_tail(1500))

    memory = [m["peak_bytes_in_use"] for m in warm_after.get("memory", [])
              if m.get("peak_bytes_in_use") is not None]
    device = {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"],
        "memory_peak_bytes": max(memory) if memory else None,
    }
    result = {
        "correct": all(checks.values()),
        "attempted": len(in_window), "failed": len(failed),
        "metrics": {}, "device": device,
    }
    if args.rehearse_cpu:
        result["rehearsal"] = True  # a walk-through: no device metric
    else:
        result["metrics"] = groups["per_layer" if args.trace else "end_to_end"]
        if args.trace:
            if reduced is None or not reduced.get("busy_s"):
                raise BenchFailure("the traced run saw no operation on the device")
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
    shutil.rmtree(run_dir, ignore_errors=True)
    # each number compared beside its limit: the last lines of standard
    # error, which the driver's record keeps where a run is not correct
    for name, value, op, limit in compared:
        print(f"compared {name}: {value} {op} {limit}", file=sys.stderr)
    print(f"correct: {result['correct']} {json.dumps(checks)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr)
        sys.exit(1)
