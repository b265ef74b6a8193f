"""Does the serving path still start, compile and answer on the chip?

    python chip_smoke.py                 # needs a TPU; fails without one
    python chip_smoke.py --rehearse-cpu  # preset=tiny on the CPU; says "cpu"

Drives the system's main path once through the entry points a user calls,
at the full width of the models the repo serves, with random weights made
from a seed:

1. ``codec``   — build the native wire codec from ``csrc/codec.cpp``
   (``make native``; no ``-march=native``), so which codec runs does not
   depend on a binary somebody's test run left behind.
2. ``ops``     — in a child of its own: both Pallas kernels, compiled by
   Mosaic at the 1B serving geometry, against their references.
3. ``llama``   — ``python -m seldon_core_tpu.engine.app`` with one
   ``JAX_GENERATIVE`` unit (``llama3-1b``, bf16, 16 slots, ``max_seq``
   2048): wait for ``/ready``; predictions and one SSE stream; every reply
   64 token ids in ``[0, vocab)``; greedy output repeats, over SSE too;
   ``/stats/warmup`` lists programs and seconds; nothing compiled after
   ``/ready``.
4. ``llama-kernel`` — the same engine with ``decode_kernel=true``.
5. ``bert``    — one ``JAX_MODEL`` unit (BERT-base, bf16, seq 128) through
   the batcher: ``rawTensor`` requests, finite logits of the right shape.
6. ``llama-tp4`` — the ``llama`` phase with ``mesh: "tp=4"``, when the
   engine reports four or more devices; says so when it does not.

One process per chip: this process never imports jax.  It starts children
that need the chip, one at a time, pins them to the TPU so a missing chip
is an error in the child, reads the serving device from the engine
(``/stats/warmup``), and stops each child with SIGTERM and waits for it to
exit before the next starts.  Any failed phase ends the run non-zero with
the child's captured stderr tail; nothing is caught and passed over.

The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as JAX reported it to the engine; the line before it is the
full report (also written to ``chiprun_out/chip_smoke.json``).
"""

from __future__ import annotations

import argparse
import base64
import functools
import json
import math
import os
import signal
import struct
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
MAX_NEW = 64


class SmokeFailure(Exception):
    """A phase failed; the message says which check and why."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------- children


def child_env(platform: str) -> dict:
    """Children run from HERE and are pinned to ``platform``: on the
    default run a missing chip is then an error in the child, not a quiet
    start on the CPU."""
    return {**os.environ, "JAX_PLATFORMS": platform}


def tail(log, limit: int = 6000) -> str:
    log.flush()
    log.seek(max(0, log.seek(0, os.SEEK_END) - limit))
    return log.read().decode(errors="replace")


class Engine:
    """One ``seldon_core_tpu.engine.app`` child serving ``graph``."""

    def __init__(self, graph: dict, port: int, platform: str):
        self.port = port
        self.log = tempfile.TemporaryFile()
        env = child_env(platform)
        env["ENGINE_PREDICTOR"] = base64.b64encode(
            json.dumps({"name": "smoke", "graph": graph}).encode()
        ).decode()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.engine.app",
             "--port", str(port), "--grpc-port", str(port + 1)],
            env=env, cwd=HERE, stdout=self.log, stderr=subprocess.STDOUT,
        )

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def get_json(self, path: str) -> dict:
        with urllib.request.urlopen(self.url(path), timeout=30) as r:
            return json.loads(r.read())

    def post(self, path: str, body: dict, timeout: float = 300.0):
        req = urllib.request.Request(
            self.url(path), data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        return urllib.request.urlopen(req, timeout=timeout)

    def wait_ready(self, timeout: float) -> float:
        t0 = time.monotonic()
        while True:
            check(
                self.proc.poll() is None,
                f"engine exited rc={self.proc.returncode} before /ready",
            )
            try:
                with urllib.request.urlopen(self.url("/ready"), timeout=5) as r:
                    if r.status == 200:
                        return time.monotonic() - t0
            except urllib.error.HTTPError as e:
                text = e.read().decode(errors="replace")
                check(
                    not text.startswith("warmup failed"),
                    f"engine /ready says: {text}",
                )
            except OSError:
                pass
            check(
                time.monotonic() - t0 < timeout,
                f"engine not ready within {timeout:.0f}s",
            )
            time.sleep(1.0)

    def stop(self) -> None:
        """SIGTERM and a bounded wait for a clean exit: the next child
        needs the chip, and only an exited process has let go of it."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise SmokeFailure("engine did not exit within 90s of SIGTERM")
        check(rc == 0, f"engine exited rc={rc} after SIGTERM")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def with_engine(graph: dict, port: int, platform: str, ready_timeout, body):
    """Run ``body(engine)`` against a ready engine; always leave no child."""
    eng = Engine(graph, port, platform)
    try:
        ready_s = eng.wait_ready(ready_timeout)
        facts = body(eng)
        facts["ready_wait_s"] = round(ready_s, 1)
        eng.stop()
        return facts
    except BaseException:
        eng.kill()
        sys.stderr.write(
            f"--- engine :{port} output tail ---\n{tail(eng.log)}\n---\n"
        )
        raise
    finally:
        eng.log.close()


def warmup_of(eng: Engine, want_platform: str) -> dict:
    """``/stats/warmup`` of a ready engine that serves on the platform
    this run is for — the engine's word, not this process's guess."""
    warm = eng.get_json("/stats/warmup")["warmup"]
    check(warm["warmed"] and warm["error"] is None, f"warmup: {warm}")
    dev = warm["device"]
    check(dev is not None, "/stats/warmup reports no device")
    check(
        dev["platform"] == want_platform,
        f"engine serves on platform={dev['platform']!r}, "
        f"this run needs {want_platform!r}",
    )
    return warm


# ------------------------------------------------------------------- phases


def phase_codec(args) -> dict:
    """The stated build step: the codec is git-ignored, so a checkout has
    none and a copied tree may carry one built for another CPU."""
    out = subprocess.run(
        ["make", "native"], cwd=HERE, capture_output=True, text=True,
        timeout=300,
    )
    check(
        out.returncode == 0,
        f"`make native` failed rc={out.returncode}: {out.stderr[-2000:]}",
    )
    return {"built": "seldon_core_tpu/_native/libsctcodec.so (g++ -O3)"}


def phase_ops(args) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--ops-child"]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    out = subprocess.run(
        cmd, env=child_env(args.platform), cwd=HERE, capture_output=True,
        timeout=600,
    )
    lines = out.stdout.decode().strip().splitlines()
    if out.returncode != 0 or not lines:
        sys.stderr.write(
            "--- ops child stderr tail ---\n"
            f"{out.stderr.decode(errors='replace')[-6000:]}\n---\n"
        )
        raise SmokeFailure(f"ops child failed rc={out.returncode}")
    res = json.loads(lines[-1])
    check(
        res["device"]["platform"] == args.platform,
        f"ops child ran on {res['device']['platform']!r}",
    )
    return res


def graph_parameters(params: dict) -> list[dict]:
    types = {bool: "BOOL", int: "INT", float: "FLOAT", str: "STRING"}
    return [
        {"name": k, "value": json.dumps(v) if isinstance(v, bool) else str(v),
         "type": types[type(v)]}
        for k, v in params.items()
    ]


def llama_graph(args, **extra) -> dict:
    return {
        "name": "gen", "type": "MODEL", "implementation": "JAX_GENERATIVE",
        "parameters": graph_parameters({
            "family": "llama",
            # the preset's own max_seq (2048 for llama3-1b), not a cut
            "preset": "tiny" if args.rehearse_cpu else "llama3-1b",
            "dtype": "bfloat16",
            "n_slots": 16,
            "decode_block": 16,
            "max_new_tokens": MAX_NEW,
            **extra,
        }),
    }


def generate(eng: Engine, prompt: list[int]) -> list[int]:
    body = {"strData": json.dumps(
        {"tokens": prompt, "max_new_tokens": MAX_NEW, "temperature": 0.0}
    )}
    with eng.post("/api/v0.1/predictions", body) as r:
        reply = json.loads(r.read())
    check(
        reply.get("status", {}).get("code") == 200,
        f"prediction failed: {json.dumps(reply)[:500]}",
    )
    return json.loads(reply["strData"])["tokens"]


def generate_sse(eng: Engine, prompt: list[int]) -> list[int]:
    streamed, final = [], None
    body = {"tokens": prompt, "max_new_tokens": MAX_NEW, "temperature": 0.0}
    with eng.post("/api/v0.1/predictions/stream", body) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            evt = json.loads(line[len("data: "):])
            if "token" in evt:
                streamed.append(evt["token"])
            if evt.get("done"):
                final = evt["tokens"]
    check(final is not None, "SSE stream ended without a done event")
    check(streamed == final, "SSE token events disagree with the done event")
    return final


def phase_llama(args, port: int = 18960, **extra) -> dict:
    """The generative engine, with ``extra`` graph parameters on top of
    the llama3-1b serving configuration."""

    def body(eng: Engine) -> dict:
        warm = warmup_of(eng, args.platform)
        programs = warm["variants"]["gen"]
        check(
            warm["programs"]["gen"] == len(programs) and len(programs) > 0,
            f"warmup lists no programs: {warm}",
        )
        check(warm["total_seconds"] > 0, f"warmup took no time: {warm}")
        (unit,) = eng.get_json("/stats/breakdown")["generation"].values()
        if "decode_kernel" in extra:
            check(unit["decode_kernel"] is True, "decode kernel is not on")
        vocab = 256 if args.rehearse_cpu else 32000
        # the longest prompt + 64 new tokens must fit the preset's max_seq
        span = 48 if args.rehearse_cpu else 1000
        prompts = [
            [5, 9, 2, 17, 3, 8, 11, 4],
            [(7 * i) % (vocab - 1) + 1 for i in range(40)],
            [(13 * i) % (vocab - 1) + 1 for i in range(span)],
        ]
        replies = [generate(eng, p) for p in prompts]
        again = generate(eng, prompts[0])
        sse = generate_sse(eng, prompts[0])
        for toks in (*replies, again, sse):
            check(len(toks) == MAX_NEW, f"{len(toks)} tokens, want {MAX_NEW}")
            check(
                all(isinstance(t, int) and 0 <= t < vocab for t in toks),
                f"token ids outside [0, {vocab}): {toks}",
            )
        check(again == replies[0], "greedy output did not repeat")
        check(sse == replies[0], "greedy output over SSE differs")
        after = eng.get_json("/stats/warmup")["warmup"]["device"]
        check(
            after["xla_compiles_since_ready"] == 0,
            f"{after['xla_compiles_since_ready']} programs compiled after "
            "/ready",
        )
        (unit,) = eng.get_json("/stats/breakdown")["generation"].values()
        late = [
            c for c in unit["programs"]["recent_compiles"] if not c["warmup"]
        ]
        check(not late, f"programs compiled mid-traffic: {late}")
        return {
            "device": warm["device"],
            "programs_compiled": len(programs),
            "programs": programs,
            "warmup_seconds": warm["total_seconds"],
            "xla_compiles": after["xla_compiles"],
            "xla_compiles_since_ready": 0,
            "requests": len(replies) + 2,
            "tokens_returned": MAX_NEW * (len(replies) + 2),
            "greedy_repeats": True,
            "sse_matches": True,
            "first_tokens": replies[0][:8],
            "memory": after["memory"],
            "pool_bytes": unit["pool"]["bytes"],
        }

    return with_engine(
        llama_graph(args, **extra), port, args.platform, args.ready_timeout,
        body,
    )


def phase_llama_tp4(args, devices: int) -> dict:
    if devices < 4:
        return {"skipped": f"engine reports {devices} device(s); tp=4 needs 4"}
    facts = phase_llama(args, 18968, mesh="tp=4")
    used = [m["bytes_in_use"] for m in facts["memory"]]
    check(len(used) >= 4, f"memory stats for {len(used)} devices")
    # weights and the KV pool are sharded over tp: no device may hold much
    # more than its quarter (device 0 would, were they resident there)
    check(
        min(used[:4]) > 0 and max(used[:4]) < 2 * min(used[:4]),
        f"bytes in use are not spread over the four devices: {used}",
    )
    return facts


def phase_bert(args) -> dict:
    tiny = args.rehearse_cpu
    rows, n_classes = 8, 2
    seq, vocab = (16, 128) if tiny else (128, 30000)
    graph = {
        "name": "bert", "type": "MODEL", "implementation": "JAX_MODEL",
        "parameters": graph_parameters({
            "family": "bert",
            "preset": "tiny" if tiny else "base",
            "dtype": "bfloat16",
            "buckets": "8,32",
            "max_batch": 32,
            "seq": seq,
        }),
    }

    def body(eng: Engine) -> dict:
        warm = warmup_of(eng, args.platform)
        check(warm["programs"]["bert"] == 2, f"warmup: {warm}")
        shapes = []
        for i in range(4):
            toks = [
                (31 * i + 7 * r + c) % (vocab - 1) + 1
                for r in range(rows) for c in range(seq)
            ]
            raw = struct.pack(f"<{len(toks)}i", *toks)
            req = {"rawTensor": {
                "shape": [rows, seq], "dtype": "int32",
                "data": base64.b64encode(raw).decode(),
            }}
            with eng.post("/api/v0.1/predictions", req) as r:
                reply = json.loads(r.read())
            check(
                reply.get("status", {}).get("code") == 200,
                f"prediction failed: {json.dumps(reply)[:500]}",
            )
            shape, values = tensor_of(reply)
            check(
                shape == [rows, n_classes],
                f"logits shape {shape}, want {[rows, n_classes]}",
            )
            check(
                all(math.isfinite(v) for v in values),
                f"non-finite logits: {values[:8]}",
            )
            shapes.append(shape)
        after = eng.get_json("/stats/warmup")["warmup"]["device"]
        check(
            after["xla_compiles_since_ready"] == 0,
            f"{after['xla_compiles_since_ready']} programs compiled after "
            "/ready",
        )
        return {
            "device": warm["device"],
            "programs_compiled": warm["programs"]["bert"],
            "warmup_seconds": warm["total_seconds"],
            "requests": len(shapes),
            "logits_shape": shapes[0],
            "xla_compiles_since_ready": 0,
        }

    return with_engine(graph, 18964, args.platform, args.ready_timeout, body)


def tensor_of(reply: dict) -> tuple[list[int], list[float]]:
    """Shape and flat values of a prediction reply, whichever of the wire's
    tensor encodings the engine chose."""
    if "rawTensor" in reply:
        rt = reply["rawTensor"]
        raw = base64.b64decode(rt["data"])
        if rt["dtype"] == "bfloat16":
            halves = struct.unpack(f"<{len(raw) // 2}H", raw)
            vals = [
                struct.unpack("<f", struct.pack("<I", h << 16))[0]
                for h in halves
            ]
        else:
            check(rt["dtype"] == "float32", f"reply dtype {rt['dtype']}")
            vals = list(struct.unpack(f"<{len(raw) // 4}f", raw))
        return list(rt["shape"]), vals
    data = reply["data"]
    if "tensor" in data:
        return list(data["tensor"]["shape"]), list(data["tensor"]["values"])
    rows = data["ndarray"]
    return [len(rows), len(rows[0])], [v for row in rows for v in row]


# ---------------------------------------------------------------- ops child


def ops_child(rehearse: bool) -> None:
    """Both Pallas kernels at the 1B serving geometry against their own
    references.  float32 cases hold the tolerance ``tests/test_ops.py``
    uses (reference matmuls at HIGHEST precision — XLA's TPU default rounds
    float32 operands); bfloat16 and int8 cases, the dtypes served, are held
    to bfloat16's 8 bits of mantissa against a float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models.llama import _dense_causal_attention
    from seldon_core_tpu.ops import (
        flash_causal_attention_blhd,
        paged_decode_attention,
        paged_decode_attention_reference,
    )
    from seldon_core_tpu.utils.device import (
        configure_compile_cache,
        serving_device,
    )

    configure_compile_cache()
    device = serving_device()
    interpret = jax.default_backend() == "cpu"
    S, H, KV, D, BS = (2, 4, 2, 16, 16) if rehearse else (16, 32, 8, 64, 16)
    WB = 4 if rehearse else 128  # the full max_seq 2048 window
    NB = 1 + S * WB
    rng = np.random.default_rng(0)
    f32 = {"rtol": 2e-5, "atol": 2e-5}
    bf16 = {"rtol": 2e-2, "atol": 2e-2}
    checks = []

    def compare(name, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if not np.isfinite(got).all():
            raise SystemExit(f"{name}: non-finite output")
        np.testing.assert_allclose(got, want, err_msg=name, **tol)
        checks.append({
            "kernel": name, "compiled": not interpret,
            "max_abs_err": float(np.abs(got - want).max()), **tol,
        })

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def reference(fn, *args, **kw):
        # references only: the context would also raise the precision of
        # the kernels' own bfloat16 matmuls and change what is under test
        with jax.default_matmul_precision("highest"):
            return fn(
                *(a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
                  for a in args),
                **{k: a.astype(jnp.float32) for k, a in kw.items()},
            )

    for L in (1, 4):
        q = normal(S, L, H, D)
        k, v = normal(NB, BS, KV, D), normal(NB, BS, KV, D)
        table = jnp.asarray(
            1 + rng.permutation(S * WB).reshape(S, WB), jnp.int32
        )
        pos = jnp.asarray(rng.integers(0, WB * BS - L, S), jnp.int32)
        compare(
            f"paged_decode_attention f32 L={L}",
            paged_decode_attention(q, k, v, table, pos),
            reference(paged_decode_attention_reference, q, k, v, table, pos),
            f32,
        )
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        compare(
            f"paged_decode_attention bf16 L={L}",
            paged_decode_attention(qb, kb, vb, table, pos),
            reference(
                paged_decode_attention_reference, qb, kb, vb, table, pos
            ),
            bf16,
        )
        ki = jnp.asarray(rng.integers(-127, 128, k.shape), jnp.int8)
        vi = jnp.asarray(rng.integers(-127, 128, v.shape), jnp.int8)
        scales = {
            name: jnp.asarray(rng.random((NB, BS, KV)) * 0.02, jnp.bfloat16)
            for name in ("k_scale", "v_scale")
        }
        compare(
            f"paged_decode_attention int8+scales L={L}",
            paged_decode_attention(qb, ki, vi, table, pos, **scales),
            reference(
                paged_decode_attention_reference, qb, ki, vi, table, pos,
                **scales,
            ),
            bf16,
        )
    # the largest and the smallest prefill bucket of max_seq 2048
    for seq in ((64, 16) if rehearse else (2048, 16)):
        q, k, v = (normal(1, seq, H, D) for _ in range(3))
        compare(
            f"flash_attention f32 seq={seq}",
            flash_causal_attention_blhd(q, k, v),
            reference(_dense_causal_attention, q, k, v),
            {"rtol": 2e-4, "atol": 2e-4} if seq > 128 else f32,
        )
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        compare(
            f"flash_attention bf16 seq={seq}",
            flash_causal_attention_blhd(qb, kb, vb),
            reference(_dense_causal_attention, qb, kb, vb),
            bf16,
        )
    print(json.dumps({"device": device, "checks": checks}))


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run every phase at preset=tiny on the CPU backend; the "
        "output names the cpu and is not a chip result",
    )
    ap.add_argument("--ops-child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.ops_child:
        ops_child(args.rehearse_cpu)
        return 0
    args.platform = "cpu" if args.rehearse_cpu else "tpu"
    args.ready_timeout = 700.0
    # JAX serves on the first platform listed ("tpu,cpu" is the TPU)
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if not args.rehearse_cpu and pinned not in ("", "tpu"):
        print(
            f"chip_smoke FAILED: JAX_PLATFORMS="
            f"{os.environ['JAX_PLATFORMS']!r} pins JAX away from the chip; "
            "this smoke serves on the TPU or not at all "
            "(--rehearse-cpu is the CPU walk-through)",
            file=sys.stderr,
        )
        return 2

    report: dict = {"rehearsal": args.rehearse_cpu, "phases": {}}
    phases = [
        ("codec", phase_codec),
        ("ops", phase_ops),
        ("llama", phase_llama),
        ("llama-kernel",
         functools.partial(phase_llama, port=18962, decode_kernel=True)),
        ("bert", phase_bert),
    ]
    t_start = time.monotonic()
    device = None
    while phases:
        name, fn = phases.pop(0)
        t0 = time.monotonic()
        try:
            facts = fn(args)
        except SmokeFailure as e:
            print(f"chip_smoke FAILED in phase {name!r}: {e}", file=sys.stderr)
            return 1
        facts["seconds"] = round(time.monotonic() - t0, 1)
        report["phases"][name] = facts
        print(f"phase {name}: {json.dumps(facts)}", flush=True)
        if name == "llama":
            device = facts["device"]
            phases.append((
                "llama-tp4",
                functools.partial(
                    phase_llama_tp4, devices=device["device_count"]
                ),
            ))
    report["seconds"] = round(time.monotonic() - t_start, 1)
    report["device"] = {
        "platform": device["platform"],
        "kind": device["device_kind"],
        "count": device["device_count"],
    }
    report["native_codec"] = device["native_codec"]
    try:
        os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
        with open(os.path.join(HERE, "chiprun_out", "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1)
    except OSError:
        pass  # a read-only checkout still gets the report on stdout
    print(json.dumps(report))
    last = {"ok": True, "device": report["device"]}
    if args.rehearse_cpu:
        last["rehearsal"] = True  # a walk-through, not a chip result
    print(json.dumps(last))
    return 0


if __name__ == "__main__":
    sys.exit(main())
