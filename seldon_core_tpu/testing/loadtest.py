"""Wire-level load harness: multi-process async clients hammering a REST or
gRPC serving endpoint.

The reference load-tests with a locust master + 192 slave workers hitting
the engine's REST endpoint (reference: util/loadtester/scripts/
predict_rest_locust.py:17-50, docs/benchmarking.md:19-36).  Here the same
shape in one tool: ``--processes`` forked client processes, each running an
asyncio loop with ``--concurrency`` in-flight requests over pooled
connections, merged into one latency histogram (log-spaced bins, so
percentiles merge exactly across processes).

Every request crosses a real socket and pays JSON/proto codec cost.  An
operator's tool for their own cluster (the repo's speeds are measured by
``benchmark/``, PERF.md):

    sct-loadtest http://host:8000/api/v0.1/predictions -c 64 -P 4 -d 10
    sct-loadtest host:5001 --grpc -c 64 -P 4 -d 10
    sct-loadtest ... --token-url http://gw:8080/oauth/token --oauth-key k \\
        --oauth-secret s                       # authenticated gateway runs
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import multiprocessing
import sys
import time
from typing import Any

import numpy as np

# log-spaced latency bins: 50us .. 50s, 40 per decade — fine enough that a
# merged-histogram percentile is within ~3% of the true value
_BIN_EDGES = np.logspace(np.log10(5e-5), np.log10(50.0), 241)


def _histogram() -> np.ndarray:
    return np.zeros(len(_BIN_EDGES) + 1, np.int64)


def _record(hist: np.ndarray, seconds: float) -> None:
    hist[int(np.searchsorted(_BIN_EDGES, seconds))] += 1


def _percentile(hist: np.ndarray, q: float) -> float:
    total = hist.sum()
    if total == 0:
        return 0.0
    target = q / 100.0 * total
    cum = np.cumsum(hist)
    idx = int(np.searchsorted(cum, target))
    idx = min(idx, len(_BIN_EDGES) - 1)
    return float(_BIN_EDGES[idx])


@dataclasses.dataclass
class WorkerConfig:
    target: str  # URL (REST) or host:port (gRPC)
    grpc: bool
    payloads: list[bytes]  # serialized request bodies to cycle through
    concurrency: int
    duration_s: float
    headers: dict[str, str]
    warmup_requests: int = 8
    grpc_lib: str = "h2"  # "h2" (wire/h2grpc client) or "grpcio"
    # > 0 switches the REST loop to OPEN-LOOP Poisson arrivals: requests
    # launch on an exponential-gap clock regardless of completions.  A
    # closed loop self-throttles under overload (every slow response slows
    # the offered rate), hiding queue growth; the open loop keeps offering
    # load, so offered-vs-achieved exposes the capacity gap.
    arrival_rps: float = 0.0
    seed: int = 0


@dataclasses.dataclass
class LoadResult:
    requests: int
    failures: int
    elapsed_s: float
    hist: np.ndarray
    # open-loop runs only: arrivals DISPATCHED (>= requests completed
    # within the drain window); 0 for closed-loop runs
    offered: int = 0

    @property
    def rps(self) -> float:
        return self.requests / self.elapsed_s if self.elapsed_s > 0 else 0.0

    @property
    def offered_rps(self) -> float:
        return self.offered / self.elapsed_s if self.elapsed_s > 0 else 0.0

    def percentile_ms(self, q: float) -> float:
        return _percentile(self.hist, q) * 1000.0

    def summary(self) -> dict[str, Any]:
        out = {
            "requests": self.requests,
            "failures": self.failures,
            "seconds": round(self.elapsed_s, 2),
            "rps": round(self.rps, 2),
            "p50_ms": round(self.percentile_ms(50), 3),
            "p90_ms": round(self.percentile_ms(90), 3),
            "p95_ms": round(self.percentile_ms(95), 3),
            "p99_ms": round(self.percentile_ms(99), 3),
        }
        if self.offered:
            out["offered"] = self.offered
            out["offered_rps"] = round(self.offered_rps, 2)
            out["achieved_ratio"] = (
                round(self.requests / self.offered, 4) if self.offered else None
            )
        return out


async def _rest_worker_loop(cfg: WorkerConfig) -> tuple[int, int, int, np.ndarray]:
    import aiohttp

    hist = _histogram()
    counts = [0, 0]  # ok, fail
    offered = 0
    # open loop: in-flight is unbounded by design (limit=0), the server's
    # admission control is what's under test
    limit = 0 if cfg.arrival_rps > 0 else cfg.concurrency + 8
    connector = aiohttp.TCPConnector(limit=limit, keepalive_timeout=60)
    headers = {"Content-Type": "application/json", **cfg.headers}
    async with aiohttp.ClientSession(connector=connector) as session:

        async def one(i: int) -> bool:
            body = cfg.payloads[i % len(cfg.payloads)]
            try:
                async with session.post(cfg.target, data=body, headers=headers) as resp:
                    await resp.read()
                    return resp.status == 200
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
                return False

        # connection warmup (outside the timed window)
        await asyncio.gather(*(one(i) for i in range(cfg.warmup_requests)))

        stop_at = time.perf_counter() + cfg.duration_s

        if cfg.arrival_rps > 0:

            async def timed(i: int) -> None:
                t0 = time.perf_counter()
                ok = await one(i)
                _record(hist, time.perf_counter() - t0)
                counts[0 if ok else 1] += 1

            rng = np.random.default_rng(cfg.seed)
            inflight: set[asyncio.Task] = set()
            i = 0
            next_t = time.perf_counter()
            while next_t < stop_at:
                delay = next_t - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                t = asyncio.get_running_loop().create_task(timed(i))
                inflight.add(t)
                t.add_done_callback(inflight.discard)
                offered += 1
                i += 1
                next_t += float(rng.exponential(1.0 / cfg.arrival_rps))
            if inflight:
                # drain window: late responses still count; stragglers
                # past it are abandoned (they'd skew elapsed_s instead)
                await asyncio.wait(inflight, timeout=30.0)
                for t in list(inflight):
                    t.cancel()
            return counts[0], counts[1], offered, hist

        async def worker(wid: int) -> None:
            i = wid
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                ok = await one(i)
                _record(hist, time.perf_counter() - t0)
                counts[0 if ok else 1] += 1
                i += cfg.concurrency

        await asyncio.gather(*(worker(w) for w in range(cfg.concurrency)))
    return counts[0], counts[1], 0, hist


async def _grpc_worker_loop(cfg: WorkerConfig) -> tuple[int, int, int, np.ndarray]:
    if cfg.grpc_lib == "grpcio":
        return await _grpcio_worker_loop(cfg)

    # default: the framework's own asyncio gRPC client (wire/h2grpc.py) —
    # the product client the engine/gateway use for pod-to-pod hops, and
    # ~3x cheaper per call than grpcio on small cores
    from seldon_core_tpu.proto import prediction_pb2 as pb
    from seldon_core_tpu.wire import FastGrpcChannel, GrpcCallError

    hist = _histogram()
    counts = [0, 0]
    path = "/seldon.protos.Seldon/Predict"
    payloads = cfg.payloads
    metadata = tuple(cfg.headers.items())
    channel = FastGrpcChannel(cfg.target)
    try:

        async def one(i: int) -> bool:
            try:
                raw = await channel.call(
                    path, payloads[i % len(payloads)], timeout=30.0, metadata=metadata
                )
                reply = pb.SeldonMessage.FromString(raw)
                return reply.status.code in (0, 200)
            except (GrpcCallError, ConnectionError, asyncio.TimeoutError, OSError):
                return False

        await asyncio.gather(*(one(i) for i in range(cfg.warmup_requests)))
        stop_at = time.perf_counter() + cfg.duration_s

        async def worker(wid: int) -> None:
            i = wid
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                ok = await one(i)
                _record(hist, time.perf_counter() - t0)
                counts[0 if ok else 1] += 1
                i += cfg.concurrency

        await asyncio.gather(*(worker(w) for w in range(cfg.concurrency)))
    finally:
        await channel.close()
    return counts[0], counts[1], 0, hist


async def _grpcio_worker_loop(cfg: WorkerConfig) -> tuple[int, int, int, np.ndarray]:
    import grpc

    from seldon_core_tpu.proto import prediction_pb2 as pb
    from seldon_core_tpu.proto.grpc_defs import SERVER_OPTIONS, Stub

    hist = _histogram()
    counts = [0, 0]
    requests = [pb.SeldonMessage.FromString(p) for p in cfg.payloads]
    metadata = tuple(cfg.headers.items()) or None
    async with grpc.aio.insecure_channel(cfg.target, options=SERVER_OPTIONS) as ch:
        stub = Stub(ch, "Seldon")

        async def one(i: int) -> bool:
            try:
                reply = await stub.Predict(
                    requests[i % len(requests)], timeout=30.0, metadata=metadata
                )
                return reply.status.code in (0, 200)
            except grpc.aio.AioRpcError:
                return False

        await asyncio.gather(*(one(i) for i in range(cfg.warmup_requests)))
        stop_at = time.perf_counter() + cfg.duration_s

        async def worker(wid: int) -> None:
            i = wid
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                ok = await one(i)
                _record(hist, time.perf_counter() - t0)
                counts[0 if ok else 1] += 1
                i += cfg.concurrency

        await asyncio.gather(*(worker(w) for w in range(cfg.concurrency)))
    return counts[0], counts[1], 0, hist


def _run_worker(cfg: WorkerConfig) -> tuple[int, int, int, bytes]:
    loop = _grpc_worker_loop if cfg.grpc else _rest_worker_loop
    ok, fail, offered, hist = asyncio.run(loop(cfg))
    return ok, fail, offered, hist.tobytes()


def run_load(
    target: str,
    payloads: list[bytes],
    *,
    grpc: bool = False,
    concurrency: int = 32,
    processes: int = 1,
    duration_s: float = 10.0,
    headers: dict[str, str] | None = None,
    grpc_lib: str = "h2",
    arrival_rps: float = 0.0,
    seed: int = 0,
) -> LoadResult:
    """Drive ``target`` for ``duration_s``; returns merged results.

    ``concurrency`` is per process — total in-flight = concurrency ×
    processes.  With ``processes > 1`` client CPU (JSON encode, socket IO)
    scales past one GIL, like the reference's locust slaves.

    ``arrival_rps > 0`` selects OPEN-LOOP Poisson arrivals (REST only):
    the rate is split evenly across processes, ``concurrency`` is ignored,
    and the result carries offered-vs-achieved throughput.
    """
    cfg = WorkerConfig(
        target=target,
        grpc=grpc,
        payloads=payloads,
        concurrency=concurrency,
        duration_s=duration_s,
        headers=headers or {},
        grpc_lib=grpc_lib,
        arrival_rps=arrival_rps / max(1, processes),
        seed=seed,
    )
    if arrival_rps > 0 and grpc:
        raise ValueError("open-loop arrivals are REST-only")
    t0 = time.perf_counter()
    if processes <= 1:
        results = [_run_worker(cfg)]
    else:
        ctx = multiprocessing.get_context("spawn")
        cfgs = [dataclasses.replace(cfg, seed=cfg.seed + p) for p in range(processes)]
        with ctx.Pool(processes) as pool:
            results = pool.map(_run_worker, cfgs)
    elapsed = time.perf_counter() - t0
    hist = _histogram()
    ok = fail = offered = 0
    for o, f, off, h in results:
        ok += o
        fail += f
        offered += off
        hist += np.frombuffer(h, np.int64)
    return LoadResult(
        requests=ok + fail, failures=fail, elapsed_s=elapsed, hist=hist,
        offered=offered,
    )


# ---------------------------------------------------------------------------
# payload sources + CLI
# ---------------------------------------------------------------------------

def default_rest_payload(rows: int = 1, features: int = 3) -> bytes:
    batch = np.random.default_rng(0).normal(size=(rows, features)).round(3)
    return json.dumps({"data": {"ndarray": batch.tolist()}}).encode()


def default_grpc_payload(rows: int = 1, features: int = 3) -> bytes:
    from seldon_core_tpu.contract import Payload, payload_to_proto

    batch = np.random.default_rng(0).normal(size=(rows, features))
    return payload_to_proto(Payload.from_array(batch)).SerializeToString()


def payloads_from_contract(
    path: str, batch_size: int, *, grpc: bool, tensor: bool = False, pool: int = 16
) -> list[bytes]:
    from seldon_core_tpu.contract import Payload, payload_to_proto
    from seldon_core_tpu.contract.payload import DataKind
    from seldon_core_tpu.testing.contract import Contract

    contract = Contract.load(path).unfold()
    rng = np.random.default_rng(0)
    out = []
    names = contract.feature_names()
    for _ in range(pool):
        batch = contract.generate_batch(batch_size, rng)
        if grpc:
            kind = DataKind.TENSOR if tensor else DataKind.NDARRAY
            out.append(
                payload_to_proto(
                    Payload.from_array(batch, names=names, kind=kind)
                ).SerializeToString()
            )
        else:
            if tensor:
                data = {"names": names, "tensor": {"shape": list(batch.shape),
                                                   "values": batch.ravel().tolist()}}
            else:
                data = {"names": names, "ndarray": batch.tolist()}
            out.append(json.dumps({"data": data}).encode())
    return out


def _fetch_token(token_url: str, key: str, secret: str) -> str:
    import urllib.parse
    import urllib.request

    req = urllib.request.Request(
        token_url,
        urllib.parse.urlencode(
            {"grant_type": "client_credentials", "client_id": key,
             "client_secret": secret}
        ).encode(),
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())["access_token"]


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description="wire-level load harness")
    parser.add_argument("target", help="URL (REST) or host:port (gRPC)")
    parser.add_argument("--grpc", action="store_true")
    parser.add_argument(
        "--grpc-lib",
        choices=("h2", "grpcio"),
        default="h2",
        help="gRPC client: the framework's asyncio data plane (default) or grpcio",
    )
    parser.add_argument("-c", "--concurrency", type=int, default=32,
                        help="in-flight requests per process")
    parser.add_argument("-P", "--processes", type=int, default=1)
    parser.add_argument("-d", "--duration", type=float, default=10.0)
    parser.add_argument("-r", "--arrival-rps", type=float, default=0.0,
                        help="open-loop Poisson arrival rate (REST only); "
                             "0 = closed loop")
    parser.add_argument("-b", "--batch-size", type=int, default=1)
    parser.add_argument("--contract", help="generate payloads from contract.json")
    parser.add_argument("--data", help="literal JSON request body (REST)")
    parser.add_argument("-t", "--tensor", action="store_true")
    parser.add_argument("--token-url", help="gateway /oauth/token URL")
    parser.add_argument("--oauth-key")
    parser.add_argument("--oauth-secret")
    args = parser.parse_args(argv)

    if args.contract:
        payloads = payloads_from_contract(
            args.contract, args.batch_size, grpc=args.grpc, tensor=args.tensor
        )
    elif args.data:
        payloads = [args.data.encode()]
    elif args.grpc:
        payloads = [default_grpc_payload(args.batch_size)]
    else:
        payloads = [default_rest_payload(args.batch_size)]

    headers: dict[str, str] = {}
    if args.token_url:
        token = _fetch_token(args.token_url, args.oauth_key or "", args.oauth_secret or "")
        if args.grpc:
            headers["oauth_token"] = token
        else:
            headers["Authorization"] = f"Bearer {token}"

    result = run_load(
        args.target,
        payloads,
        grpc=args.grpc,
        concurrency=args.concurrency,
        processes=args.processes,
        duration_s=args.duration,
        headers=headers,
        grpc_lib=args.grpc_lib,
        arrival_rps=args.arrival_rps,
    )
    print(json.dumps(result.summary()))
    sys.exit(0 if result.failures == 0 and result.requests > 0 else 1)


if __name__ == "__main__":
    main()
