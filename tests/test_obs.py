"""Observability layer tests: W3C trace-context generation/propagation
(gateway -> engine REST and gRPC hops, walker fan-out contextvar
inheritance), the span recorder + flight recorder, bounded exporters,
the perf-attribution plane (wire byte counters on every transport edge,
`/stats/wire`, the jax profiler start/stop lifecycle, event-loop lag +
export drop gauges), and the obs-check acceptance gate:
gateway -> engine -> 2-node graph -> batcher yields one trace with >= 4
spans and a breakdown whose stages account for the measured wall time."""

import asyncio
import json
import re
import time

import aiohttp
import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from seldon_core_tpu.engine.app import EngineApp
from seldon_core_tpu.engine.service import PredictionService
from seldon_core_tpu.executor.batcher import BatchQueue
from seldon_core_tpu.gateway.app import GatewayApp
from seldon_core_tpu.gateway.h1gateway import H1SpliceFrontend
from seldon_core_tpu.gateway.store import DeploymentRecord, DeploymentStore
from seldon_core_tpu.graph.spec import PredictorSpec
from seldon_core_tpu.obs import RECORDER, SpanRecorder
from seldon_core_tpu.obs.export import TaplogSpanExporter, otlp_payload
from seldon_core_tpu.obs.spans import Span
from seldon_core_tpu.utils.metrics import MetricsRegistry
from seldon_core_tpu.utils.tracectx import (
    ensure_traceparent,
    get_traceparent,
    new_traceparent,
    parse_traceparent,
    set_traceparent,
)

run = asyncio.run

TRACEPARENT_RE = re.compile(r"^00-[0-9a-f]{32}-[0-9a-f]{16}-[0-9a-f]{2}$")

SIMPLE = {
    "name": "p",
    "graph": {"name": "m", "type": "MODEL", "implementation": "SIMPLE_MODEL"},
}

# 2-node graph: identity transformer over a batched model component
TWO_NODE = {
    "name": "p",
    "graph": {
        "name": "root",
        "type": "TRANSFORMER",
        "endpoint": {"type": "LOCAL"},
        "children": [
            {"name": "batched", "type": "MODEL", "endpoint": {"type": "LOCAL"}},
        ],
    },
}


class BatchedStub:
    """Model component behind a real BatchQueue (no JAX needed): exercises
    the queue-wait / batch-assembly / device-step stages on CPU."""

    def __init__(self):
        self._q = BatchQueue(
            lambda b: b * 2.0, max_batch=8, max_delay_ms=1.0, name="stub"
        )

    async def predict(self, X, names):
        return await self._q.submit(np.asarray(X, dtype=float))

    async def close(self):
        await self._q.close()


class IdentityRoot:
    def transform_input(self, X, names):
        return X


async def _engine_client(spec=SIMPLE, components=None) -> TestClient:
    service = PredictionService(
        PredictorSpec.model_validate(spec), components=components
    )
    await service.start()
    app = EngineApp(service).build()
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def _frontend(engine_port: int, **gw_kwargs):
    store = DeploymentStore()
    store.put(
        DeploymentRecord(
            name="dep",
            oauth_key="key1",
            oauth_secret="sec1",
            engine_host="127.0.0.1",
            engine_rest_port=engine_port,
        )
    )
    gw = GatewayApp(store, **gw_kwargs)
    frontend = H1SpliceFrontend(gw)
    port = await frontend.start(0, host="127.0.0.1")
    return frontend, gw, port


async def _token(session: aiohttp.ClientSession, port: int) -> str:
    resp = await session.post(
        f"http://127.0.0.1:{port}/oauth/token",
        data={"client_id": "key1", "client_secret": "sec1"},
    )
    return (await resp.json())["access_token"]


class TestTraceContext:
    def test_new_traceparent_is_spec_valid(self):
        for _ in range(50):
            tp = new_traceparent()
            assert TRACEPARENT_RE.match(tp), tp
            trace_id, span_id, flags = parse_traceparent(tp)
            assert trace_id != "0" * 32 and span_id != "0" * 16
            assert flags & 0x01  # sampled by default

    def test_parse_rejects_malformed(self):
        bad = [
            None, "", "junk", "00-abc-def-01",
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace id
            "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # zero span id
            "ff-" + "1" * 32 + "-" + "2" * 16 + "-01",  # forbidden version
            "00-" + "Z" * 32 + "-" + "2" * 16 + "-01",  # non-hex
        ]
        for tp in bad:
            assert parse_traceparent(tp) is None, tp

    def test_ensure_generates_and_keeps(self):
        async def go():
            set_traceparent(None)
            tp, generated = ensure_traceparent()
            assert generated and TRACEPARENT_RE.match(tp)
            tp2, generated2 = ensure_traceparent()
            assert not generated2 and tp2 == tp
            # invalid incoming value is replaced, not propagated
            set_traceparent("not-a-traceparent")
            tp3, generated3 = ensure_traceparent()
            assert generated3 and TRACEPARENT_RE.match(tp3)

        run(go())


class TestSpanRecorder:
    def test_ring_is_bounded(self):
        rec = SpanRecorder(max_spans=16, max_stage_samples=8, sample=1.0)
        for i in range(100):
            with rec.span(f"s{i}", stage="node"):
                pass
            set_traceparent(None)  # each span its own trace
        assert len(rec._spans) == 16
        assert rec.recorded == 100
        bd = rec.breakdown()
        assert bd["node"]["count"] == 100 and bd["node"]["window"] == 8

    def test_sample_zero_records_nothing_but_propagates(self):
        async def go():
            rec = SpanRecorder(max_spans=16, sample=0.0)
            set_traceparent(None)
            with rec.span("root", stage="node"):
                inner = get_traceparent()
                assert inner is not None and TRACEPARENT_RE.match(inner)
            assert len(rec._spans) == 0
            assert rec.breakdown()["node"]["count"] == 1  # flight recorder still on

        run(go())

    def test_child_span_parents_and_error_status(self):
        async def go():
            rec = SpanRecorder(max_spans=16, sample=1.0)
            set_traceparent(None)
            try:
                with rec.span("parent"):
                    with rec.span("child"):
                        raise ValueError("boom")
            except ValueError:
                pass
            child, parent = rec._spans[0], rec._spans[1]
            assert child.name == "child" and parent.name == "parent"
            assert child.trace_id == parent.trace_id
            assert child.parent_id == parent.span_id
            assert child.status == "ERROR" and parent.status == "ERROR"

        run(go())

    def test_walker_fanout_children_inherit_contextvar(self):
        """The walker's gather fan-out wraps children in tasks; each must
        inherit the request's trace context (and the node spans must form
        one trace)."""
        from seldon_core_tpu.graph.walker import GraphWalker

        seen: dict[str, str] = {}

        class Capture:
            # async on purpose: runs inline on the event loop, in the
            # fan-out task's context (a sync method would hop to the thread
            # pool, which does not carry contextvars)
            def __init__(self, tag):
                self.tag = tag

            async def predict(self, X, names):
                seen[self.tag] = get_traceparent()
                return X

        class Avg:
            async def aggregate(self, Xs, names):
                return np.mean(Xs, axis=0)

        spec = {
            "name": "combo",
            "type": "COMBINER",
            "endpoint": {"type": "LOCAL"},
            "children": [
                {"name": "a", "type": "MODEL", "endpoint": {"type": "LOCAL"}},
                {"name": "b", "type": "MODEL", "endpoint": {"type": "LOCAL"}},
            ],
        }

        async def go():
            from seldon_core_tpu.contract.payload import Payload

            walker = GraphWalker(
                PredictorSpec.model_validate(
                    {"name": "p", "graph": spec}
                ).graph,
                components={"combo": Avg(), "a": Capture("a"), "b": Capture("b")},
            )
            tp = new_traceparent()
            set_traceparent(tp)
            await walker.predict(Payload.from_array(np.ones((1, 2))))
            return tp

        tp = run(go())
        trace_id = parse_traceparent(tp)[0]
        assert set(seen) == {"a", "b"}
        for tag, inner in seen.items():
            parsed = parse_traceparent(inner)
            assert parsed is not None, (tag, inner)
            assert parsed[0] == trace_id  # same trace through the fan-out
            assert parsed[1] != parse_traceparent(tp)[1]  # child span id


class TestRestHopPropagation:
    def test_aiohttp_gateway_forwards_and_mints(self):
        """gateway -> engine REST hop: a client traceparent arrives at the
        engine verbatim; a trace-naive client gets a minted one; the trace
        id is echoed in the response header."""
        received: list = []

        async def go():
            async def pred(req):
                received.append(req.headers.get("traceparent"))
                return web.json_response(
                    {"meta": {"puid": "x"}, "data": {"ndarray": [[1.0]]}}
                )

            eng = web.Application()
            eng.router.add_post("/api/v0.1/predictions", pred)
            eng_server = TestServer(eng)
            await eng_server.start_server()
            store = DeploymentStore()
            store.put(DeploymentRecord(
                name="dep", oauth_key="k", oauth_secret="s",
                engine_host="127.0.0.1", engine_rest_port=eng_server.port,
            ))
            gw = GatewayApp(store, metrics=MetricsRegistry())
            client = TestClient(TestServer(gw.build()))
            await client.start_server()
            try:
                r = await client.post(
                    "/oauth/token", data={"client_id": "k", "client_secret": "s"}
                )
                tok = (await r.json())["access_token"]
                hdrs = {"Authorization": f"Bearer {tok}"}
                body = {"data": {"ndarray": [[1.0]]}}
                tp = new_traceparent()
                r1 = await client.post(
                    "/api/v0.1/predictions", json=body,
                    headers={**hdrs, "traceparent": tp},
                )
                echo1 = r1.headers.get("x-sct-trace-id")
                r2 = await client.post("/api/v0.1/predictions", json=body, headers=hdrs)
                echo2 = r2.headers.get("x-sct-trace-id")
                return tp, echo1, echo2
            finally:
                await client.close()
                await eng_server.close()

        tp, echo1, echo2 = run(go())
        client_trace = parse_traceparent(tp)[0]
        # hop 1: client's trace id survived to the engine
        got1 = parse_traceparent(received[0])
        assert got1 is not None and got1[0] == client_trace
        assert echo1 == client_trace
        # hop 2: gateway minted a valid traceparent for the naive client
        got2 = parse_traceparent(received[1])
        assert got2 is not None and got2[0] != client_trace
        assert echo2 == got2[0]

    def test_h1_splice_injects_minted_traceparent(self):
        """The splice forwards raw bytes — when the client omits a
        traceparent the gateway must REWRITE the head to inject one, and
        echo the trace id on the response."""
        received: list = []

        async def go():
            async def pred(req):
                received.append(req.headers.get("traceparent"))
                return web.json_response({"data": {"ndarray": [[1.0]]}})

            eng = web.Application()
            eng.router.add_post("/api/v0.1/predictions", pred)
            eng_server = TestServer(eng)
            await eng_server.start_server()
            frontend, gw, port = await _frontend(eng_server.port)
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                hdrs = {"Authorization": f"Bearer {tok}"}
                body = {"data": {"ndarray": [[1.0]]}}
                r1 = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json=body, headers=hdrs,
                )
                echo1 = r1.headers.get("x-sct-trace-id")
                tp = new_traceparent()
                r2 = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json=body, headers={**hdrs, "traceparent": tp},
                )
                echo2 = r2.headers.get("x-sct-trace-id")
                assert r1.status == 200 and r2.status == 200
            await frontend.stop()
            await eng_server.close()
            return tp, echo1, echo2

        tp, echo1, echo2 = run(go())
        minted = parse_traceparent(received[0])
        assert minted is not None, "splice did not inject a traceparent"
        assert echo1 == minted[0]
        # client-sent traceparent forwards verbatim
        assert received[1] == tp
        assert echo2 == parse_traceparent(tp)[0]


class TestGrpcHopPropagation:
    def test_grpc_relay_mints_and_forwards(self):
        """The gateway gRPC relay (fast plane) must attach a minted
        traceparent to the engine-bound metadata for trace-naive clients
        and forward a client-sent one verbatim — asserted against the
        channel the relay actually dials, no sockets involved."""
        from seldon_core_tpu.gateway.grpc_gateway import FastGatewayGrpc

        calls: list = []

        class FakeChannel:
            def try_call_framed(self, path, framed, done, timeout=None, metadata=()):
                calls.append(metadata)
                done(0, "", b"\x00\x00\x00\x00\x00")
                return lambda: None

            async def close(self):
                pass

        class FakeConn:
            def __init__(self):
                self.relay_cancels: dict = {}
                self.responses: list = []

            def write_unary_response(self, stream_id, body):
                self.responses.append((stream_id, body))

        async def go():
            store = DeploymentStore()
            store.put(DeploymentRecord(
                name="dep", oauth_key="k", oauth_secret="s",
                engine_host="127.0.0.1", engine_rest_port=1,
            ))
            gw = GatewayApp(store, metrics=MetricsRegistry())
            handler = FastGatewayGrpc(gw)
            handler._channels[("k", "127.0.0.1:1")] = FakeChannel()
            tok, _ = gw.tokens.issue("k")
            relay = handler.make_relay("Predict")
            conn = FakeConn()
            base = RECORDER.recorded
            tp = new_traceparent()
            relay(conn, 1, [(b"oauth_token", tok.encode()),
                            (b"traceparent", tp.encode())], b"framed")
            relay(conn, 3, [(b"oauth_token", tok.encode())], b"framed")
            await handler.close()
            return tp, conn, base

        tp, conn, base = run(go())
        assert len(conn.responses) == 2  # both relays answered
        # hop 1: client traceparent forwarded verbatim
        md1 = dict(calls[0])
        assert md1[b"traceparent"].decode() == tp
        # hop 2: a minted, spec-valid traceparent was injected
        md2 = dict(calls[1])
        minted = parse_traceparent(md2[b"traceparent"].decode())
        assert minted is not None, "relay did not mint a traceparent"
        assert minted[0] != parse_traceparent(tp)[0]
        # both relays recorded gateway spans
        assert RECORDER.recorded - base >= 2


class TestExporters:
    def _spans(self, n=3):
        return [
            Span(
                trace_id="ab" * 16, span_id=f"{i:016x}", parent_id=None,
                name=f"s{i}", service="svc", start=1000.0 + i,
                duration_s=0.25, attrs={"code": 200},
                events=[("first-token", 1000.5, {"ms": 1.5})],
            )
            for i in range(1, n + 1)
        ]

    def test_otlp_payload_shape(self):
        payload = otlp_payload(self._spans(2))
        rs = payload["resourceSpans"][0]
        attrs = {a["key"]: a["value"] for a in rs["resource"]["attributes"]}
        assert attrs["service.name"] == {"stringValue": "seldon-core-tpu"}
        spans = rs["scopeSpans"][0]["spans"]
        assert len(spans) == 2
        s = spans[0]
        assert s["traceId"] == "ab" * 16 and len(s["spanId"]) == 16
        # nanos are proto3-JSON stringified uint64s
        assert s["startTimeUnixNano"] == str(int(1001.0 * 1e9))
        assert s["endTimeUnixNano"] == str(int(1001.25 * 1e9))
        assert s["events"][0]["name"] == "first-token"
        json.dumps(payload)  # wire-serializable

    def test_otlp_exporter_posts_to_collector(self):
        """End-to-end OTLP/HTTP: spans offered to the exporter arrive at a
        collector endpoint as a valid ExportTraceServiceRequest."""
        from seldon_core_tpu.obs.export import OtlpJsonExporter

        received: list = []

        async def go():
            async def collect(req):
                received.append(await req.json())
                return web.json_response({})

            app = web.Application()
            app.router.add_post("/v1/traces", collect)
            srv = TestServer(app)
            await srv.start_server()
            exp = OtlpJsonExporter(
                f"http://127.0.0.1:{srv.port}/v1/traces", timeout_s=2.0
            )
            for s in self._spans(3):
                exp.offer(s)
            deadline = asyncio.get_event_loop().time() + 5
            while not received and asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
            await exp.close()
            await srv.close()
            return exp.exported

        exported = run(go())
        assert exported == 3 and received
        spans = received[0]["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert [s["name"] for s in spans] == ["s1", "s2", "s3"]

    def test_dead_broker_never_blocks_offer(self):
        """Bounded-exporter discipline: a dead endpoint costs drops, not
        serving-path time (the ISSUE's bugfix satellite)."""

        async def go():
            exp = TaplogSpanExporter("127.0.0.1", 1, timeout_s=0.02, max_queue=32)
            t0 = time.perf_counter()
            for s in self._spans(200):
                exp.offer(s)
            offer_cost = time.perf_counter() - t0
            assert offer_cost < 0.5, "offer must never block"
            await asyncio.sleep(0.3)  # let the drain task hit its timeouts
            await exp.close()
            assert exp.dropped > 0 and exp.exported == 0

        run(go())

    def test_offer_without_loop_drops(self):
        exp = TaplogSpanExporter("127.0.0.1", 1, timeout_s=0.02)
        for s in self._spans(3):
            exp.offer(s)  # no running loop: must not raise
        assert exp.dropped == 3


class TestWireAccounting:
    """The perf-attribution plane's byte counters: every transport edge
    must account request/response bytes that match the payloads actually
    sent."""

    def test_h1_splice_counts_request_and_response_bytes(self):
        from seldon_core_tpu.obs import WIRE, WIRE_GATEWAY_H1

        async def go():
            engine_client = await _engine_client()
            frontend, gw, port = await _frontend(engine_client.server.port)
            counter = WIRE.counter(WIRE_GATEWAY_H1, "dep")
            base = (counter.requests, counter.bytes_in, counter.bytes_out)
            body = json.dumps({"data": {"ndarray": [[1.0, 2.0, 3.0]]}}).encode()
            resp_sizes = []
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                for _ in range(3):
                    r = await s.post(
                        f"http://127.0.0.1:{port}/api/v0.1/predictions",
                        data=body,
                        headers={
                            "Authorization": f"Bearer {tok}",
                            "Content-Type": "application/json",
                        },
                    )
                    assert r.status == 200
                    resp_sizes.append(len(await r.read()))
            await frontend.stop()
            await engine_client.close()
            return counter, base, body, resp_sizes

        counter, base, body, resp_sizes = run(go())
        d_reqs = counter.requests - base[0]
        d_in = counter.bytes_in - base[1]
        d_out = counter.bytes_out - base[2]
        assert d_reqs == 3
        # bytes_in is the spliced head+body: at least the 3 bodies, at most
        # bodies plus a sane head allowance
        assert 3 * len(body) <= d_in <= 3 * (len(body) + 2048)
        # bytes_out covers the engine's heads+bodies the client received
        assert d_out >= sum(resp_sizes)

    def test_aiohttp_gateway_counts_exact_payload_bytes(self):
        from seldon_core_tpu.obs import WIRE, WIRE_GATEWAY_REST

        async def go():
            async def pred(req):
                return web.json_response({"data": {"ndarray": [[1.0]]}})

            eng = web.Application()
            eng.router.add_post("/api/v0.1/predictions", pred)
            eng_server = TestServer(eng)
            await eng_server.start_server()
            store = DeploymentStore()
            store.put(DeploymentRecord(
                name="wiredep", oauth_key="k", oauth_secret="s",
                engine_host="127.0.0.1", engine_rest_port=eng_server.port,
            ))
            gw = GatewayApp(store, metrics=MetricsRegistry())
            client = TestClient(TestServer(gw.build()))
            await client.start_server()
            counter = WIRE.counter(WIRE_GATEWAY_REST, "wiredep")
            base = (counter.requests, counter.bytes_in, counter.bytes_out)
            body = json.dumps({"data": {"ndarray": [[1.0, 2.0]]}}).encode()
            try:
                r = await client.post(
                    "/oauth/token", data={"client_id": "k", "client_secret": "s"}
                )
                tok = (await r.json())["access_token"]
                replies = []
                for _ in range(2):
                    r = await client.post(
                        "/api/v0.1/predictions", data=body,
                        headers={"Authorization": f"Bearer {tok}",
                                 "Content-Type": "application/json"},
                    )
                    assert r.status == 200
                    replies.append(len(await r.read()))
            finally:
                await client.close()
                await eng_server.close()
            return counter, base, body, replies

        counter, base, body, replies = run(go())
        # the aiohttp front forwards the raw body verbatim and returns the
        # engine reply verbatim: the counters must match EXACTLY
        assert counter.requests - base[0] == 2
        assert counter.bytes_in - base[1] == 2 * len(body)
        assert counter.bytes_out - base[2] == sum(replies)

    def test_grpc_relay_counts_framed_bytes(self):
        from seldon_core_tpu.gateway.grpc_gateway import FastGatewayGrpc
        from seldon_core_tpu.obs import WIRE, WIRE_GATEWAY_GRPC

        reply_body = b"\x00\x00\x00\x00\x05hello"

        class FakeChannel:
            def try_call_framed(self, path, framed, done, timeout=None, metadata=()):
                done(0, "", reply_body)
                return lambda: None

            async def close(self):
                pass

        class FakeConn:
            def __init__(self):
                self.relay_cancels: dict = {}
                self.responses: list = []

            def write_unary_response(self, stream_id, body):
                self.responses.append((stream_id, body))

        async def go():
            store = DeploymentStore()
            store.put(DeploymentRecord(
                name="grpcdep", oauth_key="k", oauth_secret="s",
                engine_host="127.0.0.1", engine_rest_port=1,
            ))
            gw = GatewayApp(store, metrics=MetricsRegistry())
            handler = FastGatewayGrpc(gw)
            handler._channels[("k", "127.0.0.1:1")] = FakeChannel()
            tok, _ = gw.tokens.issue("k")
            relay = handler.make_relay("Predict")
            conn = FakeConn()
            counter = WIRE.counter(WIRE_GATEWAY_GRPC, "grpcdep")
            base = (counter.requests, counter.bytes_in, counter.bytes_out)
            framed = b"\x00\x00\x00\x00\x03abc"
            relay(conn, 1, [(b"oauth_token", tok.encode())], framed)
            await handler.close()
            return counter, base, framed, conn

        counter, base, framed, conn = run(go())
        assert conn.responses, "relay did not answer"
        assert counter.requests - base[0] == 1
        assert counter.bytes_in - base[1] == len(framed)
        assert counter.bytes_out - base[2] == len(reply_body)

    def test_stats_wire_shape_on_engine_and_both_gateway_fronts(self):
        """GET /stats/wire serves the same payload shape everywhere: wire
        stage/deployment counters + loop-lag probe + host-sync counts."""

        async def go():
            stub = BatchedStub()
            engine_client = await _engine_client(
                TWO_NODE, components={"root": IdentityRoot(), "batched": stub}
            )
            frontend, gw, port = await _frontend(engine_client.server.port)
            # aiohttp gateway front end (same GatewayApp core, own server)
            aio_client = TestClient(TestServer(gw.build()))
            await aio_client.start_server()
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                r = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json={"data": {"ndarray": [[1.0, 2.0]]}},
                    headers={"Authorization": f"Bearer {tok}"},
                )
                assert r.status == 200
                h1 = await (await s.get(f"http://127.0.0.1:{port}/stats/wire")).json()
            eng = await (await engine_client.get("/stats/wire")).json()
            aio = await (await aio_client.get("/stats/wire")).json()
            await aio_client.close()
            await frontend.stop()
            await engine_client.close()
            return h1, eng, aio

        h1, eng, aio = run(go())
        for payload in (h1, eng, aio):
            assert set(payload) >= {"wire", "loop_lag", "host_syncs"}
            assert "stages" in payload["wire"] and "totals" in payload["wire"]
            assert "interval_s" in payload["loop_lag"]
        # the h1 splice edge accounted the request we just sent
        h1_edge = h1["wire"]["stages"].get("gateway-h1", {}).get("dep")
        assert h1_edge and h1_edge["requests"] >= 1 and h1_edge["bytes_in"] > 0
        # the engine's REST middleware accounted its ingress
        assert "engine-rest" in eng["wire"]["stages"]
        # the batcher's fetch recorded a host sync for the stub queue
        assert eng["host_syncs"].get("stub", 0) >= 1


class TestProfilerLifecycle:
    def test_profile_start_stop_and_conflict(self, tmp_path):
        """POST /profile/start drives jax.profiler into a capture dir
        (created up front); a second start is a 409; stop tears down and a
        second stop is a 409."""
        import os

        target = str(tmp_path / "capture" / "run1")

        async def go():
            client = await _engine_client()
            try:
                r1 = await client.post("/profile/start", json={"dir": target})
                b1 = await r1.json()
                exists_during = os.path.isdir(target)
                r2 = await client.post("/profile/start", json={"dir": target})
                r3 = await client.post("/profile/stop")
                b3 = await r3.json()
                r4 = await client.post("/profile/stop")
            finally:
                await client.close()
            return r1.status, b1, exists_during, r2.status, r3.status, b3, r4.status

        s1, b1, exists_during, s2, s3, b3, s4 = run(go())
        assert s1 == 200 and b1["status"] == "profiling" and b1["dir"] == target
        assert exists_during, "capture dir must exist while the trace runs"
        assert s2 == 409, "second start must conflict"
        assert s3 == 200 and b3["dir"] == target
        assert s4 == 409, "stop without a running trace must conflict"
        # the capture actually wrote a trace under the dir
        captured = []
        for root, _dirs, files in os.walk(target):
            captured.extend(files)
        assert captured, "jax.profiler produced no trace files"


class TestProfilerOffTheLoop:
    def test_stop_answers_beside_other_requests_and_conflicts_hold(
        self, tmp_path, monkeypatch
    ):
        """``stop_trace`` collects and writes the whole trace: it runs on a
        thread, so a ``/ready`` issued while it is at work is answered
        before it returns; a start while a trace runs, a start while it is
        being stopped and a second stop are each a 409."""
        import threading

        import jax

        began, release = threading.Event(), threading.Event()
        on_threads = []

        def start_trace(out_dir):
            on_threads.append(threading.current_thread().name)

        def stop_trace():
            on_threads.append(threading.current_thread().name)
            began.set()
            release.wait(30)

        monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
        target = str(tmp_path / "capture")

        async def go():
            client = await _engine_client()
            loop_thread = threading.current_thread().name
            try:
                r1 = await client.post("/profile/start", json={"dir": target})
                r2 = await client.post("/profile/start", json={"dir": target})
                stop = asyncio.ensure_future(client.post("/profile/stop"))
                while not began.is_set():
                    await asyncio.sleep(0.005)
                ready = await client.get("/ready")
                await ready.read()
                in_flight = not stop.done()  # the trace is still being written
                r3 = await client.post("/profile/start", json={"dir": target})
                r4 = await client.post("/profile/stop")
                release.set()
                r5 = await stop
                r6 = await client.post("/profile/stop")
                return (loop_thread, in_flight,
                        [r.status for r in (r1, r2, r3, r4, r5, r6)])
            finally:
                release.set()
                await client.close()

        loop_thread, in_flight, statuses = run(go())
        assert in_flight, "/ready waited for stop_trace: it ran on the loop"
        assert statuses == [200, 409, 409, 409, 200, 409]
        assert len(on_threads) == 2 and loop_thread not in on_threads


class TestStallWatchdog:
    """obs/stall.py on hand-made states: what the watchdog writes for a
    part that lasts, for a park, and for a wake of its own that came late
    (tests/test_generative.py serves a slow fetch through it)."""

    @staticmethod
    def _lines(caplog):
        return [r.getMessage() for r in caplog.records
                if r.name == "seldon_core_tpu.obs.stall"]

    def _watchdog(self, state):
        import threading

        from seldon_core_tpu.obs.stall import StallWatchdog

        wd = StallWatchdog("unit", lambda: state[0])
        wd._loop_thread = threading.get_ident()
        return wd

    def test_a_part_that_lasts_is_one_line_and_one_more_at_its_end(self, caplog):
        import logging

        state = [("sched:fetch", 100.0, True)]
        wd = self._watchdog(state)
        with caplog.at_level(logging.WARNING, logger="seldon_core_tpu.obs.stall"):
            wd._look(100.9, 0.0)   # under a second: nothing yet
            wd._look(101.2, 0.001)
            wd._look(101.45, 0.0)  # the same stall goes on: no second line
            state[0] = ("sched:loop", 102.5, True)
            wd._look(102.6, 0.0)
            wd._look(102.85, 0.0)
        first, end = self._lines(caplog)
        assert first.startswith("stall unit=unit part=sched:fetch for=1.200s ")
        assert "watchdog_late=0.001s " in first and len(first) < 1200
        assert "test_obs.py" in first  # this thread stands in for the loop's
        assert end == "stall-end unit=unit part=sched:fetch lasted=2.500s"
        assert wd.snapshot() == {
            "count": 1, "longest_s": 2.5, "last_part": "sched:fetch"
        }

    def test_a_park_or_a_scheduler_with_nothing_to_do_is_no_stall(self, caplog):
        import logging

        state = [("idle-park", 100.0, True)]
        wd = self._watchdog(state)
        with caplog.at_level(logging.WARNING, logger="seldon_core_tpu.obs.stall"):
            wd._look(160.0, 0.0)
            state[0] = ("sched:loop", 100.0, False)  # no slot live, nobody waits
            wd._look(160.0, 0.0)
        assert not self._lines(caplog) and wd.snapshot()["count"] == 0

    def test_a_late_wake_says_the_whole_process_stood_still(self, caplog):
        import logging

        # the loop moved on before the watchdog could look: the part is new
        state = [("sched:deliver", 103.99, True)]
        wd = self._watchdog(state)
        with caplog.at_level(logging.WARNING, logger="seldon_core_tpu.obs.stall"):
            wd._look(104.0, 2.75)
        (line,) = self._lines(caplog)
        assert "part=sched:deliver for=2.750s watchdog_late=2.750s" in line
        assert "the whole process stood still" in line
        assert wd.snapshot()["count"] == 1

    def test_the_line_stays_under_its_limit_whatever_the_threads(self, monkeypatch):
        from seldon_core_tpu.obs import stall

        monkeypatch.setattr(stall, "_where", lambda frame, depth=1: "x" * 400)
        wd = self._watchdog([("sched:admit", 0.0, True)])
        assert len(wd._line("sched:admit", 1.5, 0.0)) < stall.LINE_MAX


class TestDeviceLedger:
    """obs/device.py on stamps handed in: what a program occupied, where
    the device stood idle and under which part of the run loop, second by
    second.  No clock is read and no size of a wall-clock time is held."""

    DISPATCH, CHAIN = ("sched:dispatch", 0.0), ("sched:chain", 0.0)

    @staticmethod
    def _ledger():
        from seldon_core_tpu.obs.device import DeviceLedger

        return DeviceLedger()

    @staticmethod
    def _snap(led, now):
        return led.snapshot(now, ("sched:loop", now))

    def test_a_chained_block_starts_at_its_predecessors_done_and_leaves_no_idle(self):
        led = self._ledger()
        led.sent(10.0, "decode", "decode_k:k16:w512", 16, part=("sched:dispatch", 9.99))
        # N+1 goes out from the device carry while N runs
        led.sent(10.02, "decode", "decode_k:k16:w512", 16, part=("sched:chain", 10.01))
        assert led.done(10.09) == pytest.approx(0.09)   # N: from its dispatch
        led.sent(10.10, "decode", "decode_k:k16:w512", 16, part=("sched:chain", 10.095))
        assert led.done(10.18) == pytest.approx(0.09)   # N+1: from N's done stamp
        assert led.done(10.27) == pytest.approx(0.09)
        snap = self._snap(led, 10.27)
        assert snap["idle_s"] == {} and snap["decode_steps"] == 48
        assert snap["busy_s"] == {"decode": pytest.approx(0.27), "prefill": 0.0, "other": 0.0}
        assert snap["programs"] == {
            "decode_k:k16:w512": {"n": 3, "steps": 48, "busy_s": pytest.approx(0.27)}
        }

    def test_a_sync_points_gap_lands_on_the_parts_between_by_overlap(self):
        led = self._ledger()
        led.sent(1.0, "decode", "d", 16, part=("sched:dispatch", 0.999))
        led.part("sched:dispatch", 0.999, 1.001)
        led.done(1.100)                            # on the worker's thread
        led.part("sched:fetch", 1.001, 1.101)      # the loop resumed 1 ms on
        led.part("sched:deliver", 1.102, 1.105)    # 1 ms of loop before it
        # the next dispatch returned at 1.108, 2 ms into its part
        led.sent(1.108, "decode", "d", 16, part=("sched:dispatch", 1.106))
        led.done(1.2)
        idle = self._snap(led, 1.2)["idle_s"]
        assert idle == {
            "sched:fetch": pytest.approx(0.001), "sched:deliver": pytest.approx(0.003),
            "sched:dispatch": pytest.approx(0.002), "sched:loop": pytest.approx(0.002),
        }
        assert sum(idle.values()) == pytest.approx(1.108 - 1.100)

    def test_a_gap_found_late_is_still_the_parts_that_ran_in_it(self):
        """N+1 was chained while N ran by the loop's word, and N's done
        stamp says it had ended before the dispatch call returned."""
        led = self._ledger()
        led.sent(1.0, "decode", "d", 16, part=("sched:dispatch", 0.999))
        led.part("sched:dispatch", 0.999, 1.001)
        led.part("sched:hold", 1.001, 1.094)
        led.sent(1.099, "decode", "d", 16, part=("sched:chain", 1.094))
        led.part("sched:chain", 1.094, 1.0995)
        assert led.done(1.097) == pytest.approx(0.097)
        assert led.done(1.2) == pytest.approx(1.2 - 1.099)
        assert self._snap(led, 1.2)["idle_s"] == {"sched:chain": pytest.approx(0.002)}

    def test_idle_for_want_of_demand_is_kept_apart(self):
        led = self._ledger()
        led.sent(5.0, "decode", "d", 16, part=("sched:dispatch", 4.999))
        led.done(5.1)
        led.part("sched:fetch", 5.0, 5.1005)
        led.part("sched:deliver", 5.1005, 5.102)
        led.part("idle-park", 5.103, 65.0)        # a minute with nobody there
        led.part("sched:admit", 65.001, 65.03)    # ... whose round is told below
        led.sent(65.04, "decode", "d", 16, part=("sched:dispatch", 65.035))
        snap = self._snap(led, 65.04)
        assert snap["idle_s"]["idle-park"] == pytest.approx(65.0 - 5.103)
        host = sum(v for k, v in snap["idle_s"].items() if k != "idle-park")
        assert host == pytest.approx(65.04 - 5.1 - (65.0 - 5.103))
        # a long park is many parts (a preempted scheduler wakes every
        # 50 ms): they are booked as they come, not kept
        led.done(65.1)
        for i in range(1000):
            led.part("idle-park", 100.0 + i, 100.9 + i)
        assert len(led._parts) < 16
        assert self._snap(led, 1100.0)["idle_s"]["idle-park"] == pytest.approx(
            65.0 - 5.103 + 900.0
        )

    def test_a_round_of_prompts_is_one_prefill_interval_with_its_rungs_counted(self):
        from seldon_core_tpu.executor.generation import GenerationScheduler

        class Model:  # what the scheduler reads of a model here
            name, n_slots, decode_block = "m", 2, 4
            prefill_rows = {"by_rung": {"256": 5}}

        sched = GenerationScheduler(Model())
        before = sched._rungs()
        Model.prefill_rows = {"by_rung": {"256": 8}}
        sched._part_now = ("sched:admit", 2.0)
        sched._sent_prompts(2.01, before, 3)
        assert sched.device.done(2.5) == pytest.approx(0.49)
        before = sched._rungs()
        Model.prefill_rows = {"by_rung": {"256": 9, "1024": 2}}
        sched._sent_prompts(2.6, before, 4)        # one KV import among them
        sched.device.done(3.0)
        before = sched._rungs()
        sched._sent_prompts(3.0, before, 1)        # an import alone
        sched.device.done(3.1)
        snap = sched.device.snapshot(3.1, ("sched:loop", 3.1))
        assert snap["programs"] == {
            "prefill:b256": {"n": 3, "steps": 0, "busy_s": pytest.approx(0.49)},
            "prefill:mixed": {"n": 4, "steps": 0, "busy_s": pytest.approx(0.4)},
            "import": {"n": 1, "steps": 0, "busy_s": pytest.approx(0.1)},
        }
        assert snap["busy_s"] == {
            "decode": 0.0, "prefill": pytest.approx(0.89), "other": pytest.approx(0.1)
        }
        assert snap["idle_s"] == {"sched:admit": pytest.approx(0.1)}

    def test_a_chunk_nobody_waits_for_is_booked_with_the_next_done(self):
        led = self._ledger()
        led.sent(1.0, "prefill", "prefill:b256", part=("sched:advance-prefill", 0.99), waits=False)
        led.sent(1.01, "decode", "d", 16, part=("sched:dispatch", 1.005))
        assert led.done(1.3) == pytest.approx(0.3)
        snap = self._snap(led, 1.3)
        # neither kind's seconds are known apart: the step stays a decode
        # block's own time
        assert snap["busy_s"]["other"] == pytest.approx(0.3)
        assert snap["decode_steps"] == 0 and snap["programs"]["mixed"]["steps"] == 16

    def test_seconds_share_an_interval_by_overlap_and_sum_to_the_totals(self):
        led = self._ledger()
        led.sent(100.25, "decode", "d", 16, part=("sched:dispatch", 100.2))
        led.done(100.75)
        led.part("sched:fetch", 100.3, 100.76)
        led.sent(100.8, "prefill", "prefill:b512", n=2, part=("sched:admit", 100.77))
        led.done(102.3)                            # over two edges
        led.sent(102.3, "decode", "d", 16, part=("sched:dispatch", 102.29))
        led.done(103.1)                            # 0.7 s and 0.1 s: steps 14 and 2
        snap = self._snap(led, 103.1)
        rows = {r[0]: dict(zip(snap["columns"], r)) for r in snap["seconds"]}
        assert sorted(rows) == [100, 101, 102, 103]
        assert rows[100]["busy_prefill_s"] == pytest.approx(0.2)
        assert rows[101]["busy_prefill_s"] == pytest.approx(1.0)
        assert rows[102]["busy_prefill_s"] == pytest.approx(0.3)
        assert rows[102]["decode_steps"] == pytest.approx(14.0)
        assert rows[103]["decode_steps"] == pytest.approx(2.0)
        assert rows[100]["idle_s"] == {
            "sched:fetch": pytest.approx(0.01), "sched:loop": pytest.approx(0.01),
            "sched:admit": pytest.approx(0.03),
        }
        for i, kind in enumerate(("decode", "prefill", "other"), 1):
            assert sum(r[i] for r in snap["seconds"]) == pytest.approx(snap["busy_s"][kind])
        assert sum(r[4] for r in snap["seconds"]) == pytest.approx(snap["decode_steps"]) == 32
        idle = sum(v for r in snap["seconds"] for v in r[5].values())
        assert idle == pytest.approx(sum(snap["idle_s"].values()))
        # busy and idle are the wall time between the first dispatch's
        # return (the books open there) and the last done stamp
        assert sum(snap["busy_s"].values()) + idle == pytest.approx(103.1 - 100.25)

    def test_the_ring_wraps_at_its_600_seconds_and_the_totals_do_not(self):
        from seldon_core_tpu.obs.device import SECONDS

        led = self._ledger()
        for i in range(1000):                      # a block a second
            led.sent(1000.0 + i, "decode", "d", 16, part=("sched:dispatch", 999.9 + i))
            led.done(1000.5 + i)
        snap = self._snap(led, 1999.5)
        assert SECONDS == 600 and len(snap["seconds"]) == 600
        assert [r[0] for r in snap["seconds"]] == list(range(1400, 2000))
        assert snap["decode_steps"] == 1000 * 16
        assert snap["busy_s"]["decode"] == pytest.approx(500.0)
        # an interval longer than the ring leaves only the seconds it holds
        led.part("idle-park", 1999.5, 3000.0)
        snap = self._snap(led, 3000.5)
        assert [r[0] for r in snap["seconds"]] == list(range(2401, 3001))
        assert snap["idle_s"]["idle-park"] == pytest.approx(1000.5)

    def test_profiler_marks_and_the_traced_stretch(self):
        led = self._ledger()
        t = 50.0
        led.sent(t, "decode", "d", 10, part=("sched:dispatch", 49.9))
        for i in range(80):                        # blocks of 0.1 s, chained
            led.sent(t + 0.05, "decode", "d", 10, part=("sched:chain", t + 0.04))
            led.done(t + 0.1)
            t += 0.1
            if i == 19:
                led.profiler("start", 52.0)        # /profile/start entered
            if i == 22:
                led.profiler("run", 52.35)         # ... and returned
            if i == 49:
                led.profiler("stop", 55.05)        # /profile/stop entered
            if i == 69:
                led.profiler("off", 57.0)          # ... and returned
        snap = self._snap(led, 58.0)
        marks = {r[0]: r[6] for r in snap["seconds"]}
        assert [marks[s] for s in range(50, 58)] == [0, 0, 1, 1, 1, 2, 2, 0]
        traced = snap["traced"]
        assert traced["running"] is False
        assert traced["wall_s"] == pytest.approx(55.05 - 52.35)
        assert traced["busy_s"]["decode"] == pytest.approx(2.7)   # blocks cut in proportion
        assert traced["decode_steps"] == pytest.approx(270.0)
        assert traced["idle_s"] == {}
        # a second trace starts the stretch anew
        led.profiler("start", 58.0)
        led.profiler("run", 58.1)
        assert self._snap(led, 58.2)["traced"] == {
            "wall_s": pytest.approx(0.1), "running": True, "decode_steps": 0.0,
            "busy_s": {"decode": 0.0, "prefill": 0.0, "other": 0.0}, "idle_s": {},
        }

    def test_what_failed_in_flight_is_not_booked(self):
        led = self._ledger()
        led.sent(1.0, "decode", "d", 16, part=("sched:dispatch", 0.9))
        led.lost()
        assert led.done(1.5) == 0.0
        led.sent(2.0, "decode", "d", 16, part=("sched:dispatch", 1.9))
        led.done(2.1)
        snap = self._snap(led, 2.1)
        assert snap["busy_s"]["decode"] == pytest.approx(0.1) and snap["idle_s"] == {}


class TestAlwaysOnProbes:
    def test_eventloop_lag_and_drop_gauges_in_prometheus(self):
        """The always-on counters are scrapeable: event-loop lag gauge
        (ticking), span ring/export gauges (pull-time set_function)."""
        from seldon_core_tpu.obs import LOOP_LAG

        async def go():
            client = await _engine_client()
            # let the lag probe tick at least once (interval 0.25s)
            await asyncio.sleep(0.35)
            prom = (await (await client.get("/prometheus")).text())
            wire = await (await client.get("/stats/wire")).json()
            await client.close()
            return prom, wire

        prom, wire = run(go())
        assert "seldon_eventloop_lag_seconds" in prom
        assert "seldon_obs_spans" in prom
        assert "seldon_obs_span_export" in prom
        assert "seldon_wire_bytes" in prom
        assert LOOP_LAG.samples >= 1
        assert wire["loop_lag"]["samples"] >= 1


class TestErrorCodeAudit:
    def test_unexpected_engine_error_records_500(self):
        """A component blowing up with an unanticipated exception must land
        in the latency histogram as a 500, not the default '200'."""

        class Exploder:
            def predict(self, X, names):
                raise RuntimeError("kaboom")

        async def go():
            metrics = MetricsRegistry()
            service = PredictionService(
                PredictorSpec.model_validate(TWO_NODE),
                components={"root": IdentityRoot(), "batched": Exploder()},
                metrics=metrics,
            )
            await service.start()
            client = TestClient(TestServer(EngineApp(service).build()))
            await client.start_server()
            try:
                r = await client.post(
                    "/api/v0.1/predictions",
                    json={"data": {"ndarray": [[1.0, 2.0]]}},
                )
                assert r.status == 500
                prom = metrics.expose().decode()
            finally:
                await client.close()
            return prom

        prom = run(go())
        assert 'code="500"' in prom
        # the 500 is in the server-requests histogram specifically
        assert re.search(
            r'seldon_api_engine_server_requests_duration_seconds_count\{[^}]*code="500"',
            prom,
        )


class TestObsCheck:
    def test_obs_check_end_to_end(self):
        """The acceptance gate: 50 requests through
        gateway -> engine -> 2-node graph -> batcher.  Asserts (1) one
        trace holds >= 4 spans, (2) /stats/breakdown reports non-zero
        queue-wait and device-step, (3) /prometheus exposes the new
        histograms, (4) the breakdown's engine-route total stays within
        10% of the measured wall time (it is a subset of it)."""

        async def go():
            stub = BatchedStub()
            engine_client = await _engine_client(
                TWO_NODE, components={"root": IdentityRoot(), "batched": stub}
            )
            frontend, gw, port = await _frontend(engine_client.server.port)
            base_recorded = RECORDER.recorded
            # the recorder is process-global: snapshot so the assertions
            # measure THIS run, not every suite that ran before it
            base_stages = RECORDER.breakdown()
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                hdrs = {"Authorization": f"Bearer {tok}"}
                body = {"data": {"ndarray": [[1.0, 2.0, 3.0]]}}
                wall_s = 0.0
                t_all0 = time.perf_counter()
                for _ in range(50):
                    t0 = time.perf_counter()
                    r = await s.post(
                        f"http://127.0.0.1:{port}/api/v0.1/predictions",
                        json=body, headers=hdrs,
                    )
                    assert r.status == 200
                    await r.read()
                    wall_s += time.perf_counter() - t0
                wall_all_s = time.perf_counter() - t_all0

                spans_resp = await s.get(
                    f"http://127.0.0.1:{port}/stats/spans?n=60"
                )
                stats = await spans_resp.json()
                bd_resp = await s.get(f"http://127.0.0.1:{port}/stats/breakdown")
                stages = (await bd_resp.json())["stages"]
                prom_resp = await s.get(f"http://127.0.0.1:{port}/prometheus")
                prom = await prom_resp.text()
            await frontend.stop()
            await engine_client.close()
            return stats, stages, prom, wall_s, wall_all_s, base_recorded, base_stages

        stats, stages, prom, wall_s, wall_all_s, base_recorded, base_stages = run(go())

        def delta(stage, field):
            before = (base_stages.get(stage) or {}).get(field, 0)
            return stages[stage][field] - before

        # (1) one request = one trace with gateway.relay + engine.predict +
        # node:root + node:batched >= 4 spans
        assert RECORDER.recorded - base_recorded >= 200  # 4 spans x 50
        full = [t for t in stats["traces"] if t["span_count"] >= 4]
        assert full, f"no trace with >=4 spans: {stats['traces'][:2]}"
        names = {s["name"] for s in full[0]["spans"]}
        assert {"gateway.relay", "engine.predict", "node:root", "node:batched"} <= names

        # (2) the batcher stages are visible and non-zero
        for stage in ("queue-wait", "device-step", "engine-route", "gateway-relay"):
            assert stage in stages, f"missing stage {stage}: {list(stages)}"
            assert delta(stage, "count") >= 50 or stage == "device-step"
            assert delta(stage, "total_ms") > 0

        # (3) the new TPU-serving histograms are scraped
        assert "seldon_executor_queue_wait_seconds" in prom
        assert "seldon_executor_device_step_seconds" in prom

        # (4) stage accounting is consistent with the measured wall time:
        # this run's engine-route total is a strict subset of the
        # client-observed wall, so it must not exceed wall + 10%, and must
        # be non-zero (the engine did real work per request)
        engine_total_s = delta("engine-route", "total_ms") / 1e3
        assert engine_total_s <= wall_s * 1.10, (engine_total_s, wall_s)
        assert engine_total_s > 0
        # and the per-stage device view cannot exceed the engine view + 10%
        device_total_s = delta("device-step", "total_ms") / 1e3
        assert device_total_s <= engine_total_s * 1.10 + 0.05
