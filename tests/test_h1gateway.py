"""h1 splice front end tests (gateway/h1gateway.py): the gateway's default
REST data plane.  Covers the raw splice hot path (auth, verbatim forward,
keep-alive, pipelined multiplexing), the fallback endpoints (oauth, ops,
feedback), framing strictness (content-length smuggling guards, chunked
uploads), chunked/SSE response forwarding, and engine-failure handling."""

import asyncio
import contextlib
import json

import aiohttp
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from seldon_core_tpu.engine.app import EngineApp
from seldon_core_tpu.engine.service import PredictionService
from seldon_core_tpu.gateway.app import GatewayApp
from seldon_core_tpu.gateway.h1gateway import H1SpliceFrontend
from seldon_core_tpu.gateway.store import DeploymentRecord, DeploymentStore
from seldon_core_tpu.graph.spec import PredictorSpec

run = asyncio.run

SIMPLE = {"name": "p", "graph": {"name": "m", "type": "MODEL", "implementation": "SIMPLE_MODEL"}}


async def _engine_client(spec=SIMPLE) -> TestClient:
    service = PredictionService(PredictorSpec.model_validate(spec))
    await service.start()
    app = EngineApp(service).build()
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


@contextlib.asynccontextmanager
async def _fake_engine(handle):
    """A raw fake engine on a free port; yields the port.  A connection
    stays open after ``handle`` returns (an engine that accepts and never
    answers is a ``handle`` that does nothing) until the test leaves the
    block, and is closed then: from Python 3.12 ``Server.wait_closed()``
    waits for every connection the server accepted."""
    done = asyncio.Event()

    async def serve(reader, writer):
        try:
            await handle(reader, writer)
            await done.wait()
        finally:
            writer.close()

    server = await asyncio.start_server(serve, "127.0.0.1", 0)
    try:
        yield server.sockets[0].getsockname()[1]
    finally:
        done.set()
        server.close()
        await server.wait_closed()


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
        data={"grant_type": "client_credentials", "client_id": "key1", "client_secret": "sec1"},
    )
    assert resp.status == 200
    return (await resp.json())["access_token"]


class TestSplicePredict:
    def test_predict_keepalive_and_ops(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                hdrs = {"Authorization": f"Bearer {tok}"}
                out = []
                # three spliced requests over ONE keep-alive connection
                for _ in range(3):
                    r = await s.post(
                        f"http://127.0.0.1:{port}/api/v0.1/predictions",
                        json={"data": {"ndarray": [[1.0, 2.0]]}},
                        headers=hdrs,
                    )
                    out.append((r.status, await r.json()))
                ping = await s.get(f"http://127.0.0.1:{port}/ping")
                ready = await s.get(f"http://127.0.0.1:{port}/ready")
                prom = await s.get(f"http://127.0.0.1:{port}/prometheus")
                prom_text = await prom.text()
                await frontend.stop()
                await engine.close()
                return out, ping.status, ready.status, prom.status, prom_text

        out, ping, ready, prom, prom_text = run(go())
        assert all(st == 200 for st, _ in out)
        assert out[0][1]["data"]["ndarray"] == [[0.1, 0.9, 0.5]]
        assert (ping, ready, prom) == (200, 200, 200)
        assert "ingress" in prom_text

    def test_auth_rejected_on_splice_path(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            async with aiohttp.ClientSession() as s:
                r1 = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions", json={}
                )
                b1 = await r1.json()
                r2 = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json={},
                    headers={"Authorization": "Bearer junk"},
                )
                # connection stays usable after an auth failure
                tok = await _token(s, port)
                r3 = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json={"data": {"ndarray": [[1.0]]}},
                    headers={"Authorization": f"Bearer {tok}"},
                )
                await frontend.stop()
                await engine.close()
                return r1.status, b1, r2.status, r3.status

        s1, b1, s2, s3 = run(go())
        assert s1 == 401 and b1["status"]["code"] == 401
        assert s2 == 401
        assert s3 == 200

    def test_concurrent_requests_multiplex(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                hdrs = {"Authorization": f"Bearer {tok}"}

                async def one(i):
                    r = await s.post(
                        f"http://127.0.0.1:{port}/api/v0.1/predictions",
                        json={"data": {"ndarray": [[float(i), 2.0]]}},
                        headers=hdrs,
                    )
                    return r.status, (await r.json())["status"]["code"]

                results = await asyncio.gather(*(one(i) for i in range(24)))
                # multiplexing respected the upstream conn cap
                pool = next(iter(frontend._pools.values()))
                n_conns = len(pool.conns)
                await frontend.stop()
                await engine.close()
                return results, n_conns

        results, n_conns = run(go())
        assert all(r == (200, 200) for r in results)
        from seldon_core_tpu.gateway.h1gateway import _MAX_UPSTREAM_CONNS

        assert 1 <= n_conns <= _MAX_UPSTREAM_CONNS

    def test_feedback_fallback_and_reward(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                r = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/feedback",
                    json={"reward": 1.0},
                    headers={"Authorization": f"Bearer {tok}"},
                )
                status = r.status
                await frontend.stop()
                await engine.close()
                return status

        assert run(go()) == 200

    def test_engine_down_gives_503(self):
        async def go():
            frontend, gw, port = await _frontend(1)  # port 1: refused
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                r = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json={"data": {"ndarray": [[1.0]]}},
                    headers={"Authorization": f"Bearer {tok}"},
                )
                body = await r.json()
                await frontend.stop()
                return r.status, body

        status, body = run(go())
        assert status == 503
        assert body["status"]["code"] == 503

    def test_404_unknown_route(self):
        async def go():
            frontend, gw, port = await _frontend(1)
            async with aiohttp.ClientSession() as s:
                r = await s.get(f"http://127.0.0.1:{port}/nope")
                await frontend.stop()
                return r.status

        assert run(go()) == 404


class TestStop:
    def test_stop_returns_with_idle_keepalive_client(self):
        """From Python 3.12 ``Server.wait_closed()`` waits for every
        accepted connection: stop() must close them first, or one idle
        keep-alive client holds a stopping gateway until it is killed."""

        async def go():
            frontend, gw, port = await _frontend(1)  # no engine needed
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            for _ in range(100):
                if frontend._conns:
                    break
                await asyncio.sleep(0.01)
            assert frontend._conns, "the gateway never saw the client"
            await asyncio.wait_for(frontend.stop(), timeout=1)
            eof = await asyncio.wait_for(reader.read(), timeout=1)
            writer.close()
            return eof

        assert run(go()) == b""


class TestFramingStrictness:
    """The splice forwards raw bytes onto a SHARED pipelined engine
    connection — framing the gateway and engine could read differently is
    a smuggling vector and must be rejected."""

    async def _raw(self, port: int, payload: bytes) -> bytes:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(payload)
        await writer.drain()
        data = await reader.read(4096)
        writer.close()
        return data

    def test_bad_content_length_rejected(self):
        async def go():
            frontend, gw, port = await _frontend(1)
            bad = (
                b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                b"host: x\r\ncontent-length: 5_0\r\n\r\n"
            )
            resp = await self._raw(port, bad)
            await frontend.stop()
            return resp

        assert b"400" in run(go()).split(b"\r\n")[0]

    def test_conflicting_content_lengths_rejected(self):
        async def go():
            frontend, gw, port = await _frontend(1)
            bad = (
                b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                b"host: x\r\ncontent-length: 3\r\ncontent-length: 5\r\n\r\nabc"
            )
            resp = await self._raw(port, bad)
            await frontend.stop()
            return resp

        assert b"400" in run(go()).split(b"\r\n")[0]

    def test_chunked_upload_rejected(self):
        async def go():
            frontend, gw, port = await _frontend(1)
            bad = (
                b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                b"host: x\r\ntransfer-encoding: chunked\r\n\r\n"
            )
            resp = await self._raw(port, bad)
            await frontend.stop()
            return resp

        assert b"411" in run(go()).split(b"\r\n")[0]


class TestChunkedResponseSplice:
    """SSE-shaped chunked responses forward through the splice."""

    def test_chunked_stream_forwards(self):
        async def go():
            # an "engine" whose stream endpoint emits chunked SSE events
            async def stream(request):
                resp = web.StreamResponse()
                resp.content_type = "text/event-stream"
                resp.enable_chunked_encoding()
                await resp.prepare(request)
                for i in range(3):
                    await resp.write(f"data: tok{i}\n\n".encode())
                await resp.write_eof()
                return resp

            app = web.Application()
            app.router.add_post("/api/v0.1/predictions/stream", stream)
            engine = TestClient(TestServer(app))
            await engine.start_server()
            frontend, gw, port = await _frontend(engine.server.port)
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                r = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions/stream",
                    data=b"{}",
                    headers={"Authorization": f"Bearer {tok}"},
                )
                body = await r.content.read()
                status = r.status
                await frontend.stop()
                await engine.close()
                return status, body

        status, body = run(go())
        assert status == 200
        assert body.count(b"data: tok") == 3


class TestUpstreamReplayCap:
    def test_engine_that_always_closes_yields_502(self):
        """An engine that answers by closing the
        connection must exhaust the replay budget (2) and fail the client
        with 502 — not connect/close-loop until the deadline reaper."""

        async def go():
            connects = []

            async def handle(reader, writer):
                connects.append(1)
                await reader.read(64)  # the request reached the engine
                writer.close()

            server = await asyncio.start_server(handle, "127.0.0.1", 0)
            eport = server.sockets[0].getsockname()[1]
            frontend, gw, port = await _frontend(eport)
            async with aiohttp.ClientSession() as s:
                tok = await _token(s, port)
                r = await s.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    json={"data": {"ndarray": [[1.0]]}},
                    headers={"Authorization": f"Bearer {tok}"},
                )
                status = r.status
                body = await r.json()
            await frontend.stop()
            server.close()
            await server.wait_closed()
            return status, body, len(connects)

        status, body, connects = run(go())
        assert status == 502
        assert body["status"]["code"] == 502
        # initial attempt + exactly 2 replays
        assert connects == 3


class TestEvictedPoolFailsFast:
    def test_spawn_send_on_closed_pool_fails_job_promptly(self):
        """A connect that lands after the pool was
        evicted (deployment removed) must fail the downstream with a
        prompt 503, not silently drop the job until the 504 reaper."""
        from seldon_core_tpu.gateway.h1gateway import _Job, _UpstreamPool

        async def go():
            async def handle(reader, writer):
                pass  # accepts, never answers

            fails = []

            class Down:
                def upstream_failed(self, reason, forwarded, status=503):
                    fails.append((reason, forwarded, status))

            async with _fake_engine(handle) as eport:
                pool = _UpstreamPool("127.0.0.1", eport, asyncio.get_running_loop())
                pool.closed = True  # evicted while the job was being dispatched
                job = _Job(Down(), b"POST /x HTTP/1.1\r\ncontent-length: 0\r\n\r\n", False)
                pending = _Job(Down(), b"POST /y HTTP/1.1\r\ncontent-length: 0\r\n\r\n", False)
                pool.pending.append(pending)
                pool.spawn_send(job)
                for _ in range(100):
                    if len(fails) >= 2:
                        break
                    await asyncio.sleep(0.02)
            return fails

        fails = run(go())
        assert len(fails) == 2, f"job+pending must both fail promptly: {fails}"
        for reason, forwarded, _status in fails:
            assert reason == "deployment removed" and forwarded is False


class TestHeaderFieldNameStrictness:
    """The raw head splices onto a SHARED pipelined
    engine connection — header names that are not RFC 7230 tokens (and
    obs-fold continuations) are smuggling vectors and must be 400'd."""

    async def _raw_request(self, port: int, head_and_body: bytes) -> bytes:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(head_and_body)
        await writer.drain()
        data = await asyncio.wait_for(reader.read(4096), timeout=5)
        writer.close()
        return data

    def test_whitespace_before_colon_rejected(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            resp = await self._raw_request(
                port,
                b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                b"Content-Length : 2\r\n\r\n{}",
            )
            await frontend.stop()
            await engine.close()
            return resp

        resp = run(go())
        assert resp.startswith(b"HTTP/1.1 400"), resp[:64]

    def test_obs_fold_continuation_rejected(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            resp = await self._raw_request(
                port,
                b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                b"x-first: a\r\n"
                b" folded-continuation\r\n"
                b"content-length: 2\r\n\r\n{}",
            )
            await frontend.stop()
            await engine.close()
            return resp

        resp = run(go())
        assert resp.startswith(b"HTTP/1.1 400"), resp[:64]

    def test_control_chars_in_name_rejected(self):
        async def go():
            engine = await _engine_client()
            frontend, gw, port = await _frontend(engine.server.port)
            resp = await self._raw_request(
                port,
                b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                b"x\x01bad: a\r\ncontent-length: 2\r\n\r\n{}",
            )
            await frontend.stop()
            await engine.close()
            return resp

        resp = run(go())
        assert resp.startswith(b"HTTP/1.1 400"), resp[:64]


class TestSpliceBackpressure:
    """Bounded buffering in BOTH directions of the
    splice — a client pipelining ahead of its response parks in the
    kernel buffer (pause_reading), and a fast engine stream toward a slow
    client pauses the ENGINE conn's reads instead of buffering unboundedly
    in the gateway."""

    def test_pipelined_flood_pauses_downstream_reads(self):
        async def go():
            release = asyncio.Event()

            async def handle(reader, writer):
                await reader.readuntil(b"\r\n\r\n")
                await release.wait()
                writer.write(
                    b"HTTP/1.1 200 OK\r\ncontent-type: application/json\r\n"
                    b"content-length: 2\r\n\r\n{}"
                )
                await writer.drain()

            async with _fake_engine(handle) as eport:
                frontend, gw, port = await _frontend(eport)
                async with aiohttp.ClientSession() as s:
                    tok = await _token(s, port)
                reader, writer = await asyncio.open_connection("127.0.0.1", port)
                writer.write(
                    b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                    + f"authorization: Bearer {tok}\r\n".encode()
                    + b"content-length: 2\r\n\r\n{}"
                )
                await writer.drain()
                # flood 1MB of pipelined bytes while the response is pending
                junk = b"X" * (1 << 20)
                writer.write(junk)
                paused_conn = None
                for _ in range(200):
                    await asyncio.sleep(0.01)
                    for conn in frontend._conns:
                        if conn._read_paused:
                            paused_conn = conn
                            break
                    if paused_conn is not None:
                        break
                buffered = len(paused_conn.buf) if paused_conn is not None else -1
                release.set()
                data = await asyncio.wait_for(reader.read(200), timeout=5)
                writer.close()
                await frontend.stop()
            return paused_conn is not None, buffered, data

        paused, buffered, data = run(go())
        assert paused, "flooded conn never paused its reads"
        # the gateway buffered at most the cap + one read chunk, not the 1MB
        assert 0 <= buffered < (1 << 19), buffered
        assert data.startswith(b"HTTP/1.1 200")

    def test_fast_engine_stream_pauses_upstream_reads(self):
        async def go():
            total = 4 * (1 << 20)  # 4MB content-length-framed response

            async def handle(reader, writer):
                await reader.readuntil(b"\r\n\r\n")
                writer.write(
                    b"HTTP/1.1 200 OK\r\ncontent-type: application/octet-stream\r\n"
                    + b"content-length: %d\r\n\r\n" % total
                )
                chunk = b"Y" * (1 << 16)
                for _ in range(total // len(chunk)):
                    writer.write(chunk)
                    await writer.drain()
                await writer.drain()

            async with _fake_engine(handle) as eport:
                frontend, gw, port = await _frontend(eport)
                async with aiohttp.ClientSession() as s:
                    tok = await _token(s, port)
                import socket as _socket

                sock = _socket.socket()
                # tiny client receive buffer: the kernel must not absorb the
                # whole stream, or the gateway-side pause never has to fire
                sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 8192)
                sock.connect(("127.0.0.1", port))
                reader, writer = await asyncio.open_connection(sock=sock, limit=1 << 16)
                writer.write(
                    b"POST /api/v0.1/predictions HTTP/1.1\r\n"
                    + f"authorization: Bearer {tok}\r\n".encode()
                    + b"content-length: 2\r\n\r\n{}"
                )
                await writer.drain()
                # force the downstream transport to signal fullness early
                for _ in range(100):
                    await asyncio.sleep(0.01)
                    if frontend._conns:
                        for c in frontend._conns:
                            if c.transport is not None:
                                c.transport.set_write_buffer_limits(high=4096)
                        break
                # do NOT read: the gateway's downstream buffer must fill and
                # propagate the pause to the ENGINE connection
                saw_pause = False
                for _ in range(500):
                    await asyncio.sleep(0.01)
                    if any(c._write_paused for c in frontend._conns):
                        saw_pause = True
                        break
                # now drain everything; the stream must complete intact
                got = 0
                head = await asyncio.wait_for(
                    reader.readuntil(b"\r\n\r\n"), timeout=10
                )
                while got < total:
                    blob = await asyncio.wait_for(reader.read(1 << 20), timeout=10)
                    if not blob:
                        break
                    got += len(blob)
                writer.close()
                await frontend.stop()
            return saw_pause, head, got

        saw_pause, head, got = run(go())
        assert saw_pause, "fast engine stream never paused upstream reads"
        assert head.startswith(b"HTTP/1.1 200")
        assert got == 4 * (1 << 20), got
