"""The load generator: one process, one event loop.  Closed loops (callers
that each wait for a reply) and open loops (arrivals on a schedule, whatever
the server does), over the engine's SSE stream route or its predictions
route.  Every time is this process's ``time.perf_counter()``.

Open loop: a request's latencies count from the instant it was DUE, not
from when the sender got round to it, so a stall that delays later sends is
charged to the requests it delayed; how late the sender ran is kept beside.
"""

from __future__ import annotations

import asyncio
import base64
import dataclasses
import json
import time

import aiohttp
import numpy as np

import traffic

STREAM_PATH = "/api/v0.1/predictions/stream"
PREDICT_PATH = "/api/v0.1/predictions"
TOKEN_PREFIX = b'data: {"token": '


@dataclasses.dataclass
class Sample:
    """One request as the client saw it."""
    index: int
    due: float            # when it was due (open) or sent (closed)
    sent: float
    first: float | None = None   # first token event / reply headers
    done: float | None = None
    asked: int = 0        # tokens or rows asked for
    got: int = 0          # tokens or rows that came back
    ok: bool = False
    error: str | None = None
    token_times: list = dataclasses.field(default_factory=list)  # (t, n)


def stream_body(spec: dict, temperature: float) -> bytes:
    return json.dumps({
        "tokens": spec["tokens"], "max_new_tokens": spec["max_new"],
        "temperature": temperature,
    }).encode()


def predict_body(tokens: np.ndarray) -> bytes:
    """A ``rawTensor`` int32 request body for a (rows, seq) token batch."""
    return json.dumps({"rawTensor": {
        "shape": list(tokens.shape), "dtype": "int32",
        "data": base64.b64encode(
            np.ascontiguousarray(tokens, "<i4").tobytes()
        ).decode(),
    }}).encode()


def decode_reply_tensor(reply: dict) -> np.ndarray:
    """The tensor of a predictions reply, whichever encoding it came in."""
    if "rawTensor" in reply:
        rt = reply["rawTensor"]
        raw = base64.b64decode(rt["data"])
        if rt["dtype"] == "bfloat16":
            u = np.frombuffer(raw, "<u2").astype(np.uint32) << 16
            arr = u.view(np.float32)
        else:
            arr = np.frombuffer(raw, np.dtype(rt["dtype"]).newbyteorder("<"))
        return arr.reshape(rt["shape"]).astype(np.float32)
    data = reply["data"]
    if "tensor" in data:
        return np.asarray(data["tensor"]["values"], np.float32).reshape(
            data["tensor"]["shape"]
        )
    return np.asarray(data["ndarray"], np.float32)


async def stream_request(
    session: aiohttp.ClientSession, base: str, body: bytes, s: Sample,
    vocab: int, keep_tokens: list | None = None,
) -> None:
    """POST one stream request and read its events into ``s``.  A stream is
    good only if it ends in a done event whose tokens are the events', as
    many as asked, ids in [0, vocab)."""
    try:
        async with session.post(
            base + STREAM_PATH, data=body,
            headers={"Content-Type": "application/json"},
        ) as resp:
            if resp.status != 200:
                s.error = f"status {resp.status}"
                return
            toks: list[int] = []
            final = None
            pending = 0
            async for line in resp.content:
                if line.startswith(TOKEN_PREFIX):
                    toks.append(int(line[len(TOKEN_PREFIX):line.rindex(b"}")]))
                    pending += 1
                    continue
                now = time.perf_counter()
                if pending:
                    # a blank line ends an event: stamp the tokens read
                    if s.first is None:
                        s.first = now
                    s.token_times.append((now, pending))
                    pending = 0
                if line.startswith(b"data: "):
                    evt = json.loads(line[6:])
                    if evt.get("done"):
                        final = evt["tokens"]
                        s.done = now
                    elif "error" in evt:
                        s.error = f"stream error: {evt['error']}"[:300]
                        return
            s.got = len(toks)
            if final is None:
                s.error = "stream ended without a done event"
            elif final != toks:
                s.error = "done event disagrees with the token events"
            elif len(toks) != s.asked:
                s.error = f"{len(toks)} tokens, asked for {s.asked}"
            elif not all(0 <= t < vocab for t in toks):
                s.error = "token id outside [0, vocab)"
            else:
                s.ok = True
                if keep_tokens is not None:
                    keep_tokens.extend(toks)
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, KeyError) as e:
        s.error = f"{type(e).__name__}: {e}"[:300]


async def predict_request(
    session: aiohttp.ClientSession, base: str, body: bytes, s: Sample,
    keep: list | None = None,
) -> None:
    """POST one predictions request; good if the status is 200 in both the
    transport and the reply, and one finite output row came back a row."""
    try:
        async with session.post(
            base + PREDICT_PATH, data=body,
            headers={"Content-Type": "application/json"},
        ) as resp:
            raw = await resp.read()
            now = time.perf_counter()
            s.first = s.done = now
            if resp.status != 200:
                s.error = f"status {resp.status}"
                return
        reply = json.loads(raw)
        if reply.get("status", {}).get("code", 200) != 200:
            s.error = f"reply status {reply.get('status')}"[:300]
            return
        out = decode_reply_tensor(reply)
        s.got = int(out.shape[0])
        if s.got != s.asked:
            s.error = f"{s.got} rows, sent {s.asked}"
        elif not np.isfinite(out).all():
            s.error = "non-finite outputs"
        else:
            s.ok = True
            if keep is not None:
                keep.append(out)
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, KeyError) as e:
        s.error = f"{type(e).__name__}: {e}"[:300]


class Load:
    """Drives one cell's traffic at a ready engine.

    ``requests`` are the specs of traffic.make_requests; ``bodies`` turns a
    spec into its encoded body (made before the window, never inside it).
    """

    def __init__(self, base: str, mix: dict, requests: list[dict],
                 bodies: list[bytes], vocab: int):
        self.base, self.mix = base, mix
        self.requests, self.bodies, self.vocab = requests, bodies, vocab
        self.samples: list[Sample] = []
        self._next = 0

    def _take(self) -> int | None:
        if self._next >= len(self.requests):
            return None
        i = self._next
        self._next += 1
        return i

    async def _one(self, session, i: int, due: float) -> None:
        spec = self.requests[i]
        s = Sample(index=i, due=due, sent=time.perf_counter())
        self.samples.append(s)
        if self.mix["route"] == "stream":
            s.asked = spec["max_new"]
            await stream_request(session, self.base, self.bodies[i], s, self.vocab)
        else:
            s.asked = spec["rows"]
            await predict_request(session, self.base, self.bodies[i], s)

    async def _closed_client(self, session, t_stop: float) -> None:
        while time.perf_counter() < t_stop:
            i = self._take()
            if i is None:
                raise RuntimeError("closed loop ran out of prepared requests")
            await self._one(session, i, time.perf_counter())

    async def run(self, window_s: float, dues: list[float] | None) -> tuple[float, float]:
        """Offer the load; returns the window's (start, end) on this clock.
        The window opens ``lead_in_s`` after the first request."""
        lead = float(self.mix.get("lead_in_s", 0.0))
        drain = float(self.mix.get("drain_s", 30.0))
        timeout = aiohttp.ClientTimeout(total=None, sock_read=drain + window_s + lead)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
            t0 = time.perf_counter()
            w0, w1 = t0 + lead, t0 + lead + window_s
            if not traffic.open_loop(self.mix):
                tasks = [
                    asyncio.create_task(self._closed_client(session, w1))
                    for _ in range(int(self.mix["clients"]))
                ]
            else:  # whatever the arrival process, the schedule is ``dues``
                tasks = []
                for due in dues:
                    if t0 + due >= w1:
                        break
                    wait = t0 + due - time.perf_counter()
                    if wait > 0:
                        await asyncio.sleep(wait)
                    i = self._take()
                    if i is None:
                        raise RuntimeError("open loop ran out of prepared requests")
                    tasks.append(asyncio.create_task(self._one(session, i, t0 + due)))
                wait = w1 - time.perf_counter()
                if wait > 0:
                    await asyncio.sleep(wait)
            # the drain: what is in flight may finish; what does not, failed
            left = w1 + drain - time.perf_counter()
            done, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
            for t in done:
                t.result()  # a bug in the generator is an error, not a sample
            for s in self.samples:
                if not s.ok and s.error is None:
                    s.error = "not finished by the end of the drain"
            return w0, w1
