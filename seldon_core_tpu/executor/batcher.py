"""Continuous micro-batching queue with a pipelined device stream.

The reference's concurrency model is one Tomcat thread per in-flight request,
each doing its own network round-trip to the model server (reference:
engine/.../PredictiveUnitBean.java:68-112).  On TPU the equivalent resource
is *device steps*: many concurrent requests should coalesce into one large
batch per step so the MXU runs full tiles.

Two latencies matter:

* collection latency — how long a request waits for batch-mates
  (``max_delay_ms``, one timer per step, drain via ``get_nowait``);
* device round-trip — dispatch is sub-ms, but *materializing* a result
  blocks for the full device round trip.  The queue therefore
  dispatches each step immediately on the event loop and fetches results on
  a thread pool with up to ``pipeline_depth`` steps in flight, so round-trip
  latency amortizes across the stream instead of serializing it.

Runners may be a plain callable ``batch -> result`` or expose the
``dispatch(batch) -> handle`` / ``fetch(*handle) -> result`` pair
(:class:`~seldon_core_tpu.executor.compiled.CompiledModel` does).
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import functools
import os
import time
from typing import Callable

import numpy as np

from seldon_core_tpu.obs import (
    RECORDER,
    STAGE_BATCH_ASSEMBLY,
    STAGE_DEVICE_DISPATCH,
    STAGE_DEVICE_STEP,
    STAGE_QUEUE_WAIT,
    current_span,
    record_host_sync,
)
from seldon_core_tpu.obs.metering import METER
from seldon_core_tpu.qos import DeadlineExceeded, QueueFull, note_deadline_miss
from seldon_core_tpu.qos.context import get_deadline
from seldon_core_tpu.utils.metrics import DEFAULT as DEFAULT_METRICS

@functools.cache
def _chip_peak() -> float | None:
    """Chip bf16 peak FLOP/s (None off-TPU), resolved once per process.
    An unknown TPU raises (utils/roofline.py) — the engine resolves this
    at startup so that is a boot failure, not a vanished MFU gauge."""
    from seldon_core_tpu.utils.roofline import chip_peak_flops

    return chip_peak_flops()


class BatchQueue:
    def __init__(
        self,
        runner: Callable[[np.ndarray], np.ndarray],
        *,
        max_batch: int = 64,
        max_delay_ms: float = 2.0,
        pipeline_depth: int | None = None,
        name: str = "model",
        maxsize: int | None = None,
    ):
        self.runner = runner
        if pipeline_depth is None:
            # in-flight device steps the stream keeps dispatched ahead of
            # the fetches (overlap depth): each step's fetch is ONE host
            # sync for the whole batch, and deeper pipelining hides more of
            # the per-step round trip behind device compute
            pipeline_depth = int(os.environ.get("SCT_BATCH_PIPELINE", "8"))
        self.max_batch = int(max_batch)
        self.max_delay = max_delay_ms / 1000.0
        self.name = name
        # intake bound (QoS plane): beyond this many waiting request
        # batches, submit() fast-fails with a typed QueueFull the engine
        # maps to 429 — an unbounded queue only converts overload into
        # client timeouts after the device burned steps on them.  0 = off.
        self.maxsize = (
            int(maxsize)
            if maxsize is not None
            else int(os.environ.get("SCT_BATCH_QUEUE_MAX", "2048"))
        )
        self._dispatch = getattr(runner, "dispatch", None)
        self._fetch = getattr(runner, "fetch", None)
        # only dispatch/fetch runners (CompiledModel) are promised to be
        # thread-safe; a plain callable keeps the single-runner-thread
        # guarantee and therefore a pipeline of 1
        self._pipelined = self._dispatch is not None and self._fetch is not None
        depth = max(1, pipeline_depth) if self._pipelined else 1
        self._queue: asyncio.Queue = asyncio.Queue()
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=depth,
            thread_name_prefix=f"batcher-{name}",
        )
        self._sem = asyncio.Semaphore(depth)
        self._inflight: set[asyncio.Task] = set()
        self._task: asyncio.Task | None = None
        self._closed = False
        # observability
        self.steps = 0
        self.rows = 0
        # FLOPs one batch row costs (set by the component wiring when the
        # model knows; feeds the MFU gauge against the chip peak)
        self.flops_per_row: float | None = getattr(runner, "flops_per_row", None)
        m = DEFAULT_METRICS
        self._m_queue_wait = m.queue_wait.labels(name)
        self._m_device_step = m.device_step.labels(name)
        self._m_batch_size = m.batch_size.labels(name)
        self._m_queue_depth = m.queue_depth.labels(name)
        self._m_mfu = m.mfu.labels(name)
        self._m_device_frac = m.device_frac.labels(name)

    # ------------------------------------------------------------- lifecycle
    def _ensure_running(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def close(self) -> None:
        """Stop the loop and fail every pending/in-flight request cleanly
        (a hung awaiter is worse than an errored one during drain)."""
        self._closed = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        for t in list(self._inflight):
            t.cancel()
        await asyncio.gather(*self._inflight, return_exceptions=True)
        err = RuntimeError(f"BatchQueue {self.name!r} closed")
        while not self._queue.empty():
            _, fut, _, _, _ = self._queue.get_nowait()
            if not fut.done():
                fut.set_exception(err)
        self._pool.shutdown(wait=False)

    # ------------------------------------------------------------- interface
    async def submit(self, x: np.ndarray) -> np.ndarray:
        """Submit one request batch (rows stay together); returns its rows.

        Raises :class:`~seldon_core_tpu.qos.QueueFull` when the bounded
        intake is at capacity, and :class:`~seldon_core_tpu.qos.
        DeadlineExceeded` when the request's deadline expires before its
        device step dispatches.  A caller that goes away (client
        disconnect cancels the awaiting task) leaves a cancelled future
        the step loop skips, so abandoned work never reaches the device."""
        if self._closed:
            raise RuntimeError("BatchQueue is closed")
        self._ensure_running()
        x = np.asarray(x)
        if self.maxsize and self._queue.qsize() >= self.maxsize:
            raise QueueFull(
                f"batch queue {self.name!r} is full "
                f"({self._queue.qsize()} waiting, cap {self.maxsize})"
            )
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        # the request's QoS deadline + live span ride the queue item so the
        # step loop can drop expired work (and say why, on the trace)
        # without re-entering this task's context
        await self._queue.put(
            (x, fut, time.perf_counter(), get_deadline(), current_span())
        )
        self._m_queue_depth.set(self._queue.qsize())
        res = await fut
        timing = getattr(fut, "_sct_timing", None)
        if timing is not None:
            # back in the request's context: attach the step timing to the
            # enclosing span (the walker's node span) as events
            sp = current_span()
            if sp is not None:
                qw, step_s = timing
                sp.event(
                    "batch-step",
                    queue_wait_ms=round(qw * 1e3, 3),
                    device_step_ms=round(step_s * 1e3, 3),
                )
        return res

    # ------------------------------------------------------------- internals
    @staticmethod
    def _key(x: np.ndarray) -> tuple:
        return (x.shape[1:] if x.ndim > 1 else x.shape, x.dtype.str)

    @staticmethod
    def _rows(x: np.ndarray) -> int:
        return x.shape[0] if x.ndim > 1 else 1

    def _viable(self, item) -> bool:
        """Pre-dispatch QoS gate: skip requests whose client is gone
        (cancelled future) and fail ones whose deadline already expired —
        a device step must never be spent on work nobody can use."""
        _x, fut, t_enq, deadline, span = item
        if fut.done():
            return False
        if deadline is not None and time.monotonic() >= deadline:
            fut.set_exception(
                DeadlineExceeded(
                    f"deadline expired after "
                    f"{time.perf_counter() - t_enq:.3f}s waiting in batch "
                    f"queue {self.name!r}"
                )
            )
            DEFAULT_METRICS.qos_deadline_miss.labels(self.name, "batch-queue").inc()
            note_deadline_miss("batch-queue")
            if span is not None:
                span.event("qos-drop", reason="deadline", stage="batch-queue")
            return False
        return True

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        pending: collections.deque = collections.deque()  # misfits, served first
        group: list = []
        try:
            while True:
                first = pending.popleft() if pending else await self._queue.get()
                if not self._viable(first):
                    continue
                t_collect0 = loop.time()  # batch-assembly stage starts here
                group = [first]
                key = self._key(first[0])
                rows = self._rows(first[0])
                # absorb compatible held-over items before waiting on the queue
                for item in list(pending):
                    if rows >= self.max_batch:
                        break
                    if self._key(item[0]) == key:
                        pending.remove(item)
                        if not self._viable(item):
                            continue
                        group.append(item)
                        rows += self._rows(item[0])

                def drain(total: int) -> int:
                    # drain immediately-available items without timer
                    # machinery (a wait_for per item costs more than the
                    # device step at high request rates)
                    while total < self.max_batch:
                        try:
                            item = self._queue.get_nowait()
                        except asyncio.QueueEmpty:
                            break
                        if self._key(item[0]) != key:
                            # hold for the *next* group so a minority shape
                            # is served right after this step, not starved
                            # behind a dominant-shape stream
                            pending.append(item)
                            continue
                        if not self._viable(item):
                            continue
                        group.append(item)
                        total += self._rows(item[0])
                    return total

                rows = drain(rows)
                # wait out the collection window, but dispatch the moment the
                # batch fills — a full batch must not sit out the timer
                deadline = loop.time() + self.max_delay
                while rows < self.max_batch:
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        break
                    try:
                        item = await asyncio.wait_for(self._queue.get(), remaining)
                    except asyncio.TimeoutError:
                        break
                    if self._key(item[0]) != key:
                        pending.append(item)
                        continue
                    if not self._viable(item):
                        continue
                    group.append(item)
                    rows += self._rows(item[0])
                    rows = drain(rows)  # absorb any burst that came with it

                RECORDER.record_stage(
                    STAGE_BATCH_ASSEMBLY, loop.time() - t_collect0
                )
                await self._sem.acquire()  # bound the in-flight pipeline
                task = loop.create_task(self._step(loop, group))
                self._inflight.add(task)
                task.add_done_callback(self._inflight.discard)
                group = []
        except asyncio.CancelledError:
            err = RuntimeError(f"BatchQueue {self.name!r} closed")
            for _, fut, _, _, _ in list(group) + list(pending):
                if not fut.done():
                    fut.set_exception(err)
            raise

    async def _step(self, loop, group) -> None:
        # final sweep at the device boundary: the collection window may
        # have outlived a deadline, and a 504 from the queue is strictly
        # cheaper than a device step for a client that stopped waiting
        group = [item for item in group if self._viable(item)]
        if not group:
            self._sem.release()
            return
        xs = [np.atleast_2d(x) for x, _, _, _, _ in group]
        batch = np.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]
        t_step0 = time.perf_counter()
        waits = []
        for _, _, t_enq, _, _ in group:
            qw = t_step0 - t_enq
            waits.append(qw)
            RECORDER.record_stage(STAGE_QUEUE_WAIT, qw)
            self._m_queue_wait.observe(qw)
        self._m_batch_size.observe(batch.shape[0])
        # host-time vs device-time split of this step: [dispatch_s] filled
        # on the pool thread; fetch (the device wait + result transfer +
        # one host sync) is the remainder of step_s
        split = [0.0]
        try:
            try:
                cap = getattr(getattr(self.runner, "buckets", None), "max", None)
                if self._pipelined and (cap is None or batch.shape[0] <= cap):
                    # dispatch+fetch both on a pool thread: dispatch may
                    # compile an un-warmed bucket (seconds) and must not
                    # block the event loop; concurrent pool threads keep the
                    # device stream pipelined
                    def run_step(b=batch):
                        t_d0 = time.perf_counter()
                        handle = self._dispatch(b)
                        split[0] = time.perf_counter() - t_d0
                        return self._fetch(*handle)

                    out = await loop.run_in_executor(self._pool, run_step)
                else:
                    # oversize group (multi-row requests can overflow the
                    # ladder): the plain runner path chunks internally
                    out = await loop.run_in_executor(self._pool, self.runner, batch)
            except asyncio.CancelledError:
                err: BaseException = RuntimeError(f"BatchQueue {self.name!r} closed")
                for _, fut, _, _, _ in group:
                    if not fut.done():
                        fut.set_exception(err)
                raise
            except Exception as exc:  # propagate to every waiter
                for _, fut, _, _, _ in group:
                    if not fut.done():
                        fut.set_exception(exc)
                return
            step_s = time.perf_counter() - t_step0
            RECORDER.record_stage(STAGE_DEVICE_STEP, step_s)
            self._m_device_step.observe(step_s)
            record_host_sync(self.name)  # the fetch materialized one result
            dispatch_s = split[0]
            device_s = step_s - dispatch_s if 0 < dispatch_s < step_s else step_s
            # usage attribution: queue items carry no adapter/qos, so the
            # whole measured device slice of this step charges the owning
            # deployment's base row (host bookkeeping at the step boundary)
            METER.add(self.name, device_s=device_s)
            if dispatch_s > 0:
                RECORDER.record_stage(STAGE_DEVICE_DISPATCH, dispatch_s)
                self._m_device_frac.set(device_s / step_s if step_s > 0 else 0.0)
            if self.flops_per_row and step_s > 0:
                peak = _chip_peak()
                if peak:
                    # MFU against DEVICE time (step minus host dispatch):
                    # the wall view charges host tracing overhead to the chip
                    self._m_mfu.set(
                        batch.shape[0] * self.flops_per_row / device_s / peak
                    )
            self.steps += 1
            self.rows += batch.shape[0]
            out = np.asarray(out)
            offset = 0
            for (x, fut, _, _, _), rows, qw in zip(
                group, (x.shape[0] for x in xs), waits
            ):
                if not fut.done():
                    fut._sct_timing = (qw, step_s)  # read back in submit()
                    res = out[offset : offset + rows]
                    fut.set_result(res if x.ndim > 1 else res[0])
                offset += rows
        finally:
            self._sem.release()
