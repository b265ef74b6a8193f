"""In-process span recorder + per-stage latency flight recorder.

The reference system had no distributed tracing at all (correlation was a
puid plus latency log lines); this module is the always-on Dapper-style
layer for the TPU serving hot path, with no OTel SDK dependency:

* **spans** — every hop (gateway relay, engine route, graph node) opens a
  span against the request's W3C trace context (``utils/tracectx.py``);
  finished spans land in a bounded in-process ring buffer and fan out to
  exporters (``obs/export.py``: OTLP/HTTP JSON, taplog topic).  A sampling
  knob (``SCT_TRACE_SAMPLE``, default 1.0) thins span RECORDING; context
  PROPAGATION is never sampled away, so downstream hops always correlate.
* **stages** — the flight recorder: fixed-vocabulary per-stage duration
  rings (gateway-relay / engine-route / node / queue-wait / batch-assembly
  / device-step / stream-flush / ttft, and the parts of a generated
  request's host path: ingress / slot-wait / admit-round / first-write /
  sync-point) that answer "where did the p99 go" without reconstructing
  traces.  Stage recording is unconditional and
  cheap (one deque append), including from executor threads.

Both are served by ``GET /stats/spans`` and ``GET /stats/breakdown`` on the
engine and the gateway.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import os
import random
import threading
import time
from collections import defaultdict, deque
from typing import Any, Iterator

from seldon_core_tpu.obs import history as _history
from seldon_core_tpu.utils.tracectx import (
    get_traceparent,
    make_span_id,
    new_traceparent,
    parse_traceparent,
    _traceparent,
)

# the flight recorder's stage vocabulary (docs/OBSERVABILITY.md)
STAGE_GATEWAY_RELAY = "gateway-relay"
STAGE_ENGINE_ROUTE = "engine-route"
STAGE_NODE = "node"
STAGE_QUEUE_WAIT = "queue-wait"
STAGE_BATCH_ASSEMBLY = "batch-assembly"
STAGE_DEVICE_STEP = "device-step"
STAGE_DEVICE_DISPATCH = "device-dispatch"
STAGE_STREAM_FLUSH = "stream-flush"
STAGE_TTFT = "ttft"
# the host path of a generated request, part by part (generation scheduler
# and the engine's stream handler): what ``ttft`` and the gap between two
# decode blocks are made of
STAGE_SLOT_WAIT = "slot-wait"
STAGE_ADMIT_ROUND = "admit-round"
STAGE_SYNC_POINT = "sync-point"
STAGE_INGRESS = "ingress"
STAGE_FIRST_WRITE = "first-write"

STAGES = (
    STAGE_GATEWAY_RELAY,
    STAGE_ENGINE_ROUTE,
    STAGE_NODE,
    STAGE_QUEUE_WAIT,
    STAGE_BATCH_ASSEMBLY,
    STAGE_DEVICE_STEP,
    STAGE_DEVICE_DISPATCH,
    STAGE_STREAM_FLUSH,
    STAGE_TTFT,
    STAGE_SLOT_WAIT,
    STAGE_ADMIT_ROUND,
    STAGE_SYNC_POINT,
    STAGE_INGRESS,
    STAGE_FIRST_WRITE,
)


@dataclasses.dataclass
class Span:
    """One finished span.  Times are epoch seconds (floats); exporters
    convert to OTLP nanos."""

    trace_id: str
    span_id: str
    parent_id: str | None
    name: str
    service: str
    start: float
    duration_s: float
    status: str = "OK"
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)
    events: list = dataclasses.field(default_factory=list)  # (name, epoch_s, attrs)

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "service": self.service,
            "start": self.start,
            "duration_ms": round(self.duration_s * 1e3, 3),
            "status": self.status,
            "attrs": self.attrs,
            "events": [
                {"name": n, "ts": ts, "attrs": a} for n, ts, a in self.events
            ],
        }


class _LiveSpan:
    """The in-flight handle yielded by :meth:`SpanRecorder.span`."""

    __slots__ = ("span", "_t0")

    def __init__(self, span: Span, t0: float):
        self.span = span
        self._t0 = t0

    def set_attr(self, key: str, value: Any) -> None:
        self.span.attrs[key] = value

    def event(self, name: str, **attrs: Any) -> None:
        self.span.events.append((name, time.time(), attrs))

    def set_status(self, status: str) -> None:
        self.span.status = status


# the innermost live span of this async context (None when unsampled or no
# span is open) — lets deeper layers (batcher submit) attach events without
# plumbing a handle through every signature
_live_span: contextvars.ContextVar["_LiveSpan | None"] = contextvars.ContextVar(
    "sct_live_span", default=None
)


def current_span() -> "_LiveSpan | None":
    return _live_span.get()


# ``engine.role`` resource attribute (docs/OBSERVABILITY.md "cross-pool
# stitching"): every recorded span names the pool role that recorded it
# (prefill / decode / unified / gateway), so a stitched disagg trace read
# from either engine's /stats/spans attributes each hop to its pool.  A
# request-scoped contextvar (seeded at every ingress) wins over the
# process-level default (seeded at boot) — test harnesses run several
# role-typed engines in one process.
_engine_role: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "sct_engine_role", default=None
)
_process_role: str | None = None


def set_engine_role(role: str | None) -> None:
    """Seed this request context's ``engine.role`` span attribute."""
    _engine_role.set(role or None)


def set_process_role(role: str | None) -> None:
    """Process-level fallback role (engine boot) for spans recorded
    outside any request context."""
    global _process_role
    _process_role = role or None


def current_engine_role() -> str | None:
    return _engine_role.get() or _process_role


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


class SpanRecorder:
    """Bounded always-on recorder; one per process (module-level RECORDER).

    Memory is bounded by construction: the span ring (``SCT_SPANS_RING``,
    default 2048 spans) and the per-stage duration rings
    (``SCT_STAGE_RING``, default 8192 samples per stage) are deques with
    maxlen — a traffic burst evicts oldest, never grows.  Exporters hang off
    :meth:`record` behind their own bounded queues (obs/export.py), so a
    dead collector or broker can only ever drop spans, never block serving.
    """

    def __init__(
        self,
        max_spans: int | None = None,
        max_stage_samples: int | None = None,
        sample: float | None = None,
    ):
        if max_spans is None:
            max_spans = int(os.environ.get("SCT_SPANS_RING", "2048"))
        if max_stage_samples is None:
            max_stage_samples = int(os.environ.get("SCT_STAGE_RING", "8192"))
        if sample is None:
            sample = float(os.environ.get("SCT_TRACE_SAMPLE", "1.0"))
        self.sample = min(1.0, max(0.0, sample))
        self._spans: deque[Span] = deque(maxlen=max_spans)
        self._stages: dict[str, deque[float]] = defaultdict(
            lambda: deque(maxlen=max_stage_samples)
        )
        for s in STAGES:  # pre-create: thread-safe appends need no __missing__
            self._stages[s]
        # cumulative (survive ring eviction); lock-free int adds are fine
        # for stats — a lost increment under a rare thread race is noise
        self._stage_counts: dict[str, int] = defaultdict(int)
        # cumulative per-stage bucket counts on the SHARED grid
        # (obs/history.BUCKET_EDGES): unlike breakdown()'s ring quantiles
        # these merge across replicas — the fleet collector sums them and
        # derives p50/p99 from the merged counts
        self._stage_hist: dict[str, list[int]] = defaultdict(_history.new_hist)
        self.recorded = 0
        self.sampled_out = 0
        self.exporters: list = []

    # -- recording ---------------------------------------------------------

    def should_sample(self) -> bool:
        if self.sample >= 1.0:
            return True
        if self.sample <= 0.0:
            return False
        return random.random() < self.sample

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        service: str = "",
        stage: str | None = None,
        attrs: dict | None = None,
    ) -> Iterator["_LiveSpan | None"]:
        """Open a span in this async context.

        Joins the current traceparent as a child (minting a root when none
        is set), and re-points the context's span-id at this span so
        downstream hops and child spans parent correctly.  Yields the live
        span (None when sampled out — stage timing still recorded).
        An exception inside marks the span ERROR and re-raises.
        """
        tp = get_traceparent()
        parsed = parse_traceparent(tp)
        t0 = time.perf_counter()
        start = time.time()
        minted_root = parsed is None
        if minted_root:
            tp = new_traceparent(sampled=self.should_sample())
            parsed = parse_traceparent(tp)
            parent_id = None
        else:
            parent_id = parsed[1]
        trace_id, _, flags = parsed
        recording = bool(flags & 0x01) and self.sample > 0.0
        live: _LiveSpan | None = None
        live_token = None
        if recording:
            span_id = make_span_id()
            token = _traceparent.set(f"00-{trace_id}-{span_id}-{flags:02x}")
            span_attrs = dict(attrs) if attrs else {}
            role = current_engine_role()
            if role is not None:
                span_attrs.setdefault("engine.role", role)
            live = _LiveSpan(
                Span(
                    trace_id=trace_id,
                    span_id=span_id,
                    parent_id=parent_id,
                    name=name,
                    service=service,
                    start=start,
                    duration_s=0.0,
                    attrs=span_attrs,
                ),
                t0,
            )
            live_token = _live_span.set(live)
        else:
            # propagate unchanged: the decision not to RECORD must not
            # break correlation for hops that do
            token = _traceparent.set(tp)
        try:
            yield live
        except BaseException:
            if live is not None:
                live.span.status = "ERROR"
            raise
        finally:
            dt = time.perf_counter() - t0
            if not minted_root:
                # restore the parent context for sibling spans.  A minted
                # root stays set instead: the ingress layer reads it after
                # the span closes to echo the trace id, and every entry
                # point re-seeds the contextvar per request
                _traceparent.reset(token)
            if live_token is not None:
                _live_span.reset(live_token)
            if stage is not None:
                self.record_stage(stage, dt)
            if live is not None:
                live.span.duration_s = dt
                self.record(live.span)

    def record(self, span: Span) -> None:
        self._spans.append(span)
        self.recorded += 1
        for exp in self.exporters:
            exp.offer(span)

    def record_span(
        self,
        name: str,
        *,
        trace_id: str,
        parent_id: str | None,
        start: float,
        duration_s: float,
        service: str = "",
        status: str = "OK",
        attrs: dict | None = None,
        sampled: bool = True,
        span_id: str | None = None,
    ) -> None:
        """Record a span built outside a contextvar scope (protocol
        callbacks like the h1 splice and the gRPC relay time requests
        across event-loop callbacks, not within one task)."""
        if not sampled or self.sample <= 0.0:
            self.sampled_out += 1
            return
        span_attrs = dict(attrs) if attrs else {}
        role = current_engine_role()
        if role is not None:
            span_attrs.setdefault("engine.role", role)
        self.record(
            Span(
                trace_id=trace_id,
                span_id=span_id or make_span_id(),
                parent_id=parent_id,
                name=name,
                service=service,
                start=start,
                duration_s=duration_s,
                status=status,
                attrs=span_attrs,
            )
        )

    def record_stage(self, stage: str, duration_s: float) -> None:
        """Flight-recorder append: unconditional, thread-safe (deque
        append is atomic), O(1)."""
        self._stages[stage].append(duration_s)
        self._stage_counts[stage] += 1
        _history.record_hist(self._stage_hist[stage], duration_s)

    # -- reading -----------------------------------------------------------

    def stage_ewma(self, stage: str, n: int = 64, alpha: float = 0.2) -> float | None:
        """EWMA over the stage ring's last ``n`` samples (None when the
        stage has no data yet).  Feeds the QoS plane's time-to-completion
        estimate at admission (qos/admission.py) — recent samples dominate
        so the estimate tracks load shifts within a few steps."""
        ring = self._stages.get(stage)
        if not ring:
            return None
        vals = list(ring)[-max(1, n):]
        est = vals[0]
        for v in vals[1:]:
            est = alpha * v + (1.0 - alpha) * est
        return est

    def breakdown(self) -> dict:
        """Aggregated per-stage latency over the ring window:
        ``{stage: {count, window, total_ms, p50_ms, p90_ms, p99_ms,
        max_ms}}``.  ``count`` is cumulative; the quantiles and total are
        over the last ``SCT_STAGE_RING`` samples."""
        out: dict[str, dict] = {}
        for stage, ring in list(self._stages.items()):
            vals = sorted(ring)
            if not vals:
                continue
            out[stage] = {
                "count": self._stage_counts[stage],
                "window": len(vals),
                "total_ms": round(sum(vals) * 1e3, 3),
                "p50_ms": round(_percentile(vals, 0.50) * 1e3, 3),
                "p90_ms": round(_percentile(vals, 0.90) * 1e3, 3),
                "p99_ms": round(_percentile(vals, 0.99) * 1e3, 3),
                "max_ms": round(vals[-1] * 1e3, 3),
            }
        return out

    def stage_histograms(self) -> dict:
        """Cumulative per-stage bucket counts over the shared log grid
        (``obs/history.BUCKET_EDGES``) — the MERGEABLE form of
        :meth:`breakdown`.  Served in ``GET /stats/summary`` so the fleet
        collector can sum counts across replicas and compute true fleet
        percentiles instead of averaging per-replica quantiles."""
        return {
            stage: list(h)
            for stage, h in list(self._stage_hist.items())
            if self._stage_counts.get(stage)
        }

    def recent_traces(self, n: int = 20) -> list[dict]:
        """The last ``n`` traces (newest first), each with its spans in
        recording order."""
        by_trace: dict[str, list[Span]] = {}
        order: list[str] = []
        for span in self._spans:
            if span.trace_id not in by_trace:
                by_trace[span.trace_id] = []
                order.append(span.trace_id)
            by_trace[span.trace_id].append(span)
        out = []
        for tid in reversed(order[-n:]):
            spans = by_trace[tid]
            out.append(
                {
                    "trace_id": tid,
                    "span_count": len(spans),
                    "duration_ms": round(
                        max(s.duration_s for s in spans) * 1e3, 3
                    ),
                    "spans": [s.to_dict() for s in spans],
                }
            )
        return out

    def slowest(self, n: int = 10) -> list[dict]:
        """Slowest-N root spans in the ring (the tail-latency suspects)."""
        roots = [s for s in self._spans if s.parent_id is None]
        roots.sort(key=lambda s: s.duration_s, reverse=True)
        return [s.to_dict() for s in roots[:n]]

    def stats(self, n: int = 20) -> dict:
        """The ``GET /stats/spans`` payload."""
        export = {}
        for exp in self.exporters:
            export[type(exp).__name__] = {
                "exported": exp.exported,
                "dropped": exp.dropped,
            }
        return {
            "recorded": self.recorded,
            "ring": len(self._spans),
            "sample": self.sample,
            "exporters": export,
            "slowest": self.slowest(min(n, 10)),
            "traces": self.recent_traces(n),
        }


# default process-wide recorder (mirrors utils/metrics.DEFAULT)
RECORDER = SpanRecorder()
