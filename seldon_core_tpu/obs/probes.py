"""Always-on cheap perf probes: event-loop lag, host-sync counts, and
span-ring/export drop gauges.

These are the "why did throughput move" counters that are too cheap to
ever turn off (Dapper's always-on discipline): a saturated event loop, a
chatty host<->device sync pattern, or a silently-dropping span exporter
each explain a benchmark swing that the latency flight recorder alone
cannot.  Everything here is O(1) per event and bounded.
"""

from __future__ import annotations

import os
import time
from collections import defaultdict

_EWMA_ALPHA = 0.2


class EventLoopLagProbe:
    """Self-rescheduling ``call_later`` probe: the delta between when the
    callback was due and when it actually ran IS the event-loop lag — the
    single number that says "the serving loop is saturated" (a blocked
    loop shows up here before it shows up anywhere else).

    One probe per process (module-level ``LOOP_LAG``); ``start()`` is
    idempotent.  Interval via ``SCT_LOOP_LAG_INTERVAL_S`` (default 0.25s).
    """

    def __init__(self, interval_s: float | None = None):
        if interval_s is None:
            interval_s = float(os.environ.get("SCT_LOOP_LAG_INTERVAL_S", "0.25"))
        self.interval_s = max(0.01, interval_s)
        self.samples = 0
        self.last_lag_s = 0.0
        self.ewma_lag_s = 0.0
        self.max_lag_s = 0.0
        self._handle = None
        self._loop = None
        self._service = ""
        self._gauge = None

    def start(self, service: str = "") -> None:
        import asyncio

        loop = asyncio.get_running_loop()
        if self._handle is not None and self._loop is loop:
            return  # already probing this loop
        self._loop = loop
        self._service = service or self._service
        from seldon_core_tpu.utils.metrics import DEFAULT

        self._gauge = DEFAULT.eventloop_lag.labels(self._service or "default")
        self._gauge.set(0.0)  # visible in /prometheus before the first tick
        self._schedule()

    def _schedule(self) -> None:
        due = self._loop.time() + self.interval_s
        self._handle = self._loop.call_later(self.interval_s, self._tick, due)

    def _tick(self, due: float) -> None:
        lag = max(0.0, self._loop.time() - due)
        self.samples += 1
        self.last_lag_s = lag
        self.ewma_lag_s = _EWMA_ALPHA * lag + (1.0 - _EWMA_ALPHA) * self.ewma_lag_s
        if lag > self.max_lag_s:
            self.max_lag_s = lag
        if self._gauge is not None:
            self._gauge.set(self.ewma_lag_s)
        self._schedule()

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None

    def snapshot(self) -> dict:
        from seldon_core_tpu.obs.wire import sig4

        return {
            "interval_s": self.interval_s,
            "samples": self.samples,
            "last_lag_ms": sig4(self.last_lag_s * 1e3),
            "ewma_lag_ms": sig4(self.ewma_lag_s * 1e3),
            "max_lag_ms": sig4(self.max_lag_s * 1e3),
        }


LOOP_LAG = EventLoopLagProbe()


# -- host-sync accounting ----------------------------------------------------
#
# Every np.asarray/device_get on a dispatched device result is one
# host<->device round trip that stalls the dispatching thread until the
# device catches up, so syncs-per-step is THE ratio that explains "device
# MFU is fine but wire throughput collapsed".  Counted per model, lock-free
# (a lost increment under a thread race is noise).

_host_syncs: dict[str, int] = defaultdict(int)


def record_host_sync(model: str, n: int = 1) -> None:
    _host_syncs[model] += n
    try:
        from seldon_core_tpu.utils.metrics import DEFAULT

        DEFAULT.host_syncs.labels(model).inc(n)
    except Exception:
        pass  # metrics must never fail a device step


def host_sync_snapshot() -> dict:
    return dict(_host_syncs)


# -- span-ring / export drop gauges ------------------------------------------

_gauges_installed = False


def install_obs_gauges() -> None:
    """Bind pull-time gauges for the span recorder's ring/export counters
    so ``/prometheus`` exposes recording pressure (sampled-out spans,
    exporter drops) without a push on every span.  Idempotent; called from
    ``configure_exporters_from_env`` at engine/gateway boot."""
    global _gauges_installed
    if _gauges_installed:
        return
    from seldon_core_tpu.obs.spans import RECORDER
    from seldon_core_tpu.utils.metrics import DEFAULT

    DEFAULT.obs_spans.labels("recorded").set_function(lambda: RECORDER.recorded)
    DEFAULT.obs_spans.labels("ring").set_function(lambda: len(RECORDER._spans))
    DEFAULT.obs_spans.labels("sampled_out").set_function(
        lambda: RECORDER.sampled_out
    )

    def _export_total(field: str) -> float:
        return float(sum(getattr(e, field, 0) for e in RECORDER.exporters))

    DEFAULT.obs_export.labels("exported").set_function(
        lambda: _export_total("exported")
    )
    DEFAULT.obs_export.labels("dropped").set_function(
        lambda: _export_total("dropped")
    )
    _gauges_installed = True
