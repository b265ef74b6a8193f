"""Observability: in-process span recorder + per-stage latency flight
recorder for the serving hot path (see docs/OBSERVABILITY.md).

``RECORDER`` is the process-wide default (like ``utils/metrics.DEFAULT``);
exporters configured via env attach on first use by the serving apps
(``configure_exporters_from_env``).
"""

from __future__ import annotations

from seldon_core_tpu.obs.spans import (  # noqa: F401
    RECORDER,
    STAGE_ADMIT_ROUND,
    STAGE_BATCH_ASSEMBLY,
    STAGE_DEVICE_DISPATCH,
    STAGE_DEVICE_STEP,
    STAGE_ENGINE_ROUTE,
    STAGE_FIRST_WRITE,
    STAGE_GATEWAY_RELAY,
    STAGE_INGRESS,
    STAGE_NODE,
    STAGE_QUEUE_WAIT,
    STAGE_SLOT_WAIT,
    STAGE_STREAM_FLUSH,
    STAGE_SYNC_POINT,
    STAGE_TTFT,
    STAGES,
    Span,
    SpanRecorder,
    current_engine_role,
    current_span,
    set_engine_role,
    set_process_role,
)
from seldon_core_tpu.obs.timeline import (  # noqa: F401
    TIMELINE,
    Timeline,
    TimelineLedger,
)
from seldon_core_tpu.obs.wire import (  # noqa: F401
    WIRE,
    WIRE_ENGINE_GRPC,
    WIRE_ENGINE_NODE,
    WIRE_ENGINE_REST,
    WIRE_GATEWAY_GRPC,
    WIRE_GATEWAY_H1,
    WIRE_GATEWAY_REST,
    WIRE_STAGES,
    WireCounter,
    WireRecorder,
)
from seldon_core_tpu.obs.probes import (  # noqa: F401
    LOOP_LAG,
    host_sync_snapshot,
    record_host_sync,
)
from seldon_core_tpu.obs.history import (  # noqa: F401
    BUCKET_EDGES,
    History,
    hist_percentile_ms,
    merge_hist,
    new_hist,
)
from seldon_core_tpu.obs.slo import (  # noqa: F401
    SLO_ANNOTATION,
    SloEngine,
    SloError,
    SloObjective,
    parse_slo,
)
from seldon_core_tpu.obs.fleet import FleetCollector  # noqa: F401
from seldon_core_tpu.obs.metering import (  # noqa: F401
    METER,
    UsageMeter,
    get_meter,
)


def configure_exporters_from_env(recorder: SpanRecorder | None = None) -> list:
    """Attach env-selected exporters (idempotent: second call is a no-op
    unless the recorder has none yet) and bind the span-ring/export drop
    gauges into /prometheus.  Called at engine/gateway boot."""
    from seldon_core_tpu.obs.export import exporters_from_env
    from seldon_core_tpu.obs.probes import install_obs_gauges

    rec = recorder or RECORDER
    if not rec.exporters:
        rec.exporters = exporters_from_env()
    install_obs_gauges()
    return rec.exporters


def wire_stats_payload() -> dict:
    """The ``GET /stats/wire`` body, shared by the engine and both gateway
    REST front ends: per-edge byte/MB-s counters plus the always-on
    probes (event-loop lag, host syncs per model)."""
    return {
        "wire": WIRE.snapshot(),
        "loop_lag": LOOP_LAG.snapshot(),
        "host_syncs": host_sync_snapshot(),
    }
