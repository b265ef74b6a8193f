"""Central registry of every ``SCT_*`` environment variable.

Deliberately stdlib-only and import-light: the operator's control plane,
the sctlint static analyzer, and the docs generator all need the full
knob table without pulling the JAX runtime (the same constraint as
utils/mesh_contract.py).  Every env var the serving plane reads MUST be
declared here — sctlint's ``env-registry`` rule fails CI on a quoted
``SCT_*`` literal that has no declaration, and docs/CONFIG.md is
generated from this table (``python -m seldon_core_tpu.tools.sctlint
--write-config-docs`` after editing).

Call sites may keep their local ``os.environ.get`` idiom (registration
is the invariant, not the accessor), but new code should prefer the
typed getters below so default + type live in exactly one place.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "Setting",
    "REGISTRY",
    "declare",
    "get_raw",
    "get_str",
    "get_int",
    "get_float",
    "get_bool",
    "markdown_table",
]


@dataclass(frozen=True)
class Setting:
    """One declared env var: its textual default (exactly the string the
    call site would pass to ``os.environ.get``; ``None`` = unset means
    feature off / value absent), coarse type, and a one-line doc."""

    name: str
    default: str | None
    type: str  # "str" | "int" | "float" | "bool" | "csv"
    doc: str
    section: str


REGISTRY: dict[str, Setting] = {}

# values get_bool treats as false; anything else (incl. bare "set") is true
_FALSY = ("", "0", "false", "off", "no")


def declare(
    name: str,
    default: str | None,
    type: str,
    doc: str,
    *,
    section: str = "general",
) -> Setting:
    if name in REGISTRY:
        raise ValueError(f"duplicate setting declaration: {name}")
    if not name.startswith("SCT_"):
        raise ValueError(f"settings registry is for SCT_* vars, got {name}")
    s = Setting(name, default, type, doc, section)
    REGISTRY[name] = s
    return s


def _lookup(name: str) -> Setting:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name} is not declared in seldon_core_tpu.runtime.settings; "
            "declare() it (sctlint env-registry enforces this)"
        ) from None


def get_raw(name: str, environ=None) -> str | None:
    """The raw env string, falling back to the declared default."""
    env = os.environ if environ is None else environ
    s = _lookup(name)
    v = env.get(name)
    return s.default if v is None else v


def get_str(name: str, environ=None) -> str | None:
    v = get_raw(name, environ)
    return v if v else _lookup(name).default


def get_int(name: str, environ=None) -> int:
    s = _lookup(name)
    v = get_raw(name, environ)
    try:
        return int(v or s.default or 0)
    except ValueError:
        return int(s.default or 0)


def get_float(name: str, environ=None) -> float:
    s = _lookup(name)
    v = get_raw(name, environ)
    try:
        return float(v or s.default or 0.0)
    except ValueError:
        return float(s.default or 0.0)


def get_bool(name: str, environ=None) -> bool:
    v = get_raw(name, environ)
    return (v if v is not None else "").strip().lower() not in _FALSY


# ---------------------------------------------------------------------------
# declarations
# ---------------------------------------------------------------------------

# -- execution plane: generation scheduler + compiled programs --------------
declare("SCT_GEN_OVERLAP", "1", "bool",
        "Overlapped decode pipeline: dispatch block N+1 before fetching "
        "block N (docs/PERFORMANCE.md).",
        section="executor")
declare("SCT_GEN_QUEUE_MAX", "256", "int",
        "Generation admission queue depth before overflow shedding.",
        section="executor")
declare("SCT_BATCH_PIPELINE", "8", "int",
        "Micro-batch pipeline depth for the non-generative batcher.",
        section="executor")
declare("SCT_BATCH_QUEUE_MAX", "2048", "int",
        "Batcher queue depth before overflow shedding.",
        section="executor")
declare("SCT_WARMUP_CONCURRENCY", "4", "int",
        "Threads compiling warmup program variants in parallel.",
        section="executor")
declare("SCT_WARMUP_SUFFIX", "1", "bool",
        "Warm suffix-prefill programs (per prefix-window bucket) at boot.",
        section="executor")
declare("SCT_SPEC_DRAFT", "0", "int",
        "Self-speculative draft length per verify pass (0 = speculation "
        "off; docs/PERFORMANCE.md §6).",
        section="executor")
declare("SCT_SPEC_NGRAM", "3", "int",
        "N-gram order of the on-device draft history ring.",
        section="executor")
declare("SCT_SPEC_METHOD", "ngram", "str",
        "Speculative proposer when SCT_SPEC_DRAFT > 0: ``ngram`` (history "
        "ring), ``heads`` (fused Medusa-style decode heads), or ``draft`` "
        "(co-resident draft model; docs/PERFORMANCE.md §6).",
        section="executor")
declare("SCT_SPEC_HEADS", "0", "int",
        "Medusa-style head count for ``heads`` speculation (0 = match "
        "SCT_SPEC_DRAFT; must be >= the draft length).",
        section="executor")
declare("SCT_SPEC_HEADS_PATH", None, "str",
        "Checkpoint directory for trained speculation heads (unset = "
        "synthesize from the base lm_head; executor/checkpoint.py layout).",
        section="executor")
declare("SCT_SPEC_DRAFT_MODEL", "truncate:auto", "str",
        "Draft model geometry for ``draft`` speculation: ``truncate:N`` "
        "(first N base layers), ``truncate:auto``, or ``preset:NAME`` "
        "(family preset sharing the base vocab).",
        section="executor")
declare("SCT_PREFILL_CHUNK", "0", "int",
        "Chunked-prefill chunk size in tokens (0 = monolithic prefill; "
        "docs/PERFORMANCE.md §7).",
        section="executor")
declare("SCT_DECODE_KERNEL", None, "bool",
        "Read the paged KV pool in decode through the Pallas paged "
        "decode-attention kernel (ops/paged_attention.py): ``1`` the "
        "kernel, ``0`` the XLA gather; chosen by the program when unset "
        "(the kernel where the pool is on one device and the backend "
        "compiles Pallas; the ``decode_kernel`` graph parameter comes "
        "first).",
        section="executor")
declare("SCT_KV_DTYPE", None, "str",
        "Paged-KV quantization dtype (``int8``; unset = model dtype).",
        section="executor")

# -- multi-LoRA adapter plane ----------------------------------------------
declare("SCT_LORA_RANK", "0", "int",
        "LoRA adapter rank (0 = multi-LoRA plane off; docs/MULTITENANT.md).",
        section="lora")
declare("SCT_LORA_SLOTS", "8", "int",
        "HBM adapter-pool slots (stacked A/B factors) per deployment.",
        section="lora")
declare("SCT_LORA_TARGETS", "qkvo", "str",
        "Projection set adapters apply to (subset of ``qkvo``).",
        section="lora")
declare("SCT_LORA_ADAPTERS", None, "csv",
        "Adapters to register at boot: ``name[:seed]`` comma list.",
        section="lora")

# -- HBM + host-DRAM memory ledgers ----------------------------------------
declare("SCT_HBM_GB", "16", "float",
        "Per-chip HBM budget the MemoryManager arbitrates (GiB).",
        section="memory")
declare("SCT_HBM_ENFORCE", "0", "bool",
        "Reject deployment builds whose reservation exceeds the HBM "
        "budget (HBMOverCommit) instead of logging.",
        section="memory")
declare("SCT_PREFIX_DRAM_GB", "0", "float",
        "Host-DRAM pool for demoted prefix KV blocks (GiB, 0 = tier "
        "off; docs/CACHING.md).",
        section="memory")
declare("SCT_PACK_SUSPEND_GB", "1", "float",
        "Host-DRAM budget for preemption suspend records (GiB; "
        "docs/PACKING.md).",
        section="memory")

# -- prefix cache + response cache -----------------------------------------
declare("SCT_CACHE_PREFIX", "0", "bool",
        "Radix prefix-KV reuse across admissions (docs/CACHING.md).",
        section="cache")
declare("SCT_PREFIX_PEER_PULL", "0", "bool",
        "Pull hot prefix KV from the peer replica advertising it instead "
        "of re-prefilling (docs/CACHING.md tiers).",
        section="cache")
declare("SCT_CACHE", "0", "bool",
        "Gateway response cache + single-flight collapser.",
        section="cache")
declare("SCT_CACHE_DEPLOYMENTS", None, "csv",
        "Restrict the response cache to these deployments (empty = all).",
        section="cache")
declare("SCT_CACHE_MAX_ENTRIES", "4096", "int",
        "Response-cache entry cap.",
        section="cache")
declare("SCT_CACHE_MAX_BYTES", "67108864", "int",
        "Response-cache byte cap.",
        section="cache")
declare("SCT_CACHE_TTL_S", "60", "float",
        "Response-cache entry TTL (seconds).",
        section="cache")
declare("SCT_SEMCACHE", "0", "bool",
        "Semantic cache tier: cosine-similarity hits over pooled prompt "
        "embeddings (needs SCT_EMBED on the unit; docs/CACHING.md).",
        section="cache")
declare("SCT_SEMCACHE_SIM", "0.95", "float",
        "Cosine-similarity threshold for a semantic cache hit.",
        section="cache")
declare("SCT_SEMCACHE_MAX_ENTRIES", "2048", "int",
        "Semantic-cache entry cap.",
        section="cache")
declare("SCT_SEMCACHE_MAX_BYTES", "33554432", "int",
        "Semantic-cache byte cap (vectors + cached response bytes).",
        section="cache")
declare("SCT_SEMCACHE_TTL_S", "300", "float",
        "Semantic-cache entry TTL (seconds).",
        section="cache")

# -- LLM inference graphs (docs/GRAPHS.md) ----------------------------------
declare("SCT_EMBED", "0", "bool",
        "Pooled-embedding path on generative units: POST /embeddings + "
        "the semantic cache tier's vector source (docs/GRAPHS.md).",
        section="graphllm")
declare("SCT_CASCADE_CONF_SIGNAL", "0", "bool",
        "Fold the per-step top-2 logit margin into the fused decode "
        "programs so replies carry a confidence signal for cascade "
        "routing (zero extra host syncs; docs/GRAPHS.md).",
        section="graphllm")
declare("SCT_CASCADE_CONF", "2.0", "float",
        "Mean logit-margin threshold below which a cascade tier's answer "
        "is escalated to the next tier.",
        section="graphllm")
declare("SCT_CASCADE_TTFT_MS", "0", "float",
        "Expected next-tier TTFT: escalation is skipped when the "
        "remaining deadline budget is smaller (0 = gate off).",
        section="graphllm")
declare("SCT_GUARDRAIL_CLASS", "interactive", "str",
        "Default QoS class guardrail units re-seed for their downstream "
        "walk (``interactive``/``batch``; docs/GRAPHS.md).",
        section="graphllm")

# -- QoS admission (engine SCT_QOS_*, gateway SCT_GW_QOS_*) -----------------
for _pfx, _where in (("SCT_QOS", "engine"), ("SCT_GW_QOS", "gateway")):
    _default_enabled = "1" if _pfx == "SCT_QOS" else None
    declare(_pfx, _default_enabled, "bool",
            f"Enable the {_where} QoS admission controller "
            "(docs/QOS.md; engine defaults on, gateway off).",
            section="qos")
    declare(f"{_pfx}_MAX_INFLIGHT", "256", "int",
            f"{_where}: in-flight request cap before shedding.",
            section="qos")
    declare(f"{_pfx}_MAX_QUEUE", "512", "int",
            f"{_where}: admission queue cap before shedding.",
            section="qos")
    declare(f"{_pfx}_RATE", "0", "float",
            f"{_where}: token-bucket refill rate, requests/s (0 = off).",
            section="qos")
    declare(f"{_pfx}_BURST", "0", "float",
            f"{_where}: token-bucket burst size.",
            section="qos")
    declare(f"{_pfx}_INTERACTIVE_RESERVE", "0.5", "float",
            f"{_where}: fraction of capacity reserved for interactive "
            "traffic under brownout.",
            section="qos")
    declare(f"{_pfx}_DEFAULT_DEADLINE_MS", "0", "float",
            f"{_where}: deadline stamped on requests that carry none "
            "(0 = no default SLO).",
            section="qos")
    declare(f"{_pfx}_PREDICTIVE", "1", "bool",
            f"{_where}: predictive shedding off queue-wait EWMAs.",
            section="qos")
    declare(f"{_pfx}_BROWNOUT_SHED_RATE", "0.5", "float",
            f"{_where}: fraction of batch traffic shed during brownout.",
            section="qos")
    declare(f"{_pfx}_BROWNOUT_WINDOW_S", "5", "float",
            f"{_where}: decision window for entering brownout (seconds).",
            section="qos")
    declare(f"{_pfx}_BROWNOUT_COOLDOWN_S", "5", "float",
            f"{_where}: cooldown before leaving brownout (seconds).",
            section="qos")
    declare(f"{_pfx}_BROWNOUT_CLAMP_TOKENS", "16", "int",
            f"{_where}: max_tokens clamp applied during brownout.",
            section="qos")
declare("SCT_DEFAULT_DEADLINE_MS", "0", "float",
        "Gateway-wide default deadline for requests without one (ms).",
        section="qos")

# -- chip packing / device arbiter -----------------------------------------
declare("SCT_PACK", "0", "bool",
        "Auto-attach every GenerativeComponent to the shared device "
        "arbiter (docs/PACKING.md).",
        section="packing")
declare("SCT_PACK_SLO_MS", None, "float",
        "Interactive queue-wait SLO band for packed deployments (ms; "
        "unset = caller/per-deployment default).",
        section="packing")
declare("SCT_PACK_PREEMPT", "1.0", "float",
        "Preempt a batch co-resident when interactive pressure >= "
        "slo * this.",
        section="packing")
declare("SCT_PACK_RESUME", "0.5", "float",
        "Resume the preempted deployment when pressure < slo * this.",
        section="packing")

# -- disaggregated prefill/decode ------------------------------------------
declare("SCT_ENGINE_ROLE", None, "str",
        "Engine pool role: ``unified`` (default), ``prefill`` or "
        "``decode`` (docs/DISAGGREGATION.md).",
        section="disagg")
declare("SCT_DISAGG_DECODE", None, "csv",
        "Decode-pool upstream URLs a prefill engine hands off to "
        "(operator-injected).",
        section="disagg")
declare("SCT_DISAGG_TIMEOUT_S", "30", "float",
        "Prefill->decode handoff timeout before unified-local fallback.",
        section="disagg")

# -- gateway data plane -----------------------------------------------------
declare("SCT_REST_IMPL", "h1", "str",
        "Gateway REST server implementation (``h1`` native, ``aiohttp`` "
        "fallback).",
        section="gateway")
declare("SCT_GRPC_IMPL", None, "str",
        "gRPC transport (default native h2; ``grpcio`` falls back to "
        "grpc.aio).",
        section="gateway")
declare("SCT_GW_UPSTREAM_CONNS", "8", "int",
        "Pooled upstream connections per engine endpoint.",
        section="gateway")
declare("SCT_GW_PIPELINE_BUF", "65536", "int",
        "Per-connection pipelined-response buffer (bytes).",
        section="gateway")
declare("SCT_GW_ROUTE_POLL_S", "2", "float",
        "Replica /stats poll interval for prefix-affine routing (s).",
        section="gateway")
declare("SCT_GW_ROUTE_PREFIX", "1", "bool",
        "Longest-prefix-match replica routing over gossiped radix "
        "digests (docs/DISAGGREGATION.md routing).",
        section="gateway")
declare("SCT_GW_PEER_YIELD", "4", "int",
        "Peer-pull yield: decode admissions awaited per peer-prefix "
        "install.",
        section="gateway")

# -- resilience / chaos plane (docs/RESILIENCE.md) --------------------------
declare("SCT_CHAOS_PLAN", None, "str",
        "Deterministic fault-injection plan "
        "(``site:kind[:key=value...];...`` — see docs/RESILIENCE.md). "
        "Unset = chaos plane fully inert (production default).",
        section="resilience")
declare("SCT_CHAOS_SEED", "0", "int",
        "Seed for probabilistic chaos rules (``p=``): one seed replays "
        "the identical fault sequence.",
        section="resilience")
declare("SCT_GW_POLL_FAILS", "2", "int",
        "Consecutive failed /stats/cache polls before the router clears "
        "a replica's prefix digests (one dropped poll must not destroy "
        "prefix affinity).",
        section="resilience")
declare("SCT_GW_RETRY_BUDGET", "10", "float",
        "Per-deployment retry-budget burst: retries available to an "
        "idle deployment before the refill rate gates them.",
        section="resilience")
declare("SCT_GW_RETRY_RATE", "0.2", "float",
        "Retry-budget refill: retries earned per forwarded request "
        "(0.2 = at most ~20% retry amplification under sustained "
        "failure).",
        section="resilience")
declare("SCT_GW_RETRY_BACKOFF_MS", "25", "float",
        "Base delay of the gateway's jittered exponential retry "
        "backoff (ms).",
        section="resilience")
declare("SCT_GW_RETRY_BACKOFF_MAX_MS", "1000", "float",
        "Cap on the gateway's per-attempt retry backoff (ms).",
        section="resilience")
declare("SCT_GW_CB_FAILS", "3", "int",
        "Consecutive forward failures that eject a replica from p2c "
        "routing (circuit breaker opens).",
        section="resilience")
declare("SCT_GW_CB_EJECT_S", "5", "float",
        "Ejection window before an open circuit admits one half-open "
        "probe request.",
        section="resilience")
declare("SCT_WATCH_BACKOFF_MS", "50", "float",
        "Base delay of the watch-relist backoff after consecutive 410 "
        "Gone (storm damping in gateway/operator watchers).",
        section="resilience")
declare("SCT_WATCH_BACKOFF_MAX_MS", "5000", "float",
        "Cap on the watch-relist backoff (ms).",
        section="resilience")
declare("SCT_KUBE_RETRIES", "4", "int",
        "Apiserver request attempts on 429/5xx before the error "
        "surfaces (Retry-After honored, capped jittered backoff).",
        section="resilience")

# -- observability ----------------------------------------------------------
declare("SCT_TIMELINE", "1", "bool",
        "Per-request lifecycle timelines (GET /stats/timeline; "
        "docs/OBSERVABILITY.md).",
        section="observability")
declare("SCT_TIMELINE_MAX", "512", "int",
        "Retained request timelines (ring).",
        section="observability")
declare("SCT_TIMELINE_EVENTS", "256", "int",
        "Events per timeline before drop-counting.",
        section="observability")
declare("SCT_SPANS_RING", "2048", "int",
        "In-memory span ring size (/stats/spans).",
        section="observability")
declare("SCT_STAGE_RING", "8192", "int",
        "Per-stage latency sample ring size (/stats/breakdown).",
        section="observability")
declare("SCT_TRACE_SAMPLE", "1.0", "float",
        "Trace sampling fraction [0, 1].",
        section="observability")
declare("SCT_SPANS_BROKER", None, "str",
        "Span fan-out broker URL for cross-pool trace stitching "
        "(unset = local ring only).",
        section="observability")
declare("SCT_SPANS_EXPORT_QUEUE", "2048", "int",
        "Bounded span export queue (drops oldest beyond this).",
        section="observability")
declare("SCT_OTLP_ENDPOINT", None, "str",
        "OTLP/HTTP collector endpoint for span export (unset = off).",
        section="observability")
declare("SCT_OTLP_TIMEOUT_S", "1.0", "float",
        "OTLP export request timeout (seconds).",
        section="observability")
declare("SCT_LOOP_LAG_INTERVAL_S", "0.25", "float",
        "Event-loop lag probe interval (seconds).",
        section="observability")
declare("SCT_METER", "1", "bool",
        "Per-tenant usage metering: device time + tokens attributed to "
        "(deployment, adapter, qos) keys (GET /stats/usage; "
        "docs/OBSERVABILITY.md cost attribution).",
        section="observability")
declare("SCT_METER_MAX_KEYS", "512", "int",
        "Live usage-meter key rows (LRU; evictions fold counter-exactly "
        "into the `other` rollup).",
        section="observability")
declare("SCT_METER_TOP_K", "16", "int",
        "seldon_usage_* label rows exported per scrape before the "
        "`other` rollup row (bounded cardinality).",
        section="observability")
declare("SCT_METER_ADAPTER_LABELS", "32", "int",
        "Distinct adapter label values on per-adapter metric families "
        "(seldon_lora_tokens and friends) before new adapters roll up "
        "into `other`.",
        section="observability")
declare("SCT_METRICS_EXEMPLARS", "0", "bool",
        "Render /prometheus in OpenMetrics format with trace-id "
        "exemplars on hot-stage latency histograms (a p99 spike links "
        "to GET /stats/timeline?trace=).",
        section="observability")

# -- fleet telemetry (collector + SLO engine; docs/OBSERVABILITY.md) --------
declare("SCT_FLEET", "1", "bool",
        "Run the fleet collector (operator + gateway): per-deployment "
        "aggregation of replica /stats/* into GET /stats/fleet.",
        section="fleet")
declare("SCT_FLEET_POLL_S", "10", "float",
        "Fleet collector poll interval (seconds, jittered).",
        section="fleet")
declare("SCT_FLEET_JITTER", "0.2", "float",
        "Poll-interval jitter fraction [0, 1] so a replica set is never "
        "scraped in lockstep.",
        section="fleet")
declare("SCT_FLEET_TIMEOUT_S", "2.0", "float",
        "Per-replica scrape HTTP timeout (seconds).",
        section="fleet")
declare("SCT_FLEET_STALE_POLLS", "3", "int",
        "Polls without a successful scrape before a replica is marked "
        "stale and excluded from aggregates (not zeroed).",
        section="fleet")
declare("SCT_FLEET_FAIL_DAMP", "3", "int",
        "Consecutive scrape failures before the collector damps that "
        "replica (skips a growing number of polls, capped).",
        section="fleet")
declare("SCT_FLEET_HISTORY_SLOTS", "360", "int",
        "Slots per time-series ring per resolution (10s and 2min rings; "
        "bounded, drop-on-full).",
        section="fleet")
declare("SCT_FLEET_PORT", "9109", "int",
        "Stats port of the operator / standalone collector "
        "(GET /stats/fleet, GET /stats/slo).",
        section="fleet")
declare("SCT_SLO", "1", "bool",
        "Evaluate declared SLO objectives as multi-window burn rates.",
        section="fleet")
declare("SCT_SLO_DEFAULT", None, "str",
        "Fallback SLO spec (seldon.io/slo grammar) for deployments "
        "without the annotation (unset = no objectives).",
        section="fleet")
declare("SCT_SLO_FAST_WINDOW_S", "60", "float",
        "Fast burn-rate window (seconds) — pages quickly on hard "
        "outages.",
        section="fleet")
declare("SCT_SLO_SLOW_WINDOW_S", "600", "float",
        "Slow burn-rate window (seconds) — confirms sustained burn "
        "before paging.",
        section="fleet")
declare("SCT_SLO_PAGE_BURN", "14.0", "float",
        "Burn-rate threshold (x budget) that flips warn -> page when "
        "both windows exceed it.",
        section="fleet")
declare("SCT_SLO_WARN_BURN", "6.0", "float",
        "Burn-rate threshold (x budget) that flips ok -> warn when "
        "both windows exceed it.",
        section="fleet")

# -- elastic autoscaler (closed-loop pool scaling; docs/AUTOSCALING.md) -----
declare("SCT_SCALE", "1", "bool",
        "Run the autoscale reconciler in the operator (scaling still "
        "requires the seldon.io/autoscale annotation on a CR).",
        section="scale")
declare("SCT_SCALE_INTERVAL_S", "15", "float",
        "Autoscale reconcile interval (seconds); each tick reads the "
        "fleet collector's latest aggregates and decides per pool.",
        section="scale")
declare("SCT_SCALE_EWMA_ALPHA", "0.4", "float",
        "EWMA smoothing factor (0, 1] applied to every policy signal "
        "before threshold comparison (1 = no smoothing).",
        section="scale")
declare("SCT_SCALE_UP_AT", "1.0", "float",
        "Upper hysteresis edge: scale up when max signal pressure "
        "(smoothed value / declared target) reaches this.",
        section="scale")
declare("SCT_SCALE_DOWN_AT", "0.5", "float",
        "Lower hysteresis edge: scale down only when EVERY fresh signal "
        "pressure sits at or below this (the band between down and up "
        "edges never moves replicas).",
        section="scale")
declare("SCT_SCALE_UP_HOLD_S", "60", "float",
        "Dwell after a scale-up before the next scale-up decision.",
        section="scale")
declare("SCT_SCALE_DOWN_HOLD_S", "180", "float",
        "Dwell after any scale decision before a scale-down (shrink is "
        "drain-based and deliberately slower than growth).",
        section="scale")
declare("SCT_SCALE_LOOKAHEAD_S", "60", "float",
        "Slope lookahead horizon: a signal is projected forward this "
        "many seconds along its history-ring trend, so a steady ramp "
        "scales up BEFORE it crosses the target.",
        section="scale")
declare("SCT_SCALE_MAX_STEP", "2", "int",
        "Max replicas added by one scale-up decision (shrink is always "
        "one drained replica per decision).",
        section="scale")
declare("SCT_SCALE_STALE_S", "90", "float",
        "Signal freshness horizon: observations older than this never "
        "drive a decision (covers collector gaps and counter dips "
        "during replica churn).",
        section="scale")
declare("SCT_SCALE_WINDOW_S", "60", "float",
        "Window for counter-derived signals (windowed shed rate) read "
        "off the fleet history rings.",
        section="scale")
declare("SCT_SCALE_LEDGER", "256", "int",
        "Decision-ledger ring size served on GET /stats/autoscale "
        "(bounded, drops oldest).",
        section="scale")
declare("SCT_SCALE_DRAIN_TIMEOUT_S", "30", "float",
        "Per-victim POST /admin/drain timeout during drain-based "
        "shrink; a failed or refused drain aborts the decision.",
        section="scale")
declare("SCT_SCALE_DEFAULT", None, "str",
        "Fallback autoscale spec (seldon.io/autoscale grammar) for "
        "deployments without the annotation (unset = static pools).",
        section="scale")

# -- multi-host mesh boot contract (operator-injected; jax-free reader in
#    utils/mesh_contract.py) ------------------------------------------------
declare("SCT_NUM_PROCESSES", None, "int",
        "Process count of the multi-host mesh (operator-injected; unset "
        "= single-process).",
        section="mesh")
declare("SCT_PROCESS_ID", None, "int",
        "Explicit process index (else derived from the pod ordinal).",
        section="mesh")
declare("SCT_COORDINATOR_ADDRESS", None, "str",
        "Explicit jax.distributed coordinator address (else derived "
        "from the mesh Service DNS).",
        section="mesh")
declare("SCT_COORDINATOR_PORT", "8476", "int",
        "Coordinator port of the multi-host boot contract.",
        section="mesh")
declare("SCT_MESH_SERVICE", None, "str",
        "Headless Service name giving each pod stable DNS for mesh "
        "formation (operator-injected).",
        section="mesh")
declare("SCT_POD_NAME", None, "str",
        "Pod name whose StatefulSet ordinal becomes the process index "
        "(operator-injected).",
        section="mesh")


# ---------------------------------------------------------------------------
# docs generation (docs/CONFIG.md)
# ---------------------------------------------------------------------------

_SECTION_TITLES = {
    "executor": "Execution plane (scheduler, compiled programs)",
    "lora": "Multi-LoRA adapter plane",
    "memory": "HBM + host-DRAM memory ledgers",
    "cache": "Prefix + response caching",
    "graphllm": "LLM inference graphs (cascades, embeddings, guardrails)",
    "qos": "QoS admission (engine `SCT_QOS_*`, gateway `SCT_GW_QOS_*`)",
    "packing": "Chip packing / device arbiter",
    "disagg": "Disaggregated prefill/decode",
    "gateway": "Gateway data plane",
    "resilience": "Resilience / chaos plane",
    "observability": "Observability",
    "fleet": "Fleet telemetry (collector + SLO engine)",
    "scale": "Elastic autoscaler (policy + drain-based actuator)",
    "mesh": "Multi-host mesh boot contract",
    "general": "General",
}


def markdown_table() -> str:
    """docs/CONFIG.md, generated.  Regenerate with
    ``python -m seldon_core_tpu.tools.sctlint --write-config-docs``."""
    out = [
        "# Configuration reference — `SCT_*` environment variables",
        "",
        "<!-- GENERATED FILE — do not edit by hand.  Source of truth: "
        "seldon_core_tpu/runtime/settings.py; regenerate with "
        "`python -m seldon_core_tpu.tools.sctlint --write-config-docs` "
        "(CI's `make lint-check` fails when stale). -->",
        "",
        f"{len(REGISTRY)} variables.  Every `SCT_*` env var the serving "
        "plane reads is declared in "
        "`seldon_core_tpu/runtime/settings.py`; sctlint's `env-registry` "
        "rule fails CI on an undeclared read (docs/STATIC_ANALYSIS.md).",
        "",
    ]
    for section, title in _SECTION_TITLES.items():
        rows = [s for s in REGISTRY.values() if s.section == section]
        if not rows:
            continue
        out += [f"## {title}", "",
                "| Variable | Default | Type | Description |",
                "|---|---|---|---|"]
        for s in rows:
            default = "_(unset)_" if s.default is None else f"`{s.default}`"
            out.append(f"| `{s.name}` | {default} | {s.type} | {s.doc} |")
        out.append("")
    return "\n".join(out) + ""
