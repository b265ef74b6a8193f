"""Engine HTTP application — the per-predictor orchestrator service.

REST surface matches the reference engine (reference:
engine/.../api/rest/RestClientController.java:62-175):

    POST /api/v0.1/predictions   (alias /api/v1.0/predictions)
    POST /api/v0.1/feedback      (alias /api/v1.0/feedback)
    GET  /ping  /ready           liveness / readiness
    GET  /pause /unpause         graceful-drain toggle used by the preStop
                                 hook (readiness flips to 503 while paused;
                                 reference: App.java:67-105 connector pause)
    GET  /prometheus             metrics scrape endpoint
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import time
from typing import Any

from aiohttp import web

from seldon_core_tpu.contract import (
    failure_status_dict,
    CodecError,
    feedback_from_dict,
    payload_from_dict,
    payload_to_dict,
)
from seldon_core_tpu import chaos
from seldon_core_tpu import disagg as disagg_mod
from seldon_core_tpu import qos
from seldon_core_tpu.engine.service import (
    PredictionService,
    load_co_predictor_specs,
    load_predictor_spec,
)
from seldon_core_tpu.graph.units import GraphUnitError
from seldon_core_tpu.obs import (
    LOOP_LAG,
    RECORDER,
    STAGE_STREAM_FLUSH,
    TIMELINE,
    WIRE,
    WIRE_ENGINE_REST,
    configure_exporters_from_env,
    set_engine_role,
    set_process_role,
    wire_stats_payload,
)
from seldon_core_tpu.utils.metrics import DEFAULT as DEFAULT_METRICS

log = logging.getLogger(__name__)


def _status_body(code: int, reason: str) -> dict[str, Any]:
    return failure_status_dict(code, reason)


class EngineApp:
    def __init__(
        self,
        service: PredictionService,
        mesh_worker: bool = False,
        qos_controller: "qos.AdmissionController | None" = None,
        role: str | None = None,
        decode_upstreams: list[str] | None = None,
        co_services: "list[PredictionService] | None" = None,
    ):
        self.service = service
        # chip packing (docs/PACKING.md): ADDITIONAL predictor services
        # co-booted in this process (ENGINE_CO_PREDICTORS) — their
        # generative units register with the device arbiter at startup
        # and time-share the chip with the primary service's units
        self.co_services = list(co_services or [])
        self.paused = False
        self.metrics = service.metrics
        # disagg plane (docs/DISAGGREGATION.md): the engine's pool role
        # (prefill / decode / unified, SCT_ENGINE_ROLE) and — for prefill
        # engines — the decode peers KV handoffs stream to
        # (SCT_DISAGG_DECODE, comma-separated host:port)
        self.role = disagg_mod.resolve_role(role)
        self.decode_upstreams = (
            list(decode_upstreams)
            if decode_upstreams is not None
            else disagg_mod.decode_upstreams()
        )
        self._handoff_session = None
        self._handoff_inflight: dict[str, int] = {}
        self._handoff_timeout_s = float(
            os.environ.get("SCT_DISAGG_TIMEOUT_S", "30") or 30.0
        )
        self.disagg_stats = {
            "handoffs_ok": 0,
            "handoffs_failed": 0,
            "local_fallbacks": 0,
            "imports_ok": 0,
            "imports_failed": 0,
        }
        # tiered prefix store, peer tier (docs/CACHING.md "Tiered prefix
        # store"): pull-client + pull-server ledgers.  Pull failures of ANY
        # kind degrade to plain suffix prefill — they are counted, never
        # surfaced to the request.
        self.prefix_pull_stats = {
            "pulls_ok": 0,
            "pulls_failed": 0,
            "pull_misses": 0,
            "pull_bytes": 0,
            "pull_blocks": 0,
            "serves_ok": 0,
            "serve_misses": 0,
        }
        # QoS plane (docs/QOS.md): per-deployment admission control +
        # deadline propagation; env-configured (SCT_QOS_*), on by default.
        # Registered process-wide so the generation scheduler's brownout
        # clamp sees the same policy object.
        self.qos = (
            qos_controller
            if qos_controller is not None
            else qos.AdmissionController.from_env(service.deployment_name)
        )
        qos.set_active_controller(self.qos)
        # Non-coordinator host of a multi-host slice: joins the mesh and
        # executes SPMD steps under the coordinator's direction (see
        # executor/multihost.py follower loop) but never serves ingress —
        # /ready stays 503 so the deployment-wide Service routes to the
        # coordinator pod only.
        self.mesh_worker = mesh_worker
        # readiness gates on warmup: every JAX unit's bucket ladder must be
        # compiled before /ready flips true, so the first real request never
        # pays an XLA compile (the reference's unwarmed engine shows a
        # 5,071 ms max-latency spike, docs/benchmarking.md:42-45)
        self.warmed = False
        self._warmup_error: BaseException | None = None
        self._warmup_task: asyncio.Task | None = None
        self._warmup_total_s: float | None = None
        # what the graph's JAX units serve on (utils/device.py), set at
        # startup; None for a graph with no device unit, which must never
        # initialise JAX (it would take the chip from a co-located engine)
        self.device: dict | None = None
        # XLA compile requests seen when readiness flipped: /stats/warmup
        # reports how many came AFTER it (a warmed server must show zero)
        self._compiles_at_ready: int | None = None
        self._profile_dir: str | None = None
        self._profile_stopping = False
        # ingress-tier response cache: bound at startup, and ONLY when the
        # whole graph is deterministic (a randomized router poisons
        # whole-response cacheability; node-tier caching still applies to
        # its deterministic MODEL children)
        self._resp_cache = None
        # semantic tier: same determinism gate as the exact tier, plus it
        # needs an embed-capable generative unit at runtime
        self._sem_cache = None
        # live drain bookkeeping (docs/AUTOSCALING.md): a second
        # POST /admin/drain answers 409 WITH this state (phase, peer,
        # migration progress) so the autoscale reconciler's retry can
        # observe the in-flight drain instead of guessing, and
        # /admin/undrain refuses to lift a drain whose peer migration is
        # still relaying streams
        self._drain_state: dict[str, Any] | None = None

    def build(self) -> web.Application:
        # wire-throughput accounting on the whole REST surface: request
        # bytes from the framing header, response bytes from the prepared
        # body, duration wall-clocked around the handler (obs/wire.py)
        wire = WIRE.counter(WIRE_ENGINE_REST, self.service.deployment_name)

        @web.middleware
        async def _wire_mw(request: web.Request, handler):
            import time as _time

            # every span this request records carries the pool role
            # (engine.role resource attr — docs/OBSERVABILITY.md): the
            # contextvar wins over the process default so test harnesses
            # running several role-typed engines in one process stay honest
            set_engine_role(self.role)
            t0 = _time.perf_counter()
            resp = await handler(request)
            body = getattr(resp, "body", None)
            wire.record(
                bytes_in=request.content_length or 0,
                bytes_out=len(body) if isinstance(body, (bytes, bytearray)) else 0,
                duration_s=_time.perf_counter() - t0,
            )
            return resp

        app = web.Application(
            client_max_size=256 * 1024 * 1024, middlewares=[_wire_mw]
        )

        # which SO_REUSEPORT worker answered — lets operators (and the
        # multi-worker test) see the kernel's accept balancing.  Resolved at
        # build() time: workers fork before building, so a module-level
        # constant would pin every worker to the parent's pid
        worker_tag = str(os.getpid())

        async def _tag_worker(request, response):
            response.headers["X-Engine-Worker"] = worker_tag

        app.on_response_prepare.append(_tag_worker)
        r = app.router
        for prefix in ("/api/v0.1", "/api/v1.0"):
            r.add_post(f"{prefix}/predictions", self.predictions)
            # SSE token streaming for generative graphs (no reference
            # analogue; see docs in predictions_stream)
            r.add_post(f"{prefix}/predictions/stream", self.predictions_stream)
            # pooled prompt embeddings off the generative unit's own
            # weights (docs/GRAPHS.md) — feeds the semantic cache tier
            r.add_post(f"{prefix}/embeddings", self.embeddings)
            r.add_post(f"{prefix}/feedback", self.feedback)
        r.add_get("/ping", self.ping)
        r.add_get("/ready", self.ready)
        # POST is what the operator's preStop hook sends (curl -X POST);
        # GET kept for hand-driving
        r.add_post("/pause", self.pause)
        r.add_get("/pause", self.pause)
        r.add_post("/unpause", self.unpause)
        r.add_get("/unpause", self.unpause)
        r.add_get("/prometheus", self.prometheus)
        # span recorder + flight recorder (docs/OBSERVABILITY.md)
        r.add_get("/stats/spans", self.stats_spans)
        r.add_get("/stats/breakdown", self.stats_breakdown)
        # QoS plane state: admission/shed counters, brownout, estimates
        r.add_get("/stats/qos", self.stats_qos)
        # wire-throughput accounting + always-on perf probes
        r.add_get("/stats/wire", self.stats_wire)
        # caching & reuse plane state (docs/CACHING.md)
        r.add_get("/stats/cache", self.stats_cache)
        # fleet-collector scrape: qos+breakdown+cache+wire+usage+mergeable
        # stage histograms in ONE round trip (docs/OBSERVABILITY.md)
        r.add_get("/stats/summary", self.stats_summary)
        # per-tenant cost attribution (obs/metering.py): device time +
        # tokens per (deployment, adapter, qos) key
        r.add_get("/stats/usage", self.stats_usage)
        # compile-warmup plane: programs compiled + seconds per unit
        # (docs/PERFORMANCE.md) — the readiness-tail attribution
        r.add_get("/stats/warmup", self.stats_warmup)
        # XLA/device profiling (SURVEY §5: the reference had only JMX):
        # POST /profile/start {"dir": "/tmp/sct-profile"} ... /profile/stop
        # then open the trace in TensorBoard / xprof
        r.add_post("/profile/start", self.profile_start)
        r.add_post("/profile/stop", self.profile_stop)
        # disaggregated prefill/decode plane (docs/DISAGGREGATION.md):
        # generate = prefill here + handoff to a decode peer (with unified
        # local fallback); import = receive a peer's KV handoff and decode
        r.add_post("/disagg/generate", self.disagg_generate)
        r.add_post("/disagg/import", self.disagg_import)
        # peer tier of the tiered prefix store (docs/CACHING.md): a replica
        # missing a prefix chain pulls the serialized KV from the replica
        # whose digest advertises it, instead of re-prefilling
        r.add_post("/disagg/prefix/pull", self.disagg_prefix_pull)
        # live-migration plane (docs/RESILIENCE.md "drain runbook"):
        # drain = pause admission, quiesce every active stream into its
        # suspend record, then ship each record bit-exactly to a peer
        # replica through the v4 handoff codec — or park locally until
        # /admin/undrain when no peer is named
        r.add_post("/admin/drain", self.admin_drain)
        r.add_post("/admin/undrain", self.admin_undrain)
        # chaos-plane evidence: per-site arrival/fired counters proving a
        # scenario injected what it claims (empty when SCT_CHAOS_PLAN unset)
        r.add_get("/stats/chaos", self.stats_chaos)
        r.add_get("/stats/disagg", self.stats_disagg)
        # per-request generation lifecycle ledger (obs/timeline.py):
        # ?trace=<id> reconstructs one request's whole story after the fact
        r.add_get("/stats/timeline", self.stats_timeline)
        app.on_startup.append(self._startup)
        app.on_cleanup.append(self._cleanup)
        return app

    async def _startup(self, app: web.Application) -> None:
        configure_exporters_from_env()
        # spans recorded outside a request context (scheduler loop,
        # executor threads) still get the pool's engine.role attribute
        set_process_role(self.role)
        LOOP_LAG.start("engine")
        await self.service.start()
        for svc in self.co_services:
            await svc.start()
        self._register_packed_units()
        if any(
            jax_units(svc.predictor.graph)
            for svc in (self.service, *self.co_services)
        ):
            from seldon_core_tpu.executor.batcher import _chip_peak
            from seldon_core_tpu.utils.device import (
                serving_device,
                xla_compile_count,
            )

            xla_compile_count()  # start counting before warmup compiles
            self.device = serving_device()
            # the MFU gauges' denominator: a TPU with no published peaks
            # fails the boot here instead of serving without the gauge
            _chip_peak()
            log.info(
                "serving on platform=%(platform)s device_kind=%(device_kind)r "
                "device_count=%(device_count)d native_codec=%(native_codec)s",
                self.device,
            )
        if self.service.response_cache is not None and self.service.graph_deterministic():
            self._resp_cache = self.service.response_cache
        if self.service.semantic_cache is not None and self.service.graph_deterministic():
            self._sem_cache = self.service.semantic_cache
        if self.mesh_worker:
            # worker host of a multi-host slice: the same units (and hence
            # the same registered SPMD step fns) were just built; execute the
            # coordinator's broadcast steps on a thread for the pod's whole
            # life.  Warmup arrives as broadcast steps from the coordinator's
            # warmup pass — running it locally too would double-issue
            # collectives and wedge the slice.
            from seldon_core_tpu.executor.multihost import get_driver

            driver = get_driver()
            if driver is not None:
                import threading

                threading.Thread(
                    target=driver.follower_loop, daemon=True, name="sct-mh-follower"
                ).start()
            return
        from seldon_core_tpu.executor.multihost import get_driver

        driver = get_driver()
        if driver is not None:
            driver.start_heartbeat()
        if os.environ.get("ENGINE_WARMUP", "1") == "0" or not self.service.warmable_units():
            self._set_warmed()
        else:
            # warm in the background so liveness (/ping) answers while the
            # compiles run; /ready stays 503 until every bucket is compiled
            self._warmup_task = asyncio.create_task(self._warm())

    def _register_packed_units(self) -> None:
        """Attach every co-resident generative unit (primary + co
        services) to the process device arbiter (docs/PACKING.md) — only
        when co-services actually exist: a sole-tenant engine keeps the
        synchronous fast path and never touches the arbiter."""
        if not self.co_services:
            return
        for svc in (self.service, *self.co_services):
            try:
                units = svc.generative_units()
            except Exception:
                continue
            for unit in units:
                reg = getattr(unit, "register_packed", None)
                if callable(reg):
                    reg()

    async def _warm(self) -> None:
        import time as _time

        t0 = _time.perf_counter()
        try:
            report = await self.service.warmup()
            for svc in self.co_services:
                # co-resident deployments warm their OWN program caches
                # before readiness flips — a packed chip must serve its
                # first real traffic with zero mid-traffic compiles on
                # every co-tenant, not just the primary
                report = await svc.warmup()
            self._warmup_total_s = round(_time.perf_counter() - t0, 3)
            log.info(
                "warmup complete in %.1fs: %s", self._warmup_total_s, report
            )
            self._set_warmed()
        except asyncio.CancelledError:
            raise
        except BaseException as e:
            self._warmup_error = e
            log.exception("warmup failed; readiness stays false")

    def _set_warmed(self) -> None:
        if self.device is not None:
            from seldon_core_tpu.utils.device import xla_compile_count

            self._compiles_at_ready = xla_compile_count()
        self.warmed = True

    async def _cleanup(self, app: web.Application) -> None:
        if self._warmup_task is not None and not self._warmup_task.done():
            self._warmup_task.cancel()
        if self._handoff_session is not None:
            await self._handoff_session.close()
            self._handoff_session = None
        for svc in self.co_services:
            await svc.close()
        await self.service.close()

    # -- handlers ---------------------------------------------------------

    def _admit(self, request: web.Request):
        """Seed the request's QoS context (deadline + priority headers ->
        contextvars the batching layers read) and pass admission control.
        Raises :class:`~seldon_core_tpu.qos.QosRejection` on shed."""
        if not self.qos.enabled:
            # SCT_QOS=0 restores the legacy plane end to end: no deadline
            # plumbing, no priority, no shedding anywhere downstream
            qos.seed_from_headers(None, None)
            return self.qos.admit()
        budget_ms, priority = qos.seed_from_headers(
            request.headers.get(qos.DEADLINE_HEADER),
            request.headers.get(qos.PRIORITY_HEADER),
        )
        if budget_ms is None and self.qos.default_deadline_ms:
            budget_ms = self.qos.default_deadline_ms
            qos.set_budget_ms(budget_ms)
        return self.qos.admit(
            priority, budget_s=budget_ms / 1e3 if budget_ms else None
        )

    def _qos_reject(self, e: "qos.QosRejection") -> web.Response:
        """Map a QoS shed to its wire response (429/504, 429s carry
        Retry-After) and record WHY on the trace — an operator reading the
        span sees the shed reason, not a silent missing request."""
        with RECORDER.span("qos.shed", service=self.service.deployment_name) as sp:
            if sp is not None:
                sp.set_attr("reason", e.reason)
                sp.set_attr("code", e.status)
                sp.set_status("ERROR")
        headers = {}
        if e.status == 429:
            headers["Retry-After"] = e.retry_after_header()
        return web.json_response(
            _status_body(e.status, str(e)), status=e.status, headers=headers
        )

    async def predictions(self, request: web.Request) -> web.Response:
        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(dep, pred, "predictions", "POST") as h:
            from seldon_core_tpu.utils.tracectx import set_traceparent

            # trace context BEFORE admission: a shed decision must land on
            # the client's trace, or overload debugging goes dark exactly
            # when it matters
            set_traceparent(request.headers.get("traceparent"))
            # cache lookup BEFORE admission (docs/CACHING.md): an exact
            # repeat of a deterministic graph's request is served from the
            # content-addressed cache with zero device steps, consuming no
            # admission slot, no queue position, and no deadline budget
            body = None
            cache_key = None
            sem_vec = None
            if self._resp_cache is not None or self._sem_cache is not None:
                try:
                    body = await self._json(request)
                except CodecError as e:
                    h["code"] = "400"
                    return web.json_response(_status_body(400, str(e)), status=400)
            if self._resp_cache is not None:
                from seldon_core_tpu.cache import canonical_body, request_key

                cache_key = request_key(
                    "predictions", self.service.spec_hash, canonical_body(body)
                )
                entry = self._resp_cache.get(dep, cache_key)
                if entry is not None:
                    with RECORDER.span("engine.cache", service=dep) as sp:
                        if sp is not None:
                            sp.event("cache.hit", tier="engine")
                    return web.Response(
                        body=entry.value,
                        content_type="application/json",
                        headers={"x-sct-cache": "hit"},
                    )
            if self._sem_cache is not None:
                # semantic tier (docs/CACHING.md): an exact miss may still
                # be a PARAPHRASE of a cached prompt — embed it with the
                # deployment's own pooled-embedding path and serve the
                # nearest same-spec entry above the similarity threshold,
                # still before admission (no slot, no deadline budget, no
                # generation steps)
                sem_vec = await self._semantic_vec(body)
                if sem_vec is not None:
                    hit = self._sem_cache.lookup(
                        dep, sem_vec, self.service.spec_hash
                    )
                    if hit is not None:
                        with RECORDER.span("engine.cache", service=dep) as sp:
                            if sp is not None:
                                sp.event("cache.hit", tier="semantic")
                        return web.Response(
                            body=hit,
                            content_type="application/json",
                            headers={"x-sct-cache": "semantic"},
                        )
            try:
                ticket = self._admit(request)
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            try:
                if body is None:
                    body = await self._json(request)
                # tiered prefix store, peer tier: a gateway-stamped hint
                # means another replica holds this prompt's KV chain —
                # pull + install it before the graph walk (no-op unless
                # SCT_PREFIX_PEER_PULL=1 and the header is present)
                await self._pull_prefix_from_header(request, body)
                # opt-in per-node wall timings (meta.tags.sct_trace_ms) —
                # request-scoped tracing the reference only had as logs
                trace = request.headers.get("X-Seldon-Trace", "") == "1"
                if cache_key is not None:
                    # single-flight: a thundering herd of identical
                    # requests (cache cold) costs ONE graph walk; the
                    # followers fan the leader's bytes out
                    raw = await self.service.collapse.do(
                        cache_key,
                        lambda: self._predict_json_bytes(body, trace),
                    )
                    self._resp_cache.put(dep, cache_key, raw)
                    if sem_vec is not None:
                        self._sem_cache.put(
                            dep, sem_vec, raw, self.service.spec_hash
                        )
                    return web.Response(
                        body=raw, content_type="application/json"
                    )
                raw = await self._predict_json_bytes(body, trace)
                if sem_vec is not None:
                    self._sem_cache.put(dep, sem_vec, raw, self.service.spec_hash)
                return web.Response(body=raw, content_type="application/json")
            except qos.QosRejection as e:
                # shed below admission: bounded queue overflow (429) or a
                # deadline that expired in a queue (504 — answered without
                # spending a device step)
                h["code"] = str(e.status)
                return self._qos_reject(e)
            except CodecError as e:
                h["code"] = "400"
                return web.json_response(_status_body(400, str(e)), status=400)
            except GraphUnitError as e:
                h["code"] = "500"
                return web.json_response(_status_body(500, str(e)), status=500)
            except web.HTTPException as e:
                # aiohttp-raised statuses (413 payload too large, ...) must
                # not be recorded as 200s
                h["code"] = str(e.status)
                raise
            except Exception:
                # unexpected failure: aiohttp answers 500 — the histogram
                # must say so too, not default to "200"
                h["code"] = "500"
                raise
            finally:
                # release covers disconnects too: aiohttp cancels this
                # handler when the client drops, the batching layers skip
                # the cancelled future, and the admission slot frees here
                ticket.release()

    async def _predict_json_bytes(self, body: dict, trace: bool) -> bytes:
        """One graph walk -> the response's JSON bytes (the unit the
        response cache stores and the collapser shares)."""
        import json

        payload = payload_from_dict(body)
        out = await self.service.predict(payload, trace=trace)
        resp = payload_to_dict(out)
        resp["status"] = {"code": 200, "status": "SUCCESS"}
        return json.dumps(resp).encode()

    # -- embeddings + semantic cache tier (docs/GRAPHS.md, docs/CACHING.md) -

    def _embed_unit(self):
        """The graph's embed-capable generative unit, or None."""
        for unit in self._generative_units_or_empty():
            if getattr(unit.model, "embed_enabled", False):
                return unit
        return None

    async def _semantic_vec(self, body: dict):
        """Pooled embedding of a single-prompt generative request, or None
        when the request doesn't qualify (batch, non-generative shape, no
        embed-capable unit).  An embed failure degrades to a cache miss —
        the semantic tier must never fail a request it could have missed."""
        import json as _json

        import numpy as np

        raw = body.get("strData") if isinstance(body, dict) else None
        if not isinstance(raw, str):
            return None
        try:
            inner = _json.loads(raw)
        except ValueError:
            return None
        prompt = inner.get("tokens") if isinstance(inner, dict) else None
        if (
            not isinstance(prompt, (list, tuple))
            or not prompt
            or isinstance(prompt[0], (list, tuple))
        ):
            return None  # one flat prompt only: batch rows cache per-row poorly
        unit = self._embed_unit()
        if unit is None:
            return None
        try:
            vecs = await unit.embed_rows([np.asarray(prompt, np.int32)])
            return vecs[0]
        except Exception:
            log.debug("semantic-cache embed failed; treating as miss", exc_info=True)
            return None

    async def embeddings(self, request: web.Request) -> web.Response:
        """``POST /api/v0.1/embeddings`` — mean-pooled final hidden states
        from the graph's generative unit (its OWN weights: the vectors live
        in the serving model's representation space).  Body: ``{"tokens":
        [...]}`` (flat list) or a batch of lists; reply carries the (B, E)
        float32 matrix through the typed ``rawTensor`` codec.  Each row
        rides the generation scheduler's bounded intake, so embeddings
        batch and shed with everything else."""
        import json as _json

        import numpy as np

        from seldon_core_tpu.contract import DataKind, Payload

        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(dep, pred, "embeddings", "POST") as h:
            from seldon_core_tpu.utils.tracectx import set_traceparent

            set_traceparent(request.headers.get("traceparent"))
            try:
                ticket = self._admit(request)
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            try:
                body = await self._json(request)
                if "strData" in body:  # full contract wrapper also accepted
                    body = _json.loads(body["strData"])
                rows_in = body.get("tokens")
                if not isinstance(rows_in, (list, tuple)) or not rows_in:
                    raise CodecError(
                        "embeddings takes 'tokens': a flat list or a batch of lists"
                    )
                if not isinstance(rows_in[0], (list, tuple)):
                    rows_in = [rows_in]
                rows = [np.asarray(r, np.int32) for r in rows_in]
                unit = self._embed_unit()
                if unit is None:
                    h["code"] = "400"
                    return web.json_response(
                        _status_body(
                            400,
                            "no embedding-capable generative unit in this "
                            "graph (enable SCT_EMBED=1 on a family with a "
                            "pooled-embedding path)",
                        ),
                        status=400,
                    )
                vecs = await unit.embed_rows(rows)
                resp = payload_to_dict(Payload(vecs, [], DataKind.RAW))
                resp["status"] = {"code": 200, "status": "SUCCESS"}
                return web.json_response(resp)
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            except (CodecError, TypeError, ValueError) as e:
                h["code"] = "400"
                return web.json_response(_status_body(400, str(e)), status=400)
            except GraphUnitError as e:
                h["code"] = "500"
                return web.json_response(_status_body(500, str(e)), status=500)
            except web.HTTPException as e:
                h["code"] = str(e.status)
                raise
            except Exception:
                h["code"] = "500"
                raise
            finally:
                ticket.release()

    async def predictions_stream(self, request: web.Request) -> web.StreamResponse:
        """Server-sent-events token streaming for a generative graph.

        Request body: the generative strData contract —
        ``{"tokens": [...], "max_new_tokens": N, "temperature": t,
        "eos_id": e}`` (bare JSON, no strData wrapper needed).
        Response: ``text/event-stream`` of ``data: {"token": id}`` events,
        closed by ``data: {"done": true, "tokens": [...]}``.  A client sees
        the first token after prefill + one decode block instead of waiting
        out the full generation (p50 397ms for 32 tokens in round 3).
        """
        import time

        # the ``ingress`` stage starts here and ends at the scheduler's own
        # submit stamp: admission, the body, the response headers
        t_in = time.perf_counter()
        dep, pred = self.service.deployment_name, self.service.predictor.name
        # the timer covers validation too: a rejected stream request must
        # be a recorded 400, not an unrecorded return
        with self.metrics.time_server_request(dep, pred, "predictions_stream", "POST") as h:
            from seldon_core_tpu.utils.tracectx import set_traceparent

            set_traceparent(request.headers.get("traceparent"))
            try:
                ticket = self._admit(request)
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            try:
                return await self._predictions_stream_admitted(request, h, t_in)
            finally:
                ticket.release()

    async def _predictions_stream_admitted(
        self, request: web.Request, h: dict, t_in: float
    ) -> web.StreamResponse:
        import json
        import time

        units = self.service.generative_units()
        if len(units) != 1:
            reason = (
                "predictor graph has no generative unit"
                if not units
                else f"streaming is ambiguous: graph has {len(units)} "
                     "generative units"
            )
            h["code"] = "400"
            return web.json_response(_status_body(400, reason), status=400)
        unit = units[0]
        import jax  # a graph with a generative unit has it loaded

        try:
            body = await self._json(request)
            if "strData" in body:  # full contract wrapper also accepted
                body = json.loads(body["strData"])
            prompt = body["tokens"]
            if not isinstance(prompt, (list, tuple)) or (
                prompt and isinstance(prompt[0], (list, tuple))
            ):
                raise CodecError("streaming takes ONE prompt: flat 'tokens' list")
            # option coercion BEFORE headers go out: a bad option must be a
            # 400 response, not a truncated 200 event stream
            max_new = body.get("max_new_tokens")
            max_new = int(max_new) if max_new is not None else None
            temperature = body.get("temperature")
            temperature = float(temperature) if temperature is not None else None
            eos = body.get("eos_id")
            eos = int(eos) if eos is not None else None
        except (CodecError, KeyError, TypeError, ValueError) as e:
            h["code"] = "400"
            return web.json_response(
                _status_body(400, f"bad stream request: {e}"), status=400
            )
        # peer-tier prefix pull (docs/CACHING.md): land the advertised chain
        # before the stream's prefill, same best-effort gate as predictions
        await self._pull_prefix_from_header(request, body)

        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Accel-Buffering": "no",
            }
        )
        await resp.prepare(request)
        out: list[int] = []
        flush_s = 0.0  # cumulative socket-write time -> stream-flush stage
        marks: dict = {}  # the scheduler's ``first_written`` lands here
        try:
            # a decode block's tokens for this stream go out as one write:
            # the same events, byte for byte, in one piece
            gen = unit.stream_bursts(
                prompt,
                max_new_tokens=max_new,
                temperature=temperature,
                eos_id=eos,
                t_ingress=t_in,
                info=marks,
            )
            async for toks in gen:
                first = not out
                out.extend(toks)
                event = "".join(
                    f"data: {json.dumps({'token': tok})}\n\n" for tok in toks
                ).encode()
                t_w = time.perf_counter()
                if not first:
                    await resp.write(event)
                else:
                    # the ``first-write`` stage: the scheduler's first-token
                    # stamp -> this write returned
                    with jax.profiler.TraceAnnotation("engine:first-write"):
                        await resp.write(event)
                    marks["first_written"]()
                flush_s += time.perf_counter() - t_w
            t_w = time.perf_counter()
            await resp.write(
                f"data: {json.dumps({'done': True, 'tokens': out})}\n\n".encode()
            )
            flush_s += time.perf_counter() - t_w
        except (ConnectionResetError, asyncio.CancelledError):
            raise  # client went away / server draining: nothing to send
        except Exception as e:
            # headers are gone; the error must ride the stream itself.
            # Broad on purpose: device failures surface as backend-
            # specific exception types (e.g. XlaRuntimeError)
            h["code"] = "500"
            await resp.write(
                f"data: {json.dumps({'error': str(e)})}\n\n".encode()
            )
        finally:
            if out:
                RECORDER.record_stage(STAGE_STREAM_FLUSH, flush_s)
        await resp.write_eof()
        return resp

    async def feedback(self, request: web.Request) -> web.Response:
        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(dep, pred, "feedback", "POST") as h:
            try:
                fb = feedback_from_dict(await self._json(request))
                await self.service.send_feedback(fb)
                return web.json_response({"status": {"code": 200, "status": "SUCCESS"}})
            except (CodecError, KeyError) as e:
                h["code"] = "400"
                return web.json_response(_status_body(400, str(e)), status=400)
            except GraphUnitError as e:
                h["code"] = "500"
                return web.json_response(_status_body(500, str(e)), status=500)
            except web.HTTPException as e:
                h["code"] = str(e.status)
                raise
            except Exception:
                h["code"] = "500"
                raise

    async def _json(self, request: web.Request) -> dict[str, Any]:
        import json

        ctype = request.content_type or ""
        if "form" in ctype:
            form = await request.post()
            raw = form.get("json")
            if raw is None:
                raise CodecError("form request missing 'json' field")
            return json.loads(raw)
        try:
            return await request.json()
        except json.JSONDecodeError as e:
            raise CodecError(f"invalid JSON body: {e}") from e

    async def ping(self, request: web.Request) -> web.Response:
        return web.Response(text="pong")

    async def ready(self, request: web.Request) -> web.Response:
        if self.mesh_worker:
            return web.Response(text="mesh-worker", status=503)
        if self.paused:
            return web.Response(text="paused", status=503)
        if not self.warmed:
            if self._warmup_error is not None:
                return web.Response(
                    text=f"warmup failed: {self._warmup_error}", status=503
                )
            return web.Response(text="warming", status=503)
        return web.Response(text="ready")

    async def pause(self, request: web.Request) -> web.Response:
        self.paused = True
        return web.Response(text="paused")

    async def unpause(self, request: web.Request) -> web.Response:
        self.paused = False
        return web.Response(text="unpaused")

    async def prometheus(self, request: web.Request) -> web.Response:
        # scrape-time refresh of the seldon_kv_* pool gauges: occupancy is
        # host bookkeeping, so reading it here costs no device sync and
        # the decode hot path never pays for gauge updates
        for unit in self._generative_units_or_empty():
            snap = getattr(unit.model, "pool_snapshot", None)
            if callable(snap):
                snap()
        # same deal for the seldon_usage_* families: re-derived from the
        # usage meter's bounded top-K table per scrape.  With exemplar
        # rendering on (SCT_METRICS_EXEMPLARS) the body is OpenMetrics —
        # trace-id exemplars on the hot histograms link to /stats/timeline
        self.metrics.refresh_usage()
        return web.Response(
            body=self.metrics.expose(),
            headers={"Content-Type": self.metrics.expose_content_type()},
        )

    async def stats_usage(self, request: web.Request) -> web.Response:
        """Per-tenant cost attribution (docs/OBSERVABILITY.md "Cost
        attribution"): cumulative device seconds, grant seconds, and
        token counters per ``deployment|adapter|qos`` key, all-numeric so
        the fleet collector merges replicas counter-exactly."""
        from seldon_core_tpu.obs.metering import METER

        return web.json_response({"usage": METER.snapshot()})

    def _generative_units_or_empty(self) -> list:
        try:
            return self.service.generative_units()
        except Exception:
            return []

    async def stats_timeline(self, request: web.Request) -> web.Response:
        """Per-request generation lifecycle ledger (obs/timeline.py):
        ``?trace=<id>`` returns every entry recorded for that trace
        (a disagg request shows its prefill-pool and decode-pool legs),
        otherwise the most recent ``n`` entries plus ledger counters."""
        trace = request.query.get("trace")
        if trace:
            return web.json_response({"timeline": TIMELINE.by_trace(trace)})
        try:
            n = int(request.query.get("n", "20"))
        except ValueError:
            n = 20
        return web.json_response(
            {
                "timeline": TIMELINE.recent(max(1, min(n, 200))),
                **TIMELINE.snapshot(),
            }
        )

    async def stats_spans(self, request: web.Request) -> web.Response:
        """Recent traces + slowest-N root spans from the in-process ring."""
        try:
            n = int(request.query.get("n", "20"))
        except ValueError:
            n = 20
        return web.json_response(RECORDER.stats(n=max(1, min(n, 200))))

    async def stats_breakdown(self, request: web.Request) -> web.Response:
        """Aggregated per-stage p50/p90/p99 (the flight recorder), plus the
        device-frontier ledger per generative unit: speculative-decode
        acceptance (``accepted_tokens_per_step``), paged-KV capacity
        (``kv_slots_per_chip``, layout dtype), and per-slot inter-token
        latency (``itl_p50_ms``/``itl_p99_ms`` — prefill-induced decode
        stalls land here; docs/PERFORMANCE.md §7)."""
        return web.json_response(self._breakdown_payload())

    @staticmethod
    def _unit_snapshot(unit) -> dict:
        """One generative unit's ledgers: the model's, then the
        scheduler's (packing, decode-block boundaries by outcome)."""
        snap = unit.model.spec_snapshot()
        snap["packing"] = unit.scheduler.packing_snapshot()
        snap["block_boundaries"] = unit.scheduler.boundary_snapshot()
        snap["stalls"] = unit.scheduler.stall_snapshot()
        snap["device"] = unit.scheduler.device_snapshot()
        return snap

    def _breakdown_payload(self) -> dict:
        payload: dict = {"stages": RECORDER.breakdown()}
        try:
            units = self.service.generative_units()
        except AssertionError:
            units = []
        gen = {}
        for unit in units:
            gen[unit.model.name] = self._unit_snapshot(unit)
        # co-resident deployments (docs/PACKING.md): keyed by
        # "<deployment>/<model>" so two co-tenants of the same preset
        # keep separate isolation ledgers
        for svc in self.co_services:
            try:
                co_units = svc.generative_units()
            except Exception:
                co_units = []
            for unit in co_units:
                gen[f"{svc.deployment_name}/{unit.model.name}"] = (
                    self._unit_snapshot(unit)
                )
        if gen:
            payload["generation"] = gen
        if self.co_services:
            from seldon_core_tpu.executor.arbiter import get_arbiter
            from seldon_core_tpu.executor.memory import MEMORY, host_memory

            # packed chip: the arbitration ledger plus the chip-wide byte
            # ledgers — owners rows prove per-deployment isolation
            payload["packing"] = get_arbiter().snapshot()
            payload["memory"] = {
                "hbm": MEMORY.snapshot(),
                "host": host_memory().snapshot(),
            }
        return payload

    async def stats_qos(self, request: web.Request) -> web.Response:
        """QoS plane state: admission caps, shed counters by reason,
        deadline-miss ledger, brownout, predicted completion time."""
        return web.json_response({"qos": self.qos.snapshot()})

    async def stats_wire(self, request: web.Request) -> web.Response:
        """Wire-throughput accounting (per-edge bytes + achieved MB/s) and
        the always-on probes: event-loop lag, host syncs per model."""
        return web.json_response(wire_stats_payload())

    async def stats_warmup(self, request: web.Request) -> web.Response:
        """Compile-warmup plane state: readiness, per-unit programs
        compiled + wall seconds, total warmup time, and the device the
        programs were compiled for (``platform`` / ``device_kind`` /
        ``device_count`` as JAX reports them, whether the native codec is
        loaded, per-device bytes in use where the backend reports them).
        Readiness stays 503 until every (bucket, program) pair is
        compiled, so a user request can never pay a first-touch XLA
        compile."""
        snap = self.service.warmup_snapshot()
        device = self.device
        if device is not None:
            import jax

            from seldon_core_tpu.utils.device import xla_compile_count

            n = xla_compile_count()
            device = {
                **device,
                "xla_compiles": n,
                "xla_compiles_since_ready": (
                    n - self._compiles_at_ready
                    if self._compiles_at_ready is not None
                    else None
                ),
                "memory": [
                    {
                        "id": d.id,
                        "bytes_in_use": st.get("bytes_in_use"),
                        "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    }
                    for d in jax.local_devices()
                    if (st := d.memory_stats())
                ],
            }
        snap.update(
            device=device,
            warmed=self.warmed,
            error=(
                str(self._warmup_error)
                if self._warmup_error is not None
                else None
            ),
            total_seconds=self._warmup_total_s,
        )
        return web.json_response({"warmup": snap})

    async def stats_cache(self, request: web.Request) -> web.Response:
        """Caching & reuse plane state: response/node cache hit rates,
        single-flight collapse counters, KV prefix-reuse index (with its
        per-tier ledgers), and this engine's peer-pull counters."""
        return web.json_response({"cache": self._cache_payload()})

    def _cache_payload(self) -> dict:
        snap = self.service.cache_snapshot()
        snap["prefix_pull"] = dict(self.prefix_pull_stats)
        return snap

    async def stats_summary(self, request: web.Request) -> web.Response:
        """One cheap scrape for the fleet collector
        (docs/OBSERVABILITY.md "Fleet telemetry"): the qos, breakdown,
        cache, and wire payloads bundled into a single round trip, plus
        the MERGEABLE per-stage histogram bucket counts (shared
        ``obs/history.BUCKET_EDGES`` grid) that fleet p50/p99 are
        computed from — replica quantiles themselves never merge."""
        from seldon_core_tpu.obs.metering import METER

        return web.json_response({
            "qos": self.qos.snapshot(),
            "breakdown": self._breakdown_payload(),
            "cache": self._cache_payload(),
            "wire": wire_stats_payload(),
            "usage": METER.snapshot(),
            "stage_hist": RECORDER.stage_histograms(),
        })

    def _tell_device_ledgers(self, state: str) -> None:
        """Every generative unit's device ledger hears where the profiler
        is (``obs/device.py``): its seconds are marked, and the stretch
        between a start returning and a stop entering is ``traced``."""
        now = time.perf_counter()
        for svc in (self.service, *self.co_services):
            try:
                units = svc.generative_units()
            except Exception:  # noqa: BLE001 - a service with no graph yet
                continue
            for unit in units:
                unit.scheduler.device.profiler(state, now)

    async def profile_start(self, request: web.Request) -> web.Response:
        import jax

        try:
            body = await request.json()
        except Exception:
            body = {}
        out_dir = body.get("dir") or "/tmp/sct-profile"
        # guard AFTER the await: no suspension between check and start_trace,
        # or two concurrent starts both pass and the second 500s
        if self._profile_dir is not None:
            return web.json_response(
                {"error": "profiler already running", "dir": self._profile_dir},
                status=409,
            )
        self._profile_dir = out_dir
        self._tell_device_ledgers("start")
        try:
            # the capture dir must exist up front: operators tail it while
            # the trace runs, and a bad path should 500 HERE, not at stop
            os.makedirs(out_dir, exist_ok=True)
            # off the event loop: starting the profiler's session takes
            # long enough for every live stream to feel it
            await asyncio.to_thread(jax.profiler.start_trace, out_dir)
        except Exception as e:
            self._profile_dir = None
            self._tell_device_ledgers("off")
            return web.json_response({"error": str(e)}, status=500)
        self._tell_device_ledgers("run")
        return web.json_response({"status": "profiling", "dir": out_dir})

    async def profile_stop(self, request: web.Request) -> web.Response:
        import jax

        # no await between the check and the claim: of two concurrent
        # stops one takes the trace and the other is a 409; the directory
        # stays set until the trace is written, so a start meanwhile is a
        # 409 too
        if self._profile_dir is None or self._profile_stopping:
            return web.json_response({"error": "profiler not running"}, status=409)
        self._profile_stopping = True
        self._tell_device_ledgers("stop")
        try:
            # off the event loop: ``stop_trace`` collects and writes the
            # whole trace, seconds during which the loop would serve nobody
            await asyncio.to_thread(jax.profiler.stop_trace)
        finally:
            out_dir, self._profile_dir = self._profile_dir, None
            self._profile_stopping = False
            self._tell_device_ledgers("off")
        return web.json_response({"status": "stopped", "dir": out_dir})

    # -- disaggregated prefill/decode (docs/DISAGGREGATION.md) -------------

    def _single_generative_unit(self):
        """The graph's one generative unit, or (None, reason) — disagg
        serves exactly one (same constraint as token streaming)."""
        units = self.service.generative_units()
        if len(units) != 1:
            reason = (
                "predictor graph has no generative unit"
                if not units
                else f"disagg is ambiguous: graph has {len(units)} "
                     "generative units"
            )
            return None, reason
        return units[0], None

    @staticmethod
    def _parse_generate_body(body: dict, unit) -> tuple:
        """Generative strData contract -> (prompt, max_new, temperature,
        eos); raises CodecError on malformed input."""
        import json as _json

        if "strData" in body:
            body = _json.loads(body["strData"])
        prompt = body.get("tokens")
        if not isinstance(prompt, (list, tuple)) or not prompt or isinstance(
            prompt[0], (list, tuple)
        ):
            raise CodecError("disagg generate takes ONE prompt: flat 'tokens' list")
        try:
            max_new = body.get("max_new_tokens")
            max_new = int(max_new) if max_new is not None else unit.max_new_tokens
            temperature = body.get("temperature")
            temperature = (
                float(temperature) if temperature is not None else unit.temperature
            )
            eos = body.get("eos_id", unit.eos_id)
            eos = int(eos) if eos is not None else None
        except (TypeError, ValueError) as e:
            raise CodecError(f"bad generate option: {e}") from e
        adapter = body.get("adapter", getattr(unit, "adapter", None))
        adapter = str(adapter) if adapter else None
        return prompt, max_new, temperature, eos, adapter

    async def disagg_generate(self, request: web.Request) -> web.Response:
        """Generate via the disagg topology: prefill HERE, stream the KV
        handoff to a decode peer, relay its tokens.  Any handoff failure
        falls back to unified-mode local decode — the request always gets
        its unified-identical answer; only the topology degrades."""
        import numpy as np

        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(dep, pred, "disagg_generate", "POST") as h:
            from seldon_core_tpu.utils.tracectx import set_traceparent

            set_traceparent(request.headers.get("traceparent"))
            try:
                ticket = self._admit(request)
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            try:
                unit, reason = self._single_generative_unit()
                if unit is None:
                    h["code"] = "400"
                    return web.json_response(_status_body(400, reason), status=400)
                try:
                    (prompt, max_new, temperature, eos, adapter) = (
                        self._parse_generate_body(
                            await self._json(request), unit
                        )
                    )
                except (CodecError, ValueError, TypeError, KeyError) as e:
                    h["code"] = "400"
                    return web.json_response(_status_body(400, str(e)), status=400)
                prompt = np.asarray(prompt, np.int32)
                # peer-tier prefix pull before the prefill (best-effort;
                # gated on SCT_PREFIX_PEER_PULL + the gateway's hint header)
                peer = request.headers.get("x-sct-prefix-peer")
                if peer and self._peer_pull_enabled():
                    await self._maybe_pull_prefix(
                        unit, prompt, adapter, peer,
                        request.headers.get("x-sct-prefix-depth"),
                    )
                # the request's generation span: child of the gateway/client
                # trace, parent of the prefill + handoff spans — the frame
                # carries the export span's id so the decode pool's import
                # span stitches UNDER this trace (docs/OBSERVABILITY.md)
                with RECORDER.span("disagg.generate", service=dep) as sp:
                    if (
                        self.role == disagg_mod.ROLE_PREFILL
                        and self.decode_upstreams
                        and max_new > 1
                    ):
                        tokens, mode = await self._prefill_and_handoff(
                            unit, prompt, max_new, temperature, eos, adapter
                        )
                    else:
                        out = await unit.scheduler.submit(
                            prompt, max_new_tokens=max_new,
                            temperature=temperature, eos_id=eos,
                            adapter=adapter,
                        )
                        tokens, mode = [int(t) for t in out], "unified"
                    if sp is not None:
                        sp.set_attr("mode", mode)
                return web.json_response({"tokens": tokens, "mode": mode})
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            except GraphUnitError as e:
                h["code"] = "500"
                return web.json_response(_status_body(500, str(e)), status=500)
            finally:
                ticket.release()

    async def _prefill_and_handoff(
        self, unit, prompt, max_new: int, temperature: float,
        eos: int | None, adapter: str | None = None,
    ) -> tuple[list[int], str]:
        """Prefill into a pinned slot, export + POST the KV handoff, relay
        the decode peer's tokens.  The slot releases in every outcome —
        the zero-leak guarantee — and failure degrades to local decode.
        Span shape (docs/OBSERVABILITY.md "cross-pool stitching"):
        ``disagg.prefill`` times the pinned prefill, ``handoff.export``
        covers the KV fetch + framing (the frame's traceparent IS this
        span, so the decode pool's import span becomes its child), and
        ``handoff.relay`` times the POST + token relay."""
        from seldon_core_tpu.utils.tracectx import current_trace_id

        dep = self.service.deployment_name
        with RECORDER.span("disagg.prefill", service=dep) as psp:
            slot, tok1 = await unit.scheduler.submit_prefill(
                prompt, temperature=temperature, adapter=adapter
            )
            if psp is not None:
                psp.set_attr("slot", slot)
        try:
            from seldon_core_tpu.disagg.handoff import build_handoff_frame

            with RECORDER.span("handoff.export", service=dep) as esp:
                # build_handoff_frame runs IN this span's context (to_thread
                # copies contextvars): the frame's traceparent names this
                # span as the origin the importer stitches under
                frame = await asyncio.to_thread(
                    build_handoff_frame, unit.model, slot, prompt, tok1,
                    max_new_tokens=max_new, temperature=temperature,
                    eos_id=eos, adapter=adapter,
                )
                if esp is not None:
                    esp.set_attr("bytes", len(frame))
                TIMELINE.note(
                    current_trace_id(), "handoff-export",
                    bytes=len(frame), slot=slot,
                )
            with RECORDER.span("handoff.relay", service=dep):
                tokens = await self._send_handoff(frame)
            self.disagg_stats["handoffs_ok"] += 1
            return tokens, "disagg"
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self.disagg_stats["handoffs_failed"] += 1
            TIMELINE.note(
                current_trace_id(), "handoff-failed", error=str(e)[:200]
            )
            log.warning(
                "KV handoff failed (%s); falling back to unified local decode", e
            )
        finally:
            unit.scheduler.release_external(slot)
        self.disagg_stats["local_fallbacks"] += 1
        out = await unit.scheduler.submit(
            prompt, max_new_tokens=max_new, temperature=temperature,
            eos_id=eos, adapter=adapter,
        )
        return [int(t) for t in out], "unified-fallback"

    def _ensure_handoff_session(self):
        """Lazy shared client session for the disagg plane (KV handoffs and
        peer prefix pulls ride the same timeout + connection pool)."""
        if self._handoff_session is None:
            import aiohttp

            self._handoff_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self._handoff_timeout_s)
            )
        return self._handoff_session

    async def _send_handoff(
        self,
        frame: bytes,
        target: str | None = None,
        extra_headers: dict[str, str] | None = None,
    ) -> list[int]:
        """POST one handoff frame to a decode peer — power-of-two-choices
        on outstanding handoffs when several are configured, or to an
        explicit ``target`` (the drain endpoint's named peer)."""
        if target is None:
            ups = self.decode_upstreams
            if len(ups) == 1:
                target = ups[0]
            else:
                import random

                a, b = random.sample(range(len(ups)), 2)
                target = min(
                    (ups[a], ups[b]),
                    key=lambda u: self._handoff_inflight.get(u, 0),
                )
        self._ensure_handoff_session()
        from seldon_core_tpu.qos.context import outgoing_qos_headers
        from seldon_core_tpu.utils.tracectx import outgoing_headers

        headers = {
            "Content-Type": "application/octet-stream",
            **outgoing_headers(),
            **outgoing_qos_headers(),
        }
        if extra_headers:
            headers.update(extra_headers)
        if chaos.ENABLED:
            # injected peer death / torn frame / slow peer on the handoff
            # hop — a raise here lands in the caller's fallback path, a
            # torn frame is rejected by the importer's codec check
            frame = await chaos.act("disagg.handoff.send", frame)
        self._handoff_inflight[target] = self._handoff_inflight.get(target, 0) + 1
        try:
            async with self._handoff_session.post(
                f"http://{target}/disagg/import", data=frame, headers=headers
            ) as resp:
                if resp.status != 200:
                    text = (await resp.text())[:200]
                    raise RuntimeError(
                        f"decode upstream {target} answered {resp.status}: {text}"
                    )
                body = await resp.json()
                return [int(t) for t in body["tokens"]]
        finally:
            self._handoff_inflight[target] -= 1

    async def disagg_import(self, request: web.Request) -> web.Response:
        """Receive a prefill engine's KV handoff, import it into the local
        paged pool at the scheduler's next sync point, decode to
        completion, and answer with the full token ids."""
        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(dep, pred, "disagg_import", "POST") as h:
            from seldon_core_tpu.utils.tracectx import set_traceparent

            set_traceparent(request.headers.get("traceparent"))
            if self.role == disagg_mod.ROLE_PREFILL:
                h["code"] = "409"
                return web.json_response(
                    _status_body(
                        409, "prefill-role engine does not import KV handoffs"
                    ),
                    status=409,
                )
            try:
                ticket = self._admit(request)
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            try:
                unit, reason = self._single_generative_unit()
                if unit is None:
                    h["code"] = "400"
                    return web.json_response(_status_body(400, reason), status=400)
                raw = await request.read()
                from seldon_core_tpu.disagg.handoff import (
                    HandoffError,
                    apply_handoff,
                    decode_handoff,
                )

                try:
                    payload = decode_handoff(raw)
                except (ValueError, HandoffError) as e:
                    # torn frame / wrong magic / version skew / wrong key:
                    # fail fast — never guess at KV bytes
                    self.disagg_stats["imports_failed"] += 1
                    h["code"] = "400"
                    return web.json_response(
                        _status_body(400, f"bad handoff frame: {e}"), status=400
                    )
                # cross-pool stitching (docs/OBSERVABILITY.md): a v3 frame
                # carries the prefill pool's traceparent — re-parent this
                # request onto it so the import span (and every generation
                # span under it) is a linked child of the EXPORT span, even
                # when an intermediary stripped the trace headers
                frame_tp = payload.get("traceparent")
                if frame_tp:
                    set_traceparent(str(frame_tp))
                # drain cutover (docs/RESILIENCE.md): the draining source
                # stamps its sampling-seed counter on each migrated frame;
                # adopting it makes the sampled continuation bit-identical
                # to the stream the source would have produced
                drain_seed = request.headers.get("x-sct-drain-seed")
                if drain_seed is not None:
                    try:
                        unit.scheduler.adopt_seed(int(drain_seed))
                    except (TypeError, ValueError):
                        h["code"] = "400"
                        return web.json_response(
                            _status_body(400, "bad x-sct-drain-seed"),
                            status=400,
                        )
                with RECORDER.span("disagg.import", service=dep) as isp:
                    if isp is not None:
                        isp.set_attr("handoff.version", int(payload.get("hv", 1)))
                        if payload.get("origin_span"):
                            isp.set_attr(
                                "origin_span_id", str(payload["origin_span"])
                            )
                    try:
                        out = await apply_handoff(unit, payload)
                    except HandoffError as e:
                        # decodable frame, incompatible pool (block size skew)
                        self.disagg_stats["imports_failed"] += 1
                        h["code"] = "409"
                        if isp is not None:
                            isp.set_status("ERROR")
                        return web.json_response(
                            _status_body(409, str(e)), status=409
                        )
                self.disagg_stats["imports_ok"] += 1
                return web.json_response({"tokens": [int(t) for t in out]})
            except qos.QosRejection as e:
                h["code"] = str(e.status)
                return self._qos_reject(e)
            except GraphUnitError as e:
                self.disagg_stats["imports_failed"] += 1
                h["code"] = "500"
                return web.json_response(_status_body(500, str(e)), status=500)
            finally:
                ticket.release()

    # -- peer tier of the tiered prefix store (docs/CACHING.md) -------------

    async def disagg_prefix_pull(self, request: web.Request) -> web.Response:
        """Serve a serialized prefix chain to a peer replica.

        Request: JSON ``{"tokens": [...], "adapter": ..., "max_blocks": N}``.
        Response: one codec-framed chain (``encode_prefix_chain``) covering
        the deepest contiguous run of FULL blocks this replica holds in HBM
        or host DRAM — adapter-salted, so a wrong-adapter pull is a plain
        404 miss.  The export pins the chain's refcounts for the duration
        of the device fetch (``PrefixIndex.acquire``), so a concurrent
        eviction can never tear the bytes mid-flight."""
        import numpy as np

        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(
            dep, pred, "disagg_prefix_pull", "POST"
        ) as h:
            unit, reason = self._single_generative_unit()
            if unit is None:
                h["code"] = "400"
                return web.json_response(_status_body(400, reason), status=400)
            try:
                body = await self._json(request)
            except CodecError as e:
                h["code"] = "400"
                return web.json_response(_status_body(400, str(e)), status=400)
            toks = body.get("tokens")
            if not (
                isinstance(toks, (list, tuple))
                and toks
                and all(
                    isinstance(t, int) and not isinstance(t, bool) for t in toks
                )
            ):
                h["code"] = "400"
                return web.json_response(
                    _status_body(400, "prefix pull takes a flat 'tokens' list"),
                    status=400,
                )
            adapter = body.get("adapter")
            adapter = str(adapter) if adapter else None
            try:
                max_blocks = int(body.get("max_blocks", 64))
            except (TypeError, ValueError):
                h["code"] = "400"
                return web.json_response(
                    _status_body(400, "bad max_blocks"), status=400
                )
            try:
                exported = await asyncio.to_thread(
                    unit.model.export_prefix_kv,
                    np.asarray(toks, np.int32),
                    adapter=adapter,
                    max_blocks=max_blocks,
                )
            except GraphUnitError as e:
                h["code"] = "500"
                return web.json_response(_status_body(500, str(e)), status=500)
            if exported is None:
                self.prefix_pull_stats["serve_misses"] += 1
                h["code"] = "404"
                return web.json_response(
                    _status_body(404, "no prefix chain for these tokens"),
                    status=404,
                )
            depth, k, v, k_scale, v_scale = exported
            from seldon_core_tpu.disagg.handoff import encode_prefix_chain

            frame = await asyncio.to_thread(
                encode_prefix_chain,
                np.asarray(toks, np.int32),
                k,
                v,
                block_size=unit.model.kv_block_size,
                k_scale=k_scale,
                v_scale=v_scale,
                adapter=adapter,
            )
            self.prefix_pull_stats["serves_ok"] += 1
            return web.Response(
                body=frame,
                content_type="application/octet-stream",
                headers={"x-sct-prefix-depth": str(int(depth))},
            )

    @staticmethod
    def _peer_pull_enabled() -> bool:
        return os.environ.get("SCT_PREFIX_PEER_PULL", "0") == "1"

    async def _pull_prefix_from_header(self, request: web.Request, body) -> None:
        """Gateway-hinted peer pull for the predictions paths: when the
        router stamped ``x-sct-prefix-peer`` (a replica advertising this
        prompt's chain) and peer pull is enabled, fetch + install the chain
        before the request queues, so its prefill covers only the novel
        suffix.  Best-effort by design — any miss or failure just leaves
        plain prefill to do what it always did."""
        peer = request.headers.get("x-sct-prefix-peer")
        if not peer or not self._peer_pull_enabled():
            return
        units = self.service.generative_units()
        if len(units) != 1:
            return
        import json as _json

        b = body
        if isinstance(b, dict) and "strData" in b:
            try:
                b = _json.loads(b["strData"])
            except (TypeError, ValueError):
                return
        if not isinstance(b, dict):
            return
        toks = b.get("tokens")
        if not (
            isinstance(toks, (list, tuple))
            and toks
            and all(isinstance(t, int) and not isinstance(t, bool) for t in toks)
        ):
            return
        adapter = b.get("adapter")
        adapter = str(adapter) if isinstance(adapter, str) and adapter else None
        await self._maybe_pull_prefix(
            units[0], toks, adapter, peer,
            request.headers.get("x-sct-prefix-depth"),
        )

    async def _maybe_pull_prefix(
        self, unit, tokens, adapter, peer: str, depth_hint=None
    ) -> bool:
        """POST ``/disagg/prefix/pull`` to ``peer`` and install the returned
        chain at the scheduler's next sync point.  Skips when the local
        tiers (HBM index + DRAM store) already cover the hinted depth.
        ANY failure — network, 4xx, torn frame, version skew, install
        race — lands in the ledger and falls back to plain suffix prefill
        with zero blocks or DRAM bytes leaked (the chain only enters the
        pool through ``install_prefix_chain``'s all-or-nothing path)."""
        import numpy as np

        from seldon_core_tpu.utils.tracectx import current_trace_id

        model = getattr(unit, "model", None)
        index = getattr(model, "prefix_index", None)
        if index is None or getattr(model, "_multihost", False):
            return False
        tokens = np.asarray(tokens, np.int32).ravel()
        bs = int(model.kv_block_size)
        cap = min(tokens.size // bs, int(model.max_blocks_per_slot))
        if cap < 1:
            return False
        try:
            hint = int(depth_hint) if depth_hint else 0
        except (TypeError, ValueError):
            hint = 0
        from seldon_core_tpu.cache.prefix import adapter_salt

        salt = adapter_salt(adapter)
        have = index.peek_depth(tokens, cap, salt)
        if model.host_store is not None and have < cap:
            have = max(
                have, model.host_store.peek_depth(tokens, have + 1, cap, salt)
            )
        if have >= cap or (hint and have >= hint):
            return False  # nothing a pull could add
        try:
            from seldon_core_tpu.qos.context import outgoing_qos_headers
            from seldon_core_tpu.utils.tracectx import outgoing_headers

            req: dict[str, Any] = {
                "tokens": [int(t) for t in tokens],
                "max_blocks": int(cap),
            }
            if adapter:
                req["adapter"] = adapter
            session = self._ensure_handoff_session()
            if chaos.ENABLED:
                # injected slow/dead peer on the pull hop: a raise here is
                # swallowed by the failure ledger below — the request falls
                # back to plain suffix prefill, never fails
                await chaos.act("disagg.prefix.pull")
            with RECORDER.span(
                "prefix.pull", service=self.service.deployment_name
            ) as sp:
                async with session.post(
                    f"http://{peer}/disagg/prefix/pull",
                    json=req,
                    headers={**outgoing_headers(), **outgoing_qos_headers()},
                ) as resp:
                    if resp.status == 404:
                        # the peer's advertisement went stale (evicted,
                        # restarted, wrong adapter): a miss, not a failure
                        self.prefix_pull_stats["pull_misses"] += 1
                        return False
                    if resp.status != 200:
                        text = (await resp.text())[:200]
                        raise RuntimeError(
                            f"peer {peer} answered {resp.status}: {text}"
                        )
                    frame = await resp.read()
                if sp is not None:
                    sp.set_attr("peer", peer)
                    sp.set_attr("bytes", len(frame))
            from seldon_core_tpu.disagg.handoff import decode_prefix_chain

            payload = decode_prefix_chain(frame)
            absorbed = await unit.scheduler.install_prefix(
                payload["tokens"],
                payload["k"],
                payload["v"],
                k_scale=payload.get("k_scale"),
                v_scale=payload.get("v_scale"),
                adapter=adapter,
            )
            self.prefix_pull_stats["pulls_ok"] += 1
            self.prefix_pull_stats["pull_bytes"] += len(frame)
            self.prefix_pull_stats["pull_blocks"] += int(absorbed)
            TIMELINE.note(
                current_trace_id(), "prefix-pull",
                peer=peer, blocks=int(absorbed), bytes=len(frame),
            )
            return absorbed > 0
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self.prefix_pull_stats["pulls_failed"] += 1
            log.warning(
                "peer prefix pull from %s failed (%s); plain suffix prefill",
                peer, e,
            )
            TIMELINE.note(
                current_trace_id(), "prefix-pull-failed",
                peer=peer, error=str(e)[:200],
            )
            return False

    async def stats_disagg(self, request: web.Request) -> web.Response:
        """Disagg plane state: this engine's role, its decode peers, and
        the handoff/import ledger."""
        return web.json_response(
            {
                "disagg": {
                    "role": self.role,
                    "decode_upstreams": list(self.decode_upstreams),
                    "handoff_inflight": dict(self._handoff_inflight),
                    **self.disagg_stats,
                    "prefix_pull": dict(self.prefix_pull_stats),
                }
            }
        )

    # -- live migration (docs/RESILIENCE.md "drain runbook") ----------------

    def _drain_snapshot(self, sched) -> dict[str, Any]:
        """Current drain state for 409 bodies: the in-flight handler's
        phase + migration progress when this app started the drain, or a
        synthesized parked view when the drain was begun at the scheduler
        level (tests, embedded harnesses)."""
        import time as _time

        st = dict(self._drain_state or {})
        start = st.pop("started_monotonic", None)
        if start is not None:
            st["elapsed_ms"] = round((_time.monotonic() - start) * 1e3, 3)
        if not st:
            st = {"phase": "parked", "peer": None}
        snap = sched.packing_snapshot()
        st["parked"] = int(snap.get("suspended", 0))
        st["draining"] = bool(
            snap.get("draining", getattr(sched, "_draining", False))
        )
        return st

    async def admin_drain(self, request: web.Request) -> web.Response:
        """Replace this engine under live traffic: pause admission, suspend
        every active stream bit-exactly at the next sync point, then ship
        each suspend record — a v4 handoff frame — to ``peer``'s
        ``/disagg/import``.  The peer adopts this scheduler's sampling-seed
        counter (``x-sct-drain-seed``), so each migrated stream's remaining
        tokens are bit-identical to the uninterrupted run; the relay feeds
        them through the original request's streaming hook, so the client
        sees ONE stream.  A stream the peer refuses re-parks and resumes
        locally — a failed migration never kills a generation.  With no
        ``peer`` the records stay parked and admission stays paused until
        ``POST /admin/undrain``.

        Body (all optional): ``{"peer": "host:port", "timeout_s": 30}``."""
        import time as _time

        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(dep, pred, "admin_drain", "POST") as h:
            unit, reason = self._single_generative_unit()
            if unit is None:
                h["code"] = "400"
                return web.json_response(_status_body(400, reason), status=400)
            body: dict[str, Any] = {}
            if request.can_read_body:
                try:
                    body = await self._json(request)
                except CodecError as e:
                    h["code"] = "400"
                    return web.json_response(_status_body(400, str(e)), status=400)
            peer = body.get("peer")
            peer = str(peer) if peer else None
            try:
                timeout_s = float(body.get("timeout_s", 30.0))
            except (TypeError, ValueError):
                h["code"] = "400"
                return web.json_response(
                    _status_body(400, "bad timeout_s"), status=400
                )
            sched = unit.scheduler
            if self._drain_state is not None or getattr(sched, "_draining", False):
                # idempotent repeat: answer with the CURRENT drain's state
                # (phase + migration progress), not a bare refusal — the
                # autoscale reconciler's retry after a timeout needs to see
                # how far the in-flight drain got
                h["code"] = "409"
                return web.json_response(
                    dict(
                        _status_body(409, "drain already in progress"),
                        drain=self._drain_snapshot(sched),
                    ),
                    status=409,
                )
            t0 = _time.perf_counter()
            self._drain_state = {
                "phase": "quiescing",
                "peer": peer,
                "timeout_s": timeout_s,
                "migrated": 0,
                "failed": 0,
                "started_monotonic": _time.monotonic(),
            }
            # no-peer path: the matching drain_finish lives in /admin/undrain
            sched.drain_begin()  # sct: pairing-ok undrain lifts it
            try:
                quiesced = await sched.drain_wait_quiesced(timeout_s)
            except BaseException:
                # a failed handler must not leave phantom in-flight state
                # that wedges every later drain/undrain behind a 409
                self._drain_state = None
                raise
            migrated, failed = 0, []
            if peer:
                self._drain_state["phase"] = "migrating"
                pairs = sched.drain_take()
                # every frame carries the SAME counter value: adoption is
                # idempotent, and the peer continues the seed sequence
                # exactly where this scheduler stopped
                drain_headers = {"x-sct-drain-seed": str(sched._seed)}
                try:
                    for req, frame in pairs:
                        try:
                            tokens = await self._send_handoff(
                                frame, target=peer, extra_headers=drain_headers
                            )
                        except asyncio.CancelledError:
                            raise
                        except Exception as e:
                            log.warning(
                                "drain: migrating one stream to %s failed "
                                "(%s); it will resume locally", peer, e,
                            )
                            failed.append((req, frame))
                            self._drain_state["failed"] = len(failed)
                            continue
                        sched.complete_migrated(req, tokens)
                        migrated += 1
                        self._drain_state["migrated"] = migrated
                finally:
                    # CancelledError mid-loop must not strand unmigrated
                    # streams: everything not relayed re-parks, then the
                    # drain lifts and parked records resume locally
                    for rest in pairs[migrated + len(failed):]:
                        failed.append(rest)
                    if failed:
                        sched.drain_abort(failed)
                    sched.drain_finish()
                    self._drain_state = None
            else:
                # parked drain: admission stays paused and the state stays
                # visible until /admin/undrain lifts it
                self._drain_state["phase"] = "parked"
            snap = sched.packing_snapshot()
            return web.json_response(
                {
                    "quiesced": bool(quiesced),
                    "peer": peer,
                    "migrated": migrated,
                    "failed": len(failed),
                    "parked": int(snap.get("suspended", 0)),
                    "draining": bool(snap.get("draining", False)),
                    "duration_ms": round(
                        (_time.perf_counter() - t0) * 1e3, 3
                    ),
                }
            )

    async def admin_undrain(self, request: web.Request) -> web.Response:
        """Lift a no-peer drain: admission resumes and every parked record
        re-queues as an imported admission, continuing bit-exactly."""
        dep, pred = self.service.deployment_name, self.service.predictor.name
        with self.metrics.time_server_request(
            dep, pred, "admin_undrain", "POST"
        ) as h:
            unit, reason = self._single_generative_unit()
            if unit is None:
                h["code"] = "400"
                return web.json_response(_status_body(400, reason), status=400)
            sched = unit.scheduler
            st = self._drain_state
            if st is not None and st.get("phase") in ("quiescing", "migrating"):
                # a drain handler is still running: lifting the drain now
                # would fork already-relayed streams (the peer continues
                # them while this scheduler re-queues the same records) —
                # undrain only applies to a PARKED no-peer drain
                h["code"] = "409"
                return web.json_response(
                    dict(
                        _status_body(
                            409,
                            "drain in flight; undrain applies only after "
                            "it parks or finishes",
                        ),
                        drain=self._drain_snapshot(sched),
                    ),
                    status=409,
                )
            if not getattr(sched, "_draining", False):
                h["code"] = "409"
                return web.json_response(
                    _status_body(409, "engine is not draining"), status=409
                )
            sched.drain_finish()
            self._drain_state = None
            return web.json_response(
                {"draining": False, "resuming": True}
            )

    async def stats_chaos(self, request: web.Request) -> web.Response:
        return web.json_response({"chaos": chaos.snapshot()})


def jax_units(graph) -> list[str]:
    """Names of the graph's units that compile for and hold the device."""
    from seldon_core_tpu.graph.spec import Implementation

    device_impls = (Implementation.JAX_MODEL, Implementation.JAX_GENERATIVE)
    names, stack = [], [graph]
    while stack:
        unit = stack.pop()
        if unit.implementation in device_impls:
            names.append(unit.name)
        stack.extend(unit.children)
    return names


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description="seldon-core-tpu engine")
    parser.add_argument("--port", type=int, default=int(os.environ.get("ENGINE_SERVER_PORT", "8000")))
    parser.add_argument("--grpc-port", type=int, default=int(os.environ.get("ENGINE_SERVER_GRPC_PORT", "5001")))
    parser.add_argument(
        "--workers",
        type=int,
        default=int(os.environ.get("ENGINE_WORKERS", "1")),
        help="worker processes sharing the ports via SO_REUSEPORT. >1 is "
        "for CPU-bound graphs (stubs, routing) only: a graph with a "
        "JAX_MODEL or JAX_GENERATIVE unit owns the chip, one process per "
        "chip, and is refused (batching provides its concurrency)",
    )
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    if args.workers > 1:
        device_units = [
            name
            for spec in (load_predictor_spec(), *load_co_predictor_specs())
            for name in jax_units(spec.graph)
        ]
        if device_units:
            parser.error(
                f"--workers {args.workers} with device units {device_units}: "
                "each worker would build the graph and claim the chip, and "
                "a chip belongs to one process.  Run one worker."
            )
        # The reference engine is a multithreaded JVM on 16 cores
        # (docs/benchmarking.md:19-36); the Python equivalent of that CPU
        # budget is processes, kernel-balanced across a shared port.
        import multiprocessing

        procs = [
            multiprocessing.Process(
                target=_serve, args=(args.port, args.grpc_port, True), daemon=False
            )
            for _ in range(args.workers - 1)
        ]
        for p in procs:
            p.start()
        try:
            _serve(args.port, args.grpc_port, True)
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                p.join(timeout=10)
    else:
        _serve(args.port, args.grpc_port, False)


def _serve(port: int, grpc_port: int, reuse_port: bool) -> None:
    # join the slice mesh BEFORE anything touches the jax backend: the
    # distributed runtime must exist when the TPU client initializes
    from seldon_core_tpu.parallel.distributed import maybe_initialize

    mesh_cfg = maybe_initialize()
    if mesh_cfg is not None:
        from seldon_core_tpu.executor.multihost import init_driver

        init_driver(mesh_cfg.is_coordinator)
    predictor = load_predictor_spec()
    # chip packing (docs/PACKING.md): ENGINE_CO_PREDICTORS co-boots extra
    # deployments in this process; they time-share the device via the
    # arbiter instead of each claiming a chip
    co_specs = load_co_predictor_specs()
    if any(jax_units(spec.graph) for spec in (predictor, *co_specs)):
        # before the first JAX call: the graph build below compiles
        from seldon_core_tpu.utils.device import configure_compile_cache

        log.info(
            "compile cache: %s",
            configure_compile_cache() or "off (process pinned to the CPU)",
        )
    service = PredictionService(
        predictor, deployment_name=os.environ.get("SELDON_DEPLOYMENT_ID", "")
    )
    co_services = [
        PredictionService(spec, deployment_name=spec.name) for spec in co_specs
    ]
    engine = EngineApp(
        service,
        mesh_worker=mesh_cfg is not None and not mesh_cfg.is_coordinator,
        co_services=co_services,
    )
    app = engine.build()
    app.on_startup.append(_tune_loop)
    app.on_startup.append(make_grpc_startup(service, grpc_port, reuse_port=reuse_port))
    app.on_cleanup.append(_grpc_cleanup)
    web.run_app(app, port=port, access_log=None, reuse_port=reuse_port or None)


async def _tune_loop(app) -> None:
    from seldon_core_tpu.utils.loops import tune_server_loop

    tune_server_loop()


def make_grpc_startup(service: PredictionService, grpc_port: int, reuse_port: bool = False):
    """aiohttp startup hook co-starting the gRPC server.

    A gRPC boot failure FAILS the whole process (a gRPC-only client must not
    see silent connection refusals from a pod that reports ready); set
    ``ENGINE_GRPC_OPTIONAL=1`` to serve REST-only instead.
    """

    async def _start_grpc(app_: web.Application) -> None:
        try:
            from seldon_core_tpu.engine.grpc_app import start_engine_grpc

            app_["grpc_server"] = await start_engine_grpc(
                service, grpc_port, reuse_port=reuse_port
            )
        except Exception as e:
            if os.environ.get("ENGINE_GRPC_OPTIONAL") == "1":
                log.warning("gRPC server not started (optional): %s", e)
                return
            log.error("gRPC server failed to start on :%d: %s", grpc_port, e)
            raise

    return _start_grpc


async def _grpc_cleanup(app_: web.Application) -> None:
    server = app_.get("grpc_server")
    if server is not None:
        await server.stop(grace=5)


if __name__ == "__main__":
    main()
