"""Autoscale actuator: policy decisions -> cluster state.

Runs inside the operator (``operator/app.py``) next to the fleet
collector it reads.  Each tick reads the collector's latest merged
aggregates, feeds every autoscaled pool's :class:`PoolPolicy`, and
actuates the decision through the SAME kube client the controller uses:

* **scale-up** patches the engine workloads' ``spec.replicas`` (a
  replicas-only change — never a pod-template roll) and registers the
  new count as a replica override with the controller so a later
  CR-driven reconcile preserves it instead of snapping back to the CR's
  static count.
* **scale-down is drain-based**: pick the victim replica (lowest
  prefix-digest affinity first — its warm set is the cheapest to lose —
  then youngest), ``POST /admin/drain {peer}`` so every active stream
  live-migrates to a surviving replica (docs/RESILIENCE.md), and only
  after the drain reports zero failed migrations decrement replicas.
  Zero dropped streams by construction; a failed drain aborts the
  shrink and the hold-down stops it from being hammered.

Embedded pools (kubesim e2e, bare-metal dev)
declare the provisionable replica set up front via
``seldon.io/autoscale-pool``; the actuator then also maintains
``seldon.io/engine-endpoints`` as the live pool-ordered subset, which is
what the gateway watcher and fleet collector discover replicas from.

Every decision lands as a span + ``seldon_autoscale_*`` metrics + an
entry in the bounded decision ledger served on ``GET /stats/autoscale``.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import Any

from seldon_core_tpu.autoscale.policy import (
    AUTOSCALE_ANNOTATION,
    AutoscaleError,
    PoolPolicy,
    extract_signals,
    extract_slopes,
    parse_autoscale,
    pool_role,
)
from seldon_core_tpu.runtime import settings

log = logging.getLogger(__name__)

CR_KIND = "SeldonDeployment"
POOL_ANNOTATION = "seldon.io/autoscale-pool"
ENDPOINTS_ANNOTATION = "seldon.io/engine-endpoints"


def _digest_count(payload: dict | None) -> int:
    """Prefix-digest cardinality of one replica's scrape payload — the
    victim-selection affinity signal (the replica advertising the
    fewest warm chains is the cheapest to drain)."""
    if not isinstance(payload, dict):
        return 0
    hashes: set[str] = set()
    for snap in ((payload.get("cache") or {}).get("prefix") or {}).values():
        digest = (snap or {}).get("digest") or {}
        hashes.update(digest.get("hashes") or ())
    return len(hashes)


class AutoscaleReconciler:
    """Closed-loop pool scaling off the fleet telemetry plane."""

    def __init__(
        self,
        kube,
        store,
        collector,
        *,
        namespace: str = "default",
        controller=None,
        interval_s: float | None = None,
        drain_timeout_s: float | None = None,
        ledger_size: int | None = None,
        metrics=None,
        policy_overrides: dict | None = None,
    ):
        self.kube = kube
        self.store = store
        self.collector = collector
        self.namespace = namespace
        self.controller = controller
        self.interval_s = (
            settings.get_float("SCT_SCALE_INTERVAL_S")
            if interval_s is None else float(interval_s)
        )
        self.drain_timeout_s = (
            settings.get_float("SCT_SCALE_DRAIN_TIMEOUT_S")
            if drain_timeout_s is None else float(drain_timeout_s)
        )
        size = (
            settings.get_int("SCT_SCALE_LEDGER")
            if ledger_size is None else int(ledger_size)
        )
        # sct: ring-growth-ok deque(maxlen=SCT_SCALE_LEDGER) drops oldest
        self.ledger: deque = deque(maxlen=max(1, size))
        self._metrics = metrics
        # per-policy constructor overrides (tests/bench shrink the holds)
        self._policy_overrides = dict(policy_overrides or {})
        # deployment -> (spec_str, role, PoolPolicy)
        self._policies: dict[str, tuple[str, str, PoolPolicy]] = {}
        self._last: dict[str, dict] = {}
        self.ticks = 0
        self.scale_ups = 0
        self.scale_downs = 0
        self.drain_failures = 0
        self.errors = 0
        self._session = None
        self._task: asyncio.Task | None = None
        self._recorder = None

    # -- plumbing ------------------------------------------------------------

    def _met(self):
        if self._metrics is None:
            from seldon_core_tpu.utils.metrics import DEFAULT
            self._metrics = DEFAULT
        return self._metrics

    def _rec(self):
        if self._recorder is None:
            from seldon_core_tpu.obs.spans import RECORDER
            self._recorder = RECORDER
        return self._recorder

    def _ensure_session(self):
        if self._session is None:
            import aiohttp

            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.drain_timeout_s + 5.0)
            )
        return self._session

    async def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._session is not None:
            await self._session.close()
            self._session = None

    async def _run(self) -> None:
        while True:
            try:
                await self.reconcile_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                # scaling must never take the operator down
                self.errors += 1
                log.exception("autoscale tick failed")
            await asyncio.sleep(self.interval_s)

    # -- policy wiring -------------------------------------------------------

    def _policy_for(self, name: str, spec_str: str, role: str) -> PoolPolicy:
        cached = self._policies.get(name)
        if cached is not None and cached[0] == spec_str and cached[1] == role:
            return cached[2]
        policy = PoolPolicy(
            parse_autoscale(spec_str), role, **self._policy_overrides
        )
        self._policies[name] = (spec_str, role, policy)
        return policy

    # -- one tick ------------------------------------------------------------

    async def reconcile_once(self, now: float | None = None) -> None:
        if now is None:
            now = time.time()
        self.ticks += 1
        records = self.store.list()
        live_names = set()
        for rec in records:
            live_names.add(rec.name)
            spec_str = (rec.annotations or {}).get(
                AUTOSCALE_ANNOTATION
            ) or settings.get_str("SCT_SCALE_DEFAULT")
            if not spec_str:
                continue
            try:
                await self._reconcile_pool(rec, str(spec_str).strip(), now)
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                self.errors += 1
                log.exception("autoscale reconcile of %s failed", rec.name)
                self._last[rec.name] = {
                    "ts": now, "error": f"{type(exc).__name__}: {exc}",
                }
        # prune state for deployments that left the store
        for name in [n for n in self._policies if n not in live_names]:
            del self._policies[name]
            self._last.pop(name, None)

    async def _reconcile_pool(self, rec, spec_str: str, now: float) -> None:
        role = pool_role(rec.annotations)
        try:
            policy = self._policy_for(rec.name, spec_str, role)
        except AutoscaleError as exc:
            # admission validates the annotation; this covers a malformed
            # SCT_SCALE_DEFAULT or a role/spec mismatch
            self._last[rec.name] = {"ts": now, "error": str(exc)}
            return
        dep = (self.collector._agg.get("deployments") or {}).get(rec.name)
        if dep is not None:
            policy.observe(
                extract_signals(
                    rec.name, dep, history=self.collector.history, now=now
                ),
                now,
            )
        current = len(rec.replica_endpoints)
        decision = policy.decide(
            current, now,
            slopes=extract_slopes(rec.name, self.collector.history, now=now),
        )
        self._last[rec.name] = {
            "ts": now, "role": role, "current": current,
            "direction": decision.direction, "target": decision.target,
            "reason": decision.reason, "pressure": decision.pressure,
        }
        try:
            m = self._met()
            m.autoscale_target.labels(rec.name, role).set(decision.target)
            if decision.pressure is not None:
                m.autoscale_pressure.labels(rec.name).set(decision.pressure)
        except Exception:  # metrics are best-effort, never break the tick
            pass
        if decision.direction == "up":
            await self._scale_up(rec, role, current, decision, now)
        elif decision.direction == "down":
            await self._scale_down(rec, role, current, decision, now)

    # -- actuation -----------------------------------------------------------

    def _pool_entries(self, rec) -> list[str] | None:
        raw = (rec.annotations or {}).get(POOL_ANNOTATION, "")
        entries = [e.strip() for e in str(raw).split(",") if e.strip()]
        return entries or None

    async def _patch_endpoints(self, rec, endpoints: list[str]) -> None:
        await self.kube.patch(
            CR_KIND, self.namespace, rec.name,
            {"metadata": {"annotations": {
                ENDPOINTS_ANNOTATION: ",".join(endpoints),
            }}},
        )

    async def _patch_workloads(self, rec, replicas: int) -> None:
        """Replicas-only merge-patch on every engine workload owned by
        the CR (template hash untouched, so StatefulSet slices never
        roll) plus the controller-side override that keeps CR-driven
        reconciles from snapping the count back."""
        from seldon_core_tpu.operator.kube import NotFound
        from seldon_core_tpu.operator.names import engine_deployment_name

        try:
            raw = await self.kube.get(CR_KIND, self.namespace, rec.name)
        except NotFound:
            return
        predictors = (raw.get("spec") or {}).get("predictors") or []
        for pred in predictors:
            wname = engine_deployment_name(rec.name, pred.get("name", ""))
            if self.controller is not None:
                self.controller.replica_overrides[wname] = replicas
            for kind in ("Deployment", "StatefulSet"):
                try:
                    await self.kube.patch(
                        kind, self.namespace, wname,
                        {"spec": {"replicas": replicas}},
                    )
                    break
                except NotFound:
                    continue

    def _span(self, name: str, direction: str, attrs: dict) -> None:
        from seldon_core_tpu.utils.tracectx import (
            new_traceparent, parse_traceparent,
        )

        trace_id = parse_traceparent(new_traceparent())[0]
        self._rec().record_span(
            "autoscale-decision", trace_id=trace_id, parent_id=None,
            start=time.time(), duration_s=0.0, service="operator",
            status="OK",
            attrs={"deployment": name, "direction": direction, **attrs},
        )

    def _ledger_entry(self, entry: dict) -> None:
        self.ledger.append(entry)

    def _count_decision(self, name: str, direction: str, reason: str) -> None:
        try:
            self._met().autoscale_decisions.labels(
                name, direction, reason
            ).inc()
        except Exception:
            pass

    async def _scale_up(self, rec, role, current, decision, now) -> None:
        target = decision.target
        pool = self._pool_entries(rec)
        if pool is not None:
            from seldon_core_tpu.gateway.store import Endpoint

            live = {ep.key for ep in rec.replica_endpoints}
            # live entries keep their order; growth appends unused pool
            # entries, so the youngest replica is always the last one
            chosen = [raw for raw in pool if Endpoint.parse(raw).key in live]
            for raw in pool:
                if len(chosen) >= target:
                    break
                key = Endpoint.parse(raw).key
                if key not in {Endpoint.parse(c).key for c in chosen}:
                    chosen.append(raw)
            if len(chosen) <= current:
                self._last[rec.name]["reason"] = "pool-exhausted"
                return
            target = len(chosen)
            await self._patch_endpoints(rec, chosen)
        await self._patch_workloads(rec, target)
        self.scale_ups += 1
        self._count_decision(rec.name, "up", decision.reason)
        self._span(rec.name, "up", {
            "from": current, "to": target, "reason": decision.reason,
            "pressure": decision.pressure, "role": role,
        })
        self._ledger_entry({
            "ts": round(now, 3), "deployment": rec.name, "role": role,
            "direction": "up", "from": current, "to": target,
            "reason": decision.reason, "pressure": decision.pressure,
            "signals": decision.signals, "outcome": "ok",
        })
        log.info("autoscale %s: %d -> %d (%s)",
                 rec.name, current, target, decision.reason)

    def _pick_victim_and_peer(self, rec):
        """Victim: lowest prefix-digest affinity, then youngest (highest
        pool position — the most recently added replica).  Peer: the
        warmest survivor (highest digest count, then oldest)."""
        eps = list(rec.replica_endpoints)
        counts = {}
        for ep in eps:
            st = self.collector._replicas.get((rec.name, ep.key)) or {}
            counts[ep.key] = _digest_count(st.get("payload"))
        indexed = list(enumerate(eps))
        victim = min(indexed, key=lambda p: (counts[p[1].key], -p[0]))
        survivors = [p for p in indexed if p[0] != victim[0]]
        peer = max(survivors, key=lambda p: (counts[p[1].key], -p[0]))
        return victim[1], peer[1], counts

    async def _drain(self, victim, peer) -> dict:
        session = self._ensure_session()
        url = f"http://{victim.host}:{victim.rest_port}/admin/drain"
        body = {
            "peer": f"{peer.host}:{peer.rest_port}",
            "timeout_s": self.drain_timeout_s,
        }
        async with session.post(url, json=body) as resp:
            payload = await resp.json()
            return {"status": resp.status, **(payload or {})}

    async def _scale_down(self, rec, role, current, decision, now) -> None:
        if current < 2:
            return
        victim, peer, counts = self._pick_victim_and_peer(rec)
        try:
            drain = await self._drain(victim, peer)
        except asyncio.CancelledError:
            raise
        except Exception as exc:
            drain = {"status": 0, "error": f"{type(exc).__name__}: {exc}"}
        ok = drain.get("status") == 200 and not drain.get("failed")
        entry = {
            "ts": round(now, 3), "deployment": rec.name, "role": role,
            "direction": "down", "from": current, "to": decision.target,
            "reason": decision.reason, "pressure": decision.pressure,
            "signals": decision.signals, "victim": victim.key,
            "peer": peer.key, "digests": counts, "drain": drain,
        }
        if not ok:
            # shrink aborts: the victim keeps serving (a failed or
            # refused migration never kills a stream), and the
            # down-hold dwell stops the drain from being hammered
            self.drain_failures += 1
            entry["outcome"] = "drain-failed"
            self._ledger_entry(entry)
            try:
                self._met().autoscale_drains.labels(rec.name, "failed").inc()
            except Exception:
                pass
            log.warning("autoscale %s: drain of %s failed (%s); shrink aborted",
                        rec.name, victim.key, drain)
            return
        pool = self._pool_entries(rec)
        if pool is not None:
            from seldon_core_tpu.gateway.store import Endpoint

            keep_keys = {
                ep.key for ep in rec.replica_endpoints
            } - {victim.key}
            chosen = [
                raw for raw in pool if Endpoint.parse(raw).key in keep_keys
            ]
            await self._patch_endpoints(rec, chosen)
        await self._patch_workloads(rec, decision.target)
        self.scale_downs += 1
        self._count_decision(rec.name, "down", decision.reason)
        try:
            self._met().autoscale_drains.labels(rec.name, "ok").inc()
        except Exception:
            pass
        self._span(rec.name, "down", {
            "from": current, "to": decision.target,
            "reason": decision.reason, "victim": victim.key,
            "peer": peer.key, "migrated": drain.get("migrated"),
            "role": role,
        })
        entry["outcome"] = "ok"
        self._ledger_entry(entry)
        log.info("autoscale %s: %d -> %d (drained %s -> %s, migrated=%s)",
                 rec.name, current, decision.target, victim.key, peer.key,
                 drain.get("migrated"))

    # -- serving -------------------------------------------------------------

    def snapshot(self) -> dict:
        deployments: dict[str, Any] = {}
        for name, (_spec, _role, policy) in self._policies.items():
            deployments[name] = {
                "policy": policy.snapshot(),
                "last": self._last.get(name),
            }
        # records seen but skipped/errored still surface their last state
        for name, last in self._last.items():
            deployments.setdefault(name, {"last": last})
        return {
            "enabled": True,
            "interval_s": self.interval_s,
            "ticks": self.ticks,
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "drain_failures": self.drain_failures,
            "errors": self.errors,
            "deployments": deployments,
            "ledger": list(self.ledger),
        }
