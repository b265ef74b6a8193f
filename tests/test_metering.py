"""Tenant cost-attribution plane (obs/metering.py, docs/OBSERVABILITY.md
"Cost attribution").

Acceptance bars this suite holds:

* **Conservation under packing** — a 3-tenant arbiter-packed run's
  per-tenant device-seconds sum to the wall device-step total within 1%,
  with zero mid-traffic program compiles and the ≤1-host-sync-per-fused-
  block audit green WITH metering on; the null-adapter row attributes to
  the base deployment, never a synthetic tenant.
* **Bounded cardinality** — 500 synthetic adapters cannot grow the
  per-adapter metric label set past the ``SCT_METER_ADAPTER_LABELS`` cap
  (the tail rolls up into ``other``), and the meter's key table stays at
  ``SCT_METER_MAX_KEYS`` with totals conserved across LRU evictions.
* **Counter-exact fleet merge** — two live stub replicas' ``usage``
  snapshots sum key-by-key into ``/stats/fleet`` (sums equal the union);
  a dead replica is excluded, not zeroed in.
* **Exemplar-linked traces** — with ``SCT_METRICS_EXEMPLARS=1`` the
  ``/prometheus`` body parses as valid OpenMetrics and every exemplar's
  trace id resolves through ``GET /stats/timeline?trace=``.
"""

import asyncio
import time

import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from seldon_core_tpu import qos
from seldon_core_tpu.executor.arbiter import DeviceArbiter
from seldon_core_tpu.executor.generation import (
    GenerationScheduler,
    GenerativeModel,
)
from seldon_core_tpu.executor.memory import MemoryManager
from seldon_core_tpu.gateway.store import (
    DeploymentRecord,
    DeploymentStore,
    Endpoint,
)
from seldon_core_tpu.models import llama
from seldon_core_tpu.obs import RECORDER, TIMELINE
from seldon_core_tpu.obs.fleet import FleetCollector, _merge_numeric
from seldon_core_tpu.obs.metering import (
    FIELDS,
    METER,
    OTHER_KEY,
    UsageMeter,
    key_str,
    split_key,
)
from seldon_core_tpu.utils.metrics import (
    OPENMETRICS_CONTENT_TYPE,
    PLAIN_CONTENT_TYPE,
    MetricsRegistry,
    observe_exemplar,
)
from seldon_core_tpu.utils.tracectx import new_traceparent, set_traceparent

run = asyncio.run

SIMPLE = {"name": "p", "graph": {"name": "m", "type": "MODEL",
                                 "implementation": "SIMPLE_MODEL"}}


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = llama.Config.tiny(max_seq=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def _fresh_context():
    """Trace/QoS-naive start, and the process-wide meter wiped so one
    test's charges never leak into another's conservation sums."""
    set_traceparent(None)
    qos.set_deadline(None)
    qos.set_priority(qos.PRIO_INTERACTIVE)
    METER.reset()
    yield
    METER.reset()


# ---------------------------------------------------------------------------
# UsageMeter unit layer
# ---------------------------------------------------------------------------


class TestUsageMeter:
    def test_key_roundtrip(self):
        k = key_str("dep", "ad", "interactive")
        assert k == "dep|ad|interactive"
        assert split_key(k) == ("dep", "ad", "interactive")
        assert split_key("bare") == ("bare", "", "")

    def test_add_accumulates_per_key(self):
        m = UsageMeter(max_keys=8, top_k=4, enabled=True)
        m.add("d", "a", "interactive", device_s=0.5, tokens_decode=3)
        m.add("d", "a", "interactive", device_s=0.25, tokens_decode=1)
        m.add("d", qos="batch", tokens_prefill=10)
        snap = m.snapshot()
        row = snap["keys"]["d|a|interactive"]
        assert row["device_s"] == 0.75 and row["tokens_decode"] == 4
        assert snap["keys"]["d||batch"]["tokens_prefill"] == 10
        assert snap["total"]["device_s"] == 0.75

    def test_disabled_meter_records_nothing(self):
        m = UsageMeter(max_keys=8, top_k=4, enabled=False)
        m.add("d", device_s=1.0)
        assert m.size() == 0 and m.totals() == {}

    def test_lru_eviction_folds_into_other_conserving_totals(self):
        m = UsageMeter(max_keys=4, top_k=2, enabled=True)
        for i in range(10):
            m.add("d", f"a{i}", "batch", device_s=0.5, tokens_decode=2)
        assert m.size() == 4  # bounded
        assert m.evicted == 6
        tot = m.totals()
        # conservation over cardinality: nothing dropped, only rolled up
        assert tot["device_s"] == pytest.approx(5.0)
        assert tot["tokens_decode"] == 20
        snap = m.snapshot()
        assert snap["other"]["device_s"] == pytest.approx(3.0)

    def test_snapshot_leaves_are_numeric(self):
        m = UsageMeter(max_keys=4, top_k=2, enabled=True)
        m.add("d", "a", "interactive", **{f: 1 for f in FIELDS})

        def walk(node):
            for v in node.values():
                if isinstance(v, dict):
                    walk(v)
                else:
                    assert isinstance(v, (bool, int, float))

        walk(m.snapshot())

    def test_export_rows_top_k_plus_other(self):
        m = UsageMeter(max_keys=64, top_k=2, enabled=True)
        for i in range(6):
            m.add("d", f"a{i}", "batch", device_s=float(i), tokens_decode=1)
        rows = m.export_rows()
        keys = [k for k, _ in rows]
        # top-2 by device time, then the rollup row
        assert keys[:2] == [("d", "a5", "batch"), ("d", "a4", "batch")]
        assert keys[-1] == OTHER_KEY
        other = rows[-1][1]
        assert other["device_s"] == pytest.approx(0 + 1 + 2 + 3)
        # export conserves the table total too
        assert sum(r.get("device_s", 0) for _, r in rows) == pytest.approx(
            m.totals()["device_s"]
        )

    def test_two_snapshots_merge_counter_exactly(self):
        a = UsageMeter(max_keys=8, top_k=4, enabled=True)
        b = UsageMeter(max_keys=8, top_k=4, enabled=True)
        a.add("d", "x", "interactive", device_s=1.0, tokens_decode=5)
        a.add("d", "y", "batch", tokens_prefill=7)
        b.add("d", "x", "interactive", device_s=0.5, tokens_decode=3)
        b.add("d", "z", "batch", requests_completed=2)
        merged: dict = {}
        _merge_numeric(merged, a.snapshot())
        _merge_numeric(merged, b.snapshot())
        # sums equal the union
        assert merged["keys"]["d|x|interactive"]["device_s"] == 1.5
        assert merged["keys"]["d|x|interactive"]["tokens_decode"] == 8
        assert merged["keys"]["d|y|batch"]["tokens_prefill"] == 7
        assert merged["keys"]["d|z|batch"]["requests_completed"] == 2
        assert merged["total"]["device_s"] == 1.5


# ---------------------------------------------------------------------------
# Cardinality guard (satellite): 500 synthetic adapters
# ---------------------------------------------------------------------------


class TestAdapterCardinality:
    def test_500_adapters_bounded_label_set(self, monkeypatch):
        monkeypatch.setenv("SCT_METER_ADAPTER_LABELS", "32")
        reg = MetricsRegistry()
        for i in range(500):
            lbl = reg.adapter_label(f"tenant-{i:03d}")
            reg.lora_tokens.labels("dep", lbl).inc(1)
        collected = {
            s.labels["adapter"]: s.value
            for metric in reg.registry.collect()
            if metric.name == "seldon_lora_tokens"
            for s in metric.samples if s.name.endswith("_total")
        }
        # 32 named adapters + the rollup, regardless of tenant count
        assert len(collected) == 33
        assert "other" in collected
        assert reg.adapter_rollups == 500 - 32
        # the rollup bucket carries everything the named rows don't
        assert collected["other"] == 500 - 32

    def test_label_is_sticky_per_adapter(self, monkeypatch):
        monkeypatch.setenv("SCT_METER_ADAPTER_LABELS", "2")
        reg = MetricsRegistry()
        assert reg.adapter_label("a") == "a"
        assert reg.adapter_label("b") == "b"
        assert reg.adapter_label("c") == "other"
        assert reg.adapter_label("a") == "a"  # early adapters keep theirs
        assert reg.adapter_label("") == ""  # base deployment passes through

    def test_meter_table_bounded_with_500_adapters(self, monkeypatch):
        monkeypatch.setenv("SCT_METER_MAX_KEYS", "64")
        m = UsageMeter(top_k=16, enabled=True)
        for i in range(500):
            m.add("dep", f"tenant-{i:03d}", "batch", tokens_decode=4)
        assert m.size() == 64
        assert m.totals()["tokens_decode"] == 2000  # conserved
        rows = m.export_rows()
        assert len(rows) <= 17  # top_k + other

    def test_refresh_usage_export_is_bounded(self):
        reg = MetricsRegistry()
        m = UsageMeter(max_keys=512, top_k=8, enabled=True)
        for i in range(200):
            m.add("dep", f"t{i}", "batch", device_s=float(i), tokens_decode=1)
        reg.refresh_usage(m)
        rows = {
            (s.labels["deployment"], s.labels["adapter"])
            for metric in reg.registry.collect()
            if metric.name == "seldon_usage_device_seconds"
            for s in metric.samples
        }
        assert len(rows) == 9  # top-8 + ("other", "")
        assert ("other", "") in rows
        # a second refresh with a smaller table drops stale label rows
        m2 = UsageMeter(max_keys=512, top_k=8, enabled=True)
        m2.add("dep", "solo", "batch", device_s=1.0)
        reg.refresh_usage(m2)
        rows = {
            s.labels["adapter"]
            for metric in reg.registry.collect()
            if metric.name == "seldon_usage_device_seconds"
            for s in metric.samples
        }
        assert rows == {"solo"}


# ---------------------------------------------------------------------------
# Attribution conservation under packing (tentpole acceptance)
# ---------------------------------------------------------------------------


class TestAttributionConservation:
    def test_three_tenant_packed_device_seconds_conserve(self, tiny):
        """3 co-resident deployments time-share one device under the
        arbiter; the meter's per-tenant device-second rows must sum to
        the seconds their decode blocks OCCUPIED the device by each
        scheduler's device ledger (obs/device.py) within 1%, paying zero
        mid-traffic compiles and keeping the sync audit green.  The
        ``device-step`` stage and its histogram, which the QoS estimate
        reads, stay the host's wait inside the fetch, once a block."""
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        mm = MemoryManager(enforce=False)
        blocks = {"met-inter": 4, "met-bulk-0": 6, "met-bulk-1": 8}
        max_new = 12
        models = {
            name: GenerativeModel(
                cfg, params, n_slots=2, decode_block=blk, name=name,
                memory=mm,
            )
            for name, blk in blocks.items()
        }
        prompt = np.asarray([5, 9, 2, 17, 3], np.int32)
        # blocks dispatched against blocks delivered: the overlapped
        # pipeline has one more block in flight when the last request's
        # future resolves, and close() would cancel it between its fetch
        # (which the wall total below counts) and its delivery (where the
        # meter charges it) — so a round trip ends once the two are level
        blocks_seen = {"dispatched": 0, "delivered": 0}
        outs: list = []

        def counted(fn, key):
            def wrapped(*args, **kwargs):
                out = fn(*args, **kwargs)
                blocks_seen[key] += 1
                return out

            return wrapped

        for model in models.values():
            model.step_k_dispatch = counted(model.step_k_dispatch, "dispatched")
            model.step_k_continue = counted(model.step_k_continue, "dispatched")

        def round_trip():
            arb = DeviceArbiter()
            scheds = {n: GenerationScheduler(m) for n, m in models.items()}
            blocks_seen.update(dispatched=0, delivered=0)
            for s in scheds.values():
                s._deliver = counted(s._deliver, "delivered")

            async def go():
                scheds["met-inter"].attach_arbiter(
                    arb, priority="interactive"
                )
                scheds["met-bulk-0"].attach_arbiter(arb, priority="batch")
                scheds["met-bulk-1"].attach_arbiter(arb, priority="batch")
                try:
                    outs.clear()
                    outs.extend(await asyncio.gather(*(
                        s.submit(prompt, max_new_tokens=max_new)
                        for s in scheds.values()
                        for _ in range(2)
                    )))
                    for _ in range(1000):
                        if blocks_seen["dispatched"] == blocks_seen["delivered"]:
                            break
                        await asyncio.sleep(0.01)
                finally:
                    for s in scheds.values():
                        await s.close()

            run(go())
            return scheds

        round_trip()  # warmup: all programs compile off the clock
        METER.reset()
        compiles_before = sum(m.program_compiles for m in models.values())
        syncs_before = {
            n: host_sync_snapshot().get(n, 0) for n in models
        }
        # what ``device-step`` is handed, and the most it may be: a block's
        # dispatch stamp to the fetch's return
        waits: list = []
        mfu_over: list = []  # the seconds the ``mfu`` gauge divides by
        for model in models.values():
            fetch, record = model.step_k_fetch, model._record_step

            def fetched(handle, _fetch=fetch):
                out = _fetch(handle)
                waits[-1].append(time.perf_counter() - handle[3])
                return out

            def recorded(step_s, _record=record):
                waits.append([step_s])
                return _record(step_s)

            model.step_k_fetch, model._record_step = fetched, recorded
            model.record_mfu = lambda tokens, busy_s: mfu_over.append(busy_s)
        stage_before = RECORDER.breakdown().get("device-step", {"count": 0})["count"]

        scheds = round_trip()
        assert all(o.size == max_new for o in outs)
        # ground truth: the seconds the decode blocks occupied the device,
        # by the ledger of the scheduler that sent them
        wall = {"s": sum(
            s.device_snapshot()["busy_s"]["decode"] for s in scheds.values()
        )}
        assert len(waits) == len(mfu_over) == blocks_seen["delivered"]
        assert sum(mfu_over) == pytest.approx(wall["s"], abs=1e-4)
        assert all(0 < step_s <= bound for step_s, bound in waits)
        assert (
            RECORDER.breakdown()["device-step"]["count"] - stage_before
            == len(waits)
        )
        # zero mid-traffic compiles with metering on
        assert sum(
            m.program_compiles for m in models.values()
        ) == compiles_before
        # sync audit stays green per deployment (PR-5 invariant)
        for name, blk in blocks.items():
            syncs = host_sync_snapshot().get(name, 0) - syncs_before[name]
            tokens = 2 * max_new
            assert syncs <= tokens // blk + 6, (
                f"{name}: {syncs} host syncs for {tokens} tokens"
            )
        # conservation: attributed device seconds == wall total within 1%
        tot = METER.totals()
        assert wall["s"] > 0
        assert tot["device_s"] == pytest.approx(wall["s"], rel=0.01)
        # the arbiter charged real grant intervals too
        assert tot.get("grant_s", 0) > 0
        snap = METER.snapshot()
        # null-adapter rows attribute to the base deployment (empty
        # adapter label) — no synthetic tenant appears
        assert not any(split_key(k)[1] for k in snap["keys"])
        per_dep: dict = {}
        for k, row in snap["keys"].items():
            dep = split_key(k)[0]
            per_dep[dep] = per_dep.get(dep, 0.0) + row.get("device_s", 0.0)
        for name in blocks:
            assert per_dep[name] > 0
        # decode tokens all attributed (the first token of each request
        # is sampled BY the prefill, not a fused decode block)
        assert tot["tokens_decode"] == 6 * (max_new - 1)
        assert tot["requests_completed"] == 6

    def test_terminal_timeline_events_stamp_usage_totals(self, tiny):
        """Satellite: every terminal event carries the request's final
        cost (device-ms, tokens in/out) so one trace answers 'what did
        this request spend'."""
        assert TIMELINE.enabled
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, name="met-terminal"
        )
        sched = GenerationScheduler(model)
        tp = new_traceparent()
        set_traceparent(tp)

        async def go():
            try:
                return await sched.submit(
                    np.asarray([5, 9, 2], np.int32), max_new_tokens=8
                )
            finally:
                await sched.close()

        out = run(go())
        assert out.size == 8
        trace = tp.split("-")[1]
        (entry,) = TIMELINE.by_trace(trace)
        assert entry["done"] in ("budget", "eos")
        usage = entry["events"][-1]["attrs"]["usage"]
        assert usage["tokens_in"] == 3
        assert usage["tokens_out"] == 8
        assert usage["device_ms"] > 0
        # the meter agrees with the stamp (the first of the 8 tokens was
        # sampled by the prefill, not a fused decode block)
        row = METER.snapshot()["keys"][
            key_str("met-terminal", "", "interactive")]
        assert row["tokens_decode"] == 7
        assert row["device_s"] * 1e3 == pytest.approx(
            usage["device_ms"], rel=0.01
        )

    def test_shed_terminal_stamps_zero_usage_and_meters(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, name="met-shed"
        )
        sched = GenerationScheduler(model)
        tp = new_traceparent()
        set_traceparent(tp)
        sched._note_shed("interactive", 5, 5)
        trace = tp.split("-")[1]
        (entry,) = TIMELINE.by_trace(trace)
        assert entry["done"] == "shed"
        usage = entry["events"][-1]["attrs"]["usage"]
        assert usage == {"device_ms": 0.0, "tokens_in": 0, "tokens_out": 0}
        row = METER.snapshot()["keys"][
            key_str("met-shed", "", "interactive")]
        assert row["requests_shed"] == 1
        assert "device_s" not in row  # zero device time by construction
        run(sched.close())

    def test_qos_controller_sheds_are_metered(self):
        from seldon_core_tpu.qos.admission import (
            AdmissionController,
            QosRejection,
        )

        ctl = AdmissionController("met-qos", max_inflight=1, max_queue=0)
        t0 = ctl.admit(priority="interactive")
        with pytest.raises(QosRejection):
            ctl.admit(priority="interactive")
        t0.release()
        row = METER.snapshot()["keys"][
            key_str("met-qos", "", "interactive")]
        assert row["requests_shed"] == 1

    def test_response_cache_hits_are_metered(self):
        from seldon_core_tpu.cache.content import ResponseCache

        c = ResponseCache("gateway", max_entries=4, max_bytes=1024,
                          ttl_s=60.0)
        c.put("dep-c", "k", b"v")
        assert c.get("dep-c", "k") is not None
        assert c.get("dep-c", "missing") is None  # miss: not metered
        row = METER.snapshot()["keys"][key_str("dep-c")]
        assert row["requests_cached"] == 1


# ---------------------------------------------------------------------------
# Fleet merge (acceptance: counter-exact across >=2 replicas)
# ---------------------------------------------------------------------------


class UsageStub:
    """A fake engine /stats/summary surface carrying a usage table."""

    def __init__(self, usage: dict):
        self.usage = usage
        self.runner = None
        self.port = None

    async def start(self):
        app = web.Application()

        async def summary(request):
            return web.json_response({
                "qos": {"admitted_total": 1, "shed_total": 0,
                        "deadline_miss_total": 0},
                "breakdown": {}, "cache": {}, "wire": {},
                "usage": self.usage, "stage_hist": {},
            })

        app.router.add_get("/stats/summary", summary)
        self.runner = web.AppRunner(app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        self.port = self.runner.addresses[0][1]
        return self

    async def stop(self):
        if self.runner is not None:
            await self.runner.cleanup()
            self.runner = None

    @property
    def endpoint(self) -> Endpoint:
        return Endpoint("127.0.0.1", self.port, self.port)


def _usage_payload(**rows) -> dict:
    keys = {k: dict(v) for k, v in rows.items()}
    total: dict = {}
    for row in keys.values():
        for f, v in row.items():
            total[f] = total.get(f, 0) + v
    return {"enabled": True, "keys": keys, "other": {}, "evicted": 0,
            "total": total}


def _store_for(*replicas, name="dep") -> DeploymentStore:
    store = DeploymentStore()
    store.put(DeploymentRecord(
        name=name, oauth_key=f"{name}-k", oauth_secret="s",
        endpoints=tuple(r.endpoint for r in replicas),
    ))
    return store


class TestFleetUsageMerge:
    def test_usage_merges_counter_exactly_across_replicas(self):
        async def go():
            a = await UsageStub(_usage_payload(**{
                "dep|x|interactive": {"device_s": 1.5, "tokens_decode": 30},
                "dep|y|batch": {"tokens_prefill": 7},
            })).start()
            b = await UsageStub(_usage_payload(**{
                "dep|x|interactive": {"device_s": 0.5, "tokens_decode": 10},
                "dep|z|batch": {"requests_completed": 2},
            })).start()
            col = FleetCollector(_store_for(a, b), interval_s=10.0,
                                 jitter=0.0)
            try:
                agg = await col.poll_once(now=1000.0)
                usage = agg["deployments"]["dep"]["usage"]
                # shared key: summed; disjoint keys: the union
                assert usage["keys"]["dep|x|interactive"] == {
                    "device_s": 2.0, "tokens_decode": 40}
                assert usage["keys"]["dep|y|batch"] == {"tokens_prefill": 7}
                assert usage["keys"]["dep|z|batch"] == {
                    "requests_completed": 2}
                assert usage["total"]["device_s"] == 2.0
                assert usage["total"]["tokens_decode"] == 40
                # usage feeds the history rings
                snap = col.fleet_snapshot()
                assert "dep.usage_device_s" in snap["history"]["metrics"]
            finally:
                await col.stop()
                await a.stop()
                await b.stop()

        run(go())

    def test_dead_replica_usage_excluded_not_zeroed(self):
        async def go():
            a = await UsageStub(_usage_payload(**{
                "dep|x|interactive": {"tokens_decode": 100}})).start()
            b = await UsageStub(_usage_payload(**{
                "dep|x|interactive": {"tokens_decode": 40}})).start()
            col = FleetCollector(_store_for(a, b), interval_s=1.0,
                                 jitter=0.0, stale_polls=3, fail_damp=99)
            try:
                agg = await col.poll_once(now=100.0)
                usage = agg["deployments"]["dep"]["usage"]
                assert usage["keys"]["dep|x|interactive"][
                    "tokens_decode"] == 140
                await b.stop()  # replica dies
                # past the stale window: b's table is EXCLUDED — the live
                # replica's counters stand alone, nothing zeroes in
                agg = await col.poll_once(now=110.0)
                dep = agg["deployments"]["dep"]
                assert dep["replicas_live"] == 1
                assert dep["usage"]["keys"]["dep|x|interactive"][
                    "tokens_decode"] == 100
            finally:
                await col.stop()
                await a.stop()

        run(go())


# ---------------------------------------------------------------------------
# Serving surfaces: /stats/usage on the engine and both gateway fronts
# ---------------------------------------------------------------------------


async def _engine_client() -> TestClient:
    from seldon_core_tpu.engine.app import EngineApp
    from seldon_core_tpu.engine.service import PredictionService
    from seldon_core_tpu.graph.spec import PredictorSpec

    service = PredictionService(PredictorSpec.model_validate(SIMPLE))
    await service.start()
    client = TestClient(TestServer(EngineApp(service).build()))
    await client.start_server()
    return client


class TestServingSurfaces:
    def test_engine_usage_route_and_summary_section(self):
        async def go():
            METER.add("dep-e", "ad", "interactive",
                      device_s=0.5, tokens_decode=4)
            engine = await _engine_client()
            try:
                r = await engine.get("/stats/usage")
                assert r.status == 200
                usage = (await r.json())["usage"]
                assert usage["keys"]["dep-e|ad|interactive"][
                    "tokens_decode"] == 4
                r = await engine.get("/stats/summary")
                body = await r.json()
                assert set(body) >= {"qos", "breakdown", "cache", "wire",
                                     "usage", "stage_hist"}
                assert body["usage"]["total"]["device_s"] == 0.5
            finally:
                await engine.close()

        run(go())

    def test_gateway_fronts_serve_usage(self):
        import aiohttp

        from seldon_core_tpu.gateway.app import GatewayApp
        from seldon_core_tpu.gateway.h1gateway import H1SpliceFrontend

        async def go():
            METER.add("dep-g", qos="batch", requests_cached=3)
            store = DeploymentStore()
            store.put(DeploymentRecord(
                name="dep-g", oauth_key="k", oauth_secret="s"))
            gw = GatewayApp(store)
            client = TestClient(TestServer(gw.build()))
            await client.start_server()
            frontend = H1SpliceFrontend(gw)
            port = await frontend.start(0, host="127.0.0.1")
            try:
                r = await client.get("/stats/usage")
                assert r.status == 200
                usage = (await r.json())["usage"]
                assert usage["keys"]["dep-g||batch"]["requests_cached"] == 3
                async with aiohttp.ClientSession() as s:
                    r = await s.get(
                        f"http://127.0.0.1:{port}/stats/usage")
                    assert r.status == 200
                    usage = (await r.json())["usage"]
                    assert usage["keys"]["dep-g||batch"][
                        "requests_cached"] == 3
            finally:
                await frontend.stop()
                await client.close()
                await gw.close()

        run(go())


# ---------------------------------------------------------------------------
# OpenMetrics exemplars (acceptance: parse + trace-id resolution)
# ---------------------------------------------------------------------------


class TestExemplars:
    def test_plain_exposition_by_default(self):
        reg = MetricsRegistry()
        assert reg.expose_content_type() == PLAIN_CONTENT_TYPE
        observe_exemplar(reg.ttft.labels("m"), 0.01, "f" * 32)
        body = reg.expose().decode()
        assert "# EOF" not in body  # classic text format
        assert "trace_id" not in body  # ... and no exemplars rendered

    def test_exemplars_render_parse_and_resolve(self, monkeypatch):
        from prometheus_client.openmetrics.parser import (
            text_string_to_metric_families,
        )

        monkeypatch.setenv("SCT_METRICS_EXEMPLARS", "1")
        reg = MetricsRegistry()
        assert reg.expose_content_type() == OPENMETRICS_CONTENT_TYPE
        traces = [f"{i:032x}" for i in (0xA, 0xB)]
        for i, t in enumerate(traces):
            tl = TIMELINE.begin(t, model="m")
            tl.event("admit")
            tl.end("eos")
            observe_exemplar(reg.ttft.labels("m"), 0.005 * (i + 1), t)
        # a meter-backed usage refresh rides the same exposition
        m = UsageMeter(max_keys=8, top_k=4, enabled=True)
        m.add("m", qos="interactive", device_s=0.1)
        reg.refresh_usage(m)
        body = reg.expose().decode()
        assert body.rstrip().endswith("# EOF")
        seen = []
        for family in text_string_to_metric_families(body):
            for sample in family.samples:
                if sample.exemplar:
                    seen.append(sample.exemplar.labels["trace_id"])
        assert set(seen) == set(traces)
        # every exemplar's trace id resolves through the timeline ledger
        for t in seen:
            assert TIMELINE.by_trace(t), f"exemplar trace {t} unresolvable"

    def test_exemplar_trace_resolves_over_engine_http(self, monkeypatch):
        """The acceptance path end-to-end: scrape /prometheus with
        exemplars on, pull each exemplar's trace id, and resolve it via
        GET /stats/timeline?trace= on the same engine."""
        from prometheus_client.openmetrics.parser import (
            text_string_to_metric_families,
        )

        monkeypatch.setenv("SCT_METRICS_EXEMPLARS", "1")

        async def go():
            engine = await _engine_client()
            try:
                trace = "ab" * 16
                tl = TIMELINE.begin(trace, model="m")
                tl.event("admit")
                tl.end("eos")
                # engine app and the process share DEFAULT metrics
                from seldon_core_tpu.utils.metrics import DEFAULT

                observe_exemplar(DEFAULT.ttft.labels("m"), 0.003, trace)
                r = await engine.get("/prometheus")
                assert r.status == 200
                assert r.headers["Content-Type"] == (
                    OPENMETRICS_CONTENT_TYPE)
                body = await r.text()
                tids = {
                    s.exemplar.labels["trace_id"]
                    for f in text_string_to_metric_families(body)
                    for s in f.samples if s.exemplar
                }
                assert trace in tids
                for tid in tids:
                    r = await engine.get(f"/stats/timeline?trace={tid}")
                    assert r.status == 200
                    legs = (await r.json())["timeline"]
                    assert legs, f"trace {tid} did not resolve"
            finally:
                await engine.close()

        run(go())

    def test_stand_in_histogram_falls_back(self, monkeypatch):
        monkeypatch.setenv("SCT_METRICS_EXEMPLARS", "1")

        class Stub:
            def __init__(self):
                self.seen = []

            def observe(self, v):  # no exemplar kwarg
                self.seen.append(v)

        h = Stub()
        observe_exemplar(h, 1.5, "c" * 32)
        assert h.seen == [1.5]
