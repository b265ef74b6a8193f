"""Fleet telemetry plane (obs/fleet.py, obs/history.py, obs/slo.py).

Unit layers: bounded step-down rings, mergeable histograms, the SLO
grammar + burn-rate state machine.  Integration layers: a FleetCollector
scraping real aiohttp stub replicas (counter sums, stale exclusion,
scrape-storm damping, timeline fan-out), the kubesim-fed watch->store->
collector pipeline, the engine's ``/stats/summary`` bundle, and both
gateway REST fronts re-exporting ``/stats/fleet`` + ``/stats/slo`` +
the ``/stats/timeline`` fan-out."""

import asyncio
import json

import aiohttp
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from seldon_core_tpu.gateway.store import (
    DeploymentRecord,
    DeploymentStore,
    Endpoint,
)
from seldon_core_tpu.obs.fleet import FleetCollector
from seldon_core_tpu.obs.history import (
    BUCKET_EDGES,
    History,
    bin_samples,
    hist_percentile_ms,
    merge_hist,
    new_hist,
)
from seldon_core_tpu.obs import TIMELINE
from seldon_core_tpu.obs.slo import (
    SLO_ANNOTATION,
    SloEngine,
    SloError,
    count_over_bound,
    parse_slo,
)

run = asyncio.run


# ---------------------------------------------------------------------------
# history rings
# ---------------------------------------------------------------------------


class TestHistoryRings:
    def test_ring_is_bounded_and_steps_down(self):
        h = History(slots=8)
        # 500 points over ~83 minutes of synthetic time
        for i in range(500):
            h.record("m", float(i), now=i * 10.0)
        now = 499 * 10.0
        fast = h.series("m", "fast", now=now)
        slow = h.series("m", "slow", now=now)
        assert 0 < len(fast) <= 8
        assert 0 < len(slow) <= 8
        # fast ring holds the newest 10 s buckets; old ones were evicted
        # in place, not retained
        assert fast[-1]["t"] == now
        assert fast[0]["t"] >= now - 8 * 10.0
        # slow ring buckets are 2 min wide: several fast points merge
        assert slow[-1]["count"] > fast[-1]["count"]

    def test_zero_allocation_at_steady_state(self):
        h = History(slots=4)
        h.record("m", 1.0, now=0.0)
        ring = h._series["m"][0]
        sizes = (len(ring._sum), len(ring._min), len(ring._max),
                 len(ring._count), len(ring._bucket))
        for i in range(1000):
            h.record("m", float(i), now=float(i))
        assert (len(ring._sum), len(ring._min), len(ring._max),
                len(ring._count), len(ring._bucket)) == sizes

    def test_metric_cardinality_is_bounded(self):
        h = History(slots=4, max_metrics=10)
        for i in range(50):
            h.record(f"m{i}", 1.0, now=0.0)
        assert len(h.metrics()) == 10
        assert h.dropped_metrics == 40
        assert h.snapshot(now=0.0)["dropped_metrics"] == 40

    def test_slope_and_delta(self):
        h = History(slots=64)
        # queue wait climbing 2 units per second
        for i in range(30):
            h.record("qw", 2.0 * (i * 10.0), now=i * 10.0)
        now = 29 * 10.0
        slope = h.slope("qw", window_s=300.0, now=now)
        assert slope == pytest.approx(2.0, rel=0.05)
        delta = h.delta("qw", window_s=300.0, now=now)
        assert delta > 0
        assert h.slope("missing") is None

    def test_mean_min_max_within_bucket(self):
        h = History(slots=8)
        for v in (1.0, 3.0, 5.0):
            h.record("m", v, now=100.0)
        (pt,) = h.series("m", "fast", now=100.0)
        assert pt["min"] == 1.0 and pt["max"] == 5.0
        assert pt["mean"] == pytest.approx(3.0)
        assert pt["count"] == 3


# ---------------------------------------------------------------------------
# mergeable histograms
# ---------------------------------------------------------------------------


class TestHistogramMerge:
    def test_merge_equals_binning_the_union(self):
        a = [0.001] * 900
        b = [0.1] * 100
        merged = merge_hist(bin_samples(a), bin_samples(b))
        assert merged == bin_samples(a + b)

    def test_merged_p99_is_not_an_average_of_p99s(self):
        # replica A: 900 fast requests; replica B: 100 slow ones.  The
        # true fleet p99 sits in B's latency range; the average of the
        # two per-replica p99s lands in no-man's land.
        ha = bin_samples([0.001] * 900)
        hb = bin_samples([0.1] * 100)
        merged = merge_hist(new_hist(), ha)
        merge_hist(merged, hb)
        fleet_p99 = hist_percentile_ms(merged, 99.0)
        avg_of_p99 = (hist_percentile_ms(ha, 99.0)
                      + hist_percentile_ms(hb, 99.0)) / 2.0
        assert fleet_p99 == pytest.approx(100.0, rel=0.06)
        assert abs(avg_of_p99 - 100.0) > 40.0

    def test_percentile_within_one_bucket(self):
        import random
        rng = random.Random(3)
        samples = [rng.lognormvariate(-5.0, 1.0) for _ in range(5000)]
        got = hist_percentile_ms(bin_samples(samples), 50.0)
        true_ms = sorted(samples)[2500] * 1e3
        # one log-spaced bucket is a 10^(1/40) ~ 5.9% step
        assert true_ms / 1.06 <= got <= true_ms * 1.06

    def test_empty_hist_has_no_percentile(self):
        assert hist_percentile_ms(new_hist(), 99.0) is None

    def test_count_over_bound(self):
        hist = bin_samples([0.001] * 10 + [0.5] * 4)
        assert count_over_bound(hist, 100.0) == 4
        assert count_over_bound(hist, 1000.0) == 0
        # a bound far under every sample counts them all
        assert count_over_bound(hist, 0.0001) == 14

    def test_merge_is_length_tolerant(self):
        short = [1] * 10
        into = new_hist()
        merge_hist(into, short)
        assert sum(into) == 10
        assert len(into) == len(BUCKET_EDGES) + 1


# ---------------------------------------------------------------------------
# SLO grammar
# ---------------------------------------------------------------------------


class TestSloGrammar:
    def test_full_spec(self):
        objs = parse_slo("ttft_p99_ms=250,deadline_hit=0.99,shed_rate=0.01")
        by_name = {o.name: o for o in objs}
        lat = by_name["ttft_p99_ms"]
        assert lat.kind == "latency" and lat.stage == "ttft"
        assert lat.quantile == 99.0 and lat.bound_ms == 250.0
        assert lat.budget == pytest.approx(0.01)
        assert by_name["deadline_hit"].kind == "good_ratio"
        assert by_name["deadline_hit"].budget == pytest.approx(0.01)
        assert by_name["shed_rate"].kind == "bad_ratio"
        assert by_name["shed_rate"].budget == pytest.approx(0.01)

    def test_stage_underscores_map_to_hyphens(self):
        (obj,) = parse_slo("queue_wait_p95_ms=50")
        assert obj.stage == "queue-wait"
        assert obj.quantile == 95.0

    @pytest.mark.parametrize("bad", [
        "bogus=1",
        "shed_rate=0.01,shed_rate=0.02",
        "ttft_p99_ms=0",
        "ttft_p99_ms=abc",
        "deadline_hit=1.5",
        "shed_rate=1.0",
        "ttft_p0_ms=250",
        "deadline_hit",
    ])
    def test_malformed_specs_raise(self, bad):
        with pytest.raises(SloError):
            parse_slo(bad)

    def test_empty_entries_tolerated(self):
        assert parse_slo("") == ()
        assert len(parse_slo("shed_rate=0.1,")) == 1

    def test_operator_rejects_bad_slo_at_admission(self):
        from seldon_core_tpu.operator.crd import SeldonDeployment
        from seldon_core_tpu.operator.defaulting import (
            ValidationError,
            validate,
        )

        def dep(slo: str) -> SeldonDeployment:
            return SeldonDeployment.model_validate({
                "metadata": {"name": "d",
                             "annotations": {SLO_ANNOTATION: slo}},
                "spec": {"name": "d", "predictors": [
                    {"name": "p", "graph": {
                        "name": "m", "type": "MODEL",
                        "implementation": "SIMPLE_MODEL"}}
                ]},
            })

        validate(dep("ttft_p99_ms=250,shed_rate=0.01"))  # well-formed: ok
        with pytest.raises(ValidationError, match="seldon.io/slo"):
            validate(dep("ttft_p99_ms=nope"))


# ---------------------------------------------------------------------------
# SLO burn-rate engine (synthetic time, synthetic counters)
# ---------------------------------------------------------------------------


def _slo_engine(**kw) -> SloEngine:
    kw.setdefault("fast_window_s", 60.0)
    kw.setdefault("slow_window_s", 600.0)
    kw.setdefault("page_burn", 14.0)
    kw.setdefault("warn_burn", 6.0)
    return SloEngine(**kw)


class TestSloEngine:
    def test_clean_traffic_stays_ok(self):
        eng = _slo_engine()
        eng.declare("d", "shed_rate=0.01", now=0.0)
        for i in range(1, 20):
            t = i * 10.0
            eng.observe("d", {"shed_rate": (i * 100.0, 0.0)}, now=t)
            eng.evaluate(now=t)
        dep = eng.evaluate(now=190.0)["deployments"]["d"]
        assert dep["state"] == "ok"
        assert dep["objectives"]["shed_rate"]["fast_burn"] == 0.0

    def test_hard_overload_pages_then_recovers_on_fast_window(self):
        eng = _slo_engine()
        eng.declare("d", "shed_rate=0.01", now=0.0)
        total = bad = 0.0
        t = 0.0
        # healthy hour-start
        for _ in range(6):
            t += 10.0
            total += 100.0
            eng.observe("d", {"shed_rate": (total, bad)}, now=t)
            eng.evaluate(now=t)
        # outage: half of everything sheds -> burn = 0.5/0.01 = 50
        for _ in range(12):
            t += 10.0
            total += 100.0
            bad += 50.0
            eng.observe("d", {"shed_rate": (total, bad)}, now=t)
            out = eng.evaluate(now=t)
        dep = out["deployments"]["d"]
        assert dep["state"] == "page"
        st = dep["objectives"]["shed_rate"]
        assert st["fast_burn"] >= 14.0 and st["slow_burn"] >= 14.0
        paged_at = st["since"]
        # recovery: clean traffic for > fast window; the slow window is
        # still digesting the incident but must not hold the page
        for _ in range(12):
            t += 10.0
            total += 100.0
            eng.observe("d", {"shed_rate": (total, bad)}, now=t)
            out = eng.evaluate(now=t)
        dep = out["deployments"]["d"]
        st = dep["objectives"]["shed_rate"]
        assert st["fast_burn"] == 0.0
        assert st["slow_burn"] > 14.0  # incident still inside slow window
        assert dep["state"] == "ok"
        assert st["since"] > paged_at
        assert st["transitions"] >= 2  # ok->page->ok at minimum

    def test_moderate_burn_warns_without_paging(self):
        eng = _slo_engine()
        eng.declare("d", "shed_rate=0.1", now=0.0)
        total = bad = 0.0
        t = 0.0
        for _ in range(20):
            t += 10.0
            total += 100.0
            bad += 100.0  # frac 1.0 / budget 0.1 = burn 10: warn-band
            eng.observe("d", {"shed_rate": (total, bad)}, now=t)
            out = eng.evaluate(now=t)
        dep = out["deployments"]["d"]
        assert dep["state"] == "warn"

    def test_counter_dip_is_tolerated_not_a_transition(self):
        eng = _slo_engine()
        eng.declare("d", "shed_rate=0.01", now=0.0)
        eng.observe("d", {"shed_rate": (1000.0, 0.0)}, now=10.0)
        eng.observe("d", {"shed_rate": (2000.0, 0.0)}, now=20.0)
        assert eng.evaluate(now=20.0)["deployments"]["d"]["state"] == "ok"
        # a replica left the aggregate: cumulative totals DROP
        eng.observe("d", {"shed_rate": (500.0, 0.0)}, now=30.0)
        dep = eng.evaluate(now=30.0)["deployments"]["d"]
        assert dep["state"] == "ok"
        assert dep["objectives"]["shed_rate"]["fast_burn"] is None

    def test_spec_change_resets_state_and_bad_spec_is_reported(self):
        eng = _slo_engine()
        eng.declare("d", "shed_rate=0.01", now=0.0)
        eng.observe("d", {"shed_rate": (100.0, 90.0)}, now=10.0)
        eng.observe("d", {"shed_rate": (200.0, 180.0)}, now=20.0)
        eng.evaluate(now=20.0)
        eng.declare("d", "shed_rate=0.5", now=30.0)  # changed -> reset
        dep = eng.evaluate(now=30.0)["deployments"]["d"]
        assert dep["objectives"]["shed_rate"]["fast_burn"] is None
        # re-declaring the SAME spec must NOT reset accumulated samples
        eng.observe("d", {"shed_rate": (100.0, 0.0)}, now=40.0)
        eng.declare("d", "shed_rate=0.5", now=50.0)
        eng.observe("d", {"shed_rate": (200.0, 0.0)}, now=50.0)
        dep = eng.evaluate(now=50.0)["deployments"]["d"]
        assert dep["objectives"]["shed_rate"]["fast_burn"] == 0.0
        # malformed spec: error surfaced, no objectives
        eng.declare("d", "nonsense", now=60.0)
        dep = eng.evaluate(now=60.0)["deployments"]["d"]
        assert dep["error"]
        assert dep["objectives"] == {}

    def test_retain_prunes_departed_deployments(self):
        eng = _slo_engine()
        eng.declare("a", "shed_rate=0.1", now=0.0)
        eng.declare("b", "shed_rate=0.1", now=0.0)
        eng.retain(["a"])
        assert set(eng.evaluate(now=1.0)["deployments"]) == {"a"}


# ---------------------------------------------------------------------------
# collector over live stub replicas
# ---------------------------------------------------------------------------


class StubReplica:
    """A fake engine stats surface: mutable qos counters, a stage
    histogram, and a timeline, served over a real socket."""

    def __init__(self, admitted=0, shed=0, miss=0):
        self.qos = {
            "admitted_total": admitted, "shed_total": shed,
            "deadline_miss_total": miss, "queue_wait_ewma_ms": 1.0,
            "inflight": 2, "predicted_completion_ms": 5.0,
            "max_inflight": 64, "max_queue": 128,
            "shed_by_reason": {"queue_full": shed},
            "brownout": {"active": False},
        }
        self.stage_hist = {}
        self.timeline = []
        self.summary_calls = 0
        self.runner = None
        self.port = None

    async def start(self):
        app = web.Application()
        app.router.add_get("/stats/summary", self._summary)
        app.router.add_get("/stats/timeline", self._timeline)
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

    async def _summary(self, request):
        self.summary_calls += 1
        return web.json_response({
            "qos": self.qos,
            "breakdown": {},
            "cache": {"hits": 1, "misses": 2},
            "wire": {"wire": {"engine-rest": {"rx_bytes": 10}}},
            "stage_hist": self.stage_hist,
        })

    async def _timeline(self, request):
        trace = request.query.get("trace", "")
        legs = [e for e in self.timeline if e.get("trace") == trace]
        return web.json_response({"timeline": legs})

    @property
    def endpoint(self) -> Endpoint:
        return Endpoint("127.0.0.1", self.port, self.port)


def _store_for(*replicas: StubReplica, name="dep",
               annotations=None) -> DeploymentStore:
    store = DeploymentStore()
    store.put(DeploymentRecord(
        name=name, oauth_key=f"{name}-k", oauth_secret="s",
        endpoints=tuple(r.endpoint for r in replicas),
        annotations=dict(annotations or {}),
    ))
    return store


class TestFleetCollector:
    def test_counters_summed_and_percentiles_merged(self):
        async def go():
            a = await StubReplica(admitted=100, shed=10).start()
            b = await StubReplica(admitted=200, shed=30).start()
            a.stage_hist = {"ttft": bin_samples([0.001] * 900)}
            b.stage_hist = {"ttft": bin_samples([0.1] * 100)}
            try:
                col = FleetCollector(_store_for(a, b), interval_s=10.0,
                                     jitter=0.0)
                agg = await col.poll_once(now=1000.0)
                dep = agg["deployments"]["dep"]
                assert dep["replicas_live"] == 2
                assert dep["qos"]["admitted_total"] == 300
                assert dep["qos"]["shed_total"] == 40
                assert dep["qos"]["shed_by_reason"]["queue_full"] == 40
                # gauges keep min/mean/max, pools sum with min/max
                assert dep["qos"]["max_inflight"]["sum"] == 128
                assert dep["qos"]["inflight"]["mean"] == 2
                # fleet p99 equals the percentile of the SUMMED buckets
                want = hist_percentile_ms(
                    merge_hist(bin_samples([0.001] * 900),
                               bin_samples([0.1] * 100)), 99.0)
                assert dep["latency"]["ttft"]["p99_ms"] == want
                assert dep["latency"]["ttft"]["count"] == 1000
                # cache/wire numeric leaves sum
                assert dep["cache"]["hits"] == 2
                # history fed from the poll
                snap = col.fleet_snapshot()
                assert "dep.admitted_total" in snap["history"]["metrics"]
                assert "stage_hist" not in snap["deployments"]["dep"]
                assert col.errors == 0
            finally:
                await col.stop()
                await a.stop()
                await b.stop()

        run(go())

    def test_dead_replica_goes_stale_and_is_excluded_not_zeroed(self):
        async def go():
            a = await StubReplica(admitted=100).start()
            b = await StubReplica(admitted=50).start()
            col = FleetCollector(_store_for(a, b), interval_s=1.0,
                                 jitter=0.0, stale_polls=3, fail_damp=99)
            try:
                agg = await col.poll_once(now=100.0)
                assert agg["deployments"]["dep"]["qos"][
                    "admitted_total"] == 150
                await b.stop()  # replica dies
                a.qos["admitted_total"] = 110
                # within the grace window b's LAST payload still counts
                agg = await col.poll_once(now=101.0)
                dep = agg["deployments"]["dep"]
                assert dep["replicas_stale"] == 0
                assert dep["qos"]["admitted_total"] == 160
                # past stale_polls * interval: excluded, not zeroed in
                agg = await col.poll_once(now=110.0)
                dep = agg["deployments"]["dep"]
                assert dep["replicas_live"] == 1
                assert dep["replicas_stale"] == 1
                assert dep["qos"]["admitted_total"] == 110
                stale_meta = [m for m in dep["replicas"] if m["stale"]]
                assert len(stale_meta) == 1
                assert stale_meta[0]["fail_streak"] >= 1
                assert col.errors == 0  # replica death is not an error
            finally:
                await col.stop()
                await a.stop()

        run(go())

    def test_scrape_storm_damping_on_dead_replica(self):
        async def go():
            a = await StubReplica().start()
            b = await StubReplica().start()
            col = FleetCollector(_store_for(a, b), interval_s=1.0,
                                 jitter=0.0, fail_damp=2)
            try:
                await col.poll_once(now=0.0)
                await b.stop()
                for i in range(1, 13):
                    await col.poll_once(now=float(i))
                # undamped, 12 polls would mean 12 failed scrapes; the
                # decaying skip schedule probes far less often
                assert col.scrapes_damped > 0
                assert col.scrapes_failed < 12
                assert col.scrapes_failed + col.scrapes_damped + 1 == 13
                # the live replica is still scraped EVERY poll
                assert a.summary_calls == 13
                assert col.errors == 0
            finally:
                await col.stop()
                await a.stop()

        run(go())

    def test_departed_replica_state_is_forgotten(self):
        async def go():
            a = await StubReplica().start()
            b = await StubReplica().start()
            store = _store_for(a, b)
            col = FleetCollector(store, interval_s=1.0, jitter=0.0)
            try:
                await col.poll_once(now=0.0)
                assert len(col._replicas) == 2
                # shrink the deployment to one replica
                store.put(DeploymentRecord(
                    name="dep", oauth_key="dep-k", oauth_secret="s",
                    endpoints=(a.endpoint,),
                ))
                await col.poll_once(now=1.0)
                assert len(col._replicas) == 1
            finally:
                await col.stop()
                await a.stop()
                await b.stop()

        run(go())

    def test_slo_fed_from_polls_and_annotation(self):
        async def go():
            a = await StubReplica(admitted=1000, shed=0).start()
            store = _store_for(
                a, annotations={SLO_ANNOTATION: "shed_rate=0.01"})
            slo = _slo_engine(fast_window_s=30.0, slow_window_s=120.0)
            col = FleetCollector(store, interval_s=10.0, jitter=0.0,
                                 slo_engine=slo)
            try:
                t = 0.0
                for _ in range(4):
                    t += 10.0
                    a.qos["admitted_total"] += 100
                    await col.poll_once(now=t)
                assert col.slo_snapshot()["deployments"]["dep"][
                    "state"] == "ok"
                for _ in range(6):
                    t += 10.0
                    a.qos["admitted_total"] += 50
                    a.qos["shed_total"] += 50
                    await col.poll_once(now=t)
                dep = col.slo_snapshot()["deployments"]["dep"]
                assert dep["state"] == "page"
                assert dep["spec"] == "shed_rate=0.01"
            finally:
                await col.stop()
                await a.stop()

        run(go())

    def test_latency_objective_counts_over_bound_from_merged_hist(self):
        async def go():
            a = await StubReplica(admitted=100).start()
            a.stage_hist = {"ttft": bin_samples([0.001] * 90 + [0.9] * 10)}
            store = _store_for(
                a, annotations={SLO_ANNOTATION: "ttft_p99_ms=250"})
            slo = _slo_engine(fast_window_s=30.0, slow_window_s=120.0)
            col = FleetCollector(store, interval_s=10.0, jitter=0.0,
                                 slo_engine=slo)
            try:
                await col.poll_once(now=10.0)
                a.stage_hist["ttft"] = bin_samples(
                    [0.001] * 90 + [0.9] * 110)
                await col.poll_once(now=20.0)
                dep = col.slo_snapshot()["deployments"]["dep"]
                obj = dep["objectives"]["ttft_p99_ms"]
                # 100 new events, 100 of them over the 250 ms bound:
                # burn = 1.0 / 0.01 -> page band on both windows
                assert obj["state"] == "page"
                assert obj["bad_events"] == 110  # cumulative over-bound
            finally:
                await col.stop()
                await a.stop()

        run(go())

    def test_timeline_fanout_stitches_replicas(self):
        async def go():
            a = await StubReplica().start()
            b = await StubReplica().start()
            a.timeline = [{"trace": "t1", "stage": "prefill", "ms": 5}]
            b.timeline = [{"trace": "t1", "stage": "decode", "ms": 9},
                          {"trace": "t2", "stage": "decode", "ms": 1}]
            col = FleetCollector(_store_for(a, b), interval_s=10.0,
                                 jitter=0.0)
            try:
                out = await col.fan_timeline("t1")
                assert out["queried"] == 2 and out["failed"] == 0
                assert out["legs"] == 2
                stages = {(e["replica"], e["stage"])
                          for e in out["timeline"]}
                assert stages == {(a.endpoint.key, "prefill"),
                                  (b.endpoint.key, "decode")}
                # a dead replica degrades the fan-out, never fails it
                await b.stop()
                out = await col.fan_timeline("t1")
                assert out["failed"] == 1 and out["legs"] == 1
            finally:
                await col.stop()
                await a.stop()

        run(go())

    def test_hung_replica_does_not_block_the_loop(self):
        """The collector shares the control loop with reconcile/watch:
        a replica that accepts and never answers must not stall other
        coroutines for longer than its own scrape timeout."""

        async def go():
            release = asyncio.Event()

            async def hang(reader, writer):
                try:
                    await release.wait()  # accepts, never answers
                finally:
                    # 3.12+: wait_closed() waits for accepted connections
                    writer.close()

            server = await asyncio.start_server(hang, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            store = DeploymentStore()
            store.put(DeploymentRecord(
                name="dep", oauth_key="k", oauth_secret="s",
                endpoints=(Endpoint("127.0.0.1", port, port),),
            ))
            col = FleetCollector(store, interval_s=10.0, jitter=0.0,
                                 timeout_s=1.0)
            try:
                loop = asyncio.get_running_loop()
                poll = loop.create_task(col.poll_once())
                # other control-plane work proceeds while the scrape hangs
                ticks = 0
                t0 = loop.time()
                while not poll.done() and ticks < 1000:
                    await asyncio.sleep(0.01)
                    ticks += 1
                await poll
                assert ticks > 5  # the loop kept turning
                assert loop.time() - t0 < 5.0  # bounded by the timeout
                assert col.scrapes_failed == 1
                assert col.errors == 0
            finally:
                await col.stop()
                release.set()
                server.close()
                await server.wait_closed()

        run(go())


# ---------------------------------------------------------------------------
# kubesim: CR -> watcher -> store -> collector
# ---------------------------------------------------------------------------


class TestKubesimFleetE2E:
    def test_cr_feeds_collector_and_slo_spec_rolls(self):
        from seldon_core_tpu.gateway.watch import CR_KIND, GatewayWatcher
        from seldon_core_tpu.operator.kube_http import HttpKube
        from seldon_core_tpu.testing.kubesim import KubeSim

        def cr(a: StubReplica, b: StubReplica, slo: str) -> dict:
            return {
                "apiVersion": "machinelearning.seldon.io/v1alpha2",
                "kind": CR_KIND,
                "metadata": {
                    "name": "mydep", "namespace": "default",
                    "annotations": {
                        "seldon.io/engine-endpoints":
                            f"127.0.0.1:{a.port},127.0.0.1:{b.port}",
                        SLO_ANNOTATION: slo,
                    },
                },
                "spec": {"name": "mydep", "oauth_key": "mk",
                         "oauth_secret": "ms", "predictors": [
                             {"name": "p", "graph": {
                                 "name": "m", "type": "MODEL",
                                 "implementation": "SIMPLE_MODEL"}}]},
            }

        async def settle(pred, timeout=5.0):
            deadline = asyncio.get_event_loop().time() + timeout
            while asyncio.get_event_loop().time() < deadline:
                if pred():
                    return
                await asyncio.sleep(0.02)
            raise AssertionError("condition never settled")

        def main(sim):
            async def go():
                a = await StubReplica(admitted=10).start()
                b = await StubReplica(admitted=20).start()
                kube = HttpKube(base_url=sim.base_url)
                store = DeploymentStore()
                watcher = GatewayWatcher(kube, store, resync_s=999.0)
                col = FleetCollector(store, interval_s=10.0, jitter=0.0)
                try:
                    await watcher.start()
                    await kube.create(
                        CR_KIND, "default", cr(a, b, "shed_rate=0.01"))
                    await settle(lambda: store.get("mk") is not None)
                    rec = store.get("mk")
                    # the watch carried the SLO annotation onto the record
                    assert rec.annotations[SLO_ANNOTATION] == \
                        "shed_rate=0.01"
                    assert len(rec.replica_endpoints) == 2
                    agg = await col.poll_once(now=10.0)
                    dep = agg["deployments"]["mydep"]
                    assert dep["replicas_live"] == 2
                    assert dep["qos"]["admitted_total"] == 30
                    slo_dep = col.slo_snapshot()["deployments"]["mydep"]
                    assert slo_dep["spec"] == "shed_rate=0.01"
                    # an SLO edit rolls the record (spec-hash) and the
                    # engine picks up the new objectives
                    old_hash = rec.spec_hash
                    await kube.update(
                        CR_KIND, "default",
                        (await kube.get(CR_KIND, "default", "mydep"))
                        | {"metadata": cr(a, b, "shed_rate=0.5")
                           ["metadata"]},
                    )
                    await settle(lambda: store.get("mk") is not None
                                 and store.get("mk").spec_hash != old_hash)
                    await col.poll_once(now=20.0)
                    slo_dep = col.slo_snapshot()["deployments"]["mydep"]
                    assert slo_dep["spec"] == "shed_rate=0.5"
                    # deleting the CR prunes both planes
                    await kube.delete(CR_KIND, "default", "mydep")
                    await settle(lambda: store.get("mk") is None)
                    agg = await col.poll_once(now=30.0)
                    assert agg["deployments"] == {}
                    assert col.slo_snapshot()["deployments"] == {}
                    assert col.errors == 0
                finally:
                    await col.stop()
                    await watcher.stop()
                    await kube.close()
                    await a.stop()
                    await b.stop()

            run(go())

        from seldon_core_tpu.testing.kubesim import KubeSim as _KS
        with _KS() as sim:
            main(sim)


# ---------------------------------------------------------------------------
# engine /stats/summary + both gateway fronts
# ---------------------------------------------------------------------------

SIMPLE = {"name": "p", "graph": {"name": "m", "type": "MODEL",
                                 "implementation": "SIMPLE_MODEL"}}


async def _engine_client() -> TestClient:
    from seldon_core_tpu.engine.app import EngineApp
    from seldon_core_tpu.engine.service import PredictionService
    from seldon_core_tpu.graph.spec import PredictorSpec

    service = PredictionService(PredictorSpec.model_validate(SIMPLE))
    await service.start()
    client = TestClient(TestServer(EngineApp(service).build()))
    await client.start_server()
    return client


class TestEngineSummary:
    def test_summary_bundles_all_four_plus_histograms(self):
        async def go():
            engine = await _engine_client()
            try:
                r = await engine.post(
                    "/api/v0.1/predictions",
                    json={"data": {"ndarray": [[1.0, 2.0]]}})
                assert r.status == 200
                r = await engine.get("/stats/summary")
                assert r.status == 200
                body = await r.json()
                assert set(body) >= {"qos", "breakdown", "cache", "wire",
                                     "stage_hist"}
                assert body["qos"]["admitted_total"] >= 1
                # histograms are full shared-grid vectors with the
                # request's stages recorded
                assert body["stage_hist"]
                for counts in body["stage_hist"].values():
                    assert len(counts) == len(BUCKET_EDGES) + 1
                assert any(sum(c) for c in body["stage_hist"].values())
            finally:
                await engine.close()

        run(go())


def _gateway_store(engine_port: int) -> DeploymentStore:
    store = DeploymentStore()
    store.put(DeploymentRecord(
        name="dep", oauth_key="key1", oauth_secret="sec1",
        engine_host="127.0.0.1", engine_rest_port=engine_port,
    ))
    return store


class TestGatewayFronts:
    def test_aiohttp_front_serves_fleet_slo_timeline(self):
        from seldon_core_tpu.gateway.app import GatewayApp

        async def go():
            engine = await _engine_client()
            gw = GatewayApp(_gateway_store(engine.server.port))
            client = TestClient(TestServer(gw.build()))
            await client.start_server()
            try:
                r = await client.get("/stats/fleet")
                assert r.status == 200
                fleet = (await r.json())["fleet"]
                assert "enabled" in fleet and "deployments" in fleet
                r = await client.get("/stats/slo")
                assert r.status == 200
                assert "deployments" in (await r.json())["slo"]
                r = await client.get("/stats/timeline")
                assert r.status == 400  # trace is required
                # seed the engine's (in-process) ledger, then fan out:
                # the gateway must find the leg over the engine's REST
                # surface, not via shared memory
                trace = "cafe" * 8
                tl = TIMELINE.begin(trace, model="m")
                tl.event("admit")
                tl.end("eos")
                r = await client.get(f"/stats/timeline?trace={trace}")
                assert r.status == 200
                body = await r.json()
                assert body["queried"] == 1 and body["failed"] == 0
                assert body["legs"] >= 1
                assert all(e["deployment"] == "dep"
                           for e in body["timeline"])
            finally:
                await client.close()
                await gw.close()
                await engine.close()

        run(go())

    def test_h1_front_serves_fleet_slo_timeline(self):
        from seldon_core_tpu.gateway.app import GatewayApp
        from seldon_core_tpu.gateway.h1gateway import H1SpliceFrontend

        async def go():
            engine = await _engine_client()
            gw = GatewayApp(_gateway_store(engine.server.port))
            frontend = H1SpliceFrontend(gw)
            port = await frontend.start(0, host="127.0.0.1")
            try:
                async with aiohttp.ClientSession() as s:
                    base = f"http://127.0.0.1:{port}"
                    r = await s.get(f"{base}/stats/fleet")
                    assert r.status == 200
                    assert "deployments" in (await r.json())["fleet"]
                    r = await s.get(f"{base}/stats/slo")
                    assert r.status == 200
                    assert "deployments" in (await r.json())["slo"]
                    r = await s.get(f"{base}/stats/timeline")
                    assert r.status == 400
                    trace = "beef" * 8
                    tl = TIMELINE.begin(trace, model="m")
                    tl.event("admit")
                    tl.end("eos")
                    r = await s.get(f"{base}/stats/timeline?trace={trace}")
                    assert r.status == 200
                    body = await r.json()
                    assert body["queried"] == 1 and body["failed"] == 0
                    assert body["legs"] >= 1
            finally:
                await frontend.stop()
                await gw.close()
                await engine.close()

        run(go())
