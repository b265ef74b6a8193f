"""Elastic pool autoscaler (autoscale/policy.py, autoscale/reconciler.py).

Unit layers: the ``seldon.io/autoscale`` annotation grammar + admission
validation, the per-pool policy state machine on synthetic time
(hysteresis, dwell, slope lookahead, freshness decay), and signal
extraction off the fleet collector's merged aggregates.  Integration
layers: the reconciler actuating against a FakeKube (pool-mode endpoint
growth, drain-based shrink, aborted shrink on a failed drain), the
idempotent ``POST /admin/drain`` race semantics over a real generative
engine, and the kubesim diurnal e2e — load triples and ebbs, one
unified pool goes 1 -> N -> 1 with zero dropped streams, and role-typed
prefill/decode pools move INDEPENDENTLY (a TTFT surge scales only
prefill, an ITL surge only decode)."""

import asyncio
import json

import pytest
from aiohttp import web

from seldon_core_tpu.autoscale.policy import (
    AUTOSCALE_ANNOTATION,
    ROLE_SIGNALS,
    SIGNAL_KEYS,
    AutoscaleError,
    PoolPolicy,
    extract_signals,
    extract_slopes,
    parse_autoscale,
    pool_role,
)
from seldon_core_tpu.autoscale.reconciler import (
    ENDPOINTS_ANNOTATION,
    POOL_ANNOTATION,
    AutoscaleReconciler,
)
from seldon_core_tpu.gateway.store import (
    DeploymentRecord,
    DeploymentStore,
    Endpoint,
    EndpointDiff,
)
from seldon_core_tpu.obs.history import History, bin_samples
from seldon_core_tpu.operator.kube import FakeKube

run = asyncio.run


# ---------------------------------------------------------------------------
# annotation grammar
# ---------------------------------------------------------------------------


class TestGrammar:
    def test_full_spec_round_trips(self):
        spec = parse_autoscale(
            "min=2,max=6,ttft_p99_ms=250,itl_p99_ms=40,occupancy=0.85"
        )
        assert spec.min_replicas == 2 and spec.max_replicas == 6
        assert spec.target_map == {
            "ttft_p99_ms": 250.0, "itl_p99_ms": 40.0, "occupancy": 0.85,
        }
        # spec_str is canonical: re-parsing it is a fixed point
        assert parse_autoscale(spec.spec_str()) == spec

    def test_defaults_and_whitespace(self):
        spec = parse_autoscale(" queue_wait_ms = 500 , ")
        assert spec.min_replicas == 1 and spec.max_replicas == 8
        assert spec.target_map == {"queue_wait_ms": 500.0}

    @pytest.mark.parametrize("bad", [
        "min=1,max=8",                      # no signal targets
        "",                                  # empty
        "min=0,max=8,occupancy=0.8",        # min=0: drain needs a peer
        "min=4,max=2,occupancy=0.8",        # max < min
        "min=1,max=1000,occupancy=0.8",     # above the sanity cap
        "occupancy=0.8,occupancy=0.9",      # duplicate key
        "min=1,min=2,occupancy=0.8",        # duplicate bound
        "occupancy=1.5",                     # ratio out of (0, 1]
        "shed_rate=0",                       # ratio out of (0, 1]
        "ttft_p99_ms=0",                     # ms must be > 0
        "ttft_p99_ms=-5",                    # ms must be > 0
        "warp_factor=9",                     # unknown key
        "occupancy",                         # not key=value
        "min=fast,occupancy=0.8",           # non-integer bound
        "occupancy=hot",                     # non-numeric target
    ])
    def test_malformed_specs_rejected(self, bad):
        with pytest.raises(AutoscaleError):
            parse_autoscale(bad)

    def test_role_signal_families_cover_every_key(self):
        assert set(ROLE_SIGNALS["unified"]) == set(SIGNAL_KEYS)
        assert set(ROLE_SIGNALS["prefill"]) | set(ROLE_SIGNALS["decode"]) \
            == set(SIGNAL_KEYS)

    def test_pool_role_parsing(self):
        assert pool_role(None) == "unified"
        assert pool_role({}) == "unified"
        assert pool_role({"seldon.io/engine-role": " Prefill "}) == "prefill"
        assert pool_role({"seldon.io/engine-role": "decode"}) == "decode"
        assert pool_role({"seldon.io/engine-role": "warp"}) == "unified"

    def test_role_with_no_declared_target_rejected(self):
        # a decode pool whose spec only declares prefill signals would
        # never move — that's a config error, not a silent hold
        spec = parse_autoscale("min=1,max=4,ttft_p99_ms=250")
        with pytest.raises(AutoscaleError):
            PoolPolicy(spec, "decode")


class TestAdmission:
    def _cr(self, annotation=None):
        from seldon_core_tpu.operator.crd import SeldonDeployment

        meta = {"name": "mydep", "namespace": "default"}
        if annotation is not None:
            meta["annotations"] = {AUTOSCALE_ANNOTATION: annotation}
        return SeldonDeployment.from_dict({
            "metadata": meta,
            "spec": {
                "name": "mydep", "oauth_key": "k", "oauth_secret": "s",
                "predictors": [{
                    "name": "p1",
                    "graph": {"name": "m", "type": "MODEL",
                              "implementation": "SIMPLE_MODEL"},
                }],
            },
        })

    def test_valid_annotation_admitted(self):
        from seldon_core_tpu.operator.defaulting import defaulting, validate

        validate(defaulting(self._cr("min=1,max=4,occupancy=0.8")))

    def test_malformed_annotation_rejected_by_name(self):
        from seldon_core_tpu.operator.defaulting import (
            ValidationError, defaulting, validate,
        )

        with pytest.raises(ValidationError) as exc:
            validate(defaulting(self._cr("min=0,occupancy=2")))
        assert AUTOSCALE_ANNOTATION in str(exc.value)

    def test_absent_annotation_is_fine(self):
        from seldon_core_tpu.operator.defaulting import defaulting, validate

        validate(defaulting(self._cr(None)))


# ---------------------------------------------------------------------------
# policy state machine on synthetic time
# ---------------------------------------------------------------------------


def _policy(spec="min=1,max=8,queue_wait_ms=100", role="unified", **kw):
    defaults = dict(
        ewma_alpha=1.0, up_at=1.0, down_at=0.5, up_hold_s=60.0,
        down_hold_s=120.0, lookahead_s=60.0, max_step=2, stale_s=90.0,
    )
    defaults.update(kw)
    return PoolPolicy(parse_autoscale(spec), role, **defaults)


class TestPolicyStateMachine:
    def test_oscillation_inside_the_band_holds(self):
        p = _policy()
        # pressure bouncing between down_at and up_at: never moves
        for i, qw in enumerate([60.0, 95.0, 55.0, 99.0, 70.0]):
            now = float(i * 15)
            p.observe({"queue_wait_ms": qw}, now)
            d = p.decide(4, now)
            assert (d.direction, d.reason) == ("hold", "in-band"), (qw, d)

    def test_pressure_crossing_scales_up_with_proportional_step(self):
        p = _policy()
        p.observe({"queue_wait_ms": 150.0}, 0.0)
        d = p.decide(4, 0.0)
        # pressure 1.5: step = min(max_step, ceil(4 * 0.5)) = 2
        assert (d.direction, d.target, d.reason) == ("up", 6, "pressure")
        assert d.pressure == pytest.approx(1.5)

    def test_up_dwell_then_release(self):
        p = _policy()
        p.observe({"queue_wait_ms": 200.0}, 0.0)
        assert p.decide(2, 0.0).direction == "up"
        p.observe({"queue_wait_ms": 200.0}, 30.0)
        d = p.decide(4, 30.0)
        assert (d.direction, d.reason) == ("hold", "up-hold")
        p.observe({"queue_wait_ms": 200.0}, 61.0)
        assert p.decide(4, 61.0).direction == "up"

    def test_down_dwells_after_any_decision_then_steps_by_one(self):
        p = _policy()
        p.observe({"queue_wait_ms": 200.0}, 0.0)
        assert p.decide(2, 0.0).direction == "up"
        # idle immediately after the up: shrink dwells off the UP stamp
        p.observe({"queue_wait_ms": 10.0}, 30.0)
        d = p.decide(4, 30.0)
        assert (d.direction, d.reason) == ("hold", "down-hold")
        p.observe({"queue_wait_ms": 10.0}, 121.0)
        d = p.decide(4, 121.0)
        # shrink is drain-based: always one replica at a time
        assert (d.direction, d.target, d.reason) == ("down", 3, "idle")
        # and the next shrink dwells off the DOWN stamp
        p.observe({"queue_wait_ms": 10.0}, 180.0)
        assert p.decide(3, 180.0).reason == "down-hold"
        p.observe({"queue_wait_ms": 10.0}, 242.0)
        assert p.decide(3, 242.0).direction == "down"

    def test_at_max_and_at_min_hold(self):
        p = _policy(spec="min=2,max=4,queue_wait_ms=100")
        p.observe({"queue_wait_ms": 500.0}, 0.0)
        assert p.decide(4, 0.0).reason == "at-max"
        p.observe({"queue_wait_ms": 1.0}, 200.0)
        assert p.decide(2, 200.0).reason == "at-min"

    def test_bounds_bypass_signals_entirely(self):
        p = _policy(spec="min=2,max=4,queue_wait_ms=100")
        # no observations at all: bounds still actuate
        d = p.decide(1, 0.0)
        assert (d.direction, d.target, d.reason) == ("up", 2, "below-min-bound")
        d = p.decide(9, 500.0)
        assert (d.direction, d.target, d.reason) == ("down", 8, "above-max-bound")

    def test_slope_lookahead_fires_before_the_target_is_crossed(self):
        p = _policy()
        # 80 ms now (pressure 0.8, in-band) but ramping 1 ms/s: the
        # 60 s projection crosses the 100 ms target -> scale up EARLY
        p.observe({"queue_wait_ms": 80.0}, 0.0)
        d = p.decide(2, 0.0, slopes={"queue_wait_ms": 1.0})
        assert (d.direction, d.reason) == ("up", "slope-lookahead")
        assert d.signals["queue_wait_ms"]["projected"] == pytest.approx(1.4)

    def test_negative_slope_never_projects(self):
        p = _policy()
        p.observe({"queue_wait_ms": 80.0}, 0.0)
        d = p.decide(2, 0.0, slopes={"queue_wait_ms": -5.0})
        assert (d.direction, d.reason) == ("hold", "in-band")

    def test_none_observations_decay_to_a_hold(self):
        p = _policy()
        p.observe({"queue_wait_ms": 500.0}, 0.0)
        # counter dips / missing polls report None: they never refresh
        for t in (15.0, 30.0, 45.0):
            p.observe({"queue_wait_ms": None}, t)
        # within stale_s the last real sample still drives a decision
        assert p.decide(2, 45.0).direction == "up"
        # ... but past it the pool HOLDS instead of guessing
        d = p.decide(2, 200.0)
        assert (d.direction, d.reason) == ("hold", "no-fresh-signals")

    def test_ewma_smooths_a_single_spike(self):
        p = _policy(ewma_alpha=0.2)
        p.observe({"queue_wait_ms": 50.0}, 0.0)
        # one wild poll moves the EWMA to 50 + 0.2*(500-50) = 140...
        p.observe({"queue_wait_ms": 500.0}, 15.0)
        # ...but a policy with alpha low enough rides it out
        p2 = _policy(ewma_alpha=0.05)
        p2.observe({"queue_wait_ms": 50.0}, 0.0)
        p2.observe({"queue_wait_ms": 500.0}, 15.0)
        assert p2.decide(2, 15.0).direction == "hold"
        assert p.decide(2, 15.0).direction == "up"

    def test_role_filters_signals(self):
        spec = "min=1,max=8,ttft_p99_ms=100,itl_p99_ms=100,occupancy=0.8"
        pf = _policy(spec=spec, role="prefill")
        # an ITL surge is a DECODE signal: the prefill policy ignores it
        pf.observe({"ttft_p99_ms": 20.0, "itl_p99_ms": 900.0}, 0.0)
        assert pf.decide(2, 0.0).direction in ("hold", "down")
        de = _policy(spec=spec, role="decode")
        de.observe({"ttft_p99_ms": 900.0, "itl_p99_ms": 150.0}, 0.0)
        d = de.decide(2, 0.0)
        assert d.direction == "up"
        assert "ttft_p99_ms" not in d.signals

    def test_snapshot_carries_state(self):
        p = _policy()
        p.observe({"queue_wait_ms": 150.0}, 5.0)
        p.decide(2, 5.0)
        snap = p.snapshot()
        assert snap["role"] == "unified"
        assert snap["ewma"]["queue_wait_ms"] == pytest.approx(150.0)
        assert snap["last_up"] == 5.0 and snap["decisions"] == 1


# ---------------------------------------------------------------------------
# signal extraction off collector aggregates
# ---------------------------------------------------------------------------


class TestSignalExtraction:
    def test_windowed_p99_preferred_lifetime_fallback(self):
        dep = {"latency": {
            "ttft": {"p99_ms": 900.0, "win_p99_ms": 120.0},
            "itl": {"p99_ms": 33.0},  # no window yet: first poll
        }}
        sig = extract_signals("d", dep, window_s=60.0)
        assert sig["ttft_p99_ms"] == 120.0
        assert sig["itl_p99_ms"] == 33.0

    def test_occupancy_is_fleet_inflight_over_fleet_capacity(self):
        dep = {
            "replicas_live": 3,
            "qos": {"inflight": {"mean": 16.0},
                    "max_inflight": {"sum": 192}},
        }
        sig = extract_signals("d", dep, window_s=60.0)
        assert sig["occupancy"] == pytest.approx(48.0 / 192.0)
        # zero capacity (no live scrape) never divides
        assert extract_signals("d", {"replicas_live": 0, "qos": {}},
                               window_s=60.0)["occupancy"] is None

    def test_queue_wait_from_merged_ewma(self):
        dep = {"qos": {"queue_wait_ewma_ms": {"mean": 42.0, "max": 90.0}}}
        assert extract_signals("d", dep, window_s=60.0)[
            "queue_wait_ms"] == 42.0

    def test_shed_rate_windowed_and_dip_tolerant(self):
        h = History()
        for t, adm, shed in [(0.0, 100, 0), (30.0, 190, 10)]:
            h.record("d.admitted_total", adm, now=t)
            h.record("d.shed_total", shed, now=t)
        sig = extract_signals("d", {}, history=h, now=30.0, window_s=60.0)
        # 90 admitted + 10 shed over the window
        assert sig["shed_rate"] == pytest.approx(0.1)
        # a replica leaving rewinds the fleet sum: the dip reads as None,
        # never as a load change
        h.record("d.admitted_total", 40, now=60.0)
        h.record("d.shed_total", 12, now=60.0)
        sig = extract_signals("d", {}, history=h, now=60.0, window_s=60.0)
        assert sig["shed_rate"] is None

    def test_slopes_come_off_the_history_rings(self):
        h = History()
        for i in range(5):
            h.record("d.queue_wait_ms", 10.0 + 2.0 * i * 10.0, now=i * 10.0)
        slopes = extract_slopes("d", h, now=40.0, window_s=60.0)
        assert slopes["queue_wait_ms"] == pytest.approx(2.0, rel=0.2)
        assert slopes["ttft_p99_ms"] is None  # no such metric recorded


# ---------------------------------------------------------------------------
# endpoint diff (satellite: warm state survives scale events)
# ---------------------------------------------------------------------------


def _rec(name, *eps, **kw):
    return DeploymentRecord(
        name=name, oauth_key=f"{name}-k", oauth_secret="s",
        endpoints=tuple(Endpoint.parse(e) for e in eps), **kw)


class TestEndpointDiff:
    def test_update_reports_only_departed_replicas(self):
        d = EndpointDiff()
        assert d.removed("added", _rec("d", "a:1", "b:2")) == set()
        gone = d.removed("updated", _rec("d", "a:1", "c:3"))
        assert gone == {"b:2"}

    def test_removal_reports_the_whole_set(self):
        d = EndpointDiff()
        d.removed("added", _rec("d", "a:1", "b:2"))
        assert d.removed("removed", _rec("d", "a:1", "b:2")) == {"a:1", "b:2"}
        # and the tracking entry is gone: a re-add starts fresh
        assert d.removed("added", _rec("d", "a:1")) == set()

    def test_spec_change_detection(self):
        d = EndpointDiff()
        r1 = _rec("d", "a:1")
        assert d.spec_changed("added", r1) is True  # first sight flushes
        assert d.spec_changed("updated", r1) is False  # same hash: keep cache
        r2 = _rec("d", "a:1", annotations={"seldon.io/slo": "shed_rate=0.1"})
        assert r1.spec_hash != r2.spec_hash
        assert d.spec_changed("updated", r2) is True

    def test_seed_primes_pre_listener_records(self):
        d = EndpointDiff()
        d.seed([_rec("d", "a:1", "b:2")])
        assert d.removed("updated", _rec("d", "a:1")) == {"b:2"}
        assert d.spec_changed("updated", _rec("d", "a:1")) is True


# ---------------------------------------------------------------------------
# reconciler actuation against a FakeKube
# ---------------------------------------------------------------------------


class _FakeCollector:
    """The three surfaces the reconciler reads: merged aggregate, history
    rings, per-replica scrape payloads."""

    def __init__(self):
        self._agg = {"deployments": {}}
        self.history = History()
        self._replicas = {}

    def set_queue_wait(self, name, ms):
        self._agg["deployments"][name] = {
            "qos": {"queue_wait_ewma_ms": {"mean": ms}},
            "latency": {},
        }

    def set_digests(self, name, ep_key, hashes):
        self._replicas[(name, ep_key)] = {"payload": {"cache": {"prefix": {
            "gen": {"digest": {"hashes": list(hashes)}},
        }}}}


def _cr_obj(name="dep", endpoints="", pool=None, scale="min=1,max=8,queue_wait_ms=100"):
    ann = {AUTOSCALE_ANNOTATION: scale}
    if endpoints:
        ann[ENDPOINTS_ANNOTATION] = endpoints
    if pool:
        ann[POOL_ANNOTATION] = pool
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha2",
        "kind": "SeldonDeployment",
        "metadata": {"name": name, "namespace": "default",
                     "annotations": ann},
        "spec": {"name": name, "oauth_key": f"{name}-k",
                 "oauth_secret": "s",
                 "predictors": [{"name": "p", "graph": {
                     "name": "m", "type": "MODEL",
                     "implementation": "SIMPLE_MODEL"}}]},
    }


class _Ctl:
    def __init__(self):
        self.replica_overrides = {}


class TestReconciler:
    def _fixture(self, *eps, pool=None, scale="min=1,max=8,queue_wait_ms=100"):
        kube = FakeKube()
        store = DeploymentStore()
        ann = {AUTOSCALE_ANNOTATION: scale}
        if pool:
            ann[POOL_ANNOTATION] = pool
        store.put(_rec("dep", *eps, annotations=ann))
        col = _FakeCollector()
        ctl = _Ctl()
        rx = AutoscaleReconciler(
            kube, store, col, controller=ctl, drain_timeout_s=2.0,
            policy_overrides=dict(
                ewma_alpha=1.0, up_at=1.0, down_at=0.5, up_hold_s=0.0,
                down_hold_s=0.0, lookahead_s=0.0, max_step=2, stale_s=1e9,
            ),
        )
        return kube, store, col, ctl, rx

    def test_pool_scale_up_appends_youngest_last(self):
        kube, store, col, ctl, rx = self._fixture(
            "10.0.0.1:9000", pool="10.0.0.1:9000,10.0.0.2:9000,10.0.0.3:9000")

        async def go():
            await kube.create("SeldonDeployment", "default", _cr_obj(
                endpoints="10.0.0.1:9000",
                pool="10.0.0.1:9000,10.0.0.2:9000,10.0.0.3:9000"))
            await kube.create("Deployment", "default", {
                "metadata": {"name": "dep-p-engine", "namespace": "default"},
                "spec": {"replicas": 1}})
            col.set_queue_wait("dep", 500.0)  # pressure 5
            await rx.reconcile_once(now=100.0)
            cr = await kube.get("SeldonDeployment", "default", "dep")
            eps = cr["metadata"]["annotations"][ENDPOINTS_ANNOTATION]
            # pressure 5 at 1 replica: step clamps to max_step=2 -> 3,
            # live entry keeps slot 0, growth appends in pool order
            assert eps == "10.0.0.1:9000,10.0.0.2:9000,10.0.0.3:9000"
            wl = await kube.get("Deployment", "default", "dep-p-engine")
            assert wl["spec"]["replicas"] == 3
            assert ctl.replica_overrides["dep-p-engine"] == 3
            assert rx.scale_ups == 1 and rx.errors == 0
            assert rx.ledger[-1]["direction"] == "up"
            assert rx.ledger[-1]["outcome"] == "ok"
            snap = rx.snapshot()
            assert snap["deployments"]["dep"]["last"]["target"] == 3

        run(go())

    def test_exhausted_pool_reports_instead_of_scaling(self):
        kube, store, col, ctl, rx = self._fixture(
            "10.0.0.1:9000", pool="10.0.0.1:9000")

        async def go():
            await kube.create("SeldonDeployment", "default", _cr_obj(
                endpoints="10.0.0.1:9000", pool="10.0.0.1:9000"))
            col.set_queue_wait("dep", 500.0)
            await rx.reconcile_once(now=100.0)
            assert rx.scale_ups == 0
            assert rx.snapshot()["deployments"]["dep"]["last"][
                "reason"] == "pool-exhausted"

        run(go())

    def test_victim_is_coldest_then_youngest_peer_is_warmest(self):
        _, _, col, _, rx = self._fixture("a:1", "b:2", "c:3")
        col.set_digests("dep", "a:1", ["h1", "h2", "h3"])
        col.set_digests("dep", "b:2", ["h1"])
        col.set_digests("dep", "c:3", ["h4"])
        rec = rx.store.get("dep-k")
        victim, peer, counts = rx._pick_victim_and_peer(rec)
        # b and c tie at 1 digest: the YOUNGER (higher index) drains
        assert victim.key == "c:3"
        assert peer.key == "a:1"  # warmest survivor absorbs the streams
        assert counts == {"a:1": 3, "b:2": 1, "c:3": 1}

    def test_drain_failure_aborts_the_shrink(self):
        async def go():
            refusals = []

            async def refuse(request):
                refusals.append(await request.json())
                return web.json_response({"migrated": 0, "failed": 1},
                                         status=200)

            app = web.Application()
            app.router.add_post("/admin/drain", refuse)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            try:
                kube, store, col, ctl, rx = self._fixture(
                    f"127.0.0.1:{port}", "10.9.9.9:9000")
                await kube.create("SeldonDeployment", "default", _cr_obj(
                    endpoints=f"127.0.0.1:{port},10.9.9.9:9000"))
                # make the live stub the victim: zero digests, youngest
                col.set_digests("dep", "10.9.9.9:9000", ["h1"])
                store.put(_rec(
                    "dep", "10.9.9.9:9000", f"127.0.0.1:{port}",
                    annotations={AUTOSCALE_ANNOTATION:
                                 "min=1,max=8,queue_wait_ms=100"}))
                col.set_queue_wait("dep", 10.0)  # idle: pressure 0.1
                await rx.reconcile_once(now=100.0)
                # the drain refused: the victim keeps serving, nothing
                # was patched, and the ledger records the abort
                assert rx.drain_failures == 1 and rx.scale_downs == 0
                assert refusals and refusals[0]["peer"] == "10.9.9.9:9000"
                cr = await kube.get("SeldonDeployment", "default", "dep")
                assert "10.9.9.9" in cr["metadata"]["annotations"][
                    ENDPOINTS_ANNOTATION]
                assert rx.ledger[-1]["outcome"] == "drain-failed"
            finally:
                await rx.stop()
                await runner.cleanup()

        run(go())

    def test_unreachable_victim_aborts_the_shrink(self):
        async def go():
            kube, store, col, ctl, rx = self._fixture(
                "127.0.0.1:1", "127.0.0.1:2")  # nothing listens there
            await kube.create("SeldonDeployment", "default", _cr_obj(
                endpoints="127.0.0.1:1,127.0.0.1:2"))
            col.set_queue_wait("dep", 10.0)
            await rx.reconcile_once(now=100.0)
            assert rx.drain_failures == 1 and rx.scale_downs == 0
            assert rx.ledger[-1]["drain"]["status"] == 0
            await rx.stop()

        run(go())

    def test_ledger_ring_is_bounded(self):
        kube = FakeKube()
        rx = AutoscaleReconciler(
            kube, DeploymentStore(), _FakeCollector(), ledger_size=4)
        for i in range(10):
            rx._ledger_entry({"i": i})
        assert [e["i"] for e in rx.ledger] == [6, 7, 8, 9]

    def test_malformed_default_spec_surfaces_not_raises(self):
        kube, store, col, ctl, rx = self._fixture(
            "a:1", scale="min=0,warp=9")

        async def go():
            await rx.reconcile_once(now=1.0)
            assert rx.errors == 0
            assert "error" in rx.snapshot()["deployments"]["dep"]["last"]

        run(go())

    def test_departed_deployment_prunes_policy_state(self):
        kube, store, col, ctl, rx = self._fixture("a:1")

        async def go():
            col.set_queue_wait("dep", 500.0)
            await rx.reconcile_once(now=1.0)
            assert "dep" in rx._policies
            store.remove("dep-k")
            await rx.reconcile_once(now=2.0)
            assert rx._policies == {}

        run(go())


# ---------------------------------------------------------------------------
# idempotent POST /admin/drain over a live generative engine
# ---------------------------------------------------------------------------


PREDICTOR = {
    "name": "llm",
    "graph": {
        "name": "gen",
        "type": "MODEL",
        "implementation": "JAX_GENERATIVE",
        "parameters": [
            {"name": "family", "value": "llama", "type": "STRING"},
            {"name": "preset", "value": "tiny", "type": "STRING"},
            {"name": "n_slots", "value": "2", "type": "INT"},
        ],
    },
}


class TestDrainIdempotency:
    # boots real generative engines (one JAX compile each) — excluded from
    # the tier-1 `-m 'not slow'` sweep
    pytestmark = pytest.mark.slow

    def test_repeat_drain_conflicts_with_state_undrain_races_refused(self):
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        async def go():
            service = PredictionService(
                PredictorSpec.model_validate(PREDICTOR))
            engine = EngineApp(service)
            client = TestClient(TestServer(engine.build()))
            await client.start_server()
            try:
                for _ in range(600):
                    if (await client.get("/ready")).status == 200:
                        break
                    await asyncio.sleep(0.05)
                (unit,) = service.generative_units()
                sched = unit.scheduler

                # gate the quiesce so the drain stays observably in-flight
                gate = asyncio.Event()
                entered = asyncio.Event()
                orig = sched.drain_wait_quiesced

                async def gated(timeout_s):
                    entered.set()
                    await gate.wait()
                    return await orig(timeout_s)

                sched.drain_wait_quiesced = gated
                first = asyncio.ensure_future(
                    client.post("/admin/drain", json={}))
                await asyncio.wait_for(entered.wait(), 10)

                # a REPEAT while in flight answers 409 with the live
                # phase — the reconciler's retry reads progress, not a
                # bare refusal
                r = await client.post("/admin/drain", json={})
                assert r.status == 409
                body = await r.json()
                assert body["drain"]["phase"] == "quiescing"
                assert "elapsed_ms" in body["drain"]

                # undrain mid-quiesce is REFUSED: lifting it here would
                # fork streams a peer may already be continuing
                r = await client.post("/admin/undrain")
                assert r.status == 409
                assert "in flight" in (await r.json())["status"]["info"]

                gate.set()
                resp = await asyncio.wait_for(first, 30)
                assert resp.status == 200
                out = await resp.json()
                assert out["quiesced"] is True and out["peer"] is None

                # the no-peer drain PARKS: a repeat still conflicts, now
                # reporting the parked phase
                r = await client.post("/admin/drain", json={})
                assert r.status == 409
                assert (await r.json())["drain"]["phase"] == "parked"

                # ... and THIS is the state undrain exists for
                sched.drain_wait_quiesced = orig
                r = await client.post("/admin/undrain")
                assert r.status == 200
                assert (await r.json())["resuming"] is True

                # fully lifted: a fresh drain cycle works again
                r = await client.post("/admin/drain", json={})
                assert r.status == 200
                r = await client.post("/admin/undrain")
                assert r.status == 200

                # nothing draining: undrain is a 409, not a silent no-op
                r = await client.post("/admin/undrain")
                assert r.status == 409
            finally:
                await client.close()

        run(go())

    def test_idle_engine_drains_immediately(self):
        """An idle victim (no run loop alive) must quiesce at once, not
        sit out the full timeout — the autoscaler's common shrink case."""
        import time

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        async def go():
            service = PredictionService(
                PredictorSpec.model_validate(PREDICTOR))
            engine = EngineApp(service)
            client = TestClient(TestServer(engine.build()))
            await client.start_server()
            try:
                for _ in range(600):
                    if (await client.get("/ready")).status == 200:
                        break
                    await asyncio.sleep(0.05)
                t0 = time.perf_counter()
                r = await client.post("/admin/drain",
                                      json={"timeout_s": 30})
                took = time.perf_counter() - t0
                assert r.status == 200
                assert (await r.json())["quiesced"] is True
                assert took < 5.0, f"idle drain took {took:.1f}s"
                r = await client.post("/admin/undrain")
                assert r.status == 200
            finally:
                await client.close()

        run(go())

    def test_scheduler_level_drain_still_undrainable(self):
        """A drain begun OUTSIDE the HTTP handler (chaos harness, tests)
        has no handler state; undrain must still lift it."""
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        async def go():
            service = PredictionService(
                PredictorSpec.model_validate(PREDICTOR))
            engine = EngineApp(service)
            client = TestClient(TestServer(engine.build()))
            await client.start_server()
            try:
                for _ in range(600):
                    if (await client.get("/ready")).status == 200:
                        break
                    await asyncio.sleep(0.05)
                (unit,) = service.generative_units()
                unit.scheduler.drain_begin()
                # the handler synthesizes a parked view for the repeat...
                r = await client.post("/admin/drain", json={})
                assert r.status == 409
                assert (await r.json())["drain"]["phase"] == "parked"
                # ...and undrain lifts it
                r = await client.post("/admin/undrain")
                assert r.status == 200
            finally:
                await client.close()

        run(go())


class TestSchedulerLoopTurnover:
    # boots a real generative model — slow-marked like TestDrainIdempotency
    pytestmark = pytest.mark.slow

    def test_component_survives_short_lived_event_loops(self):
        """A component driven through several ``asyncio.run`` loops (CLI
        tools, the loadtest harness, per-call test helpers) must not crash
        at close: the scheduler's run-loop task is respawned per loop, and
        its wake event must bind to the CURRENT loop — a stale event from
        a dead loop makes the idle park raise a cross-loop RuntimeError
        that ``close()`` then re-raises."""
        import jax

        from seldon_core_tpu.contract.payload import DataKind, Payload
        from seldon_core_tpu.executor.generation import (
            GenerativeComponent,
            GenerativeModel,
        )
        from seldon_core_tpu.models import llama

        cfg = llama.Config.tiny(max_seq=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        comp = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4),
            max_new_tokens=4,
        )
        payload = Payload(
            json.dumps({"tokens": [5, 9, 2]}), [], DataKind.STRING, None
        )

        async def ask_and_idle():
            out = json.loads((await comp.predict_raw(payload)).data)["tokens"]
            # spin enough turns for the run loop to reach its fully-idle
            # park on THIS loop before asyncio.run tears the loop down —
            # the park is where a stale cross-loop event would kill it
            for _ in range(200):
                await asyncio.sleep(0)
            return out

        first = asyncio.run(ask_and_idle())
        second = asyncio.run(ask_and_idle())
        assert first == second  # greedy decode is loop-agnostic
        asyncio.run(comp.close())


# ---------------------------------------------------------------------------
# kubesim e2e: the diurnal day and role independence
# ---------------------------------------------------------------------------


class ElasticStub:
    """A fake engine replica for the autoscale loop: mutable qos + stage
    histograms on ``/stats/summary`` and a recording ``/admin/drain``."""

    def __init__(self):
        self.qos = {
            "admitted_total": 0, "shed_total": 0,
            "deadline_miss_total": 0, "queue_wait_ewma_ms": 1.0,
            "inflight": 0, "predicted_completion_ms": 1.0,
            "max_inflight": 64, "max_queue": 128,
            "shed_by_reason": {}, "brownout": {"active": False},
        }
        self.stage_hist = {}
        self.drain_calls = []
        self.runner = None
        self.port = None

    async def start(self):
        app = web.Application()
        app.router.add_get("/stats/summary", self._summary)
        app.router.add_post("/admin/drain", self._drain)
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
        return web.json_response({
            "qos": self.qos, "breakdown": {}, "cache": {},
            "wire": {}, "stage_hist": self.stage_hist,
        })

    async def _drain(self, request):
        self.drain_calls.append(await request.json())
        return web.json_response(
            {"quiesced": True, "migrated": 1, "failed": 0, "parked": 0})

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.port}"


_FAST_POLICY = dict(
    ewma_alpha=1.0, up_at=1.0, down_at=0.5, up_hold_s=0.0,
    down_hold_s=0.0, lookahead_s=0.0, max_step=2, stale_s=1e9,
)


async def _settle(pred, timeout=5.0):
    deadline = asyncio.get_event_loop().time() + timeout
    while asyncio.get_event_loop().time() < deadline:
        if pred():
            return
        await asyncio.sleep(0.02)
    raise AssertionError("condition never settled")


def _elastic_cr(name, endpoints, pool, scale, role=None):
    from seldon_core_tpu.gateway.watch import CR_KIND

    ann = {
        ENDPOINTS_ANNOTATION: endpoints,
        POOL_ANNOTATION: pool,
        AUTOSCALE_ANNOTATION: scale,
    }
    if role:
        ann["seldon.io/engine-role"] = role
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha2",
        "kind": CR_KIND,
        "metadata": {"name": name, "namespace": "default",
                     "annotations": ann},
        "spec": {"name": name, "oauth_key": f"{name}-k",
                 "oauth_secret": "s",
                 "predictors": [{"name": "p", "graph": {
                     "name": "m", "type": "MODEL",
                     "implementation": "SIMPLE_MODEL"}}]},
    }


class TestKubesimElasticE2E:
    def test_diurnal_day_one_to_n_to_one_zero_drops(self):
        """Load triples, the pool follows it up 1 -> 3, the ebb drains
        it back 3 -> 2 -> 1 — every shrink preceded by a successful
        drain (zero dropped streams) and the response-cache-bearing
        spec hash NEVER rolling across any scale event."""
        from seldon_core_tpu.gateway.watch import CR_KIND, GatewayWatcher
        from seldon_core_tpu.obs.fleet import FleetCollector
        from seldon_core_tpu.operator.kube_http import HttpKube
        from seldon_core_tpu.testing.kubesim import KubeSim

        async def go(sim):
            stubs = [await ElasticStub().start() for _ in range(3)]
            kube = HttpKube(base_url=sim.base_url)
            store = DeploymentStore()
            watcher = GatewayWatcher(kube, store, resync_s=999.0)
            col = FleetCollector(store, interval_s=10.0, jitter=0.0)
            rx = AutoscaleReconciler(
                kube, store, col, drain_timeout_s=5.0,
                policy_overrides=_FAST_POLICY)
            try:
                await watcher.start()
                pool = ",".join(s.addr for s in stubs)
                await kube.create(CR_KIND, "default", _elastic_cr(
                    "elastic", stubs[0].addr, pool,
                    "min=1,max=3,queue_wait_ms=100"))
                await _settle(lambda: store.get("elastic-k") is not None)
                hash0 = store.get("elastic-k").spec_hash

                # --- morning surge: queue wait triples past the target
                for s in stubs:
                    s.qos["queue_wait_ewma_ms"] = 500.0
                await col.poll_once(now=10.0)
                await rx.reconcile_once(now=10.0)
                await _settle(lambda: len(
                    store.get("elastic-k").replica_endpoints) == 3)
                rec = store.get("elastic-k")
                # growth appended pool order: youngest is LAST
                assert [e.key for e in rec.replica_endpoints] == \
                    [s.addr for s in stubs]
                assert rec.spec_hash == hash0  # cache survives the grow
                assert rx.scale_ups == 1

                # --- at max, pressure still high: hold, not thrash
                await col.poll_once(now=20.0)
                await rx.reconcile_once(now=20.0)
                assert rx.snapshot()["deployments"]["elastic"]["last"][
                    "reason"] == "at-max"

                # --- evening ebb: two drain-based shrinks back to 1
                for s in stubs:
                    s.qos["queue_wait_ewma_ms"] = 10.0
                await col.poll_once(now=30.0)
                await rx.reconcile_once(now=30.0)
                await _settle(lambda: len(
                    store.get("elastic-k").replica_endpoints) == 2)
                await col.poll_once(now=40.0)
                await rx.reconcile_once(now=40.0)
                await _settle(lambda: len(
                    store.get("elastic-k").replica_endpoints) == 1)

                rec = store.get("elastic-k")
                assert rec.spec_hash == hash0  # ...and both shrinks
                assert rx.scale_downs == 2 and rx.drain_failures == 0
                # zero dropped streams: every departed replica was
                # drained exactly once, toward a surviving peer
                survivors = {e.key for e in rec.replica_endpoints}
                drained = [s for s in stubs if s.addr not in survivors]
                assert len(drained) == 2
                for s in drained:
                    assert len(s.drain_calls) == 1
                    assert s.drain_calls[0]["peer"] in \
                        {x.addr for x in stubs} - {s.addr}
                # the survivor never saw a drain
                (kept,) = [s for s in stubs if s.addr in survivors]
                assert kept.drain_calls == []
                # steady state: a further tick holds at min
                await col.poll_once(now=50.0)
                await rx.reconcile_once(now=50.0)
                assert rx.snapshot()["deployments"]["elastic"]["last"][
                    "reason"] == "at-min"
                # the ledger tells the whole day's story
                dirs = [e["direction"] for e in rx.ledger]
                assert dirs == ["up", "down", "down"]
            finally:
                await rx.stop()
                await col.stop()
                await watcher.stop()
                await kube.close()
                for s in stubs:
                    await s.stop()

        from seldon_core_tpu.testing.kubesim import KubeSim as _KS
        with _KS() as sim:
            run(go(sim))

    def test_roles_scale_independently(self):
        """A TTFT surge moves the PREFILL pool and leaves decode flat;
        an ITL surge then moves only DECODE."""
        from seldon_core_tpu.gateway.watch import CR_KIND, GatewayWatcher
        from seldon_core_tpu.obs.fleet import FleetCollector
        from seldon_core_tpu.operator.kube_http import HttpKube
        from seldon_core_tpu.testing.kubesim import KubeSim

        def _count(store, key):
            rec = store.get(key)
            return len(rec.replica_endpoints) if rec else 0

        async def go(sim):
            pf = [await ElasticStub().start() for _ in range(2)]
            de = [await ElasticStub().start() for _ in range(2)]
            kube = HttpKube(base_url=sim.base_url)
            store = DeploymentStore()
            watcher = GatewayWatcher(kube, store, resync_s=999.0)
            col = FleetCollector(store, interval_s=10.0, jitter=0.0)
            rx = AutoscaleReconciler(
                kube, store, col, drain_timeout_s=5.0,
                policy_overrides=_FAST_POLICY)
            try:
                await watcher.start()
                await kube.create(CR_KIND, "default", _elastic_cr(
                    "pf", pf[0].addr, ",".join(s.addr for s in pf),
                    "min=1,max=2,ttft_p99_ms=250", role="prefill"))
                await kube.create(CR_KIND, "default", _elastic_cr(
                    "de", de[0].addr, ",".join(s.addr for s in de),
                    "min=1,max=2,itl_p99_ms=40", role="decode"))
                await _settle(lambda: store.get("pf-k") is not None
                              and store.get("de-k") is not None)

                # both stages healthy on the first poll (establishes the
                # window baseline), then TTFT surges on the second
                pf[0].stage_hist = {"ttft": bin_samples([0.1] * 50)}
                de[0].stage_hist = {"itl": bin_samples([0.005] * 50)}
                await col.poll_once(now=10.0)
                pf[0].stage_hist = {
                    "ttft": bin_samples([0.1] * 50 + [0.6] * 200)}
                de[0].stage_hist = {"itl": bin_samples([0.005] * 100)}
                await col.poll_once(now=20.0)
                await rx.reconcile_once(now=20.0)
                await _settle(lambda: _count(store, "pf-k") == 2)
                # ITL stayed flat: decode did NOT move
                assert _count(store, "de-k") == 1
                assert rx.scale_ups == 1

                # vice versa: TTFT cools into the band, ITL surges
                pf[0].stage_hist = {
                    "ttft": bin_samples([0.1] * 50 + [0.6] * 200
                                        + [0.2] * 400)}
                pf[1].stage_hist = {"ttft": bin_samples([0.2] * 400)}
                de[0].stage_hist = {
                    "itl": bin_samples([0.005] * 100 + [0.1] * 200)}
                await col.poll_once(now=30.0)
                await rx.reconcile_once(now=30.0)
                await _settle(lambda: _count(store, "de-k") == 2)
                # the prefill pool held: in-band TTFT is not a reason
                # to move in either direction
                assert _count(store, "pf-k") == 2
                assert rx.drain_failures == 0
            finally:
                await rx.stop()
                await col.stop()
                await watcher.stop()
                await kube.close()
                for s in pf + de:
                    await s.stop()

        from seldon_core_tpu.testing.kubesim import KubeSim as _KS
        with _KS() as sim:
            run(go(sim))


# ---------------------------------------------------------------------------
# the gateway surface: /stats/autoscale
# ---------------------------------------------------------------------------


class TestStatsSurface:
    def test_disabled_gateway_reports_disabled(self):
        from seldon_core_tpu.gateway.app import GatewayApp

        async def go():
            from aiohttp.test_utils import TestClient, TestServer

            store = DeploymentStore()
            gw = GatewayApp(store)
            client = TestClient(TestServer(gw.build()))
            await client.start_server()
            try:
                resp = await client.get("/stats/autoscale")
                assert resp.status == 200
                body = await resp.json()
                assert body["autoscale"] == {"enabled": False}
            finally:
                await client.close()

        run(go())

    def test_wired_reconciler_snapshot_served(self):
        from seldon_core_tpu.gateway.app import GatewayApp

        async def go():
            from aiohttp.test_utils import TestClient, TestServer

            store = DeploymentStore()
            gw = GatewayApp(store)
            gw.autoscaler = AutoscaleReconciler(
                FakeKube(), store, _FakeCollector(), ledger_size=8)
            gw.autoscaler._ledger_entry({"direction": "up"})
            client = TestClient(TestServer(gw.build()))
            await client.start_server()
            try:
                body = await (await client.get("/stats/autoscale")).json()
                assert body["autoscale"]["enabled"] is True
                assert body["autoscale"]["ledger"] == [{"direction": "up"}]
            finally:
                await client.close()

        run(go())
