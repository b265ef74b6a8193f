"""Hot-path perf gates (docs/PERFORMANCE.md), CPU-safe for CI:

* host-sync audit — steady-state decode must pay ZERO per-token host
  syncs (one fetch per fused k-token block, the overlapped pipeline's
  contract), counted by the PR-3 always-on probe;
* warmup plane — /stats/warmup attributes the readiness tail per unit,
  and a warmed stub engine's p99 stays bounded relative to its p95
  (first-touch compiles must never land on a user request);
* overlap smoke — the overlap actually engages under concurrent load.
"""

from __future__ import annotations

import asyncio
import json

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from seldon_core_tpu.engine.app import EngineApp
from seldon_core_tpu.engine.service import PredictionService
from seldon_core_tpu.executor.generation import (
    GenerationScheduler,
    GenerativeModel,
)
from seldon_core_tpu.graph.spec import PredictorSpec
from seldon_core_tpu.models import llama

run = asyncio.run


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = llama.Config.tiny(max_seq=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestHostSyncAudit:
    """The PR-3 host-sync counter audits the decode loop: syncs per
    generated token must be ~1/decode_block, never ~1."""

    def test_steady_state_decode_has_no_per_token_syncs(self, tiny):
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        block = 8
        max_new = 24
        n_req = 3
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=block, name="sync-audit"
        )
        sched = GenerationScheduler(model, overlap=True)
        before = host_sync_snapshot().get("sync-audit", 0)

        async def go():
            try:
                return await asyncio.gather(
                    *(
                        sched.submit(
                            np.asarray([5 + i, 9, 2], np.int32),
                            max_new_tokens=max_new,
                        )
                        for i in range(n_req)
                    )
                )
            finally:
                await sched.close()

        outs = run(go())
        assert all(o.size == max_new for o in outs)
        syncs = host_sync_snapshot().get("sync-audit", 0) - before
        tokens = n_req * max_new
        # one fetch per fused block (+ slack for the final speculative
        # block and ragged admission rounds) — NOT one per token
        budget = tokens // block + 4
        assert syncs <= budget, f"{syncs} host syncs for {tokens} tokens"
        assert syncs < tokens / 2, "per-token sync pattern detected"
        # the overlap engaged: blocks were dispatched from the device carry
        assert model.overlapped >= 1


    @pytest.mark.parametrize(
        "n_req,eos,chained",
        [(8, False, "chained_early"), (4, True, "chained_late")],
        ids=["full-house-with-waiters", "eos-decides-at-the-fetch"],
    )
    def test_one_sync_a_block_whichever_way_the_boundary_goes(
        self, tiny, n_req, eos, chained
    ):
        """The boundary rule reads only what the one fetch brought: chained
        before the block's tokens are seen (a full house of fixed budgets,
        four more waiting) or once they are in hand (an ``eos_id`` nobody
        samples), a block still costs one host sync."""
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        block, max_new = 8, 25
        name = f"sync-audit-{chained}"

        def serve(model, eos_id):
            sched = GenerationScheduler(model, overlap=True)

            async def go():
                try:
                    return await asyncio.gather(
                        *(
                            sched.submit(
                                np.asarray([5 + i, 9, 2], np.int32),
                                max_new_tokens=max_new, eos_id=eos_id,
                            )
                            for i in range(n_req)
                        )
                    )
                finally:
                    await sched.close()

            return sched, run(go())

        eos_id = None
        if eos:
            _, outs = serve(
                GenerativeModel(cfg, params, n_slots=4, decode_block=block), None
            )
            eos_id = min(set(range(cfg.vocab_size)) - set(np.concatenate(outs).tolist()))
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=block, name=name
        )
        before = host_sync_snapshot().get(name, 0)
        sched, outs = serve(model, eos_id)
        assert all(o.size == max_new for o in outs)
        syncs = host_sync_snapshot().get(name, 0) - before
        blocks = model.steps // block
        assert blocks == (n_req // 4) * 3  # 24 tokens a wave after the prefill's
        # one fetch a block, one first-token fetch an admission round
        assert syncs <= blocks + n_req // 4 + 1, f"{syncs} syncs, {blocks} blocks"
        snap = sched.boundary_snapshot()
        assert snap[chained] == (n_req // 4) * 2, snap
        assert (snap["chained_early"] + snap["chained_due"]
                + snap["chained_late"]) == model.overlapped


    def test_the_boundary_counter_is_in_stats_summary(self):
        """``breakdown.generation.<unit>.block_boundaries`` of
        ``/stats/summary``: every fetched decode block is one boundary, by
        outcome; the chained ones are ``overlapped_blocks``."""
        predictor = {
            "name": "p",
            "graph": {
                "name": "gen", "type": "MODEL",
                "implementation": "JAX_GENERATIVE",
                "parameters": [
                    {"name": "family", "value": "llama", "type": "STRING"},
                    {"name": "preset", "value": "tiny", "type": "STRING"},
                    {"name": "n_slots", "value": "2", "type": "INT"},
                    {"name": "decode_block", "value": "4", "type": "INT"},
                ],
            },
        }

        async def go():
            service = PredictionService(PredictorSpec.model_validate(predictor))
            client = TestClient(TestServer(EngineApp(service).build()))
            await client.start_server()
            try:
                async def post(i):
                    resp = await client.post(
                        "/api/v0.1/predictions",
                        json={"strData": json.dumps(
                            {"tokens": [5 + i, 9, 2], "max_new_tokens": 14})},
                    )
                    assert resp.status == 200, await resp.text()

                await asyncio.gather(*(post(i) for i in range(4)))
                stats = await (await client.get("/stats/summary")).json()
                (unit,) = stats["breakdown"]["generation"].values()
                (gen,) = service.generative_units()
                return unit["block_boundaries"], gen.model
            finally:
                await client.close()

        found, model = run(go())
        chained = ("chained_early", "chained_due", "chained_late")
        assert set(found) == {*chained, "idle", "sync"}
        total = (sum(found[c] for c in chained)
                 + found["idle"] + sum(found["sync"].values()))
        # 13 tokens after the prefill's: four blocks of 4 a request, two
        # requests a block at the most (warm-up's steps are in
        # ``model.steps`` too)
        assert 8 <= total <= model.steps // 4
        assert sum(found[c] for c in chained) == model.overlapped
        # four requests on two slots: somebody waited, and was admitted at
        # a boundary that names it
        assert found["sync"].get("admission", 0) >= 1
        assert set(found["sync"]) <= {"admission", "carry-dirty"}


class TestWarmupPlane:
    JAX_PREDICTOR = {
        "name": "warm",
        "graph": {
            "name": "m",
            "type": "MODEL",
            "implementation": "JAX_MODEL",
            "parameters": [
                {"name": "family", "value": "mlp", "type": "STRING"},
                {"name": "preset", "value": "tiny", "type": "STRING"},
            ],
        },
    }

    def test_stats_warmup_attributes_the_readiness_tail(self):
        """GET /stats/warmup reports per-unit programs + seconds once
        readiness flips — the attribution for a slow warm start — and the
        device the programs were compiled for, with the count of XLA
        compiles since readiness (a warmed server shows zero)."""

        async def go():
            service = PredictionService(
                PredictorSpec.model_validate(self.JAX_PREDICTOR)
            )
            app = EngineApp(service).build()
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                deadline = asyncio.get_event_loop().time() + 120
                while asyncio.get_event_loop().time() < deadline:
                    if (await client.get("/ready")).status == 200:
                        break
                    await asyncio.sleep(0.1)
                resp = await client.get("/stats/warmup")
                assert resp.status == 200
                snap = (await resp.json())["warmup"]
                assert snap["warmed"] is True
                assert snap["error"] is None
                model = service.walker.root.client.component.model
                assert snap["programs"]["m"] == len(model.buckets.sizes)
                assert snap["seconds"]["m"] > 0
                assert snap["total_seconds"] >= snap["seconds"]["m"] * 0.5
                resp = await client.post(
                    "/api/v0.1/predictions",
                    json={"data": {"ndarray": [[0.0] * 16]}},
                )
                assert resp.status == 200
                dev = (await (await client.get("/stats/warmup")).json())[
                    "warmup"]["device"]
                assert dev["platform"] == "cpu"
                assert dev["device_kind"] == jax.devices()[0].device_kind
                assert dev["device_count"] == len(jax.devices())
                assert isinstance(dev["native_codec"], bool)
                assert dev["xla_compiles"] >= snap["programs"]["m"]
                assert dev["xla_compiles_since_ready"] == 0
            finally:
                await client.close()

        run(go())

    def test_warm_start_p99_bound_on_stub_graph(self):
        """After readiness, a stub graph's tail must be queueing noise,
        not compile spikes: p99 bounded by max(2x p95, p95 + 25ms, 30ms)
        over a short in-process load burst (floors absorb shared-CI
        scheduler jitter; a first-touch compile is 100x the floor)."""
        import time

        async def go():
            service = PredictionService(
                PredictorSpec.model_validate(
                    {"name": "p", "graph": {"name": "m", "type": "MODEL",
                                            "implementation": "SIMPLE_MODEL"}}
                )
            )
            app = EngineApp(service).build()
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                assert (await client.get("/ready")).status == 200
                body = {"data": {"ndarray": [[1.0, 2.0, 3.0]]}}
                lat: list[float] = []

                async def one():
                    t0 = time.perf_counter()
                    resp = await client.post("/api/v0.1/predictions", json=body)
                    assert resp.status == 200
                    await resp.read()
                    lat.append(time.perf_counter() - t0)

                # small warm trickle, then the measured burst
                for _ in range(5):
                    await one()
                lat.clear()
                for _ in range(30):
                    await asyncio.gather(*(one() for _ in range(8)))
                lat.sort()
                p95 = lat[int(len(lat) * 0.95) - 1] * 1e3
                p99 = lat[int(len(lat) * 0.99) - 1] * 1e3
                bound = max(2 * p95, p95 + 25.0, 30.0)
                assert p99 <= bound, f"p99 {p99:.1f}ms > bound {bound:.1f}ms (p95 {p95:.1f}ms)"
            finally:
                await client.close()

        run(go())


class TestOverlapConfig:
    def test_env_kill_switch_disables_overlap(self, tiny, monkeypatch):
        cfg, params = tiny
        monkeypatch.setenv("SCT_GEN_OVERLAP", "0")
        model = GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        sched = GenerationScheduler(model)
        assert sched.overlap is False

        async def go():
            try:
                return await sched.submit(
                    np.asarray([5, 9, 2], np.int32), max_new_tokens=8
                )
            finally:
                await sched.close()

        out = run(go())
        assert out.size == 8
        assert model.overlapped == 0

    def test_decode_block_one_never_overlaps(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(cfg, params, n_slots=2, decode_block=1)
        sched = GenerationScheduler(model, overlap=True)
        assert sched.overlap is False
