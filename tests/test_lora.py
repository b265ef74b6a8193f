"""Batched multi-LoRA serving gates (docs/MULTITENANT.md), CPU-safe:

* **pinned-equal null adapter** — a lora-enabled build serving the null
  adapter is bit-identical to a lora-off build: plain greedy, seeded
  top-k, overlapped, spec-on, chunked prefill, KV prefix reuse, int8 KV,
  tp=2 sharded mesh, and across a disagg KV handoff;
* **per-slot gather** — a mixed-adapter batch emits, per slot, exactly
  what a single-adapter run of that slot's adapter emits;
* **adapter-tagged prefix chains** — adapter-A KV blocks never serve
  adapter-B (or the base model), and the gateway-side chain hashes fold
  the adapter exactly like the engine's salted index;
* **adapter pool** — LRU eviction under pressure, refcount pinning,
  unknown-adapter rejection;
* **HBM memory manager** — admission-time byte reservation with
  ``adapter_pool`` in the class ledger, enforcement on over-commit;
* **handoff codec v4** — the adapter rides the frame; a decode pool
  missing it rejects (sender falls back to unified);
* **program cache-key audit** — ``(lora_rank, lora_slots)`` folded into
  every compiled-program key; warmup labels carry the ``[loraR]`` tag;
* **host-sync audit** — adapters must not reintroduce per-token host
  syncs: still <= 1 per fused block;
* **traffic split** — the existing RandomABTest machinery routing between
  two adapter ids of one base deployment, asserted over the per-adapter
  token ledger and the timeline ledger.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.cache.prefix import PrefixIndex, adapter_salt, chain_hash
from seldon_core_tpu.disagg.handoff import (
    HANDOFF_VERSION,
    HandoffError,
    apply_handoff,
    build_handoff_frame,
    decode_handoff,
)
from seldon_core_tpu.disagg.router import (
    extract_prompt_request,
    prompt_chain_hashes,
)
from seldon_core_tpu.executor.generation import (
    GenerationScheduler,
    GenerativeComponent,
    GenerativeModel,
)
from seldon_core_tpu.executor.lora import AdapterPool, AdapterPoolFull
from seldon_core_tpu.executor.memory import HBMOverCommit, MemoryManager
from seldon_core_tpu.graph.units import GraphUnitError
from seldon_core_tpu.models import llama

run = asyncio.run


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = llama.Config.tiny(max_seq=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


PROMPTS = [
    [5, 9, 2, 17, 3],
    [30, 7],
    [1, 2, 3, 4],
    [11, 13, 17, 19, 23],
]

LORA_KW = dict(lora_rank=2, lora_slots=4, lora_adapters="alpha,beta")


def _generate(
    cfg, params, prompts, *, adapters=None, max_new=9, temperature=0.0,
    seed=123, n_slots=4, decode_block=4, **kw
):
    model = GenerativeModel(
        cfg, params, n_slots=n_slots, decode_block=decode_block, **kw
    )
    sched = GenerationScheduler(model)
    sched._seed = seed

    async def go():
        try:
            return await asyncio.gather(
                *(
                    sched.submit(
                        np.asarray(p, np.int32),
                        max_new_tokens=max_new,
                        temperature=temperature,
                        adapter=(adapters[i] if adapters else None),
                    )
                    for i, p in enumerate(prompts)
                )
            )
        finally:
            await sched.close()

    return run(go()), model


class TestNullAdapterPinnedEqual:
    """A lora-enabled deployment whose requests name no adapter must be a
    pure capacity feature: bit-identical outputs to a lora-off build."""

    def test_plain_greedy(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        null, model = _generate(cfg, params, PROMPTS, **LORA_KW)
        for p, a, b in zip(PROMPTS, base, null):
            assert np.array_equal(a, b), (p, a.tolist(), b.tolist())
        assert model.lora_rank == 2

    def test_seeded_topk_sampled(self, tiny):
        cfg, params = tiny
        base, _ = _generate(
            cfg, params, PROMPTS, temperature=0.8, seed=7, top_k=4
        )
        null, _ = _generate(
            cfg, params, PROMPTS, temperature=0.8, seed=7, top_k=4, **LORA_KW
        )
        for a, b in zip(base, null):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    def test_spec_on(self, tiny):
        cfg, params = tiny
        rep = np.tile([3, 7, 11], 8).astype(np.int32)
        base, _ = _generate(cfg, params, [rep], max_new=16, spec_draft=3)
        null, model = _generate(
            cfg, params, [rep], max_new=16, spec_draft=3, **LORA_KW
        )
        assert np.array_equal(base[0], null[0])
        assert model.spec_verify_passes > 0

    def test_chunked_prefill(self, tiny):
        cfg, params = tiny
        long_prompt = np.arange(1, 40, dtype=np.int32)
        base, _ = _generate(
            cfg, params, [long_prompt] + PROMPTS[:2], prefill_chunk=16
        )
        null, model = _generate(
            cfg, params, [long_prompt] + PROMPTS[:2], prefill_chunk=16,
            **LORA_KW,
        )
        for a, b in zip(base, null):
            assert np.array_equal(a, b)

    def test_prefix_reuse(self, tiny):
        cfg, params = tiny
        prefix = list(range(7, 39))  # 2 full 16-token blocks
        prompts = [prefix + [40 + i, 41 + i] for i in range(3)]
        kw = dict(kv_block_size=16, prefix_reuse=True)
        base, _ = _generate(cfg, params, prompts, n_slots=2, **kw)
        null, model = _generate(
            cfg, params, prompts, n_slots=2, **kw, **LORA_KW
        )
        for a, b in zip(base, null):
            assert np.array_equal(a, b)
        assert model.prefills_reused > 0  # reuse actually engaged

    def test_int8_kv(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS, kv_cache_dtype="int8")
        null, _ = _generate(
            cfg, params, PROMPTS, kv_cache_dtype="int8", **LORA_KW
        )
        for a, b in zip(base, null):
            assert np.array_equal(a, b)

    def test_tp2_sharded_mesh(self, tiny):
        from seldon_core_tpu.parallel import best_mesh

        cfg, params = tiny
        mesh = best_mesh(2, tp=2)

        def gen(**kw):
            return _generate(
                cfg, params, PROMPTS, max_new=8, mesh=mesh,
                param_axes=llama.param_logical_axes(params), **kw
            )[0]

        base = gen()
        null = gen(**LORA_KW)
        for a, b in zip(base, null):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    def test_disagg_handoff_null_adapter(self, tiny):
        cfg, params = tiny
        prompt = np.asarray(PROMPTS[0], np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=9)

        model_a = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, **LORA_KW
        )
        model_b = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, **LORA_KW
        )
        sched_a = GenerationScheduler(model_a)
        sched_b = GenerationScheduler(model_b)

        async def go():
            try:
                slot, tok1 = await sched_a.submit_prefill(prompt)
                frame = build_handoff_frame(
                    model_a, slot, prompt, tok1, max_new_tokens=9
                )
                sched_a.release_external(slot)
                payload = decode_handoff(frame)
                return await sched_b.submit_imported(
                    payload["prompt"],
                    first_token=payload["first_token"],
                    k=payload["k"],
                    v=payload["v"],
                    max_new_tokens=9,
                )
            finally:
                await sched_a.close()
                await sched_b.close()

        got = run(go())
        np.testing.assert_array_equal(got, base[0])


class TestMixedAdapterBatch:
    """The per-slot gather: one fused program serves a heterogeneous
    batch, and each row's output matches its adapter's solo run."""

    def test_mixed_batch_matches_solo_runs(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        mixed, _ = _generate(
            cfg, params, PROMPTS, adapters=["alpha", None, "beta", None],
            **LORA_KW,
        )
        solo_alpha, _ = _generate(
            cfg, params, PROMPTS, adapters=["alpha"] * 4, **LORA_KW
        )
        solo_beta, _ = _generate(
            cfg, params, PROMPTS, adapters=["beta"] * 4, **LORA_KW
        )
        assert np.array_equal(mixed[0], solo_alpha[0])
        assert np.array_equal(mixed[2], solo_beta[2])
        assert np.array_equal(mixed[1], base[1])
        assert np.array_equal(mixed[3], base[3])
        # distinct adapters actually produce distinct generations
        assert not np.array_equal(mixed[0], base[0])
        assert not np.array_equal(mixed[2], base[2])

    def test_unknown_adapter_is_client_error(self, tiny):
        cfg, params = tiny
        with pytest.raises(GraphUnitError, match="not resident"):
            _generate(
                cfg, params, [PROMPTS[0]], adapters=["missing"], **LORA_KW
            )

    def test_adapter_without_lora_build_is_client_error(self, tiny):
        cfg, params = tiny
        with pytest.raises(GraphUnitError, match="without multi-LoRA"):
            _generate(cfg, params, [PROMPTS[0]], adapters=["alpha"])

    def test_per_adapter_token_ledger(self, tiny):
        cfg, params = tiny
        _, model = _generate(
            cfg, params, PROMPTS, adapters=["alpha", "alpha", "beta", None],
            max_new=8, **LORA_KW,
        )
        snap = model.adapters_snapshot()
        assert snap["resident"] == 2
        assert snap["bytes"] > 0
        # prefill emits the first token, decode blocks deliver the rest
        assert snap["adapters"]["alpha"]["tokens"] == 2 * 7
        assert snap["adapters"]["beta"]["tokens"] == 7
        # all slots released at completion
        assert all(a["slots"] == 0 for a in snap["adapters"].values())


class TestAdapterPrefixIsolation:
    """LoRA changes K/V: adapter-tagged chains must never cross."""

    def _reuse_model(self, cfg, params):
        return GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, kv_block_size=16,
            prefix_reuse=True, **LORA_KW,
        )

    def _run(self, model, prompts, adapters, seed=3):
        sched = GenerationScheduler(model)
        sched._seed = seed

        async def go():
            try:
                return await asyncio.gather(
                    *(
                        sched.submit(
                            np.asarray(p, np.int32), max_new_tokens=4,
                            adapter=a,
                        )
                        for p, a in zip(prompts, adapters)
                    )
                )
            finally:
                await sched.close()

        return run(go())

    def test_chains_never_cross_adapters(self, tiny):
        cfg, params = tiny
        prompt = list(range(7, 39)) + [50]  # 2 full blocks + suffix
        model = self._reuse_model(cfg, params)
        self._run(model, [prompt], ["alpha"])
        assert model.prefills_reused == 0
        # same prompt, same adapter: the chain is reused
        self._run(model, [prompt], ["alpha"])
        assert model.prefills_reused == 1
        # same prompt, DIFFERENT adapter (and base): no reuse
        self._run(model, [prompt], ["beta"])
        assert model.prefills_reused == 1
        self._run(model, [prompt], [None])
        assert model.prefills_reused == 1
        # and the base-model chain now exists independently
        self._run(model, [prompt], [None])
        assert model.prefills_reused == 2

    def test_salted_index_and_gateway_hashes_agree(self):
        idx = PrefixIndex(4)
        tokens = np.arange(1, 13, dtype=np.int32)
        salt = adapter_salt("billing")
        idx.insert(tokens, [10, 11, 12], 0, salt=salt)
        digest = idx.digest()
        want = prompt_chain_hashes(tokens, 4, adapter="billing")
        assert digest["hashes"] == want[::-1] or set(digest["hashes"]) == set(
            want
        )
        # unsalted hashes differ chain-by-chain
        base = prompt_chain_hashes(tokens, 4)
        assert set(base).isdisjoint(set(want))
        # and match/release honor the salt
        assert idx.match(tokens, 3) == []
        assert idx.match(tokens, 3, salt=salt) == [10, 11, 12]
        idx.release(tokens, 3, salt=salt)

    def test_router_prefix_pick_folds_adapter(self):
        """The gateway /stats/route machinery: a replica holding
        adapter-salted chains only prefix-attracts requests carrying THAT
        adapter — base-model (or other-adapter) requests fall back to
        load routing instead of landing on KV they cannot use."""
        import random

        from seldon_core_tpu.gateway.store import Endpoint
        from seldon_core_tpu.disagg.router import ReplicaRouter

        router = ReplicaRouter(rng=random.Random(7))
        eps = (Endpoint("warm", 8000), Endpoint("cold", 8000))
        sys_prompt = np.arange(1000, 1064, dtype=np.int32)
        router.update_replica(
            "dep", "warm:8000",
            hashes=prompt_chain_hashes(sys_prompt, 16, adapter="billing"),
            block_size=16,
        )
        router.update_replica("dep", "cold:8000", hashes=(), block_size=16)
        hits = sum(
            router.pick("dep", eps, sys_prompt, "billing").host == "warm"
            for _ in range(20)
        )
        assert hits == 20 and router.prefix_picks == 20
        # same prompt WITHOUT the adapter: no prefix match
        router.pick("dep", eps, sys_prompt, None)
        router.pick("dep", eps, sys_prompt, "support")
        assert router.prefix_picks == 20

    def test_adapter_salt_shape(self):
        assert adapter_salt(None) == b""
        assert adapter_salt("") == b""
        assert adapter_salt("x") == b"x\x00"

    def test_extract_prompt_request_reads_adapter(self):
        import json

        raw = json.dumps({"tokens": [1, 2, 3], "adapter": "billing"}).encode()
        toks, adapter = extract_prompt_request(raw)
        np.testing.assert_array_equal(toks, [1, 2, 3])
        assert adapter == "billing"
        raw = json.dumps(
            {"strData": json.dumps({"tokens": [4, 5]})}
        ).encode()
        toks, adapter = extract_prompt_request(raw)
        np.testing.assert_array_equal(toks, [4, 5])
        assert adapter is None


class TestAdapterPool:
    def _pool(self, n=4, writes=None):
        writes = writes if writes is not None else []
        return AdapterPool(
            n, 2, writer=lambda idx, fac: writes.append((idx, fac))
        ), writes

    def test_register_assigns_rows_and_writes(self):
        pool, writes = self._pool()
        assert pool.register("a", "fa") == 1
        assert pool.register("b", "fb") == 2
        assert pool.register("a", "fa2") == 1  # refresh keeps the row
        assert [w[0] for w in writes] == [1, 2, 1]
        assert "a" in pool and "c" not in pool

    def test_lru_eviction_under_pressure(self):
        pool, _ = self._pool(n=3)  # capacity 2 named rows
        pool.register("a", None)
        pool.register("b", None)
        pool.acquire("a")  # touch a (and pin it)
        pool.release_ref(1)
        # b is now LRU; c takes its row
        idx = pool.register("c", None)
        assert idx == 2
        assert "b" not in pool and pool.evictions == 1

    def test_pool_full_when_all_referenced(self):
        pool, _ = self._pool(n=3)
        pool.register("a", None)
        pool.register("b", None)
        pool.acquire("a")
        pool.acquire("b")
        with pytest.raises(AdapterPoolFull):
            pool.register("c", None)
        pool.release_ref(1)
        pool.register("c", None)  # now the idle row evicts

    def test_null_row_reserved(self):
        pool, _ = self._pool()
        assert pool.capacity == 3
        assert pool.name_of(0) is None


class TestMemoryManager:
    def test_ledger_reserve_release(self):
        mm = MemoryManager(budget_bytes=1000, enforce=True)
        mm.reserve("m1", {"weights": 400, "kv_pool": 300})
        assert mm.reserved_bytes == 700
        assert mm.headroom_bytes() == 300
        mm.release("m1")
        assert mm.reserved_bytes == 0

    def test_overcommit_raises_when_enforcing(self):
        mm = MemoryManager(budget_bytes=1000, enforce=True)
        mm.reserve("m1", {"weights": 800})
        with pytest.raises(HBMOverCommit):
            mm.reserve("m2", {"weights": 300})
        # the failed reservation left nothing behind
        assert mm.reserved_bytes == 800
        # re-reserving the same owner replaces, never double-counts
        mm.reserve("m1", {"weights": 900})
        assert mm.reserved_bytes == 900

    def test_non_enforcing_records_overcommit(self):
        mm = MemoryManager(budget_bytes=100, enforce=False)
        mm.reserve("m1", {"weights": 800})
        assert mm.reserved_bytes == 800
        assert mm.rejections == 1

    def test_model_reserves_all_classes(self, tiny):
        cfg, params = tiny
        mm = MemoryManager(budget_bytes=1 << 30, enforce=True)
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, memory=mm,
            kv_cache_dtype="int8", **LORA_KW,
        )
        by_class = mm.snapshot()["by_class"]
        assert by_class["weights"] == model.param_bytes
        assert by_class["adapter_pool"] == model.lora_bytes > 0
        assert by_class["kv_pool"] > 0
        assert by_class["kv_scales"] > 0
        # the pool ledger on /stats/breakdown carries the same classes
        snap = model.pool_snapshot()
        assert snap["bytes"]["adapter_pool"] == model.lora_bytes
        assert snap["hbm"]["reserved_bytes"] == mm.reserved_bytes
        model.release_memory()
        assert mm.reserved_bytes == 0

    def test_second_deployment_rejected_at_build(self, tiny):
        cfg, params = tiny
        mm = MemoryManager(budget_bytes=800_000, enforce=True)
        m1 = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, memory=mm, name="dep-a"
        )
        with pytest.raises(HBMOverCommit):
            GenerativeModel(
                cfg, params, n_slots=2, decode_block=2, memory=mm,
                name="dep-b",
            )
        m1.release_memory()


class TestHandoffAdapter:
    def _prefill_frame(self, tiny, adapter):
        cfg, params = tiny
        model_a = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, **LORA_KW
        )
        sched_a = GenerationScheduler(model_a)
        prompt = np.asarray(PROMPTS[0], np.int32)

        async def go():
            try:
                slot, tok1 = await sched_a.submit_prefill(
                    prompt, adapter=adapter
                )
                frame = build_handoff_frame(
                    model_a, slot, prompt, tok1, max_new_tokens=9,
                    adapter=adapter,
                )
                sched_a.release_external(slot)
                return frame
            finally:
                await sched_a.close()

        return prompt, run(go())

    def test_frame_carries_adapter_v4(self, tiny):
        prompt, frame = self._prefill_frame(tiny, "alpha")
        payload = decode_handoff(frame)
        assert payload["hv"] == HANDOFF_VERSION == 5
        assert payload["adapter"] == "alpha"

    def test_decode_pool_miss_rejects(self, tiny):
        cfg, params = tiny
        _, frame = self._prefill_frame(tiny, "alpha")
        payload = decode_handoff(frame)
        # decode pool with a different resident set: must reject
        comp = GenerativeComponent(
            GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, lora_rank=2,
                lora_slots=4, lora_adapters="other",
            )
        )

        async def go():
            try:
                with pytest.raises(HandoffError, match="not resident"):
                    await apply_handoff(comp, payload)
            finally:
                await comp.close()

        run(go())

    def test_lora_off_decode_pool_rejects(self, tiny):
        cfg, params = tiny
        _, frame = self._prefill_frame(tiny, "alpha")
        payload = decode_handoff(frame)
        comp = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        )

        async def go():
            try:
                with pytest.raises(HandoffError, match="not resident"):
                    await apply_handoff(comp, payload)
            finally:
                await comp.close()

        run(go())

    def test_adapter_handoff_pinned_equal_to_unified(self, tiny):
        cfg, params = tiny
        unified, _ = _generate(
            cfg, params, [PROMPTS[0]], adapters=["alpha"], **LORA_KW
        )
        _, frame = self._prefill_frame(tiny, "alpha")
        payload = decode_handoff(frame)
        comp = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4, **LORA_KW)
        )

        async def go():
            try:
                return await apply_handoff(comp, payload)
            finally:
                await comp.close()

        got = run(go())
        np.testing.assert_array_equal(got, unified[0])


class TestProgramKeyAudit:
    def test_program_config_folds_lora_geometry(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, top_k=3, **LORA_KW
        )
        assert model._program_config[-3:-1] == (2, 4)
        off = GenerativeModel(cfg, params, n_slots=2, decode_block=2, top_k=3)
        assert off._program_config[-3:-1] == (0, 0)
        assert model._program_config != off._program_config

    def test_decode_k_keys_fold_lora(self, tiny):
        cfg, params = tiny
        _, model = _generate(cfg, params, [PROMPTS[0]], **LORA_KW)
        assert model._decode_k_jit
        for key in model._decode_k_jit:
            assert key[2:] == model._program_config, key

    def test_warmup_labels_carry_lora_tag(self, tiny):
        cfg, params = tiny
        comp = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4, **LORA_KW)
        )
        n = comp.warmup()
        variants = comp.warmup_variants()
        assert len(variants) == n
        assert any(
            v.startswith("decode_k:") and "[lora2]" in v for v in variants
        )
        assert any(
            v.startswith("prefill:") and "[lora2]" in v for v in variants
        )
        run(comp.close())


class TestHostSyncAudit:
    def test_sync_audit_with_adapters_on(self, tiny):
        """Adapter gathers must stay on-device: still <= 1 host sync per
        fused block (the PR-5 overlapped-pipeline bar)."""
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        block, max_new, n_req = 8, 24, 3
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=block,
            name="lora-sync-audit", **LORA_KW,
        )
        sched = GenerationScheduler(model, overlap=True)
        before = host_sync_snapshot().get("lora-sync-audit", 0)

        async def go():
            try:
                return await asyncio.gather(
                    *(
                        sched.submit(
                            np.asarray([5 + i, 9, 2], np.int32),
                            max_new_tokens=max_new,
                            adapter=["alpha", "beta", None][i],
                        )
                        for i in range(n_req)
                    )
                )
            finally:
                await sched.close()

        outs = run(go())
        assert all(o.size == max_new for o in outs)
        syncs = host_sync_snapshot().get("lora-sync-audit", 0) - before
        tokens = n_req * max_new
        budget = tokens // block + 4
        assert syncs <= budget, f"{syncs} host syncs for {tokens} tokens"


class TestTrafficSplit:
    def test_random_abtest_splits_between_adapters(self, tiny):
        """SURVEY §2 rows 58-59 machinery on one base deployment: the
        seeded RandomABTest router picks which ADAPTER each request
        decodes through; the split lands in the per-adapter token ledger
        and every request's timeline admit event names its adapter."""
        from seldon_core_tpu.graph.units import RandomABTest
        from seldon_core_tpu.obs import TIMELINE
        from seldon_core_tpu.utils.tracectx import (
            new_traceparent,
            parse_traceparent,
            set_traceparent,
        )

        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=4, name="lora-ab", **LORA_KW
        )
        sched = GenerationScheduler(model)
        ab = RandomABTest(ratioA=0.5, seed=1337)
        n_req = 24
        arms = [
            ["alpha", "beta"][ab.route(np.zeros((1, 1)), [])]
            for _ in range(n_req)
        ]
        tids = []

        async def one(i):
            tp = new_traceparent()
            tids.append((parse_traceparent(tp)[0], arms[i]))
            set_traceparent(tp)
            return await sched.submit(
                np.asarray([3 + i % 5, 9, 2], np.int32), max_new_tokens=5,
                adapter=arms[i],
            )

        async def go():
            try:
                return await asyncio.gather(*(one(i) for i in range(n_req)))
            finally:
                await sched.close()

        outs = run(go())
        assert all(o.size == 5 for o in outs)
        snap = model.adapters_snapshot()["adapters"]
        served_a = arms.count("alpha")
        served_b = arms.count("beta")
        assert served_a > 0 and served_b > 0  # seeded split hits both arms
        # ledger tokens = decode-delivered tokens (prefill emits the first)
        assert snap["alpha"]["tokens"] == served_a * 4
        assert snap["beta"]["tokens"] == served_b * 4
        # timeline: every request's admit event names its adapter
        for tid, arm in tids:
            entries = TIMELINE.by_trace(tid)
            assert entries, tid
            admits = [
                e
                for ent in entries
                for e in ent["events"]
                if e["name"] == "admit"
            ]
            assert admits and all(
                e["attrs"].get("adapter") == arm for e in admits
            )


class TestComponentContract:
    def test_strdata_adapter_field_and_default(self, tiny):
        cfg, params = tiny
        import json

        comp = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4, **LORA_KW),
            max_new_tokens=6,
            adapter="alpha",
        )
        base = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4),
            max_new_tokens=6,
        )
        from seldon_core_tpu.contract.payload import DataKind, Payload

        def ask(c, body):
            p = Payload(json.dumps(body), [], DataKind.STRING, None)

            async def go():
                return json.loads((await c.predict_raw(p)).data)["tokens"]

            return run(go())

        body = {"tokens": [5, 9, 2]}
        default_out = ask(comp, body)  # deployment default: alpha
        base_out = ask(base, body)
        assert default_out != base_out
        # per-request override back to the base model matches lora-off
        override = ask(comp, {**body, "adapter": None})
        assert override == base_out
        run(comp.close())
        run(base.close())

    def test_spec_snapshot_carries_adapters_section(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, **LORA_KW
        )
        snap = model.spec_snapshot()
        assert snap["lora_rank"] == 2
        assert snap["adapters"]["resident"] == 2
        assert snap["pool"]["bytes"]["adapter_pool"] == model.lora_bytes
        off = GenerativeModel(cfg, params, n_slots=2, decode_block=2)
        assert off.spec_snapshot()["adapters"] is None
