"""Device-side decode frontier gates (docs/PERFORMANCE.md), CPU-safe:

* **pinned-equal speculation** — greedy generation with self-speculative
  decoding ON is bit-identical to OFF: plain, overlapped, with KV prefix
  reuse, on a tp=2 sharded mesh, and across a disagg prefill→decode
  handoff; seeded sampling stays run-to-run reproducible;
* **acceptance floor** — on repetitive text the n-gram proposer must win:
  ``accepted_tokens_per_step`` > 1.2;
* **host-sync audit** — speculation must not reintroduce per-token host
  syncs: still <= 1 sync per fused block;
* **int8 paged KV** — >= 1.9x slots-per-chip at equal HBM on the bf16
  bench shape, bit-exact handoff (codec v2) and checkpoint round-trips on
  the quantized representation, prefix reuse pinned-equal under int8;
* **program cache-key audit** — static sampling/speculation/quantization
  config is folded into every compiled-program cache key.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.disagg.handoff import (
    HandoffError,
    apply_handoff,
    build_handoff_frame,
    decode_handoff,
    encode_handoff,
)
from seldon_core_tpu.executor.generation import (
    GenerationScheduler,
    GenerativeComponent,
    GenerativeModel,
)
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
REPETITIVE = np.tile([3, 7, 11], 8).astype(np.int32)


def _generate(
    cfg, params, prompts, *, max_new=11, temperature=0.0, seed=None, **kw
):
    model = GenerativeModel(cfg, params, n_slots=4, decode_block=4, **kw)
    sched = GenerationScheduler(model)
    if seed is not None:
        sched._seed = seed

    async def go():
        try:
            return await asyncio.gather(
                *(
                    sched.submit(
                        np.asarray(p, np.int32),
                        max_new_tokens=max_new,
                        temperature=temperature,
                    )
                    for p in prompts
                )
            )
        finally:
            await sched.close()

    return run(go()), model


class TestSpecPinnedEqual:
    """Greedy speculation must be a pure latency optimization: the emitted
    token stream is bit-identical to the non-speculative path."""

    def test_greedy_spec_on_equals_off(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        spec, model = _generate(cfg, params, PROMPTS, spec_draft=3)
        for p, a, b in zip(PROMPTS, base, spec):
            assert np.array_equal(a, b), (p, a.tolist(), b.tolist())
        assert model.spec_verify_passes > 0

    def test_greedy_repetitive_spec_on_equals_off(self, tiny):
        """Exactly the input where drafts ARE accepted: accepted tokens
        must be the ones the sequential path would have emitted."""
        cfg, params = tiny
        base, _ = _generate(cfg, params, [REPETITIVE], max_new=24)
        spec, model = _generate(
            cfg, params, [REPETITIVE], max_new=24, spec_draft=4
        )
        assert np.array_equal(base[0], spec[0]), (
            base[0].tolist(), spec[0].tolist()
        )
        assert model.spec_emitted_tokens > model.spec_verify_passes

    def test_greedy_spec_with_prefix_reuse(self, tiny):
        cfg, params = tiny
        prefix = list(range(7, 39))  # 2 full 16-token blocks
        prompts = [prefix + [40 + i, 41 + i] for i in range(3)]

        def gen(**kw):
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, kv_block_size=16, **kw
            )
            sched = GenerationScheduler(model)

            async def go():
                try:
                    # sequential: later prompts reuse absorbed prefix blocks
                    return [
                        await sched.submit(
                            np.asarray(p, np.int32), max_new_tokens=6
                        )
                        for p in prompts
                    ]
                finally:
                    await sched.close()

            return run(go()), model

        base, _ = gen()
        spec, model = gen(spec_draft=3, prefix_reuse=True)
        for a, b in zip(base, spec):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.prefills_reused >= 1

    def test_greedy_spec_on_tp2_sharded_mesh(self, tiny):
        from seldon_core_tpu.parallel import best_mesh

        cfg, params = tiny
        mesh = best_mesh(2, tp=2)

        def build(**kw):
            return GenerativeModel(
                cfg, params, n_slots=4, decode_block=4, mesh=mesh,
                param_axes=llama.param_logical_axes(params), **kw
            )

        def gen(model):
            sched = GenerationScheduler(model)

            async def go():
                try:
                    return await asyncio.gather(
                        *(
                            sched.submit(
                                np.asarray(p, np.int32), max_new_tokens=8
                            )
                            for p in PROMPTS
                        )
                    )
                finally:
                    await sched.close()

            return run(go())

        base = gen(build())
        model = build(spec_draft=3)
        spec = gen(model)
        for a, b in zip(base, spec):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    def test_greedy_spec_across_disagg_handoff(self, tiny):
        """Prefill engine (no speculation needed) -> KV handoff -> decode
        engine with speculation ON: bit-identical to the unified run."""
        cfg, params = tiny
        prompt = np.asarray(PROMPTS[0], np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=9)

        model_a = GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        model_b = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=3
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
        assert model_b.imports == 1

    def test_eos_mid_spec_pass_stops_exactly(self, tiny):
        """A draft position that lands on EOS must truncate the emission
        inside the verify pass — same stream as the sequential path."""
        cfg, params = tiny
        base, _ = _generate(cfg, params, [REPETITIVE], max_new=24)
        eos = int(base[0][5])  # force a stop a few tokens in
        stop_at = int(np.argmax(base[0] == eos)) + 1

        def gen(**kw):
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, **kw
            )
            sched = GenerationScheduler(model)

            async def go():
                try:
                    return await sched.submit(
                        REPETITIVE, max_new_tokens=24, eos_id=eos
                    )
                finally:
                    await sched.close()

            return run(go())

        a = gen()
        b = gen(spec_draft=4)
        assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert a.size == stop_at

    def test_sampled_spec_seeded_reproducible(self, tiny):
        cfg, params = tiny
        one, _ = _generate(
            cfg, params, PROMPTS, temperature=0.8, seed=4242, spec_draft=3
        )
        two, _ = _generate(
            cfg, params, PROMPTS, temperature=0.8, seed=4242, spec_draft=3
        )
        for a, b in zip(one, two):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    def test_top_k_spec_seeded_reproducible(self, tiny):
        cfg, params = tiny
        kw = dict(temperature=0.9, seed=99, spec_draft=2, top_k=4)
        one, _ = _generate(cfg, params, PROMPTS, **kw)
        two, _ = _generate(cfg, params, PROMPTS, **kw)
        for a, b in zip(one, two):
            assert np.array_equal(a, b)


class TestSpecAcceptance:
    def test_repetitive_prompt_acceptance_floor(self, tiny):
        """On repetitive text the n-gram drafter must pay for itself:
        > 1.2 tokens per verify pass (1.0 = nothing ever accepted)."""
        cfg, params = tiny
        _, model = _generate(
            cfg, params, [REPETITIVE], max_new=24, spec_draft=4
        )
        snap = model.spec_snapshot()
        assert snap["accepted_tokens_per_step"] is not None
        assert snap["accepted_tokens_per_step"] > 1.2, snap

    def test_host_sync_audit_with_spec_on(self, tiny):
        """Speculation must not reintroduce per-token host syncs: still
        one fetch per fused block (the PR-5 overlapped-pipeline bar)."""
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        block, max_new, n_req = 8, 24, 3
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=block, spec_draft=3,
            name="spec-sync-audit",
        )
        sched = GenerationScheduler(model, overlap=True)
        before = host_sync_snapshot().get("spec-sync-audit", 0)

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
        syncs = host_sync_snapshot().get("spec-sync-audit", 0) - before
        tokens = n_req * max_new
        budget = tokens // block + 4
        assert syncs <= budget, f"{syncs} host syncs for {tokens} tokens"

    def test_proposer_drafts_continuation_of_match(self):
        from seldon_core_tpu.executor.speculative import propose_ngram

        import jax.numpy as jnp

        # position p at hist[p % 16]; sequence 1 2 3 4 1 2 3 -> pos=6,
        # suffix (n=2) = [2, 3], most recent earlier match at pos 1 ->
        # drafts the tokens that followed: [4, 1]
        hist = np.zeros((1, 16), np.int32)
        seq = [1, 2, 3, 4, 1, 2, 3]
        for p, t in enumerate(seq):
            hist[0, p % 16] = t
        out = propose_ngram(
            jnp.asarray(hist), jnp.asarray([6]), jnp.asarray([3]),
            n=2, draft=2,
        )
        assert np.asarray(out).tolist() == [[4, 1]]

    def test_proposer_no_match_falls_back_to_cur(self):
        from seldon_core_tpu.executor.speculative import propose_ngram

        import jax.numpy as jnp

        hist = np.zeros((1, 16), np.int32)
        for p, t in enumerate([9, 8, 7, 6, 5]):
            hist[0, p] = t
        out = propose_ngram(
            jnp.asarray(hist), jnp.asarray([4]), jnp.asarray([5]),
            n=2, draft=3,
        )
        assert np.asarray(out).tolist() == [[5, 5, 5]]


class TestInt8KV:
    def test_slots_per_chip_geometry_doubles(self):
        """>= 1.9x max-seq sequences per HBM byte on the bf16 bench shape
        (the acceptance bar; per-(position, head) scales cost ~3%)."""
        cfg = llama.Config.llama3_1b()
        bf16 = llama.paged_kv_slot_bytes(cfg, 16, dtype="bfloat16")
        int8 = llama.paged_kv_slot_bytes(
            cfg, 16, kv_dtype="int8", dtype="bfloat16"
        )
        assert bf16 / int8 >= 1.9, (bf16, int8)

    def test_int8_model_reports_capacity(self, tiny):
        cfg, params = tiny
        base = GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        q = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, kv_cache_dtype="int8"
        )
        assert q.kv_bytes_per_slot() < base.kv_bytes_per_slot()
        assert q.kv_slots_per_chip() > base.kv_slots_per_chip()
        snap = q.spec_snapshot()
        assert snap["kv_dtype"] == "int8"
        assert snap["kv_slots_per_chip"] > 0

    def test_int8_generation_deterministic_and_spec_pinned(self, tiny):
        """int8 greedy output is deterministic, and speculation on an int8
        pool pins to the non-speculative int8 path."""
        cfg, params = tiny
        a, _ = _generate(cfg, params, PROMPTS, kv_cache_dtype="int8")
        b, _ = _generate(cfg, params, PROMPTS, kv_cache_dtype="int8")
        c, _ = _generate(
            cfg, params, PROMPTS, kv_cache_dtype="int8", spec_draft=3
        )
        for x, y, z in zip(a, b, c):
            assert np.array_equal(x, y)
            assert np.array_equal(x, z), (x.tolist(), z.tolist())

    def test_int8_prefix_reuse_bit_equal_to_cold(self, tiny):
        """Fake-quant consistency: a suffix prefill over reused int8
        blocks generates bit-identically to the cold int8 prefill."""
        cfg, params = tiny
        prefix = list(range(7, 39))
        prompts = [prefix + [40 + i, 41 + i] for i in range(3)]

        def gen(reuse):
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, kv_block_size=16,
                kv_cache_dtype="int8", prefix_reuse=reuse,
            )
            sched = GenerationScheduler(model)

            async def go():
                try:
                    return [
                        await sched.submit(
                            np.asarray(p, np.int32), max_new_tokens=6
                        )
                        for p in prompts
                    ]
                finally:
                    await sched.close()

            return run(go()), model

        cold, _ = gen(False)
        reused, model = gen(True)
        for a, b in zip(cold, reused):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.prefills_reused >= 1

    def test_int8_handoff_roundtrip_bit_exact(self, tiny):
        """Codec v2 carries the QUANTIZED representation verbatim: the
        decoded frame's int8 blocks and scales equal the exported ones."""
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, kv_cache_dtype="int8"
        )
        prompt = np.asarray(PROMPTS[0], np.int32)
        tok = model.admit(0, prompt, 0.0, 0, reserve_tokens=4)
        k, v, ks, vs = model.export_slot_kv(0, prompt.size)
        assert str(k.dtype) == "int8"
        frame = encode_handoff(
            prompt, tok, k, v, block_size=model.kv_block_size,
            max_new_tokens=4, k_scale=ks, v_scale=vs,
        )
        payload = decode_handoff(frame)
        from seldon_core_tpu.disagg.handoff import HANDOFF_VERSION

        assert payload["hv"] == HANDOFF_VERSION  # int8 rides >= v2
        assert payload["kv_quant"] == "int8"
        np.testing.assert_array_equal(payload["k"], k)
        np.testing.assert_array_equal(payload["v"], v)
        np.testing.assert_array_equal(payload["k_scale"], ks)
        np.testing.assert_array_equal(payload["v_scale"], vs)

    def test_int8_disagg_handoff_pinned_equal(self, tiny):
        """Two int8 engines: prefill -> handoff -> decode equals the
        unified int8 generation exactly."""
        cfg, params = tiny
        prompt = np.asarray(PROMPTS[0], np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=9,
                            kv_cache_dtype="int8")

        def build():
            return GenerativeModel(
                cfg, params, n_slots=2, decode_block=4,
                kv_cache_dtype="int8",
            )

        model_a, model_b = build(), build()
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
                    k_scale=payload["k_scale"],
                    v_scale=payload["v_scale"],
                    max_new_tokens=9,
                )
            finally:
                await sched_a.close()
                await sched_b.close()

        got = run(go())
        np.testing.assert_array_equal(got, base[0])

    def test_handoff_layout_skew_rejected(self, tiny):
        """An int8 frame must not import into a float pool (and vice
        versa): codec v2 fails fast instead of mis-decoding KV bytes."""
        cfg, params = tiny
        q = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, kv_cache_dtype="int8"
        )
        prompt = np.asarray(PROMPTS[0], np.int32)
        tok = q.admit(0, prompt, 0.0, 0, reserve_tokens=4)
        k, v, ks, vs = q.export_slot_kv(0, prompt.size)
        frame = encode_handoff(
            prompt, tok, k, v, block_size=q.kv_block_size,
            max_new_tokens=4, k_scale=ks, v_scale=vs,
        )
        float_pool = GenerativeComponent(
            GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        )

        async def go():
            try:
                with pytest.raises(HandoffError, match="layout"):
                    await apply_handoff(float_pool, decode_handoff(frame))
            finally:
                await float_pool.close()

        run(go())

    def test_future_codec_version_rejected(self, tiny):
        from seldon_core_tpu.disagg.handoff import HANDOFF_KEY
        from seldon_core_tpu.executor.multihost import encode_step

        frame = encode_step(
            HANDOFF_KEY,
            {
                "prompt": np.asarray([1, 2], np.int32),
                "first_token": 1,
                "block_size": 16,
                "kv_dtype": "float32",
                "hv": 99,
                "k": np.zeros((1,), np.float32),
                "v": np.zeros((1,), np.float32),
            },
        )
        with pytest.raises(HandoffError, match="version"):
            decode_handoff(frame)

    def test_int8_checkpoint_roundtrip_lossless(self, tiny, tmp_path):
        """The quantized pool (int8 blocks + scales) checkpoints and
        restores bit-exactly through executor/checkpoint.py."""
        import jax

        from seldon_core_tpu.executor.checkpoint import (
            load_params,
            save_params,
        )

        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, kv_cache_dtype="int8"
        )
        model.admit(0, np.asarray(PROMPTS[0], np.int32), 0.0, 0,
                    reserve_tokens=4)
        cache = {k: np.asarray(jax.device_get(v))
                 for k, v in model._cache.items()}
        path = str(tmp_path / "kv.npz")
        save_params(path, cache)
        back = load_params(path)
        for key in ("k", "v", "k_scale", "v_scale", "pos", "table"):
            np.testing.assert_array_equal(back[key], cache[key])
            assert back[key].dtype == cache[key].dtype


class TestProgramKeyAudit:
    """ISSUE 7 satellite: `_decode_k_jit` keying was bare ``(k, window)``
    — static sampling/speculation/quantization config must ride the key so
    no two configurations can ever share a compiled program."""

    def _touch(self, model):
        model.step_k(
            np.zeros(model.n_slots, np.int32),
            np.zeros(model.n_slots, bool),
            np.zeros(model.n_slots, np.float32),
            0,
            np.full(model.n_slots, -1, np.int32),
            np.zeros(model.n_slots, np.int32),
            model.decode_block,
            window=64,
        )

    def test_decode_k_keys_fold_static_config(self, tiny):
        cfg, params = tiny
        variants = [
            {},
            {"top_k": 4},
            {"spec_draft": 2},
            {"kv_cache_dtype": "int8"},
        ]
        keys = []
        for kw in variants:
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=2, **kw
            )
            self._touch(model)
            (key,) = model._decode_k_jit.keys()
            keys.append(key)
        # same (k, window) everywhere — only the config tail distinguishes
        assert all(k[:2] == (2, 64) for k in keys)
        assert len(set(keys)) == len(keys), keys

    def test_program_config_covers_sampling_spec_and_quant(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, top_k=3, spec_draft=2,
            kv_cache_dtype="int8",
        )
        assert model._program_config == (3, 2, model.spec_ngram,
                                         model.spec_hist, "ngram", 0, None,
                                         "int8",
                                         model.prefill_chunk,
                                         model.decode_kernel,
                                         model.lora_rank, model.lora_slots,
                                         model.conf_signal)


class TestWarmupVariants:
    def test_warmup_names_spec_and_int8_programs(self, tiny):
        """/stats/warmup attribution (ISSUE 7 satellite): the compiled
        program list names the speculative-verify and int8 variants, suffix
        prefills included, so readiness provably covered them."""
        cfg, params = tiny
        comp = GenerativeComponent(
            GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, spec_draft=2,
                kv_cache_dtype="int8", prefix_reuse=True,
            )
        )
        n = comp.warmup()
        variants = comp.warmup_variants()
        assert len(variants) == n
        assert any(v.startswith("decode_k:") and "[spec2,int8]" in v
                   for v in variants)
        assert any(v.startswith("prefill:") for v in variants)
        assert any(v.startswith("suffix:") and "[spec2,int8]" in v
                   for v in variants)

        async def _close():
            await comp.close()

        run(_close())
