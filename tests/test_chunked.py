"""Chunked prefill + Pallas paged decode-attention gates
(docs/PERFORMANCE.md §7), CPU-safe:

* **pinned-equal chunking** — generation with chunked prefill ON is
  bit-identical to the monolithic prefill: greedy and seeded top-k, with
  KV prefix reuse (chunking applies to the novel suffix only), under int8
  paged KV, on a tp=2 sharded mesh, and across a disagg handoff of a
  chunk-prefilled slot;
* **stall-free interleave** — admissions arriving while streams decode are
  paced one chunk per sync point (the Sarathi property), the greedy stream
  stays bit-identical, and the host-sync audit stays <= 1 sync per fused
  block;
* **ITL ledger** — per-slot inter-token latency lands in
  ``spec_snapshot()`` (``itl_p50_ms``/``itl_p99_ms``, the
  ``/stats/breakdown`` generation section) and the ``seldon_itl_seconds``
  histogram;
* **kernel pinned-equal** — generation with the Pallas decode kernel ON
  matches the XLA gather path bit-for-bit in interpret mode (float and
  int8 pools); direct kernel-vs-reference equality lives in test_ops.py;
* **program cache-key audit** — ``prefill_chunk`` and ``decode_kernel``
  are folded into every compiled-program cache key, and ``/stats/warmup``
  variant labels name the chunk programs.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.disagg.handoff import (
    build_handoff_frame,
    decode_handoff,
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

    cfg = llama.Config.tiny(max_seq=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# mixed lengths: several longer than one 16-token chunk, one shorter
PROMPTS = [
    list(range(5, 50)),
    [30, 7],
    list(range(1, 70)),
    [11, 13, 17, 19, 23],
]


def _generate(
    cfg, params, prompts, *, max_new=9, temperature=0.0, seed=None, **kw
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


class TestChunkedPinnedEqual:
    """Chunked prefill must be a pure scheduling optimization: the written
    K/V and every emitted token are bit-identical to the monolithic path."""

    def test_greedy_chunked_equals_monolithic(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        chunk, model = _generate(cfg, params, PROMPTS, prefill_chunk=16)
        for p, a, b in zip(PROMPTS, base, chunk):
            assert np.array_equal(a, b), (len(p), a.tolist(), b.tolist())
        assert model.prefill_chunks >= 2  # the long prompts really chunked
        assert model.prefills == len(PROMPTS)  # one LOGICAL prefill each

    def test_seeded_topk_chunked_equals_monolithic(self, tiny):
        cfg, params = tiny
        kw = dict(temperature=0.9, seed=4242)
        base, _ = _generate(cfg, params, PROMPTS, top_k=4, **kw)
        chunk, model = _generate(
            cfg, params, PROMPTS, top_k=4, prefill_chunk=16, **kw
        )
        for a, b in zip(base, chunk):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.prefill_chunks >= 2

    def test_chunked_with_prefix_reuse(self, tiny):
        """Reuse composes: the matched prefix skips its chunks entirely,
        only the novel suffix is chunked."""
        cfg, params = tiny
        prefix = list(range(7, 39))  # 2 full 16-token blocks
        prompts = [prefix + list(range(40 + i, 60 + i)) for i in range(3)]

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
        chunk, model = gen(prefill_chunk=16, prefix_reuse=True)
        for a, b in zip(base, chunk):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.prefills_reused >= 1
        assert model.prefill_chunks >= 2

    def test_chunked_int8_kv(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS, kv_cache_dtype="int8")
        chunk, _ = _generate(
            cfg, params, PROMPTS, kv_cache_dtype="int8", prefill_chunk=16
        )
        for a, b in zip(base, chunk):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    def test_chunked_spec_draft_greedy_pinned(self, tiny):
        """Chunking + fused speculation together still match the plain
        sequential path bit-for-bit on greedy."""
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        both, model = _generate(
            cfg, params, PROMPTS, spec_draft=3, prefill_chunk=16
        )
        for a, b in zip(base, both):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.prefill_chunks >= 2

    def test_chunked_tp2_sharded_mesh(self, tiny):
        from seldon_core_tpu.parallel import best_mesh

        cfg, params = tiny
        mesh = best_mesh(2, tp=2)

        def gen(**kw):
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, mesh=mesh,
                param_axes=llama.param_logical_axes(params), **kw
            )
            sched = GenerationScheduler(model)

            async def go():
                try:
                    return [
                        await sched.submit(
                            np.asarray(p, np.int32), max_new_tokens=6
                        )
                        for p in PROMPTS[:2]
                    ]
                finally:
                    await sched.close()

            return run(go()), model

        base, _ = gen()
        chunk, model = gen(prefill_chunk=16)
        for a, b in zip(base, chunk):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.prefill_chunks >= 2

    def test_chunked_disagg_handoff(self, tiny):
        """A chunk-prefilled slot exports byte-identical KV: the handoff
        decode matches the unified (unchunked) run exactly."""
        cfg, params = tiny
        prompt = np.asarray(list(range(7, 42)), np.int32)
        base, _ = _generate(cfg, params, [prompt])

        model_a = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, prefill_chunk=16
        )
        model_b = GenerativeModel(cfg, params, n_slots=2, decode_block=4)
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
        assert model_a.prefill_chunks >= 2  # the export WAS chunk-built

    def test_eos_stops_exactly_with_chunking(self, tiny):
        cfg, params = tiny
        prompt = np.asarray(list(range(3, 40)), np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=12)
        eos = int(base[0][4])
        stop_at = int(np.argmax(base[0] == eos)) + 1

        def gen(**kw):
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, **kw
            )
            sched = GenerationScheduler(model)

            async def go():
                try:
                    return await sched.submit(
                        prompt, max_new_tokens=12, eos_id=eos
                    )
                finally:
                    await sched.close()

            return run(go())

        a = gen()
        b = gen(prefill_chunk=16)
        assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert a.size == stop_at


async def _interleaved_flood(cfg, params, *, chunked: bool):
    """One interactive stream decoding while long-prompt admissions flood
    in: the scenario chunking exists for."""
    model = GenerativeModel(
        cfg, params, n_slots=3, decode_block=4,
        prefill_chunk=16 if chunked else 0,
        name=f"chunk-flood-{int(chunked)}",
    )
    sched = GenerationScheduler(model)
    long_p = np.arange(1, 80, dtype=np.int32)
    interactive = asyncio.create_task(
        sched.submit(np.asarray([5, 9, 2], np.int32), max_new_tokens=40)
    )
    await asyncio.sleep(0.3)  # let the stream reach steady-state decode
    floods = [
        asyncio.create_task(sched.submit(long_p, max_new_tokens=2))
        for _ in range(3)
    ]
    out = await interactive
    await asyncio.gather(*floods)
    await sched.close()
    return out, model


class TestChunkedInterleave:
    def test_flood_admissions_are_chunk_paced_and_greedy_pinned(self, tiny):
        cfg, params = tiny
        base, _ = run(_interleaved_flood(cfg, params, chunked=False))
        chunk, model = run(_interleaved_flood(cfg, params, chunked=True))
        assert np.array_equal(base, chunk), (base.tolist(), chunk.tolist())
        # the floods really went through the paced pipeline (80-token
        # prompt over 16-token chunks = 5 chunks each)
        assert model.prefill_chunks >= 10

    def test_host_sync_audit_with_chunking_on(self, tiny):
        """Chunking must not reintroduce per-token host syncs: still <= 1
        sync per fused block — intermediate chunks dispatch unfetched, only
        each admission's final chunk materializes its first token."""
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        name = "chunk-sync-audit"
        before = host_sync_snapshot().get(name, 0)

        async def go():
            model = GenerativeModel(
                cfg, params, n_slots=3, decode_block=8, prefill_chunk=16,
                name=name,
            )
            sched = GenerationScheduler(model, overlap=True)
            interactive = asyncio.create_task(
                sched.submit(np.asarray([5, 9, 2], np.int32),
                             max_new_tokens=24)
            )
            await asyncio.sleep(0.3)
            floods = [
                asyncio.create_task(
                    sched.submit(np.arange(1, 60, dtype=np.int32),
                                 max_new_tokens=2)
                )
                for _ in range(2)
            ]
            out = await interactive
            await asyncio.gather(*floods)
            await sched.close()
            return out, model

        out, model = run(go())
        assert out.size == 24
        syncs = host_sync_snapshot().get(name, 0) - before
        blocks = model.steps / model.decode_block
        assert syncs <= blocks + 4, (
            f"{syncs} host syncs for {blocks} fused blocks"
        )

    def test_itl_ledger_records_delivery_gaps(self, tiny):
        cfg, params = tiny
        _, model = _generate(cfg, params, PROMPTS, max_new=12)
        snap = model.spec_snapshot()
        assert snap["itl_samples"] > 0
        assert snap["itl_p50_ms"] is not None
        assert snap["itl_p99_ms"] >= snap["itl_p50_ms"]

    def test_itl_histogram_metric_exists(self):
        from seldon_core_tpu.utils.metrics import DEFAULT

        DEFAULT.itl.labels("itl-smoke").observe(0.01)
        assert b"seldon_itl_seconds" in DEFAULT.expose()


class TestChunkConfig:
    def test_chunk_rounds_up_to_block_multiple(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, prefill_chunk=20, kv_block_size=16
        )
        assert model.prefill_chunk == 32

    def test_env_opt_in(self, tiny, monkeypatch):
        cfg, params = tiny
        monkeypatch.setenv("SCT_PREFILL_CHUNK", "16")
        model = GenerativeModel(cfg, params, n_slots=2)
        assert model.prefill_chunk == 16
        monkeypatch.setenv("SCT_DECODE_KERNEL", "1")
        model = GenerativeModel(cfg, params, n_slots=2)
        assert model.decode_kernel is True

    def test_kernel_on_mesh_is_a_build_error(self, tiny):
        """The Pallas kernel does not partition over a mesh: asking for it
        on a sharded deployment fails at build — never a logged switch to
        the XLA gather path under the kernel's name."""
        from seldon_core_tpu.graph.units import GraphUnitError
        from seldon_core_tpu.parallel import best_mesh

        cfg, params = tiny
        with pytest.raises(GraphUnitError, match="single-device"):
            GenerativeModel(
                cfg, params, n_slots=2, mesh=best_mesh(2, tp=2),
                param_axes=llama.param_logical_axes(params),
                decode_kernel=True,
            )

    def test_kernel_without_family_path_is_a_build_error(self, tiny):
        import types

        from seldon_core_tpu.graph.units import GraphUnitError

        cfg, params = tiny
        no_kernel = types.SimpleNamespace(
            __name__="no_kernel_family",
            decode_slots_paged=lambda params, tokens, cache, active, cfg: None,
        )
        with pytest.raises(GraphUnitError, match="no kernel decode path"):
            GenerativeModel(
                cfg, params, family_mod=no_kernel, n_slots=2,
                decode_kernel=True,
            )


class TestKernelGeneration:
    """Generation-level pin: the fused Pallas decode step emits the same
    greedy stream as the XLA gather path (interpret mode on CPU)."""

    def test_kernel_generation_pinned_equal(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        kern, model = _generate(cfg, params, PROMPTS, decode_kernel=True)
        for a, b in zip(base, kern):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.decode_kernel is True

    def test_kernel_int8_generation_pinned_equal(self, tiny):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS[:2], kv_cache_dtype="int8")
        kern, _ = _generate(
            cfg, params, PROMPTS[:2], kv_cache_dtype="int8",
            decode_kernel=True,
        )
        for a, b in zip(base, kern):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())


class TestProgramKeyAudit:
    """ISSUE 8 satellite: ``prefill_chunk`` and ``decode_kernel`` must ride
    the compiled-program cache keys — two deployments differing only in
    chunking/kernel config can never share a compiled step."""

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

    def test_decode_k_keys_fold_chunk_and_kernel(self, tiny):
        cfg, params = tiny
        variants = [{}, {"prefill_chunk": 32}, {"decode_kernel": True}]
        keys = []
        for kw in variants:
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=2, **kw
            )
            self._touch(model)
            (key,) = model._decode_k_jit.keys()
            keys.append(key)
        assert all(k[:2] == (2, 64) for k in keys)
        assert len(set(keys)) == len(keys), keys

    def test_prefill_suffix_keys_fold_chunk(self, tiny):
        """A chunked admission's suffix programs key on the full static
        config (regression: bare (bucket, window) keys would let a
        chunked and an unchunked deployment share a program)."""
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, prefill_chunk=16
        )
        model.admit(0, np.arange(1, 40, dtype=np.int32), 0.0, 0)
        assert model._prefill_suffix_jit, "long admission must chunk"
        for key in model._prefill_suffix_jit:
            assert key[2:] == model._program_config, key

    def test_program_config_covers_chunk_and_kernel(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, top_k=3,
            prefill_chunk=32, decode_kernel=True,
        )
        assert model._program_config == (
            3, 0, model.spec_ngram, model.spec_hist, None, 0, None, None,
            32, True, 0, 0, False,
        )


class TestWarmupChunkVariants:
    def test_warmup_names_chunk_programs(self, tiny):
        """/stats/warmup attribution: with chunking on the variant list
        names the chunk suffix programs per prefix window (e.g.
        ``prefill:b32:w64[chunk32]``) so readiness provably covered the
        chunk pipeline, and monolithic labels stop at the chunk size."""
        cfg, params = tiny
        comp = GenerativeComponent(
            GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, prefill_chunk=32,
            )
        )
        n = comp.warmup()
        variants = comp.warmup_variants()
        assert len(variants) == n
        assert any(
            v.startswith("prefill:b32:w") and "[chunk32]" in v
            for v in variants
        ), variants
        # no monolithic label beyond the chunk size: those programs are
        # never compiled (long admissions run the chunk pipeline)
        assert not any(
            v.startswith("prefill:b64") or v.startswith("prefill:b128")
            for v in variants
        ), variants

        async def _close():
            await comp.close()

        run(_close())
