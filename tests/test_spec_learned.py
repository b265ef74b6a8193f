"""Learned speculative decoding (ISSUE 20, docs/PERFORMANCE.md §6).

Two learned proposers ride the PR-7 draft→verify→accept scan — fused
Medusa-style heads (``spec_method='heads'``) and a co-resident draft
model (``spec_method='draft'``) — and both must be pure latency
optimizations:

* **pinned-equal matrix** — greedy output bit-identical to spec-off for
  BOTH methods: plain, overlapped, chunked prefill, prefix reuse, int8
  paged KV, tp=2 sharded mesh, across a disagg handoff, and across
  suspend/resume and drain/live-migration of a mid-decode slot;
* **host-sync audit** — still <= 1 sync per fused block with heads or a
  draft model on (draft prefills are dispatch-only);
* **codec v5 back-compat** — frames carry the proposer state (the heads
  hidden) and pre-v5 frames still import;
* **zero leaked draft-KV blocks** — the draft pool's static per-slot
  block table owns nothing an exit path could leak;
* **telemetry** — acceptance splits per proposer in the snapshot, the
  Prometheus ledger, and the usage meter;
* **rider** — ``spec_draft`` with ``decode_block=1`` is a loud
  build-time error, not a silent degradation.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.disagg.handoff import (
    build_handoff_frame,
    decode_handoff,
    encode_handoff,
)
from seldon_core_tpu.executor.generation import (
    GenerationScheduler,
    GenerativeModel,
)
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

# the two learned proposers, as build kwargs (spec_draft added per test);
# tiny has 2 layers so truncate:1 is the only legal self-draft
METHODS = {
    "heads": {"spec_method": "heads", "spec_heads": 3},
    "draft": {"spec_method": "draft", "spec_draft_model": "truncate:1"},
}
method = pytest.mark.parametrize(
    "mkw", list(METHODS.values()), ids=list(METHODS)
)


def _generate(
    cfg, params, prompts, *, max_new=11, temperature=0.0, seed=None,
    overlap=None, **kw
):
    kw.setdefault("decode_block", 4)
    model = GenerativeModel(cfg, params, n_slots=4, **kw)
    skw = {"overlap": overlap} if overlap is not None else {}
    sched = GenerationScheduler(model, **skw)
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


# ---------------------------------------------------------------------------
# model-layer units: the Medusa head block + the layer-truncated self-draft
# ---------------------------------------------------------------------------


class TestMedusaHeadUnits:
    def test_init_and_apply_shapes(self, tiny):
        import jax
        import jax.numpy as jnp

        cfg, params = tiny
        heads = llama.init_medusa_heads(
            jax.random.PRNGKey(1), cfg, 3, base_head=params["head"]
        )
        e, v = cfg.hidden, cfg.vocab_size
        assert heads["w1"].shape == (3, e, e)
        assert heads["head"].shape == (3, e, v)
        # synthesized heads start AT the base lm_head (residual block near
        # identity): a trained checkpoint only improves acceptance
        np.testing.assert_array_equal(
            np.asarray(heads["head"][0]), np.asarray(params["head"])
        )
        h = jnp.ones((4, e), jnp.float32)
        logits = llama.apply_medusa_heads(heads, h)
        assert logits.shape == (4, 3, v)

    def test_head_bytes_accounting(self, tiny):
        import jax

        cfg, params = tiny
        heads = llama.init_medusa_heads(
            jax.random.PRNGKey(1), cfg, 2, base_head=params["head"]
        )
        want = sum(int(x.nbytes) for x in jax.tree.leaves(heads))
        assert llama.medusa_head_bytes(cfg, 2, np.float32) == want

    def test_truncate_params_shares_non_layer_leaves(self, tiny):
        cfg, params = tiny
        dp = llama.truncate_params(params, 1)
        # embeddings/head are shared by reference — only layer stacks slice
        assert dp["tok_emb"] is params["tok_emb"]
        assert dp["head"] is params["head"]
        for k, v in dp["layers"].items():
            assert int(v.shape[0]) == 1, k


# ---------------------------------------------------------------------------
# pinned-equal matrix (the ISSUE 20 acceptance bar)
# ---------------------------------------------------------------------------


class TestLearnedPinnedEqual:
    """Greedy output with heads/draft ON is bit-identical to spec-off:
    drafts gate acceptance, never the emitted values."""

    def _check(self, base, out):
        for p, a, b in zip(PROMPTS, base, out):
            assert np.array_equal(a, b), (p, a.tolist(), b.tolist())

    @method
    def test_plain(self, tiny, mkw):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        out, model = _generate(cfg, params, PROMPTS, spec_draft=2, **mkw)
        self._check(base, out)
        assert model.spec_verify_passes > 0

    @method
    def test_overlapped(self, tiny, mkw):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS, overlap=True)
        out, model = _generate(
            cfg, params, PROMPTS, overlap=True, spec_draft=2, **mkw
        )
        self._check(base, out)

    @method
    def test_chunked_prefill(self, tiny, mkw):
        cfg, params = tiny
        long = [list(range(1, 30))] + PROMPTS[1:]
        base, _ = _generate(cfg, params, long, prefill_chunk=8)
        out, _ = _generate(
            cfg, params, long, prefill_chunk=8, spec_draft=2, **mkw
        )
        for a, b in zip(base, out):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    @method
    def test_prefix_reuse(self, tiny, mkw):
        cfg, params = tiny
        prompts = [PROMPTS[0], PROMPTS[0], PROMPTS[2]]
        base, _ = _generate(cfg, params, prompts, prefix_reuse=True)
        out, model = _generate(
            cfg, params, prompts, prefix_reuse=True, spec_draft=2, **mkw
        )
        for a, b in zip(base, out):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())

    @method
    def test_int8_kv(self, tiny, mkw):
        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS, kv_cache_dtype="int8")
        out, _ = _generate(
            cfg, params, PROMPTS, kv_cache_dtype="int8", spec_draft=2, **mkw
        )
        self._check(base, out)

    @method
    def test_tp2_sharded_mesh(self, tiny, mkw):
        from seldon_core_tpu.parallel import best_mesh

        cfg, params = tiny
        mesh = best_mesh(2, tp=2)
        axes = llama.param_logical_axes(params)

        base, _ = _generate(
            cfg, params, PROMPTS, max_new=8, mesh=mesh, param_axes=axes
        )
        out, _ = _generate(
            cfg, params, PROMPTS, max_new=8, mesh=mesh, param_axes=axes,
            spec_draft=2, **mkw
        )
        self._check(base, out)

    @method
    def test_seeded_sampling_reproducible(self, tiny, mkw):
        cfg, params = tiny
        kw = dict(temperature=0.8, seed=4242, spec_draft=2, **mkw)
        one, _ = _generate(cfg, params, PROMPTS, **kw)
        two, _ = _generate(cfg, params, PROMPTS, **kw)
        for a, b in zip(one, two):
            assert np.array_equal(a, b)

    @method
    def test_host_sync_audit(self, tiny, mkw):
        """Learned proposers must not reintroduce per-token host syncs:
        the draft model runs INSIDE the fused block and its prefills are
        dispatch-only, so the budget stays one fetch per block."""
        from seldon_core_tpu.obs import host_sync_snapshot

        cfg, params = tiny
        name = f"learned-sync-{mkw['spec_method']}"
        block, max_new, n_req = 8, 24, 3
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=block, spec_draft=2,
            name=name, **mkw,
        )
        sched = GenerationScheduler(model, overlap=True)
        before = host_sync_snapshot().get(name, 0)

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
        syncs = host_sync_snapshot().get(name, 0) - before
        tokens = n_req * max_new
        budget = tokens // block + 4
        assert syncs <= budget, f"{syncs} host syncs for {tokens} tokens"


# ---------------------------------------------------------------------------
# disagg handoff + codec v5
# ---------------------------------------------------------------------------


class TestLearnedDisaggHandoff:
    @method
    def test_import_into_learned_decoder_pinned_equal(self, tiny, mkw):
        """Plain prefill engine -> handoff -> decode engine with a learned
        proposer ON: bit-identical to the unified run."""
        cfg, params = tiny
        prompt = np.asarray(PROMPTS[0], np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=9)

        model_a = GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        model_b = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=2, **mkw
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
                    spec_state=payload.get("spec_state"),
                )
            finally:
                await sched_a.close()
                await sched_b.close()

        got = run(go())
        np.testing.assert_array_equal(got, base[0])
        assert model_b.imports == 1

    def test_heads_prefill_exports_spec_state(self, tiny):
        """A heads-speculating prefill engine stamps the v5 envelope: the
        frame carries the slot's Medusa hidden and a heads importer
        installs it (warm first speculative block, same bits)."""
        cfg, params = tiny
        prompt = np.asarray(PROMPTS[0], np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=9)

        def build():
            return GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, spec_draft=2,
                **METHODS["heads"],
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
                spec = payload.get("spec_state")
                assert spec is not None and spec["method"] == "heads"
                assert spec["hlast"].shape == (cfg.hidden,)
                assert np.abs(np.asarray(spec["hlast"])).sum() > 0
                return await sched_b.submit_imported(
                    payload["prompt"],
                    first_token=payload["first_token"],
                    k=payload["k"],
                    v=payload["v"],
                    max_new_tokens=9,
                    spec_state=spec,
                )
            finally:
                await sched_a.close()
                await sched_b.close()

        got = run(go())
        np.testing.assert_array_equal(got, base[0])

    def test_draft_import_reprefills_draft_pool(self, tiny):
        """A draft importer rebuilds its draft KV from the carried token
        history (the frame ships no draft tensor) — the import must
        trigger one draft prefill and stay pinned-equal."""
        cfg, params = tiny
        prompt = np.asarray(PROMPTS[0], np.int32)
        base, _ = _generate(cfg, params, [prompt], max_new=9)
        model_a = GenerativeModel(cfg, params, n_slots=2, decode_block=4)
        model_b = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=2,
            **METHODS["draft"],
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
                    spec_state=payload.get("spec_state"),
                )
            finally:
                await sched_a.close()
                await sched_b.close()

        got = run(go())
        np.testing.assert_array_equal(got, base[0])
        assert model_b.draft_prefills >= 1


class TestHandoffCodecV5:
    def _frame_args(self):
        prompt = np.asarray([1, 2, 3], np.int32)
        k = np.zeros((2, 1, 16, 1, 4), np.float32)
        v = np.ones((2, 1, 16, 1, 4), np.float32)
        return prompt, k, v

    def test_spec_state_round_trips(self):
        prompt, k, v = self._frame_args()
        hlast = np.arange(8, dtype=np.float32)
        frame = encode_handoff(
            prompt, 7, k, v, block_size=16, max_new_tokens=4,
            spec_state={"method": "heads", "hlast": hlast},
        )
        payload = decode_handoff(frame)
        spec = payload["spec_state"]
        assert spec["method"] == "heads"
        np.testing.assert_array_equal(spec["hlast"], hlast)

    def test_spec_state_bf16_hidden_bit_exact(self):
        import ml_dtypes

        prompt, k, v = self._frame_args()
        hlast = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16)
        frame = encode_handoff(
            prompt, 7, k, v, block_size=16, max_new_tokens=4,
            spec_state={"method": "heads", "hlast": hlast},
        )
        spec = decode_handoff(frame)["spec_state"]
        assert spec["hlast"].dtype == hlast.dtype
        np.testing.assert_array_equal(
            spec["hlast"].view(np.uint16), hlast.view(np.uint16)
        )

    def test_method_only_state(self):
        prompt, k, v = self._frame_args()
        frame = encode_handoff(
            prompt, 7, k, v, block_size=16, max_new_tokens=4,
            spec_state={"method": "draft"},
        )
        spec = decode_handoff(frame)["spec_state"]
        assert spec == {"method": "draft"}

    def test_v4_frames_still_decode(self):
        """Back-compat: a frame with no speculation envelope (everything
        pre-v5 produced) decodes with no ``spec_state`` — the importer's
        ``spec_state=None`` path is the old behavior exactly."""
        from seldon_core_tpu.disagg import handoff as ho

        prompt, k, v = self._frame_args()
        frame = encode_handoff(
            prompt, 7, k, v, block_size=16, max_new_tokens=4
        )
        payload = decode_handoff(frame)
        assert "spec_state" not in payload
        # a literal v4 frame (old sender, old version stamp) too
        old = dict(payload)
        for fld in ("k", "v"):
            old[fld] = np.ascontiguousarray(old[fld])
        old["hv"] = 4
        from seldon_core_tpu.executor.multihost import encode_step

        payload4 = decode_handoff(encode_step(ho.HANDOFF_KEY, old))
        assert int(payload4["hv"]) == 4
        assert "spec_state" not in payload4


# ---------------------------------------------------------------------------
# lifecycle verbs: suspend/resume (PR 12) + drain/live-migration (PR 14)
# ---------------------------------------------------------------------------

LPROMPT = [5, 9, 2, 17, 3]
LMAX = 12


def _uninterrupted(model, *, seed):
    sched = GenerationScheduler(model)
    sched._seed = seed

    async def go():
        try:
            return await sched.submit(
                np.asarray(LPROMPT, np.int32), max_new_tokens=LMAX
            )
        finally:
            await asyncio.wait_for(sched.close(), 20)

    return run(go())


def _suspended(model, *, seed, after=3):
    """Preempt after ``after`` tokens, park the slot in the suspend store,
    resume, and return the full stream (tests/test_packing.py idiom)."""
    sched = GenerationScheduler(model)
    sched._seed = seed
    seen = []

    def hook(tok):
        seen.append(tok)
        if len(seen) == after:
            sched.request_preempt()

    async def go():
        try:
            task = asyncio.ensure_future(sched.submit(
                np.asarray(LPROMPT, np.int32), max_new_tokens=LMAX,
                on_token=hook,
            ))
            for _ in range(20_000):
                if sched._suspended:
                    break
                await asyncio.sleep(0.001)
            assert sched._suspended, "preemption never suspended the slot"
            await asyncio.sleep(0.02)
            sched.request_resume()
            out = await task
            assert sched.suspends == 1 and sched.resumes == 1
            return out
        finally:
            await asyncio.wait_for(sched.close(), 20)

    return run(go()), sched


def _drained(model_src, model_dst, *, seed, after=3):
    """Drain the source mid-stream and migrate the frame onto a peer
    (tests/test_chaos.py idiom) — spec state rides the frame."""
    src = GenerationScheduler(model_src)
    src._seed = seed
    seen = []

    def hook(tok):
        seen.append(tok)
        if len(seen) == after:
            src.drain_begin()

    async def go():
        dst = GenerationScheduler(model_dst)
        try:
            task = asyncio.ensure_future(src.submit(
                np.asarray(LPROMPT, np.int32), max_new_tokens=LMAX,
                on_token=hook,
            ))
            assert await src.drain_wait_quiesced(30.0), "never quiesced"
            pairs = src.drain_take()
            assert len(pairs) == 1
            dst.adopt_seed(src._seed)
            for req, frame in pairs:
                payload = decode_handoff(frame)
                out = await dst.submit_imported(
                    payload["prompt"],
                    first_token=int(payload["first_token"]),
                    k=payload["k"], v=payload["v"],
                    max_new_tokens=int(payload["max_new_tokens"]),
                    spec_state=payload.get("spec_state"),
                )
                src.complete_migrated(req, [int(t) for t in out])
            src.drain_finish()
            return await asyncio.wait_for(task, 30)
        finally:
            await asyncio.wait_for(src.close(), 20)
            await asyncio.wait_for(dst.close(), 20)

    got = run(go())
    np.testing.assert_array_equal(np.asarray(seen), got)
    return got


class TestLearnedLifecycle:
    @method
    def test_suspend_resume_bit_identical(self, tiny, mkw):
        cfg, params = tiny

        def build():
            return GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, spec_draft=2, **mkw
            )

        m_a, m_b = build(), build()
        expect = _uninterrupted(m_a, seed=123)
        got, _ = _suspended(m_b, seed=123)
        np.testing.assert_array_equal(got, expect)
        # zero leaked blocks — main pool fully returned; the draft pool
        # has no allocator at all (static per-slot table), so there is
        # nothing a suspend path could leak by construction
        assert m_b.free_block_count == m_b.kv_blocks - 1

    @method
    def test_suspend_frame_carries_spec_envelope(self, tiny, mkw):
        """The parked frame itself is a codec-v5 handoff: heads ship the
        hidden, draft ships the method tag only."""
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=2, **mkw
        )
        sched = GenerationScheduler(model)
        sched._seed = 5
        seen = []

        def hook(tok):
            seen.append(tok)
            if len(seen) == 3:
                sched.request_preempt()

        async def go():
            try:
                task = asyncio.ensure_future(sched.submit(
                    np.asarray(LPROMPT, np.int32), max_new_tokens=LMAX,
                    on_token=hook,
                ))
                for _ in range(20_000):
                    if sched._suspended:
                        break
                    await asyncio.sleep(0.001)
                assert sched._suspended
                rec = sched._suspended[0]
                frame = sched._suspend_store._frames[rec["key"]]
                payload = decode_handoff(frame)
                spec = payload.get("spec_state")
                if mkw["spec_method"] == "heads":
                    assert spec["method"] == "heads"
                    assert spec["hlast"].shape == (cfg.hidden,)
                else:
                    assert spec == {"method": "draft"}
                sched.request_resume()
                return await task
            finally:
                await asyncio.wait_for(sched.close(), 20)

        out = run(go())
        assert out.size == LMAX

    @method
    def test_drain_migration_bit_identical(self, tiny, mkw):
        cfg, params = tiny

        def build():
            return GenerativeModel(
                cfg, params, n_slots=2, decode_block=4, spec_draft=2, **mkw
            )

        m_a, m_src, m_dst = build(), build(), build()
        expect = _uninterrupted(m_a, seed=321)
        got = _drained(m_src, m_dst, seed=321)
        np.testing.assert_array_equal(got, expect)
        assert m_src.free_block_count == m_src.kv_blocks - 1


# ---------------------------------------------------------------------------
# arbiter time-sharing of the draft model
# ---------------------------------------------------------------------------


class TestDraftArbiterRegistrant:
    def test_draft_prefills_defer_to_sync_points(self, tiny):
        """With an arbiter attached, draft prefills register as a second
        batch-class tenant and run at sync points — output unchanged."""
        from seldon_core_tpu.executor.arbiter import DeviceArbiter

        cfg, params = tiny
        base, _ = _generate(cfg, params, PROMPTS)
        model = GenerativeModel(
            cfg, params, n_slots=4, decode_block=4, spec_draft=2,
            **METHODS["draft"],
        )
        sched = GenerationScheduler(model)
        arb = DeviceArbiter()
        sched.attach_arbiter(arb)
        assert sched._arb_draft_key == f"{model.name}/draft"
        assert model.defer_draft_prefill is True
        assert f"{model.name}/draft" in arb.snapshot()["deployments"]

        async def go():
            try:
                out = await asyncio.gather(
                    *(
                        sched.submit(
                            np.asarray(p, np.int32), max_new_tokens=11
                        )
                        for p in PROMPTS
                    )
                )
                # batch-class work drains once the interactive side goes
                # quiet — wait for the sync points to catch up before
                # asserting (the defer is the point: it must NOT have
                # finished inline with the admissions)
                for _ in range(20_000):
                    if model.draft_prefills >= len(PROMPTS):
                        break
                    await asyncio.sleep(0.001)
                return out
            finally:
                await sched.close()

        out = run(go())
        for a, b in zip(base, out):
            assert np.array_equal(a, b), (a.tolist(), b.tolist())
        assert model.draft_prefills == len(PROMPTS)
        assert not model._pending_draft_prefill
        sched.detach_arbiter()
        assert sched._arb_draft_key is None
        assert model.defer_draft_prefill is False

    def test_inline_without_arbiter(self, tiny):
        """Sole tenant: draft prefills run inline at admission (no defer
        queue builds up)."""
        cfg, params = tiny
        out, model = _generate(
            cfg, params, PROMPTS, spec_draft=2, **METHODS["draft"]
        )
        assert model.draft_prefills == len(PROMPTS)
        assert not model._pending_draft_prefill


# ---------------------------------------------------------------------------
# accounting: HBM ledger classes + per-method telemetry
# ---------------------------------------------------------------------------


class TestSpecAccounting:
    def test_memory_classes_declared(self):
        from seldon_core_tpu.executor.memory import CLASSES

        for cls in ("spec_heads", "draft_weights", "draft_kv"):
            assert cls in CLASSES

    def test_heads_bytes_billed(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=2,
            **METHODS["heads"],
        )
        assert model.spec_heads_bytes > 0
        assert model.draft_weight_bytes == 0

    def test_draft_bytes_billed(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=2,
            **METHODS["draft"],
        )
        assert model.draft_weight_bytes > 0
        assert model.draft_kv_bytes > 0
        # truncate:1 bills exactly the sliced layer stacks — strictly
        # less than the full parameter set (the rest is shared by ref)
        import jax

        full = sum(int(x.nbytes) for x in jax.tree.leaves(params))
        assert model.draft_weight_bytes < full

    @method
    def test_snapshot_splits_acceptance_by_method(self, tiny, mkw):
        cfg, params = tiny
        _, model = _generate(cfg, params, PROMPTS, spec_draft=2, **mkw)
        snap = model.spec_snapshot()
        m = mkw["spec_method"]
        assert snap["spec_method"] == m
        by = snap["accepted_tokens_per_step_by_method"]
        assert list(by) == [m]
        assert by[m] == snap["accepted_tokens_per_step"]

    @method
    def test_timeline_admit_stamps_spec_method(self, tiny, mkw):
        """Forensics satellite: the admit event names the proposer, so a
        timeline read answers "was this request speculating, and how"."""
        from seldon_core_tpu.obs import TIMELINE
        from seldon_core_tpu.utils.tracectx import (
            new_traceparent,
            parse_traceparent,
            set_traceparent,
        )

        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=4, spec_draft=2, **mkw
        )
        sched = GenerationScheduler(model)
        tp = new_traceparent()
        tid = parse_traceparent(tp)[0]

        async def go():
            set_traceparent(tp)
            try:
                return await sched.submit(
                    np.asarray(LPROMPT, np.int32), max_new_tokens=6
                )
            finally:
                await sched.close()

        run(go())
        (entry,) = TIMELINE.by_trace(tid)
        admit = next(e for e in entry["events"] if e["name"] == "admit")
        assert admit["attrs"]["spec_method"] == mkw["spec_method"]

    @method
    def test_usage_meter_attributes_per_method(self, tiny, mkw):
        from seldon_core_tpu.obs.metering import METER

        cfg, params = tiny
        was = METER.enabled
        METER.enabled = True
        METER.reset()
        try:
            # repetitive prompts so SOME draft survives verification
            rep = [np.tile([3, 7, 11], 8).astype(np.int32)]
            _generate(cfg, params, rep, max_new=18, spec_draft=2, **mkw)
            tot = METER.totals()
            m = mkw["spec_method"]
            assert tot.get("tokens_spec_accepted", 0) == tot.get(
                f"tokens_spec_accepted_{m}", 0
            )
        finally:
            METER.enabled = was
            METER.reset()


# ---------------------------------------------------------------------------
# program-key audit + the decode_block=1 rider
# ---------------------------------------------------------------------------


class TestProgramKeyAudit:
    def test_heads_config_pinned(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, spec_draft=2,
            **METHODS["heads"],
        )
        assert model._program_config == (
            0, 2, model.spec_ngram, model.spec_hist, "heads", 3, None,
            None, model.prefill_chunk, model.decode_kernel,
            model.lora_rank, model.lora_slots, model.conf_signal,
        )
        assert "+heads3" in model.variant_sfx

    def test_draft_config_pinned(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(
            cfg, params, n_slots=2, decode_block=2, spec_draft=2,
            **METHODS["draft"],
        )
        assert model._program_config == (
            0, 2, model.spec_ngram, model.spec_hist, "draft", 0,
            ("truncate", 1), None, model.prefill_chunk,
            model.decode_kernel, model.lora_rank, model.lora_slots,
            model.conf_signal,
        )
        assert "+draft:truncate1" in model.variant_sfx

    def test_methods_never_share_compiled_programs(self, tiny):
        """Same (k, window), different proposer → different program cache
        keys: sharing one would run the wrong fused scan."""
        cfg, params = tiny
        keys = []
        for mkw in ({}, METHODS["heads"], METHODS["draft"]):
            model = GenerativeModel(
                cfg, params, n_slots=2, decode_block=2, spec_draft=2, **mkw
            )
            model.admit(0, np.asarray([5, 9, 2], np.int32), 0.0, 0)
            model.step_k(
                np.zeros(2, np.int32), np.zeros(2, bool),
                np.zeros(2, np.float32), 0, np.full(2, -1, np.int32),
                np.zeros(2, np.int32), 2, window=64,
            )
            (key,) = model._decode_k_jit.keys()
            keys.append(key)
        assert len(set(keys)) == len(keys), keys


class TestDecodeBlockRider:
    def test_spec_with_decode_block_one_is_loud(self, tiny):
        """Regression (ISSUE 20 rider): spec_draft with decode_block=1
        used to degrade silently; now it's a build-time error that names
        both knobs."""
        cfg, params = tiny
        with pytest.raises(GraphUnitError) as ei:
            GenerativeModel(
                cfg, params, n_slots=2, decode_block=1, spec_draft=2
            )
        msg = str(ei.value)
        assert "decode_block" in msg and "spec_draft" in msg
        assert "SCT_DECODE_BLOCK" in msg and "SCT_SPEC_DRAFT" in msg

    def test_decode_block_one_without_spec_still_fine(self, tiny):
        cfg, params = tiny
        out, _ = _generate(
            cfg, params, [PROMPTS[0]], max_new=5, decode_block=1
        )
        assert out[0].size == 5
