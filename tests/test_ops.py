"""Pallas kernels pinned to their dense references.

Runs in interpret mode on the CPU harness.  The same kernels are compiled
by Mosaic and held to the same references at the 1B serving geometry by
``chip_smoke.py``'s ``ops`` phase, on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops import flash_attention


def _dense(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        S, Sk = q.shape[2], k.shape[2]
        mask = jnp.arange(S)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [(1, 2, 128, 64), (2, 4, 256, 32), (1, 1, 64, 128)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, shape, causal):
        B, H, S, D = shape
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=shape), jnp.float32)
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense(q, k, v, causal)), rtol=2e-5, atol=2e-5
        )

    def test_multi_block_accumulation(self):
        """More key blocks than query blocks: the online-softmax recurrence
        must rescale across every key tile."""
        B, H, S, D = 1, 2, 512, 64
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(B, H, S, D)) * 3, jnp.float32)  # big logits
        k = jnp.asarray(rng.normal(size=(B, H, S, D)) * 3, jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense(q, k, v, True)), rtol=2e-4, atol=2e-4
        )

    def test_indivisible_seq_rejected(self):
        q = jnp.zeros((1, 1, 100, 64), jnp.float32)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, q, q, block_q=64, block_k=64)

    @pytest.mark.parametrize("window,blocks", [
        (40, (64, 64)),    # the window inside one tile: its lower edge cuts tiles
        (64, (32, 64)),    # the edge on a tile boundary
        (100, (64, 32)),   # key tiles wholly before i - window are skipped
        (1, (32, 32)),     # a query sees itself alone
        (4096, (64, 64)),  # wider than the sequence: plain causal
    ])
    @pytest.mark.parametrize("kv_heads", [4, 2, 1])
    def test_window_matches_dense_mask(self, window, blocks, kv_heads):
        """A sliding window (key j visible to query i iff i - window < j <=
        i) inside the kernel, the tile-skipping test knowing its lower edge
        as it knows the causal upper one, pinned against the dense mask in
        interpret mode; grouped key heads are read by index, not repeated."""
        B, H, S, D = 1, 4, 256, 32
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, kv_heads, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, kv_heads, S, D)), jnp.float32)
        out = flash_attention(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
            window=window,
        )
        kr = jnp.repeat(k, H // kv_heads, axis=1)
        vr = jnp.repeat(v, H // kv_heads, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / np.sqrt(D)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        s = jnp.where((j <= i) & (j > i - window), s, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_window_needs_causal(self):
        q = jnp.zeros((1, 1, 64, 32), jnp.float32)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, q, q, causal=False, window=8)

    @pytest.mark.parametrize("kv_heads,causal", [(4, True), (2, True), (4, False)])
    def test_values_of_their_own_width_and_a_stated_scale(self, kv_heads, causal):
        """Latent attention's prompt: keys 24 wide under values 16 wide, the
        softmax scale stated (not ``D ** -0.5``), grouped or not, over
        several key tiles."""
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(1, 4, 128, 24)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, kv_heads, 128, 24)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, kv_heads, 128, 16)), jnp.float32)
        out = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=32, scale=0.31
        )
        assert out.shape == (1, 4, 128, 16)
        rep = 4 // kv_heads
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, rep, 1)) * 0.31
        if causal:
            s = jnp.where(jnp.arange(128)[:, None] >= jnp.arange(128)[None, :], s, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), jnp.repeat(v, rep, 1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)

    def test_with_neither_given_a_caller_gets_what_it_got(self):
        """No ``scale`` and values as wide as the keys: the kernel of before,
        bit for bit what the scale ``D ** -0.5`` said aloud gives."""
        rng = np.random.default_rng(6)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16) for _ in range(3)
        )
        plain = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        said = flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, scale=1.0 / np.sqrt(64)
        )
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(said))
        assert plain.shape == q.shape and plain.dtype == q.dtype
        with pytest.raises(ValueError, match="share their"):
            flash_attention(q, k[..., :32], v)

    def test_rounded_scores_are_a_control_of_their_own(self):
        """``score_dtype`` (a negative control, never served) rounds each
        tile's scores: what the dense form gives with its scores rounded the
        same way, and no longer what float32 scores give."""
        rng = np.random.default_rng(7)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32) for _ in range(3)
        )
        kw = dict(causal=True, block_q=64, block_k=32, scale=0.4)
        sound = flash_attention(q, k, v, **kw)
        got = flash_attention(q, k, v, score_dtype=jnp.bfloat16, **kw)
        s = jnp.einsum("bhqd,bhkd->bhqk", q * 0.4, k)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        s = jnp.where(jnp.arange(128)[:, None] >= jnp.arange(128)[None, :], s, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
        assert np.abs(np.asarray(got) - np.asarray(sound)).max() > 1e-3


def _dense_seen(q, k, v, seen, scale):
    """The dense form under a visibility matrix ``seen (S, Sk)``, grouped
    keys repeated, a row that sees no key left at zeros."""
    rep = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, rep, 1)) * scale
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, -1e30), -1), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, rep, 1))


class TestFlashTilePlan:
    """PR 44: a causal tile costs what it needs.  The grid steps the live
    tiles alone, and only a tile that straddles the diagonal or the window's
    edge builds a mask."""

    @pytest.mark.parametrize("S,block,want", [
        (6144, 512, (78, 78, 12)),
        (8192, 512, (136, 136, 16)),
        (12288, 512, (300, 300, 24)),   # of the square's 576: 276 never stepped
        (12288, 1024, (78, 78, 12)),
        (512, 512, (1, 1, 1)),
    ])
    def test_counts_of_a_causal_prompt(self, S, block, want):
        from seldon_core_tpu.ops.flash_attention import tile_plan

        assert tile_plan(S, S, block, block, True, None) == want
        n = S // block
        assert tile_plan(S, S, block, block, False, None) == (n * n, n * n, 0)

    @pytest.mark.parametrize("S,Sk,bq,bk,window", [
        (2048, 2048, 256, 256, 600),    # the window's edge cuts tiles
        (2048, 2048, 256, 128, 512),    # the edge on a tile boundary
        (6144, 6144, 512, 512, 4096),   # Command A+'s rung between 4,096 and 8,192
        (1024, 1024, 128, 256, None),   # key tiles wider than query tiles
        (512, 256, 64, 64, 32),         # query tiles that see no key at all
    ])
    def test_counts_are_the_visibility_matrixs(self, S, Sk, bq, bk, window):
        """Against the dense matrix: live = tiles that hold a visible pair,
        masked = those of them that also hold a hidden one, stepped = live +
        one step for a query tile that sees nothing (it writes its zeros)."""
        from seldon_core_tpu.ops.flash_attention import tile_plan

        i, j = np.arange(S)[:, None], np.arange(Sk)[None, :]
        seen = j <= i
        if window is not None:
            seen &= j > i - window
        tiles = seen.reshape(S // bq, bq, Sk // bk, bk)
        live = tiles.any(axis=(1, 3))
        masked = live & ~tiles.all(axis=(1, 3))
        blind = int((~live.any(axis=1)).sum())
        assert tile_plan(S, Sk, bq, bk, True, window) == (
            int(live.sum()) + blind, int(live.sum()), int(masked.sum())
        )

    @staticmethod
    def _steps_by_tile(S, Sk, bq, bk, window, q_offset=0):
        """The step list from each tile's own visibility matrix: ``(query
        tile, key tile, live, masked, first, last)``, key tiles ascending."""
        want = []
        for qi in range(S // bq):
            i = q_offset + qi * bq + np.arange(bq)[:, None]
            row = []
            for ki in range(Sk // bk):
                j = ki * bk + np.arange(bk)[None, :]
                seen = j <= i
                if window is not None:
                    seen &= j > i - window
                if seen.any():
                    row.append((ki, True, not seen.all()))
            row = row or [(0, False, False)]
            want += [
                (qi, ki, live, masked, n == 0, n == len(row) - 1)
                for n, (ki, live, masked) in enumerate(row)
            ]
        return want

    @staticmethod
    def _steps_as_tuples(steps):
        from seldon_core_tpu.ops.flash_attention import _FIRST, _LAST, _LIVE, _MASKED

        return [
            (int(q), int(k), bool(w & _LIVE), bool(w & _MASKED),
             bool(w & _FIRST), bool(w & _LAST))
            for q, k, w in zip(*steps)
        ]

    @pytest.mark.parametrize("S,window", [
        (6144, None), (8192, None), (12288, None),   # Kimi-K2.6's rungs
        (4096, 4096), (6144, 4096), (8192, 4096),    # Command A+'s, windowed
    ])
    def test_the_steps_of_a_whole_prompt_are_what_they_were(self, S, window):
        """``q_offset`` defaults to 0, and the other callers' lists (512 x
        512 tiles) are each tile's own visibility, as before it came."""
        from seldon_core_tpu.ops.flash_attention import _tile_steps

        steps = _tile_steps(S, S, 512, 512, True, window)
        assert all(a.dtype == np.int32 for a in steps)
        assert self._steps_as_tuples(steps) == self._steps_by_tile(S, S, 512, 512, window)
        for a, b in zip(steps, _tile_steps(S, S, 512, 512, True, window, 0)):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("Lq,q_offset,bq,bk", [
        (8192, 0, 512, 512), (8192, 8192, 512, 512), (8192, 16384, 512, 512),
        (4096, 8192, 512, 512),       # Keye-VL-2.0's chunks of 24,576 and 12,288
        (8192, 16384, 256, 512),      # and at the tiles it had before PR 46
        (64, 40, 8, 16),              # an offset that is no multiple of a tile
        (32, 24, 16, 8), (48, 0, 16, 32),
    ])
    def test_a_later_chunks_live_tiles(self, Lq, q_offset, bq, bk):
        """Queries at ``q_offset ..`` over every key so far: a key tile is
        live iff it starts at or before the strip's last query."""
        from seldon_core_tpu.ops.flash_attention import _LIVE, _tile_steps, tile_plan

        Lk = -(-(q_offset + Lq) // bk) * bk
        q_of, k_of, kind = _tile_steps(Lq, Lk, bq, bk, True, None, q_offset)
        want = [
            (qi, ki) for qi in range(Lq // bq) for ki in range(Lk // bk)
            if ki * bk <= q_offset + qi * bq + bq - 1
        ]
        assert list(zip(q_of.tolist(), k_of.tolist())) == want
        assert (kind & _LIVE).all()   # a strip always sees position 0
        stepped, live, _ = tile_plan(Lq, Lk, bq, bk, True, None, q_offset)
        assert stepped == live == len(want)
        if Lq <= 64:   # and by each tile's own visibility, flags and all
            assert self._steps_as_tuples((q_of, k_of, kind)) == self._steps_by_tile(
                Lq, Lk, bq, bk, None, q_offset
            )

    @pytest.mark.parametrize("case,S,H,Hk,D,Dv,window,blocks", [
        ("latent widths, grouped", 256, 4, 2, 192, 128, None, (64, 64)),
        ("one tile", 128, 2, 2, 32, 32, None, (128, 128)),
        ("no multiple of the preferred tile", 192, 2, 1, 32, 32, None, (96, 96)),
        ("a window over many tiles", 1024, 2, 1, 32, 32, 300, (128, 128)),
        ("key tiles wider than query tiles", 512, 2, 2, 32, 32, 200, (64, 256)),
        ("query tiles taller than key tiles", 512, 1, 1, 32, 16, None, (256, 64)),
    ])
    def test_matches_dense(self, case, S, H, Hk, D, Dv, window, blocks):
        rng = np.random.default_rng(len(case))
        q = jnp.asarray(rng.normal(size=(1, H, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, Hk, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, Hk, S, Dv)), jnp.float32)
        out = flash_attention(
            q, k, v, block_q=blocks[0], block_k=blocks[1], window=window, scale=0.11
        )
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (j > i - window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense_seen(q, k, v, seen, 0.11)),
            rtol=2e-5, atol=2e-5,
        )

    def test_a_row_that_sees_no_key_gives_zeros(self):
        """Queries past the keys' window: whole query tiles without a live key
        tile (they step once and write zeros), and rows of a live tile that
        see nothing in it."""
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.normal(size=(1, 2, 256, 32)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
        out = np.asarray(flash_attention(q, k, v, block_q=32, block_k=32, window=32))
        i, j = jnp.arange(256)[:, None], jnp.arange(128)[None, :]
        seen = (j <= i) & (j > i - 32)
        want = np.asarray(_dense_seen(q, k, v, seen, 32 ** -0.5))
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
        assert not out[:, :, 159:].any() and out[:, :, :159].any(axis=-1).all()

    @pytest.mark.parametrize("S,length,window", [
        (2048, 2048, None),    # the rung full: no tile is dead
        (2048, 1025, None),    # one token into the third tile: it straddles, and is whole
        (2048, 1024, None),    # on a tile's edge
        (2048, 1, None),       # one live tile
        (6144, 4100, 4096),    # Command A+'s rung, windowed: dead tiles behind a window
        (1536, 700, None),
    ])
    def test_a_prompts_tiles_end_at_its_real_length(self, S, length, window):
        """PR 58: with the prompt's ``length`` (traced) a query tile that
        starts at or past it runs no product, fetches no key and writes
        zeros; every row before it, and the rest of the tile that straddles
        it, is bit for bit what it is without, whatever the padding holds."""
        from seldon_core_tpu.ops.flash_attention import (
            _FIRST, _LAST, _LIVE, _MASKED, _steps_at, _tile_steps, tile_plan,
        )

        rng = np.random.default_rng(S + length)
        shape = (1, 1, S, 16)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3))
        kw = dict(block_q=512, block_k=512, window=window)
        want = np.asarray(flash_attention(q, k, v, **kw))
        at = jax.jit(lambda q, k, v, n: flash_attention(q, k, v, length=n, **kw))
        got = np.asarray(at(q, k, v, jnp.int32(length)))
        edge = -(-length // 512) * 512   # where the first dead tile starts
        assert np.array_equal(got[:, :, :edge], want[:, :, :edge])
        assert not got[:, :, edge:].any()
        # garbage past the length (large, finite) changes no row before it
        junk = jnp.where(jnp.arange(S)[None, None, :, None] >= length, 3e4, 0.0)
        dirty = np.asarray(at(q + junk, k - junk, v + junk, jnp.int32(length)))
        assert np.array_equal(dirty[:, :, :length], want[:, :, :length])
        assert np.isfinite(dirty).all() and not dirty[:, :, edge:].any()
        # the plan counts what the kernel's lists hold
        static = _tile_steps(S, S, 512, 512, True, window)
        q_of, k_of, kind = (np.asarray(a) for a in _steps_at(static, 512, jnp.int32(length)))
        dead = q_of * 512 >= length
        assert np.array_equal(q_of, static[0])
        assert np.array_equal(k_of[~dead], static[1][~dead])
        assert np.array_equal(kind[~dead], static[2][~dead])
        assert not (kind[dead] & (_LIVE | _MASKED)).any()
        assert np.array_equal(kind[dead], static[2][dead] & (_FIRST | _LAST))
        assert tile_plan(S, S, 512, 512, True, window, length=length) == (
            len(kind), int(np.count_nonzero(kind & _LIVE)),
            int(np.count_nonzero(kind & _MASKED)),
        )
        assert tile_plan(S, S, 512, 512, True, window, length=S) == tile_plan(
            S, S, 512, 512, True, window
        )
        # a dead step names the key tile the step before it left in VMEM
        for t in np.flatnonzero(dead):
            assert k_of[t] == (k_of[t - 1] if t else 0)
        assert dead.sum() == 0 or dead[np.flatnonzero(dead)[0]:].all()  # the list's tail

    def test_an_admission_counts_the_rungs_plans_at_the_prompts_length(self):
        """``admitted_tiles``: what the engine adds up a prompt — over the
        calls traced at the rung WITH a length (Command A+'s two: the windowed
        layers' and the full layer's), not those without; Kimi-K2.6's 8,704
        tokens in the 12,288 rung multiply 153 of 300 tiles."""
        from seldon_core_tpu.ops.flash_attention import admitted_tiles

        def trace(S, heads, window, follows):
            sds = jax.ShapeDtypeStruct((1, heads, S, 16), jnp.float32)
            n = [jax.ShapeDtypeStruct((), jnp.int32)] if follows else []
            jax.eval_shape(
                lambda q, k, v, *n: flash_attention(
                    q, k, v, block_q=512, block_k=512, window=window,
                    length=n[0] if n else None,
                ),
                sds, sds, sds, *n,
            )

        assert admitted_tiles(12800, 9000) == (0, 0)   # no call traced at such a rung
        trace(12288, 3, None, True)
        trace(12288, 5, None, True)    # the same plan from another call site: once
        trace(8192, 3, None, False)    # the rung alone: nothing to follow
        trace(6144, 3, None, True)
        trace(6144, 3, 4096, True)
        assert admitted_tiles(12288, 8704) == (300, 153)
        assert admitted_tiles(12288, 12288) == (300, 300)
        assert admitted_tiles(8192, 7000) == (0, 0)
        assert admitted_tiles(6144, 4576) == (78 + 72, 45 + 45)


class TestFlashBlhdAdapter:
    """Direct unit coverage for ``flash_causal_attention_blhd`` — the
    model-zoo entry (``seq_impl=flash``) — against the dense reference
    (``models/llama.py::_dense_causal_attention``), across sequence
    lengths that are NOT multiples of the preferred 128 tile and across
    GQA head counts (the adapter receives kv already repeated to full
    heads, exactly as ``_layer`` calls it)."""

    def _ref(self, q, k, v):
        from seldon_core_tpu.models.llama import _dense_causal_attention

        return _dense_causal_attention(q, k, v)

    @pytest.mark.parametrize("seq", [48, 96, 120, 192])
    def test_matches_dense_at_non_multiple_of_block_lengths(self, seq):
        from seldon_core_tpu.ops import flash_causal_attention_blhd

        B, H, D = 2, 4, 32
        rng = np.random.default_rng(seq)
        q = jnp.asarray(rng.normal(size=(B, seq, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, seq, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, seq, H, D)), jnp.float32)
        out = flash_causal_attention_blhd(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._ref(q, k, v)),
            rtol=2e-5, atol=2e-5,
        )

    @pytest.mark.parametrize("n_heads,n_kv", [(8, 2), (4, 1), (6, 3)])
    def test_matches_dense_across_gqa_head_counts(self, n_heads, n_kv):
        from seldon_core_tpu.models.llama import _gqa_repeat
        from seldon_core_tpu.ops import flash_causal_attention_blhd

        B, S, D = 1, 80, 16
        rng = np.random.default_rng(n_heads * 10 + n_kv)
        q = jnp.asarray(rng.normal(size=(B, S, n_heads, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, n_kv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, n_kv, D)), jnp.float32)
        kf, vf = _gqa_repeat(k, n_heads), _gqa_repeat(v, n_heads)
        out = flash_causal_attention_blhd(q, kf, vf)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._ref(q, kf, vf)),
            rtol=2e-5, atol=2e-5,
        )

    def test_fit_block_picks_largest_divisor(self):
        from seldon_core_tpu.ops.flash_attention import _fit_block

        assert _fit_block(128) == 128
        assert _fit_block(192) == 96
        assert _fit_block(48) == 48
        assert _fit_block(120) == 120
        assert _fit_block(97) == 97  # <= preferred: one tile, never rejects
        assert _fit_block(131) == 1  # prime past the tile: degrades


class TestFlashInLlama:
    def test_forward_seq_impl_flash_matches_dense(self):
        from seldon_core_tpu.models import llama

        cfg = llama.Config.tiny(max_seq=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 64)), jnp.int32
        )
        dense = llama.forward(params, toks, cfg, seq_impl="dense")
        flash = llama.forward(params, toks, cfg, seq_impl="flash")
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(flash), rtol=5e-4, atol=5e-4
        )

    def test_generative_flash_matches_reference(self):
        from seldon_core_tpu.executor.generation import GenerativeModel
        from seldon_core_tpu.models import llama

        cfg = llama.Config.tiny(max_seq=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompt = np.array([5, 9, 2, 17, 3], np.int32)
        # reference: dense full-forward greedy loop
        toks = list(prompt)
        for _ in range(4):
            logits = llama.forward(
                params, jnp.asarray([toks], jnp.int32), cfg, seq_impl="dense"
            )
            toks.append(int(jnp.argmax(logits[0, -1])))
        expected = toks[len(prompt):]

        model = GenerativeModel(cfg, params, n_slots=1, seq_impl="flash", decode_block=4)
        first = model.admit(0, prompt, 0.0, 0)
        got = [first]
        cur = np.array([first], np.int32)
        toks_seq, act_seq = model.step_k(
            cur,
            np.array([True]),
            np.zeros(1, np.float32),
            0,
            np.array([-1], np.int32),
            np.array([3], np.int32),
            3,
        )
        for i in range(3):
            if act_seq[i, 0]:
                got.append(int(toks_seq[i, 0]))
        assert got == expected


class TestPagedDecodeAttention:
    """Paged decode-attention kernel (docs/PERFORMANCE.md §7) pinned to its
    pure-JAX reference — the exact math ``_decode_paged_multi``'s XLA
    gather path runs — across query counts (plain step and speculative
    verify), positions that are NOT multiples of the KV block size, GQA
    head counts, and the int8 dequant-fusion path."""

    def _rand(self, rng, *shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def _compare(self, S, L, KV, G, D, NB, BS, WB, *, quant=False, seed=0):
        from seldon_core_tpu.ops import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )

        rng = np.random.default_rng(seed)
        H = KV * G
        q = self._rand(rng, S, L, H, D)
        table = jnp.asarray(rng.integers(0, NB, (S, WB)), jnp.int32)
        # positions deliberately off block boundaries
        pos = jnp.asarray(rng.integers(0, WB * BS - L, S), jnp.int32)
        kw = {}
        if quant:
            k = jnp.asarray(
                rng.integers(-127, 128, (NB, BS, KV, D)), jnp.int8
            )
            v = jnp.asarray(
                rng.integers(-127, 128, (NB, BS, KV, D)), jnp.int8
            )
            kw["k_scale"] = jnp.asarray(
                rng.random((NB, BS, KV)) * 0.1, jnp.float32
            )
            kw["v_scale"] = jnp.asarray(
                rng.random((NB, BS, KV)) * 0.1, jnp.float32
            )
        else:
            k = self._rand(rng, NB, BS, KV, D)
            v = self._rand(rng, NB, BS, KV, D)
        out = paged_decode_attention(q, k, v, table, pos, **kw)
        ref = paged_decode_attention_reference(q, k, v, table, pos, **kw)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("L", [1, 3, 5])
    def test_matches_reference_across_query_counts(self, L):
        self._compare(3, L, 2, 2, 16, 9, 16, 3, seed=L)

    @pytest.mark.parametrize("L,window", [(1, 24), (1, 40), (3, 24), (2, 1000)])
    def test_a_sliding_window_reads_its_own_blocks(self, L, window):
        """``first`` + ``window``: the table holds the blocks from the one
        with the window's lower edge on, ``first`` is that block's first
        position, and query ``j`` sees positions ``(pos + j - window, pos +
        j]`` — against dense attention over the slot's rows in order."""
        from seldon_core_tpu.ops import paged_decode_attention

        rng = np.random.default_rng(7 * L + window)
        S, KV, G, D, NB, BS, MB = 3, 2, 4, 16, 40, 8, 12
        H = KV * G
        q = self._rand(rng, S, L, H, D)
        k = self._rand(rng, NB, BS, KV, D)
        v = self._rand(rng, NB, BS, KV, D)
        slot_blocks = jnp.asarray(
            rng.permutation(NB - 1)[: S * MB].reshape(S, MB) + 1, jnp.int32
        )
        pos = jnp.asarray([70, 37, 11], jnp.int32)
        nb = -(-(window + L - 2) // BS) + 1  # blocks that cover the window
        nb = min(nb, MB)
        start = jnp.maximum(pos - window + 1, 0) // BS
        logical = jnp.minimum(start[:, None] + jnp.arange(nb)[None, :], MB - 1)
        table = jnp.take_along_axis(slot_blocks, logical, axis=1)
        out = paged_decode_attention(
            q, k, v, table, pos, first=start * BS, window=window
        )
        # dense: every row of the slot in order, masked by position
        kw = k[slot_blocks].reshape(S, MB * BS, KV, D)
        vw = v[slot_blocks].reshape(S, MB * BS, KV, D)
        qpos = pos[:, None] + jnp.arange(L)[None, :]
        rows = jnp.arange(MB * BS)[None, None, :]
        seen = (rows <= qpos[..., None]) & (rows > qpos[..., None] - window)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", q.reshape(S, L, KV, G, D), kw
        ) / np.sqrt(D)
        s = jnp.where(seen[:, None, None], s, -1e30)
        want = jnp.einsum(
            "bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), vw
        ).reshape(S, L, H, D)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("KV,G", [(1, 4), (2, 2), (3, 2), (4, 1)])
    def test_matches_reference_across_gqa_head_counts(self, KV, G):
        self._compare(2, 2, KV, G, 16, 7, 8, 3, seed=KV * 10 + G)

    @pytest.mark.parametrize("BS,WB", [(4, 7), (16, 2), (8, 5)])
    def test_matches_reference_at_non_multiple_positions(self, BS, WB):
        # pos values land mid-block; the mask must cut inside a KV block
        self._compare(4, 2, 2, 2, 8, 11, BS, WB, seed=BS)

    def test_int8_dequant_fusion_matches_reference(self):
        self._compare(3, 2, 2, 2, 16, 9, 16, 3, quant=True)
        self._compare(2, 1, 2, 4, 8, 5, 4, 4, quant=True, seed=7)

    def test_zero_position_first_token(self):
        # pos = 0 everywhere: only row 0 of block table[ :, 0] is visible
        from seldon_core_tpu.ops import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )

        rng = np.random.default_rng(3)
        q = self._rand(rng, 2, 1, 4, 8)
        k = self._rand(rng, 5, 4, 2, 8)
        v = self._rand(rng, 5, 4, 2, 8)
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        pos = jnp.zeros(2, jnp.int32)
        out = paged_decode_attention(q, k, v, table, pos)
        ref = paged_decode_attention_reference(q, k, v, table, pos)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
        # with one visible row, attention must return exactly that row's v
        np.testing.assert_allclose(
            np.asarray(out[0, 0, 0]), np.asarray(v[1, 0, 0]),
            rtol=2e-5, atol=2e-5,
        )

    def _blocks_case(self, BS, WB, pos, L, *, quant=False, active=None,
                     poison=False, seed=0, KV=2, D=256, pool=jnp.float32,
                     blocks=None, scale=0.02):
        """One call at ``BS``-token blocks, every slot with blocks of its
        own, against the reference; an inactive slot reads nothing and gets
        zeros.  ``poison``: every block past a slot's last query, and every
        block of an inactive slot, holds NaN (an int8 pool: NaN scales).
        The pool's row is ``KV * D`` values of ``pool`` (int8 under
        ``quant``): 2 KB unless a case says otherwise, which is the row PR
        29's tiles were sized at; the queries stay float32, so a bfloat16
        pool's products are exact and the class's tolerance holds.
        ``blocks``: the blocks a step the case means to run at; ``scale``:
        the int8 scales' range (an output is as large as a value and the
        tolerance is absolute: rows of 512 values up to 12.7 would leave
        float32 no room at 2e-5)."""
        from seldon_core_tpu.ops import (
            paged_decode_attention,
            paged_decode_attention_reference,
        )
        from seldon_core_tpu.ops.paged_attention import blocks_per_step

        rng = np.random.default_rng(seed)
        S, G = len(pos), 2
        NB = 1 + S * WB
        if blocks is not None:
            row = KV * D * (1 if quant else jnp.dtype(pool).itemsize)
            assert blocks_per_step(BS, row) == blocks
        q = self._rand(rng, S, L, KV * G, D)
        table = jnp.asarray(
            rng.permutation(NB - 1).reshape(S, WB) + 1, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        act = np.ones(S, bool) if active is None else np.asarray(active)
        kw = {}
        if quant:
            k = jnp.asarray(rng.integers(-127, 128, (NB, BS, KV * D)), jnp.int8)
            v = jnp.asarray(rng.integers(-127, 128, (NB, BS, KV * D)), jnp.int8)
            kw["k_scale"] = jnp.asarray(rng.random((NB, BS, KV)) * scale, jnp.float32)
            kw["v_scale"] = jnp.asarray(rng.random((NB, BS, KV)) * scale, jnp.float32)
        else:
            k = self._rand(rng, NB, BS, KV * D).astype(pool)
            v = self._rand(rng, NB, BS, KV * D).astype(pool)
        ref = paged_decode_attention_reference(
            q, k.reshape(NB, BS, KV, D), v.reshape(NB, BS, KV, D), table,
            pos, **kw)
        if poison:
            last = (np.asarray(pos) + L - 1) // BS
            dead = np.zeros(NB, bool)
            for s_ in range(S):
                cols = np.arange(WB) > (last[s_] if act[s_] else -1)
                dead[np.asarray(table)[s_, cols]] = True
            bad = jnp.asarray(dead)[:, None, None]
            if quant:
                kw = {n: jnp.where(bad, jnp.nan, a) for n, a in kw.items()}
            else:
                k = jnp.where(bad, jnp.nan, k)
                v = jnp.where(bad, jnp.nan, v)
        out = np.asarray(paged_decode_attention(
            q, k, v, table, pos, active=jnp.asarray(act), **kw))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(
            out[act], np.asarray(ref)[act], rtol=2e-5, atol=2e-5)
        assert not out[~act].any()

    # (block, bytes of a pool row, blocks a step): a tile is 512 KB a pool,
    # never under 256 rows, never over 2,048, never under one block
    TILES = [
        # 2-KB rows (8 kv heads of 128 in bfloat16): PR 29's 256 rows
        (16, 2048, 16), (64, 2048, 4), (128, 2048, 2), (256, 2048, 1),
        # 1 KB: 4 kv heads in bfloat16 (Keye-VL-2.0's pool), 8 in int8
        (16, 1024, 32), (64, 1024, 8), (128, 1024, 4), (256, 1024, 2),
        # 512 B: 2 kv heads in bfloat16 (ZAYA1-8B), 4 in int8
        (16, 512, 64), (64, 512, 16), (128, 512, 8), (256, 512, 4),
        # 256 B: 1 kv head in bfloat16 (Jamba2-3B), 2 in int8
        (16, 256, 128), (64, 256, 32), (128, 256, 16), (256, 256, 8),
        # wider than 2 KB: the floor, not the bytes
        (16, 4096, 16), (64, 4096, 4), (128, 4096, 2), (256, 4096, 1),
        # 128 B: 1 kv head in int8 — the cap, not the bytes
        (256, 128, 8),
        # a block larger than the floor is still one block at the least
        (512, 2048, 1), (512, 256, 4),
    ]

    @pytest.mark.parametrize("BS,row,G", TILES)
    def test_blocks_a_step_follow_the_block_and_the_rows_bytes(self, BS, row, G):
        from seldon_core_tpu.ops import paged_attention as pa

        assert pa.blocks_per_step(BS, row) == G
        if row >= 2048 and BS <= 256:
            # PR 29's tile, unchanged: what the rule returned for the block
            # alone, so the 2-KB cells' programs are the parent's
            assert G == max(1, 256 // BS) and G * BS == pa.STEP_ROWS == 256

    @pytest.mark.parametrize("pool,row", [
        (jnp.float32, 1024), (jnp.bfloat16, 512), (jnp.int8, 256)])
    def test_the_rule_reads_the_operands_shape_and_dtype(
            self, monkeypatch, pool, row):
        """What the kernel hands the rule is the pool's own block size and
        ``KV * D`` x its item size: nothing a caller passes."""
        from seldon_core_tpu.ops import paged_attention as pa

        asked = []
        rule = pa.blocks_per_step
        monkeypatch.setattr(
            pa, "blocks_per_step",
            lambda *a: asked.append(a) or rule(*a))
        rng = np.random.default_rng(5)
        q = self._rand(rng, 2, 1, 4, 128)
        k = (self._rand(rng, 5, 32, 256) * 20).astype(pool)
        kw = {}
        if pool == jnp.int8:
            kw = {n: jnp.ones((5, 32, 2), jnp.float32)
                  for n in ("k_scale", "v_scale")}
        pa.paged_decode_attention(
            q, k, k, jnp.asarray([[1, 2], [3, 4]], jnp.int32),
            jnp.asarray([40, 7], jnp.int32), **kw)
        assert asked == [(32, row)]

    # (block, blocks a step) at 2-KB float32 rows: PR 29's cases
    BLOCKS = [(16, 16), (64, 4), (128, 2), (256, 1)]
    # (kv heads, pool dtype, int8, blocks of 256 a step) at a head of 128:
    # KV * D = 256 and 128, the rows of the two newest families
    NARROW = [
        (2, jnp.bfloat16, False, 4),  # 512 B: ZAYA1-8B's pool
        (1, jnp.bfloat16, False, 8),  # 256 B: Jamba2-3B's
        (1, jnp.float32, False, 4),   # 512 B in float32
        (2, jnp.int8, True, 8),       # 256 B: int8 with its scales
        (1, jnp.int8, True, 8),       # 128 B: the cap
    ]
    NARROW_IDS = ["kvd256-bf16", "kvd128-bf16", "kvd128-f32", "kvd256-int8",
                  "kvd128-int8"]

    @pytest.mark.parametrize("L", [1, 3])
    @pytest.mark.parametrize("BS,G", BLOCKS)
    def test_several_blocks_a_step(self, BS, G, L):
        """Three steps of ``G`` blocks (the last one short): positions on
        and off block and step boundaries, a slot at position 0, a slot far
        below the window (two dead steps), the last row of the window."""
        WB = 2 * G + max(1, G // 2)
        top = WB * BS - L
        self._blocks_case(
            BS, WB, [0, BS - 1, BS, 255, 256, 300, 511, top], L,
            seed=BS + L, blocks=G)

    @pytest.mark.parametrize("L", [1, 3])
    @pytest.mark.parametrize("KV,pool,quant,G", NARROW, ids=NARROW_IDS)
    def test_several_blocks_a_step_at_narrow_rows(self, KV, pool, quant, G, L):
        """The same three steps where the row's bytes, not the block, make
        the tile: blocks of 256 at ``KV * D`` = 256 and 128.  A slot at
        position 0, one whose only tile holds one live block of ``G``, one
        on a tile's last row and one on the next tile's first (that tile
        holds ONE live block), one in mid-tile, one whose last (short) tile
        holds one live block, the window's last row."""
        BS = 256
        WB = 2 * G + max(1, G // 2)
        T = G * BS
        top = WB * BS - L
        self._blocks_case(
            BS, WB, [0, 200, T - L, T, T + BS + 7, 2 * T + 5, top], L,
            quant=quant, KV=KV, D=128, pool=pool, seed=G + L, blocks=G)

    @pytest.mark.parametrize("L", [1, 3])
    def test_inactive_slots_read_nothing(self, L):
        self._blocks_case(
            16, 40, [0, 300, 17, 639 - L, 255], L,
            active=[True, False, True, True, False], seed=3 + L, blocks=16)

    @pytest.mark.parametrize("L", [1, 3])
    @pytest.mark.parametrize("KV,pool,quant,G", NARROW, ids=NARROW_IDS)
    def test_inactive_slots_read_nothing_at_narrow_rows(
            self, KV, pool, quant, G, L):
        """Inactive slots between live ones whose tiles are full, short and
        one block of ``G``: the step in flight skips them."""
        T = G * 256
        self._blocks_case(
            256, 2 * G + 1, [0, T + 300, 17, 2 * T + 255 - L, T - 1, T], L,
            active=[True, False, True, True, False, True],
            quant=quant, KV=KV, D=128, pool=pool, seed=7 + G + L, blocks=G)

    # an int8 row of 512 values is 512 B: 64 blocks of 16 a step, 4 of 256
    @pytest.mark.parametrize(
        "BS,WB", [(16, 40), (16, 3), (256, 3), (16, 150), (256, 10)])
    def test_int8_with_several_blocks_a_step(self, BS, WB):
        top = WB * BS - 2
        self._blocks_case(
            BS, WB, [0, top // 3, top], 2, quant=True,
            active=[True, True, True], seed=BS + WB)

    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("BS", [16, 256])
    def test_dead_blocks_are_not_read(self, BS, quant):
        """Blocks past a slot's position and the blocks of inactive slots
        hold NaN: the output is finite and what the clean pool gives."""
        WB = 40 if BS == 16 else 3
        top = WB * BS - 2
        self._blocks_case(
            BS, WB, [0, 5, top // 2, 300, top], 2, quant=quant, poison=True,
            active=[True, True, True, False, True], seed=BS + quant)

    @pytest.mark.parametrize("KV,pool,quant,G", NARROW, ids=NARROW_IDS)
    def test_dead_blocks_are_not_read_at_narrow_rows(self, KV, pool, quant, G):
        """The same at several blocks of 256 a step: a tile's blocks past the
        slot's last query are not fetched, though the tile is attended."""
        WB = 2 * G + 1
        T = G * 256
        top = WB * 256 - 2
        self._blocks_case(
            256, WB, [0, 5, T + 3, 300, T - 2, top], 2, quant=quant,
            poison=True, active=[True, True, True, False, True, True],
            KV=KV, D=128, pool=pool, seed=G + quant, blocks=G)

    @pytest.mark.parametrize("BS,KV,D,pool,G,window,pos,MB", [
        # 16-token blocks of 2-KB rows: the window's blocks span three
        # steps of 16
        (16, 2, 256, jnp.float32, 16, 500, [930, 411, 37], 60),
        # blocks of 256 at 512-B and 256-B rows, four and eight a step: the
        # window's lower edge falls INSIDE a tile (its first blocks dead)
        (256, 2, 128, jnp.bfloat16, 4, 1500, [3400, 2100, 300], 14),
        (256, 1, 128, jnp.bfloat16, 8, 2600, [5800, 4000, 2700], 24),
    ], ids=["bs16-2KB", "bs256-512B", "bs256-256B"])
    def test_a_sliding_window_over_several_steps(
            self, BS, KV, D, pool, G, window, pos, MB):
        """``first`` + ``window``: the window's blocks span several steps,
        and blocks before its lower edge (NaN here) are not fetched even
        inside a step that is."""
        from seldon_core_tpu.ops import paged_decode_attention
        from seldon_core_tpu.ops.paged_attention import blocks_per_step

        assert blocks_per_step(BS, KV * D * jnp.dtype(pool).itemsize) == G
        rng = np.random.default_rng(11)
        S, L, GQ = 3, 2, 2
        NB = 1 + S * MB
        q = self._rand(rng, S, L, KV * GQ, D)
        k = self._rand(rng, NB, BS, KV * D).astype(pool)
        v = self._rand(rng, NB, BS, KV * D).astype(pool)
        slot_blocks = jnp.asarray(
            rng.permutation(NB - 1).reshape(S, MB) + 1, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        nb = -(-(window + L - 2) // BS) + 1  # blocks that cover the window
        # the table starts two blocks below the window's lower edge
        start = jnp.maximum((pos - window + 1) // BS - 2, 0)
        logical = jnp.minimum(start[:, None] + jnp.arange(nb + 2)[None, :], MB - 1)
        table = jnp.take_along_axis(slot_blocks, logical, axis=1)
        kw = k[slot_blocks].reshape(S, MB * BS, KV, D)
        vw = v[slot_blocks].reshape(S, MB * BS, KV, D)
        qpos = pos[:, None] + jnp.arange(L)[None, :]
        rows = jnp.arange(MB * BS)[None, None, :]
        seen = (rows <= qpos[..., None]) & (rows > qpos[..., None] - window)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", q.reshape(S, L, KV, GQ, D), kw) / np.sqrt(D)
        s = jnp.where(seen[:, None, None], s, -1e30)
        want = jnp.einsum(
            "bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), vw
        ).reshape(S, L, KV * GQ, D)
        # poison what no query sees, by whole blocks
        blk_seen = np.asarray(seen.any(1)).reshape(S, MB, BS).any(-1)
        dead = np.ones(NB, bool)
        dead[np.asarray(slot_blocks)[blk_seen]] = False
        bad = jnp.asarray(dead)[:, None, None]
        out = paged_decode_attention(
            q, jnp.where(bad, jnp.nan, k), jnp.where(bad, jnp.nan, v),
            table, pos, first=start * BS, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)

    def test_in_model_decode_matches_dense_path(self):
        """The kernel call site inside ``decode_slots_paged``: one decode
        step with kernel on equals the XLA gather path bit-for-bit-ish
        (same fp32 accumulation; interpret mode on CPU)."""
        from seldon_core_tpu.models import llama

        cfg = llama.Config.tiny(max_seq=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        for kv_dtype in (None, "int8"):
            cache = llama.init_paged_cache(cfg, 2, 9, 16, kv_dtype=kv_dtype)
            row = np.zeros(4, np.int32)
            row[:4] = np.arange(1, 5)
            logits, cache = llama.prefill_slot_paged(
                params,
                jnp.asarray(np.arange(1, 17)[None, :], jnp.int32),
                jnp.int32(16), jnp.int32(0), jnp.asarray(row), cache, cfg,
            )
            tok = jnp.asarray([int(jnp.argmax(logits)), 0], jnp.int32)
            act = jnp.asarray([True, False])
            dense_logits, _ = llama.decode_slots_paged(
                params, tok, dict(cache), act, cfg, window=64, kernel=False
            )
            kern_logits, _ = llama.decode_slots_paged(
                params, tok, dict(cache), act, cfg, window=64, kernel=True
            )
            np.testing.assert_allclose(
                np.asarray(dense_logits[0]), np.asarray(kern_logits[0]),
                rtol=2e-5, atol=2e-5,
            )


def _old_pool_read(pool, li, read_idx, kv_sharded=False):
    """The read this repo had before ``llama._pool_read``: cut the layer out
    of the pool, then gather from the layer's view.  Kept here only as the
    yardstick: same elements, same order, one whole-layer copy more."""
    layer = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
    return layer[read_idx]


class TestPoolRead:
    """The XLA paged read addresses the carried pool by (layer, block) in
    one gather (``llama._pool_read``); a pool whose kv heads are split over
    a mesh (``kv_sharded``) keeps the layer-first read.  Either is a choice
    of addressing only: every program that reads the pool is bit-equal to
    the same program on the old read, and the one-device lowering cuts no
    layer out of the pool."""

    S, NB, BS = 3, 17, 8

    def _setup(self, pool, kv_sharded=False):
        """A tiny model and a pool full of random rows (the sink block 0
        too); the table's blocks are out of order, slots 0 and 1 share two
        prefix blocks, and entries past a slot's length point at the sink.
        ``kv_sharded``: the same rows in the pool a mesh splits by head."""
        from seldon_core_tpu.models import llama

        dtype = jnp.bfloat16 if pool == "bfloat16" else jnp.float32
        cfg = llama.Config.tiny(max_seq=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype)
        cache = llama.init_paged_cache(
            cfg, self.S, self.NB, self.BS, dtype,
            kv_dtype="int8" if pool == "int8" else None,
            kv_sharded=kv_sharded,
        )
        rng = np.random.default_rng(7)
        for name in ("k", "v", "k_scale", "v_scale"):
            if name not in cache:
                continue
            shape, dt = cache[name].shape, cache[name].dtype
            if dt == jnp.int8:
                rows = rng.integers(-127, 128, shape)
            elif name.endswith("_scale"):
                rows = rng.uniform(0.001, 0.02, shape)
            else:
                rows = rng.standard_normal(shape)
            cache[name] = jnp.asarray(rows).astype(dt)
        cache["table"] = jnp.asarray(
            [[11, 3, 14, 2, 9, 16, 0, 0],
             [11, 3, 7, 0, 0, 0, 0, 0],
             [5, 13, 1, 8, 15, 4, 10, 12]], jnp.int32)
        cache["pos"] = jnp.asarray([43, 17, 60], jnp.int32)
        return llama, cfg, params, cache

    def _run(self, which, window, llama, cfg, params, cache, **kw):
        """One of the three pool readers, as a fresh trace of ``llama`` as
        it stands (so a patched ``_pool_read`` is what gets traced)."""
        active = jnp.asarray([True, True, False])
        if which == "decode":
            return llama.decode_slots_paged(
                params, jnp.asarray([5, 9, 2], jnp.int32), dict(cache),
                active, cfg, window=window, **kw,
            )
        if which == "spec":
            qtokens = jnp.asarray(
                np.arange(12).reshape(self.S, 4) + 3, jnp.int32)
            qvalid = jnp.asarray(
                [[True] * 4, [True, True, False, False], [False] * 4])
            return llama.decode_slots_spec_paged(
                params, qtokens, dict(cache), active, qvalid, cfg,
                window=window, **kw,
            )
        # suffix prefill of slot 1: ``window // 2`` rows of prefix are read
        # from slot 0's blocks, 16 new rows go to block 6 and the sink
        row = cache["table"][0]
        return llama.prefill_suffix_paged(
            params, jnp.asarray(np.arange(16)[None, :] + 1, jnp.int32),
            jnp.int32(window // 2), jnp.int32(window // 2 + 11),
            jnp.int32(1), row, jnp.asarray([6, 0], jnp.int32), dict(cache),
            cfg, prefix_window=window // 2, **kw,
        )

    @pytest.mark.parametrize("window", [16, 64])
    @pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("which", ["decode", "spec", "suffix"])
    @pytest.mark.parametrize("against", ["old-read", "kv-sharded-read"])
    def test_bit_equal(self, against, which, pool, window, monkeypatch):
        setup = self._setup(pool)
        new_logits, new_cache = self._run(which, window, *setup)
        if against == "old-read":
            monkeypatch.setattr(setup[0], "_pool_read", _old_pool_read)
            old_logits, old_cache = self._run(which, window, *setup)
        else:
            old_logits, old_cache = self._run(
                which, window, *self._setup(pool, kv_sharded=True),
                kv_sharded=True)
        assert new_logits.dtype == old_logits.dtype
        assert np.array_equal(
            np.asarray(new_logits, np.float32), np.asarray(old_logits, np.float32)
        )
        assert new_cache.keys() == old_cache.keys()
        for name in new_cache:
            assert np.array_equal(
                np.asarray(new_cache[name], np.float32).reshape(
                    old_cache[name].shape),
                np.asarray(old_cache[name], np.float32),
            ), name

    def _layer_slices(self, which, llama, cfg, params, cache, **kw):
        """``dynamic_slice`` lines of the lowered program whose result is
        one layer of a pool tensor, ``(1, n_blocks, block, kv[, hd])``."""
        text = jax.jit(
            lambda p, c: self._run(which, 32, llama, cfg, p, c, **kw)
        ).lower(params, cache).as_text()
        layer = "tensor<" + "x".join(
            map(str, (1,) + cache["k"].shape[1:4])) + "x"
        return [
            line for line in text.splitlines()
            if "dynamic_slice" in line and layer in line.split("->")[-1]
        ]

    @pytest.mark.parametrize("pool", ["float32", "int8"])
    @pytest.mark.parametrize("which", ["decode", "spec", "suffix"])
    def test_lowering_cuts_no_layer_out_of_the_pool(self, which, pool):
        setup = self._setup(pool)
        assert self._layer_slices(which, *setup) == []
        # the guard sees the layer-first read where it is chosen: K and V,
        # and an int8 pool's two scales
        found = self._layer_slices(
            which, *self._setup(pool, kv_sharded=True), kv_sharded=True)
        assert len(found) == (4 if pool == "int8" else 2), found


def _parents_masked_flash_attention(q, k, v, mask, *, q_offset, block_q, block_k):
    """``masked_flash_attention`` as it stood before PR 46, kept to hold the
    kernel's bits: one-lane statistics cut out of the scratch and broadcast
    back, two selects a score, the square grid with clamped index maps."""
    import functools
    import math

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops.paged_attention import NEG_INF, mxu_operands

    def kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
               *, bq, bk, n_k, scale):
        qi, ki = pl.program_id(1), pl.program_id(2)
        G, D = q_ref.shape[1], q_ref.shape[3]

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        cdt, prec = mxu_operands(q_ref.dtype)

        @pl.when(ki * bk <= q_offset + qi * bq + bq - 1)
        def _tile():
            qs = (q_ref[0] * scale).astype(cdt).reshape(G * bq, D)
            s = jax.lax.dot_general(
                qs, k_ref[0].astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ).reshape(G, bq, bk)
            s = jnp.where((mask_ref[...] != 0)[None], s, NEG_INF).reshape(G * bq, bk)
            m_prev = m_scr[:, 0]
            l_prev = l_scr[:, 0]
            m_cur = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_cur[:, None]), 0.0)
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = alpha * l_prev + p.sum(axis=-1)
            acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
                p.astype(cdt), v_ref[0].astype(cdt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            m_scr[:] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

        @pl.when(ki == n_k - 1)
        def _emit():
            l = l_scr[:, 0]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[:] / safe_l[:, None]).reshape(G, bq, D).astype(o_ref.dtype)

    H, Lq, D = q.shape
    KV, Lk = k.shape[:2]
    G, bq, bk = H // KV, block_q, block_k
    n_k = Lk // bk

    def last(qi):
        return jnp.minimum((q_offset + qi * bq + bq - 1) // bk, n_k - 1)

    return pl.pallas_call(
        functools.partial(kernel, bq=bq, bk=bk, n_k=n_k, scale=1.0 / math.sqrt(D)),
        grid=(KV, Lq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda h, qi, ki: (h, 0, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda h, qi, ki: (h, jnp.minimum(ki, last(qi)), 0)),
            pl.BlockSpec((1, bk, D), lambda h, qi, ki: (h, jnp.minimum(ki, last(qi)), 0)),
            pl.BlockSpec((bq, bk), lambda h, qi, ki: (qi, jnp.minimum(ki, last(qi)))),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D), lambda h, qi, ki: (h, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((KV, G, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, 128), jnp.float32),
            pltpu.VMEM((G * bq, 128), jnp.float32),
            pltpu.VMEM((G * bq, D), jnp.float32),
        ],
        interpret=True,
    )(q.reshape(KV, G, Lq, D), k, v, mask).reshape(H, Lq, D)


class TestSparseAttention:
    """``ops/sparse_attention.py``: the two prompt kernels in interpret mode
    against their XLA references, and the decode read (XLA: a gather of the
    selected rows) against dense attention under the same selection."""

    HI, DI, TOPK = 4, 8, 16

    def _index(self, lq, lk, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (
            jax.random.normal(ks[0], (lq, self.HI, self.DI)),
            jax.random.normal(ks[1], (lq, self.HI)),
            jax.random.normal(ks[2], (lk, self.DI)),
        )

    @pytest.mark.parametrize("q_offset", [0, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_the_selection_is_the_stable_sorts(self, q_offset, dtype):
        from seldon_core_tpu.ops import sparse_attention as sa

        lq = 32
        qi, wi, ki = self._index(lq, q_offset + lq)
        qi, ki = qi.astype(dtype), ki.astype(dtype)
        got = sa.select_topk_mask(
            qi, wi, ki, topk=self.TOPK, q_offset=q_offset, block_q=16, block_k=32
        )
        want = sa.select_topk_mask_reference(
            qi, wi, ki, topk=self.TOPK, q_offset=q_offset
        )
        assert got.dtype == jnp.int8 and int((got != want).sum()) == 0
        rows = np.asarray(got).sum(1)
        t = q_offset + np.arange(lq)
        np.testing.assert_array_equal(rows, np.minimum(self.TOPK, t + 1))
        # nothing after a query's own position
        assert not np.triu(np.asarray(got), k=q_offset + 1).any()

    @pytest.mark.parametrize("case", ["all equal", "two levels", "negative zero"])
    def test_ties_go_to_the_lower_positions(self, case):
        """Equal scores at the ``topk``-th place: the lower positions are
        taken, as a stable descending sort takes them."""
        from seldon_core_tpu.ops import sparse_attention as sa

        lq, off = 16, 64
        qi, wi, ki = self._index(lq, off + lq, seed=1)
        if case == "all equal":
            qi = qi * 0
        elif case == "two levels":
            # scores take few distinct values: many ties at every level
            qi = jnp.round(qi)
            ki = jnp.round(ki)
            wi = jnp.round(wi)
        else:  # every product rectified to zero under negative weights
            qi, ki, wi = -jnp.abs(qi), jnp.abs(ki), -jnp.abs(wi)
        got = sa.select_topk_mask(
            qi, wi, ki, topk=self.TOPK, q_offset=off, block_q=16, block_k=16
        )
        want = sa.select_topk_mask_reference(qi, wi, ki, topk=self.TOPK, q_offset=off)
        assert int((got != want).sum()) == 0
        if case != "two levels":
            assert np.asarray(got)[:, : self.TOPK].all()

    def _qkv(self, lq, lk, h, kv, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        return (
            jax.random.normal(ks[0], (h, lq, d)).astype(dtype),
            jax.random.normal(ks[1], (kv, lk, d)).astype(dtype),
            jax.random.normal(ks[2], (kv, lk, d)).astype(dtype),
        )

    # (queries, q_offset, query heads, kv heads, (block_q, block_k))
    MASKED_SHAPES = {
        "no offset": (32, 0, 8, 2, (16, 32)),
        "two kv heads": (32, 64, 8, 2, (16, 32)),
        # eight heads a step, four and five key tiles a strip, the offset no
        # multiple of a key tile
        "a group of eight": (24, 40, 8, 1, (8, 16)),
        "from the first key": (32, 0, 16, 2, (8, 16)),
    }

    @pytest.mark.parametrize("case,dtype", [
        ("no offset", jnp.float32),
        ("two kv heads", jnp.float32), ("two kv heads", jnp.bfloat16),
        ("a group of eight", jnp.float32), ("a group of eight", jnp.bfloat16),
    ])
    def test_tiled_attention_under_the_mask(self, case, dtype):
        from seldon_core_tpu.ops import sparse_attention as sa

        lq, q_offset, h, kv, (bq, bk) = self.MASKED_SHAPES[case]
        lk = q_offset + lq
        q, k, v = self._qkv(lq, lk, h, kv, 16, dtype)
        mask = sa.select_topk_mask_reference(
            *self._index(lq, lk), topk=self.TOPK, q_offset=q_offset
        )
        got = sa.masked_flash_attention(
            q, k, v, mask, q_offset=q_offset, block_q=bq, block_k=bk
        )
        want = sa.masked_attention_reference(q, k, v, mask)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
        )

    def test_a_row_that_selects_nothing_gives_zeros(self):
        from seldon_core_tpu.ops import sparse_attention as sa

        q = jnp.ones((2, 16, 8))
        kv = jnp.ones((1, 32, 8))
        mask = jnp.zeros((16, 32), jnp.int8).at[3:, 0].set(1)
        out = np.asarray(sa.masked_flash_attention(q, kv, kv, mask, block_q=8, block_k=16))
        assert not out[:, :3].any() and np.allclose(out[:, 3:], 1.0)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_a_rows_first_selected_key_may_lie_in_a_later_tile(self, dtype):
        """The running max starts at ``M_INIT``: over live tiles that hold
        nothing of a row's it stays zeros (no exponent of a masked score is
        1), and from the row's first selected key on it is the reference's."""
        from seldon_core_tpu.ops import sparse_attention as sa

        lq, q_offset, bk = 16, 48, 16
        lk = q_offset + lq   # four key tiles, every one live for every row
        q, k, v = self._qkv(lq, lk, 8, 1, 16, dtype)
        first = np.asarray([0, 17, 33, 50, 16, 47, 48, 63] * 2)  # tile 0 .. 3
        mask = (np.arange(lk)[None, :] >= first[:, None]) & (
            np.arange(lk)[None, :] <= q_offset + np.arange(lq)[:, None]
        ) & (np.arange(lk)[None, :] % 3 != 1)
        mask[7] = mask[15] = False   # 63 lies after rows 7's and 15's own place
        mask[15, 63] = True
        mask = jnp.asarray(mask, jnp.int8)
        got = np.asarray(sa.masked_flash_attention(
            q, k, v, mask, q_offset=q_offset, block_q=8, block_k=bk
        ), np.float32)
        want = np.asarray(sa.masked_attention_reference(q, k, v, mask), np.float32)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
        assert not got[:, 7].any() and got[:, 15].any()
        # one key selected: the row is that key's value
        np.testing.assert_allclose(
            got[:, 15], np.asarray(v, np.float32)[0, 63][None].repeat(8, 0), atol=tol
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("case", list(MASKED_SHAPES))
    def test_the_masked_kernel_gives_its_parents_bits(self, case, dtype):
        """Whole-vreg statistics, one select a score and the grid over live
        tiles change layouts and which steps run, not the sums or their
        order (PERF.md §6, PR 46): equal to the kernel as it stood, bit for
        bit, rows with nothing selected and late first keys among them."""
        from seldon_core_tpu.ops import sparse_attention as sa

        lq, q_offset, h, kv, (bq, bk) = self.MASKED_SHAPES[case]
        lk = q_offset + lq
        q, k, v = self._qkv(lq, lk, h, kv, 16, dtype)
        mask = sa.select_topk_mask_reference(
            *self._index(lq, lk), topk=self.TOPK, q_offset=q_offset
        )
        mask = mask.at[3].set(0).at[5, : lk - 8].set(0)
        got = sa.masked_flash_attention(
            q, k, v, mask, q_offset=q_offset, block_q=bq, block_k=bk
        )
        want = _parents_masked_flash_attention(
            q, k, v, mask, q_offset=q_offset, block_q=bq, block_k=bk
        )
        assert got.dtype == want.dtype == dtype
        assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
        assert not np.asarray(got, np.float32)[:, 3].any()
        # a row's sums run over a key tile: a taller query tile, the same bits
        taller = sa.masked_flash_attention(
            q, k, v, mask, q_offset=q_offset, block_q=lq, block_k=bk
        )
        assert np.array_equal(np.asarray(taller, np.float32), np.asarray(want, np.float32))

    def test_the_decode_read_attends_the_selected_rows_alone(self):
        from seldon_core_tpu.ops import sparse_attention as sa

        s, h, d, kv, nr, k = 3, 8, 16, 2, 200, 20
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (s, h, d))
        k_rows = jax.random.normal(ks[1], (nr, kv * d))
        v_rows = jax.random.normal(ks[2], (nr, kv * d))
        rows = jnp.stack([
            jax.random.permutation(jax.random.fold_in(ks[3], i), nr)[:k]
            for i in range(s)
        ])
        count = jnp.asarray([20, 0, 7])
        got = sa.sparse_decode_attention(q, k_rows, v_rows, rows, count)
        # dense attention over the whole pool under the same selection
        sel = np.zeros((s, nr), bool)
        for i in range(s):
            sel[i, np.asarray(rows)[i, : int(count[i])]] = True
        qg = q.reshape(s, kv, h // kv, d)
        sc = jnp.einsum("bkgd,skd->bkgs", qg, k_rows.reshape(nr, kv, d)) / np.sqrt(d)
        sc = jnp.where(sel[:, None, None, :], sc, -jnp.inf)
        p = jnp.where(sel[:, None, None, :], jax.nn.softmax(sc, -1), 0.0)
        want = jnp.einsum("bkgs,skd->bkgd", p, v_rows.reshape(nr, kv, d))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want.reshape(s, h, d)), atol=2e-5, rtol=2e-5
        )
        assert not np.asarray(got)[1].any()  # a slot that selects nothing


# (name, slots' positions (under 0: inactive), block, table columns, topk,
#  pool dtype, score dtype, what the index data is)
DECODE_SELECT_CASES = [
    ("float32 pool", (4, 50, 79), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("bfloat16 pool", (52, 133, 29, 142, 175), 16, 11, 40, jnp.bfloat16, jnp.float32, "normal"),
    ("ties: every score equal", (70, 33), 16, 5, 24, jnp.float32, jnp.float32, "zero"),
    ("ties: few levels", (79, 41, 60), 16, 5, 24, jnp.float32, jnp.float32, "levels"),
    ("ties: negative zero", (79, 30), 16, 5, 24, jnp.float32, jnp.float32, "negative zero"),
    ("fewer seen than topk", (0, 7, 22, 23), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("pos at a block's last token", (31, 47, 63), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("pos at a block's first token", (32, 48, 64), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("pos one past a block's first", (33, 49, 65), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("an inactive slot", (60, -1, 45), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("every slot inactive", (-1, -1), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("a window of 3 blocks", (47, 20, 33), 16, 3, 8, jnp.float32, jnp.float32, "normal"),
    ("a window of 11 blocks", (175, 90, 128), 16, 11, 40, jnp.float32, jnp.float32, "normal"),
    ("the window's last token, and past it", (79, 200), 16, 5, 24, jnp.float32, jnp.float32, "normal"),
    ("topk no whole blocks", (79, 50), 16, 5, 20, jnp.float32, jnp.float32, "normal"),
    ("topk under a block", (40, 5), 16, 4, 8, jnp.float32, jnp.float32, "normal"),
    ("blocks of 128", (1000, 300, 129), 128, 9, 256, jnp.bfloat16, jnp.float32, "normal"),
    ("bfloat16 scores, the control", (79, 41, 60), 16, 5, 24, jnp.bfloat16, jnp.bfloat16, "small integers"),
    ("bfloat16 scores over a float32 pool", (79, 30), 16, 5, 24, jnp.float32, jnp.bfloat16, "small integers"),
]


class TestDecodeSelection:
    """``ops/sparse_attention.py::select_decode_topk`` (interpret mode): the
    decode step's selection as one kernel, held to the XLA lines it replaces
    (``select_decode_topk_reference``: the window gathered, scored, masked
    and sorted) — the same SET of pool rows, as many of them real as are
    seen up to ``topk``, each once, the rest of a row in bounds."""

    HI, DI = 4, 8

    def _inputs(self, case):
        _, pos, bs, wb, topk, dtype, score_dtype, data = case
        S = len(pos)
        ks = jax.random.split(jax.random.PRNGKey(len(case[0])), 4)
        nb = 2 * S * wb + 3
        qi = jax.random.normal(ks[0], (S, self.HI, self.DI))
        wi = jax.random.normal(ks[1], (S, self.HI))
        ik = jax.random.normal(ks[2], (nb, self.DI, bs))
        if data == "zero":
            qi = qi * 0
        elif data == "levels":  # few distinct scores: ties at every level
            qi, wi, ik = jnp.round(qi), jnp.round(wi), jnp.round(ik)
        elif data == "negative zero":  # every product rectified to zero
            qi, ik, wi = -jnp.abs(qi), jnp.abs(ik), -jnp.abs(wi)
        elif data == "small integers":  # exact in bfloat16 in any order
            qi, ik = jnp.sign(jnp.round(qi)), jnp.sign(jnp.round(ik))
            wi = jnp.abs(jnp.sign(jnp.round(wi))) * 2
        # a slot's blocks lie scattered over the pool, out of order
        table = jax.random.permutation(ks[3], nb)[: S * wb].reshape(S, wb)
        return (qi.astype(dtype), wi, ik.astype(dtype), table,
                jnp.asarray(pos, jnp.int32))

    @pytest.mark.parametrize(
        "case", DECODE_SELECT_CASES, ids=[c[0] for c in DECODE_SELECT_CASES]
    )
    def test_the_kernel_selects_the_set_the_xla_lines_select(self, case):
        from seldon_core_tpu.ops import sparse_attention as sa

        _, pos, bs, wb, topk, _, score_dtype, data = case
        args = self._inputs(case)
        kw = dict(topk=topk, score_dtype=score_dtype)
        got, read = map(np.asarray, sa.select_decode_topk(*args, **kw))
        want, gathered = map(np.asarray, sa.select_decode_topk_reference(*args, **kw))
        assert got.shape == want.shape == (len(pos), topk) and got.dtype == np.int32
        # the kernel's own count of the blocks it brought in: the live ones
        # (none for a slot that is not active); the XLA lines' the window
        live = [0 if p < 0 else min(p // bs + 1, wb) for p in pos]
        np.testing.assert_array_equal(read, live)
        np.testing.assert_array_equal(gathered, [wb] * len(pos))
        table = np.asarray(args[3])
        for s, p in enumerate(pos):
            n = min(topk, min(p, wb * bs - 1) + 1)  # seen, up to topk: the real ones
            np.testing.assert_array_equal(np.sort(got[s, :n]), np.sort(want[s, :n]))
            assert len(set(got[s, :n].tolist())) == n
            assert (got[s, n:] == 0).all()  # in bounds, and never read as real
            if data in ("zero", "negative zero"):  # equal scores: the lowest positions
                at = np.arange(n)
                np.testing.assert_array_equal(got[s, :n], table[s, at // bs] * bs + at % bs)

    def test_slots_that_share_blocks_select_from_the_same_keys(self):
        """Prefix reuse: two slots' tables name the same blocks, and a third
        slot's scratch rows from the slot before it are not its own."""
        from seldon_core_tpu.ops import sparse_attention as sa

        qi, wi, ik, table, _ = self._inputs(DECODE_SELECT_CASES[0])
        table = table.at[1].set(table[0])
        qi = qi.at[1].set(qi[0])
        wi = wi.at[1].set(wi[0])
        pos = jnp.asarray([79, 79, 17])
        got = np.asarray(sa.select_decode_topk(qi, wi, ik, table, pos, topk=24)[0])
        want = np.asarray(
            sa.select_decode_topk_reference(qi, wi, ik, table, pos, topk=24)[0]
        )
        np.testing.assert_array_equal(got[0], got[1])
        np.testing.assert_array_equal(  # every seen key, in the order of its position
            got[2, :18], np.asarray(table)[2, np.arange(18) // 16] * 16 + np.arange(18) % 16
        )
        np.testing.assert_array_equal(np.sort(got[0]), np.sort(want[0]))

    def test_a_topk_past_the_window_is_refused(self):
        from seldon_core_tpu.ops import sparse_attention as sa

        qi, wi, ik, table, pos = self._inputs(DECODE_SELECT_CASES[0])
        with pytest.raises(ValueError, match="more than the window"):
            sa.select_decode_topk(qi, wi, ik, table[:, :1], pos, topk=200)



# (name, slots, heads, latent, rotary, block, table columns, rows a step,
#  positions, active)
LATENT_READ_CASES = [
    ("one block", 2, 4, 16, 8, 8, 1, 8, [5, 7], None),
    ("many blocks, several a step", 3, 4, 16, 8, 4, 9, 8, [33, 17, 2], None),
    ("many steps of one block", 2, 2, 32, 4, 8, 5, 8, [39, 12], None),
    ("a slot at position 0", 3, 4, 16, 8, 4, 4, 8, [0, 9, 15], None),
    ("slots that read nothing", 4, 4, 16, 8, 4, 4, 4, [6, 11, 0, 13], [False, True, False, True]),
    ("a window that is no whole step", 2, 4, 16, 8, 4, 5, 8, [19, 4], None),
]


class TestLatentDecodeRead:
    """``ops/mla_attention.py``: a decode step's read of the latent pool, in
    interpret mode, against its XLA reference and against the expanded
    mathematics it absorbs."""

    def _inputs(self, case, dtype=jnp.float32):
        _, S, H, C, R, BS, WB, _, pos, active = case
        rng = np.random.default_rng(len(case[0]))
        NB = S * WB + 3
        ql = jnp.asarray(rng.normal(size=(S, H, C)), dtype)
        qr = jnp.asarray(rng.normal(size=(S, H, R)), dtype)
        c = jnp.asarray(rng.normal(size=(NB, BS, C)), dtype)
        krt = jnp.asarray(rng.normal(size=(NB, R, BS)), dtype)
        # every slot its own blocks, out of order; block 0 is the sink
        table = jnp.asarray(
            rng.permutation(np.arange(1, S * WB + 1)).reshape(S, WB), jnp.int32
        )
        act = None if active is None else jnp.asarray(active)
        return ql, qr, c, krt, table, jnp.asarray(pos, jnp.int32), act

    @pytest.mark.parametrize("case", LATENT_READ_CASES, ids=[c[0] for c in LATENT_READ_CASES])
    def test_the_kernel_gives_what_the_xla_lines_give(self, case):
        from seldon_core_tpu.ops import mla_attention as ma

        ql, qr, c, krt, table, pos, act = self._inputs(case)
        BS, step = case[5], case[7]
        got, rows = ma.mla_decode_attention(
            ql, qr, c, krt, table, pos, scale=0.2, active=act, step_rows=step
        )
        want, gathered = ma.mla_decode_attention_reference(
            ql, qr, c, krt, table, pos, scale=0.2, active=act
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
        # the kernel counts the live blocks it awaited; the lines gather the window
        live = [(p // BS + 1) * BS for p in case[8]]
        if case[9] is not None:
            live = [n if a else 0 for n, a in zip(live, case[9])]
            assert np.abs(np.asarray(got)[~np.asarray(case[9])]).max() == 0
        assert np.asarray(rows).tolist() == live
        assert np.asarray(gathered).tolist() == [case[6] * BS] * case[1]

    def test_it_is_the_expanded_attention_of_the_same_rows(self):
        """Scores of ``[ql | qr]`` against ``[c | kr]`` and the weighted sum
        of the same ``c``, written out densely for one slot."""
        from seldon_core_tpu.ops import mla_attention as ma

        case = LATENT_READ_CASES[1]
        ql, qr, c, krt, table, pos, _ = self._inputs(case)
        got, _ = ma.mla_decode_attention(ql, qr, c, krt, table, pos, scale=0.2, step_rows=8)
        for s in range(case[1]):
            n = int(pos[s]) + 1
            rows_c = c[table[s]].reshape(-1, c.shape[-1])[:n]
            rows_r = jnp.swapaxes(krt[table[s]], 1, 2).reshape(-1, krt.shape[1])[:n]
            sc = 0.2 * (ql[s] @ rows_c.T + qr[s] @ rows_r.T)
            want = jax.nn.softmax(sc, -1) @ rows_c
            np.testing.assert_allclose(np.asarray(got[s]), np.asarray(want), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("control", [
        {"rope": False}, {"score_dtype": jnp.bfloat16},
    ], ids=["no rotary part", "bfloat16 scores"])
    def test_the_controls_are_the_references_controls_and_another_result(self, control):
        from seldon_core_tpu.ops import mla_attention as ma

        args = self._inputs(LATENT_READ_CASES[1])[:6]
        sound, _ = ma.mla_decode_attention(*args, scale=0.2, step_rows=8)
        got, _ = ma.mla_decode_attention(*args, scale=0.2, step_rows=8, **control)
        want, _ = ma.mla_decode_attention_reference(*args, scale=0.2, **control)
        tol = 2e-2 if "score_dtype" in control else 2e-5  # rounded scores round apart
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)
        assert np.abs(np.asarray(got) - np.asarray(sound)).max() > 1e-3

    def test_bfloat16_as_the_pool_holds_it(self):
        from seldon_core_tpu.ops import mla_attention as ma

        args = self._inputs(LATENT_READ_CASES[1], jnp.bfloat16)[:6]
        got, _ = ma.mla_decode_attention(*args, scale=0.2, step_rows=8)
        want, _ = ma.mla_decode_attention_reference(*args, scale=0.2)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0.03, atol=0.03
        )


# (name, tokens, channels, real length, chunk): across chunk edges, a length
# that is no multiple of the chunk, a real length inside the last chunk, a
# whole chunk of padding, a prompt shorter than a group of sixteen
SCAN_CASES = [
    ("two chunks and a part", 72, 256, 72, 32),
    ("a length inside the last chunk", 96, 256, 70, 32),
    ("a chunk of padding behind the length", 96, 128, 40, 32),
    ("whole chunks", 64, 128, 64, 32),
    ("shorter than a group", 5, 128, 3, 256),
    ("one token", 16, 128, 1, 16),
]


class TestSelectiveScan:
    """``ops/selective_scan.py``: the prompt's recurrence as a Pallas kernel
    (interpret mode here) against its ``lax.scan`` reference against the
    plain loop."""

    @staticmethod
    def _inputs(T, di, n=16, seed=0, dtype=jnp.float32):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        c = jax.random.normal(ks[0], (T, di)).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (T, di)) - 3.0)
        b = jax.random.normal(ks[2], (T, n))
        cc = jax.random.normal(ks[3], (T, n))
        a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, di))
        d = 1.0 + 0.1 * jax.random.normal(ks[4], (di,))
        return c, dt, b, cc, a, d

    @staticmethod
    def _plain(c, dt, b, cc, a, d, length):
        """The recurrence as the papers write it, a Python loop in numpy
        float64, the state ``(channels, state index)``."""
        c, dt, b, cc, a, d = (np.asarray(x, np.float64) for x in (c, dt, b, cc, a, d))
        s = np.zeros((c.shape[1], a.shape[0]))
        ys = []
        for t in range(length):
            s = np.exp(dt[t][:, None] * a.T) * s + (dt[t] * c[t])[:, None] * b[t][None, :]
            ys.append(s @ cc[t] + d * c[t])
        return np.stack(ys), s.T

    @pytest.mark.parametrize("case", SCAN_CASES, ids=[c[0] for c in SCAN_CASES])
    def test_the_kernel_the_reference_and_the_plain_loop_agree(self, case):
        from seldon_core_tpu.ops import selective_scan as ss

        _, T, di, length, chunk = case
        args = self._inputs(T, di)
        y, s = ss.selective_scan(*args, length, chunk=chunk, tile=128)
        yr, sr = ss.selective_scan_reference(*args, length)
        yp, sp = self._plain(*args, length)
        assert y.shape == (T, di) and s.shape == (16, di) and s.dtype == jnp.float32
        for got_y, got_s in ((y, s), (yr, sr)):
            np.testing.assert_allclose(np.asarray(got_y[:length]), yp, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(np.asarray(got_s), sp, rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(y)).all()  # padding's rows are finite

    def test_padding_moves_neither_the_state_nor_the_real_rows(self):
        from seldon_core_tpu.ops import selective_scan as ss

        args = self._inputs(96, 128)
        y, s = ss.selective_scan(*args, 40, chunk=32, tile=128)
        short = tuple(x[:48] if x.shape[0] == 96 else x for x in args)
        y2, s2 = ss.selective_scan(*short, 40, chunk=16, tile=128)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(y[:40]), np.asarray(y2[:40]))
        # a chunk wholly past the length is not computed: zeros
        assert not np.asarray(y[64:]).any()

    def test_a_steps_update_is_one_more_token_of_the_scan(self):
        from seldon_core_tpu.ops import selective_scan as ss

        c, dt, b, cc, a, d = self._inputs(33, 128)
        y, s = ss.selective_scan(c, dt, b, cc, a, d, 33, chunk=16, tile=128)
        _, s32 = ss.selective_scan(c, dt, b, cc, a, d, 32, chunk=16, tile=128)
        # four "slots" at once, the state of the first 32 tokens in slot 2
        slots = jnp.zeros((4, 16, 128)).at[2].set(s32)
        y1, s1 = ss.selective_step(
            slots, jnp.tile(c[32], (4, 1)), jnp.tile(dt[32], (4, 1)),
            jnp.tile(b[32], (4, 1)), jnp.tile(cc[32], (4, 1)), a, d,
        )
        np.testing.assert_allclose(np.asarray(s1[2]), np.asarray(s), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y1[2]), np.asarray(y[32]), rtol=1e-5, atol=1e-5)
        # a step of 0 leaves a state as it was
        _, still = ss.selective_step(slots, c[:4], jnp.zeros((4, 128)), b[:4], cc[:4], a, d)
        np.testing.assert_array_equal(np.asarray(still), np.asarray(slots))

    def test_bfloat16_activations_keep_a_float32_state(self):
        from seldon_core_tpu.ops import selective_scan as ss

        args = self._inputs(48, 256, dtype=jnp.bfloat16)
        y, s = ss.selective_scan(*args, 45, chunk=16, tile=128)
        yr, sr = ss.selective_scan_reference(*args, 45)
        assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(y[:45], np.float32), np.asarray(yr[:45], np.float32),
            rtol=0.02, atol=0.02,
        )

    def test_the_control_rounds_the_products_and_gives_another_state(self):
        from seldon_core_tpu.ops import selective_scan as ss

        args = self._inputs(64, 128)
        _, sound = ss.selective_scan(*args, 64, chunk=32, tile=128)
        _, got = ss.selective_scan(*args, 64, chunk=32, tile=128, product_dtype=jnp.bfloat16)
        _, want = ss.selective_scan_reference(*args, 64, product_dtype=jnp.bfloat16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)
        err = np.linalg.norm(np.asarray(got - sound)) / np.linalg.norm(np.asarray(sound))
        assert 1e-3 < err < 5e-2

    @pytest.mark.parametrize("slots,channels", [(2, 128), (8, 256), (16, 128), (32, 256)])
    def test_the_update_in_place_is_the_step_of_one_layer(self, slots, channels):
        """``selective_update``: the layer named is updated in the carried
        array as ``selective_step`` would update it taken out, the other
        layers are not touched, and the array is the call's own (aliased)."""
        from seldon_core_tpu.ops import selective_scan as ss

        c, dt, b, cc, a, d = self._inputs(slots, channels, seed=slots)
        states = jax.random.normal(jax.random.PRNGKey(7), (3, slots, 16, channels))
        kept = np.asarray(states)
        want_y, want_s = ss.selective_step(states[1], c, dt, b, cc, a, d)
        update = jax.jit(
            lambda st, li: ss.selective_update(st, li, c, dt, b, cc, a, d, tile=128),
            donate_argnums=(0,),
        )
        got, y = update(states, jnp.int32(1))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want_s), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[0]), kept[0])
        np.testing.assert_array_equal(np.asarray(got[2]), kept[2])

    def test_slots_that_are_no_whole_groups_are_refused(self):
        from seldon_core_tpu.ops import selective_scan as ss

        assert ss.update_group(128) == 16 and ss.update_group(24) == 8
        assert ss.update_group(3) == 3 and ss.update_group(100) is None
        with pytest.raises(ValueError, match="100 slots"):
            ss.selective_update(
                jnp.zeros((1, 100, 16, 128)), 0, jnp.zeros((100, 128)),
                jnp.zeros((100, 128)), jnp.zeros((100, 16)), jnp.zeros((100, 16)),
                jnp.zeros((16, 128)), jnp.zeros((128,)),
            )
