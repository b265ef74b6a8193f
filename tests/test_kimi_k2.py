"""The ``kimi_k2`` family (models/kimi_k2.py) against the benchmark's plain
reference (benchmark/reference/kimi_k2_decoder.py: the EXPANDED form only),
at a small size on the CPU: hidden 64, 4 heads with 8-wide keys' position-free
part, an 8-wide rotary key a token, 8-wide values, latents of 24 (queries)
and 16 (keys and values), one dense layer (96 wide) and two expert layers (16
experts top-4 of width 32, one shared), vocabulary 256.  YaRN's original
length is 16 and its ramp [0, 1/2, 1, 1]; contexts run from 37 to 50 tokens,
two to three times that length.  Logits, not tokens."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import kimi_k2 as m
from seldon_core_tpu.models import moe

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "benchmark", "reference")
)
import kimi_k2_decoder as ref  # noqa: E402

BS = 4  # pool block
# float32 against float32: summation order only (the absorbed form sums a
# score over 16 latent dims where the expanded one sums over 8 key dims).
# Each control below moves a logit by hundredths to tenths
TOL = 2e-5


def _cfg(**kw):
    return m.Config.tiny(max_seq=64, **kw)


def _params(cfg, seed=3, dtype=jnp.float32):
    """The weights: the controls and YaRN's settings change none of them, so
    one init (jitted, made once) serves every variant of a size."""
    plain = dataclasses.replace(
        cfg, decode_rope="on", softmax_mscale="on", decode_score_dtype="float32",
        prompt_score_dtype="float32", rope_factor=8.0,
    )
    return _made(plain, seed, dtype)


@functools.lru_cache(maxsize=None)
def _made(cfg, seed, dtype):
    return jax.jit(lambda key: m.init_params(key, cfg, dtype))(jax.random.PRNGKey(seed))


def _ref_kw(cfg):
    """The reference's keyword arguments, as the benchmark's kind makes them."""
    import frame

    return frame.named_module("kinds", "kimi_k2_decoder").reference_kw(cfg)


@functools.lru_cache(maxsize=None)
def _jitted(cfg, which, **static):
    fn = {
        "prefill": m.prefill_slot_paged, "suffix": m.prefill_suffix_paged,
        "decode": m.decode_slots_paged,
    }[which]
    cfg_at = {"prefill": 6, "suffix": 8, "decode": 4}[which]

    def call(*args):
        return fn(*args[:cfg_at], cfg, *args[cfg_at:], **static)

    return jax.jit(call)


def _slot_row(n_blocks=14, width=16):
    """A table row whose blocks are out of order (block 0 is the sink)."""
    row = np.zeros(width, np.int32)
    row[:n_blocks] = np.arange(1, n_blocks + 1)[::-1]
    return row


def _prefill(cfg, params, prompt, *, seq_impl="dense", chunks=None, slot=1):
    """Prompt -> (last logits, cache), whole or in ``chunks`` (the first
    through ``prefill_slot_paged``, the others through the suffix program
    over the slot's own blocks: a reused prefix, a chunked prompt)."""
    cache = m.init_paged_cache(cfg, 2, 40, BS, params["ln_f"].dtype)
    row = jnp.asarray(_slot_row())
    spans = [(0, len(prompt))] if not chunks else list(zip(chunks[:-1], chunks[1:]))
    logits = None
    for a, b in spans:
        bucket = -(-(b - a) // BS) * BS
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : b - a] = prompt[a:b]
        if a == 0:
            logits, cache = _jitted(cfg, "prefill", seq_impl=seq_impl)(
                params, jnp.asarray(padded), jnp.int32(b), jnp.int32(slot),
                row, cache,
            )
        else:
            sb = np.zeros(bucket // BS, np.int32)
            have = np.asarray(row)[a // BS: a // BS + bucket // BS]
            sb[: have.size] = have
            pw = BS
            while pw < a:
                pw *= 2
            logits, cache = _jitted(
                cfg, "suffix", prefix_window=min(pw, cfg.max_seq)
            )(
                params, jnp.asarray(padded), jnp.int32(a), jnp.int32(b),
                jnp.int32(slot), row, jnp.asarray(sb), cache,
            )
    return logits, cache


def _decode(cfg, params, cache, first, steps, **kw):
    """Greedy decode of slot 1 -> (tokens fed, logits of every step, cache)."""
    active = jnp.asarray([False, True])
    kw.setdefault("window", cfg.max_seq)
    fed, out, nxt = [], [], int(first)
    for _ in range(steps):
        fed.append(nxt)
        lg, cache = _jitted(cfg, "decode", **kw)(
            params, jnp.asarray([0, nxt], jnp.int32), cache, active,
        )
        out.append(np.asarray(lg[1]))
        nxt = int(np.argmax(out[-1]))
    return fed, out, cache


def _served_logits(cfg, params, prompt, steps=12, *, seq_impl="dense",
                   chunks=None, **decode_kw):
    """(the sequence served, the logits at the prompt's last position and at
    every decode step)."""
    logits, cache = _prefill(cfg, params, prompt, seq_impl=seq_impl, chunks=chunks)
    fed, out, _ = _decode(
        cfg, params, cache, int(np.argmax(logits)), steps, **decode_kw
    )
    return np.concatenate([prompt, fed]), np.stack([np.asarray(logits)] + out)


def _reference(cfg, params, seq, n):
    full = np.asarray(ref.logits(params, seq, **_ref_kw(cfg)))
    return full[len(seq) - n:]


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(1, 256, 37)


class TestAgainstReference:
    @pytest.mark.parametrize("seq_impl,kernel", [
        ("dense", False), ("flash", True),  # the XLA paths; the Pallas paths
    ])
    def test_prefill_then_decode(self, prompt, seq_impl, kernel):
        """Prefill (expanded) then 12 steps through the latent pool
        (absorbed) against the reference's full expanded forward pass."""
        cfg = _cfg()
        params = _params(cfg)
        seq, got = _served_logits(
            cfg, params, prompt, seq_impl=seq_impl, kernel=kernel
        )
        want = _reference(cfg, params, seq, len(got))
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    @pytest.mark.parametrize("control,least", [
        ({"decode_rope": "off"}, 0.05),  # qr.kr left out of the decode score
        ({"softmax_mscale": "off"}, 0.05),  # sigma without m^2
        ({"decode_score_dtype": "bfloat16"}, 2e-4),  # the decode scores rounded
    ], ids=["no rotary part", "sigma without mscale", "bfloat16 scores"])
    @pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
    def test_a_control_fails_the_same_tolerance(self, prompt, control, least, kernel):
        """Each control, never served, is another model by the tolerance the
        sound program meets: the first two by whole hundredths of a logit,
        rounded scores by ten tolerances."""
        cfg = _cfg(**control)
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, prompt, kernel=kernel)
        want = _reference(cfg, params, seq, len(got))
        assert np.abs(got - want)[1:].max() > least >= 10 * TOL

    @pytest.mark.parametrize("seq_impl", ["dense", "flash"])
    def test_a_prompt_with_rounded_scores_fails_the_same_tolerance(self, prompt, seq_impl):
        """The fourth control, never served: a prompt's scores rounded to
        bfloat16 as they leave the MXU, in the tiled kernel and in the XLA
        lines alike, move the prompt's own logits by ten tolerances."""
        cfg = _cfg(prompt_score_dtype="bfloat16")
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, prompt, steps=1, seq_impl=seq_impl)
        want = _reference(cfg, params, seq, len(got))
        assert np.abs(got - want)[0].max() > 2e-4 >= 10 * TOL

    @pytest.mark.parametrize("chunks", [(0, 16, 37), (0, 8, 24, 37)])
    def test_suffix_over_a_reused_prefix(self, prompt, chunks):
        """The suffix program reads the prefix's ``c`` and ``kr`` from the
        pool, up-projects them and attends in the expanded form: a prompt
        prefilled in spans gives what it gives whole."""
        cfg = _cfg()
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, prompt, steps=6, chunks=chunks)
        want = _reference(cfg, params, seq, len(got))
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    @pytest.mark.parametrize("window", [48, 64])
    def test_a_static_window_bounds_the_columns_read(self, window):
        short = np.random.default_rng(2).integers(1, 256, 21)
        cfg = _cfg()
        params = _params(cfg)
        for kernel in (False, True):
            seq, got = _served_logits(
                cfg, params, short, steps=3, window=window, kernel=kernel
            )
            want = _reference(cfg, params, seq, len(got))
            np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    def test_bfloat16_as_served(self, prompt):
        """bfloat16 weights, activations and pool against the float32
        reference on the same weights: the served token IS the reference's
        top logit but where rounding swaps an expert at a near-tie (one of
        these 13 positions, by 0.31 at these toy widths, where one expert is
        a quarter of a token's routed sum); rounding of activations alone
        moves logits by hundredths."""
        cfg = _cfg()
        params = _params(cfg, dtype=jnp.bfloat16)
        seq, got = _served_logits(cfg, params, prompt, seq_impl="flash", kernel=True)
        want = _reference(cfg, params, seq, len(got))
        served = np.concatenate([seq[len(prompt):], [np.argmax(got[-1])]])
        deficit = want.max(-1) - want[np.arange(len(served)), served]
        assert deficit.max() < 0.5 and (deficit > 0.05).sum() <= 2

    def test_yarn_is_at_work_at_these_contexts(self, prompt):
        """The rotary frequencies are the reference's own, ramp and all, and
        a model without YaRN is another model at 37 to 50 tokens."""
        cfg = _cfg()
        f_ref, mscale = ref.yarn(_ref_kw(cfg)["rope"], cfg.qk_rope_dim, cfg.rope_theta)
        np.testing.assert_allclose(m.yarn_freqs(cfg), f_ref, rtol=1e-6)
        assert mscale == pytest.approx(cfg.mscale) and cfg.mscale > 1.2
        plain = cfg.rope_theta ** (-np.arange(0, 8, 2) / 8)
        np.testing.assert_allclose(  # r = [0, 1/2, 1, 1]
            np.asarray(f_ref) / plain, [1, (1 + 1 / 8) / 2, 1 / 8, 1 / 8], rtol=1e-6
        )
        # the published numbers: low 8, high 20, sigma 0.14468
        real = m.Config()
        ratio = np.asarray(m.yarn_freqs(real)) / (
            real.rope_theta ** (-np.arange(0, 64, 2) / 64)
        )
        np.testing.assert_allclose(ratio[:9], 1, rtol=1e-6)
        assert ratio[9] < 1 - 1e-3
        np.testing.assert_allclose(ratio[20:], 1 / 64, rtol=1e-6)
        assert ratio[19] > 1 / 64 + 1e-3
        assert real.mscale == pytest.approx(1.41589, abs=1e-5)
        assert real.softmax_scale == pytest.approx(0.14468, abs=1e-5)
        off = dataclasses.replace(cfg, rope_factor=1.0)
        params = _params(cfg)
        seq, got = _served_logits(off, params, prompt, steps=2)
        assert np.abs(got - _reference(cfg, params, seq, len(got))).max() > 0.05


class TestTwoAttentions:
    def test_the_absorbed_and_the_expanded_agree_on_the_same_rows(self, prompt):
        """``qn.kn = qn.(W_UK c) = (qn W_UK).c``: the last 6 positions of a
        prompt read as a decode step reads them, from a pool of the prompt's
        own latents, give the rows the expanded attention gives."""
        cfg = _cfg()
        lp = {k: v[0] for k, v in _params(cfg)["layers"].items()}
        L = 40
        h = jax.random.normal(jax.random.PRNGKey(7), (L, cfg.hidden))
        pos = jnp.arange(L)
        qn, qr, c, kr = m._latents(h, lp, cfg, pos)
        want = m._attend_prompt(qn, qr, c, kr, lp, cfg, "dense")
        flash = m._attend_prompt(qn, qr, c, kr, lp, cfg, "flash")
        np.testing.assert_allclose(flash, want, atol=TOL, rtol=0)
        spos = jnp.arange(L - 6, L)
        table = jnp.broadcast_to(jnp.arange(L // BS), (6, L // BS))
        for kernel in (False, True):
            got, rows = m._decode_attention(
                qn[spos], qr[spos], c.reshape(1, L // BS, BS, -1),
                m._kr_by_token(kr.reshape(1, L // BS, BS, -1)), 0, lp, table,
                spos, jnp.ones((6,), bool), cfg, kernel=kernel,
            )
            np.testing.assert_allclose(got, want[L - 6:], atol=TOL, rtol=0)
            # the kernel brings in a slot's live blocks; the XLA lines the window
            live = [(int(p) // BS + 1) * BS for p in spos]
            assert np.asarray(rows).tolist() == (live if kernel else [L] * 6)

    def test_a_decode_step_makes_nothing_by_head(self):
        """No array of a decode step's program holds the window's rows by
        head (keys or values): the only thing of heads x rows is the scores."""
        import re

        cfg = _cfg()
        params = _params(cfg)
        cache = m.init_paged_cache(cfg, 2, 40, BS)
        text = jax.jit(
            lambda p, t, c, a: m.decode_slots_paged(p, t, c, a, cfg, window=48)
        ).lower(
            params, jnp.zeros(2, jnp.int32), cache, jnp.ones(2, bool)
        ).as_text()
        # 48 rows of the window x 4 heads x anything: K or V by head
        assert re.search(r"[<x]48x4x\d+x", text) is None
        assert "2x4x48xf32" in text  # the scores (S, H, W)
        assert "2x48x16xf32" in text  # the gathered latents (S, W, C)


class TestRouter:
    def test_the_bias_chooses_and_never_weighs(self):
        cfg = _cfg()
        lp = {k: v[0] for k, v in _params(cfg)["layers"].items()}
        h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.hidden))
        idx, w = m._route(h, lp["w_router"], lp["b_router"], cfg)
        idx0, w0 = m._route(h, lp["w_router"], jnp.zeros_like(lp["b_router"]), cfg)
        changed = np.asarray(jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1)
        assert 0 < changed.sum() < 64  # some choices move, not all
        # a weight is the chosen score alone, renormalised and scaled
        s = jax.nn.sigmoid(h @ lp["w_router"])
        vals = jnp.take_along_axis(s, idx, -1)
        np.testing.assert_allclose(
            w, cfg.routed_scale * vals / vals.sum(-1, keepdims=True), rtol=1e-5
        )
        np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scale, rtol=1e-5)
        # where the bias moved no choice it moved no weight
        np.testing.assert_allclose(
            np.sort(np.asarray(w)[~changed]), np.sort(np.asarray(w0)[~changed]),
            rtol=1e-6,
        )
        # a large bias on one expert puts it in every token's choice at its own score
        big = jnp.zeros_like(lp["b_router"]).at[3].set(10.0)
        idx3, w3 = m._route(h, lp["w_router"], big, cfg)
        assert (np.asarray(idx3) == 3).any(-1).all()
        assert np.asarray(w3).max() < cfg.routed_scale


class TestShareTiesToTheModel:
    def test_the_shares_add_up_to_the_uncut_layer(self):
        """The routed parts that all 8 shares of an expert layer give (2 of
        the 16 experts each), with the shared expert counted once, add up to
        the uncut reference layer; the dense layer and attention are every
        share's alike and are the uncut model's as they stand."""
        whole = _cfg()
        wp = _params(whole)
        h = jax.random.normal(jax.random.PRNGKey(9), (21, whole.hidden))
        lp0 = {k: v[0] for k, v in wp["layers"].items()}
        with jax.default_matmul_precision("highest"):
            want = ref.moe(
                h, lp0, top_k=whole.experts_per_tok, held=(0, 16),
                scale=whole.routed_scale,
            )
            shared = ref.swiglu(
                h, lp0["ws_gate"][0], lp0["ws_up"][0], lp0["ws_down"][0]
            )
        mask = jnp.ones((21,), bool)

        @functools.partial(jax.jit, static_argnums=0)
        def part(cfg, lp):
            return m._moe(h, lp, cfg, mask, None, decode=True)[0]

        total = 0.0
        for k in range(8):
            cfg = dataclasses.replace(whole, experts_held=f"{2 * k}:2")
            lp = {
                name: a[2 * k: 2 * k + 2] if name.startswith("we_") else a
                for name, a in lp0.items()
            }
            if k in (0, 5):  # a share's own init makes the same weights
                own = _params(cfg)
                for name in lp:
                    np.testing.assert_array_equal(own["layers"][name][0], lp[name])
                for name, a in own["dense_layers"].items():
                    np.testing.assert_array_equal(a, wp["dense_layers"][name])
            routed = part(cfg, lp) - shared  # the shared expert counted once
            total = total + routed
        np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=0)
        assert np.abs(np.asarray(routed + shared - want)).max() > 1e-2

    def test_grouped_products_give_what_dense_gives(self, monkeypatch):
        cfg = _cfg()
        params = _params(cfg)
        lp = {k: v[1] for k, v in params["layers"].items()}
        stacks = {k: params["layers"][k] for k in moe.EXPERT_KEYS}
        h = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.hidden))
        mask = jnp.arange(40) < 33
        dense, _ = m._moe(h, lp, cfg, mask, None, decode=False)
        monkeypatch.setattr(moe, "GROUPED_FROM", 8)
        monkeypatch.setattr(moe, "GROUP_CHUNK", 64)
        monkeypatch.setattr(moe, "GROUP_ROWS_AN_EXPERT", 1)
        for kw in ({}, {"stacks": stacks, "li": 1}):
            grouped, _ = m._moe(h, lp, cfg, mask, None, decode=False, **kw)
            np.testing.assert_allclose(grouped, dense, atol=TOL, rtol=0)

    def test_the_dense_mlp_in_chunks_is_the_mlp(self, monkeypatch):
        cfg = _cfg()
        lp = {k: v[0] for k, v in _params(cfg)["dense_layers"].items()}
        h = jax.random.normal(jax.random.PRNGKey(4), (32, cfg.hidden))
        whole = m._mlp_dense(h, lp)
        monkeypatch.setattr(m, "MLP_CHUNK", 8)
        np.testing.assert_allclose(m._mlp_dense(h, lp), whole, atol=TOL, rtol=0)


class TestCache:
    def test_latents_under_one_table_and_nothing_by_head(self):
        cfg = _cfg()
        cache = m.init_paged_cache(cfg, 2, 40, BS, jnp.bfloat16)
        assert sorted(cache) == ["c", "counters", "kr", "pos", "table"]
        assert m.POOL_ARRAYS == ("c", "kr")
        assert cache["c"].shape == (3, 40, BS, cfg.kv_lora_rank)
        assert cache["kr"].shape == (3, 40, cfg.qk_rope_dim, BS)  # a block transposed
        assert cache["c"].dtype == cache["kr"].dtype == jnp.bfloat16
        per_token = (cache["c"].nbytes + cache["kr"].nbytes) // (40 * BS)
        assert per_token == 3 * (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
        # the published sizes: 512 + 64 values, 1,152 B a token a layer, no
        # padding; by heads it would be 40,960 B
        real = m.Config(n_layers=5, max_seq=16384)
        per_token = m.paged_kv_slot_bytes(real, 256, dtype="bfloat16") // real.max_seq
        assert per_token == 5 * 1152
        shapes = jax.eval_shape(
            lambda: m.init_paged_cache(real, 32, 1633, 256, jnp.bfloat16)
        )
        pool = sum(
            int(np.prod(shapes[k].shape)) * 2 for k in m.POOL_ARRAYS
        )
        assert pool == 1633 * 256 * 5 * 1152

    def test_every_program_writes_the_latents_at_the_tokens_place(self, prompt):
        cfg = _cfg()
        params = _params(cfg)
        _, whole = _prefill(cfg, params, prompt)
        _, spans = _prefill(cfg, params, prompt, chunks=(0, 16, 37))
        row = _slot_row()

        def by_token(cache, name):  # (layers, blocks, block, ...)
            a = cache[name]
            return np.asarray(m._kr_by_token(a) if name == "kr" else a)

        for name in m.POOL_ARRAYS:
            a = by_token(whole, name)[:, row[:9]].reshape(3, 36, -1)
            b = by_token(spans, name)[:, row[:9]].reshape(3, 36, -1)
            assert np.abs(a[:, :30]).max(axis=-1).min() > 0
            np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
        # a decode step's token lands at its position, on both arrays, and
        # nowhere else in its block
        _, _, after = _decode(cfg, params, whole, 5, 1)
        for name in m.POOL_ARRAYS:
            was = by_token(whole, name)[:, row[9]]
            now = by_token(after, name)[:, row[9]]
            assert np.abs(now[:, 1] - was[:, 1]).max() > 0
            np.testing.assert_array_equal(now[:, [0, 2, 3]], was[:, [0, 2, 3]])


class TestCounters:
    @pytest.mark.parametrize("kernel,rows", [
        # the kernel brings in the live blocks of the one active slot:
        # positions 37, 38, 39 in blocks of 4 are 10 blocks each, on 3 layers
        (True, 3 * 3 * 10 * BS),
        # the XLA lines gather the window of both slots: 2 x 64 rows
        (False, 3 * 3 * 2 * 64),
    ])
    def test_the_latent_read_is_counted_by_the_way_taken(self, prompt, kernel, rows):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, prompt)
        _, _, cache = _decode(cfg, params, cache, 5, 3, kernel=kernel)
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        assert m.COUNTERS[:9] == moe.COUNTERS
        assert m.COUNTERS[9:] == (
            "mla.rows_read", "mla.prefill_rows_expanded", "mla.rows_live",
        )
        assert c["mla.rows_read"] == rows
        # what the steps HAD to read, whatever read it: the one live slot at
        # positions 37, 38, 39 attends 38 + 39 + 40 rows on each of 3 layers
        assert c["mla.rows_live"] == 3 * (38 + 39 + 40)
        # the prompt's rung of 40 rows up-projected on 3 layers
        assert c["mla.prefill_rows_expanded"] == 3 * 40
        assert c["moe.steps"] == 3 and c["moe.prefill_tokens"] == 37
        # the moe.* counters run over the TWO expert layers, not the three
        assert c["moe.pairs_routed"] == 2 * 3 * 4
        assert c["moe.prefill_pairs_routed"] == 2 * 37 * 4

    def test_a_suffix_counts_the_prefix_it_expands(self, prompt):
        cfg = _cfg()
        _, cache = _prefill(cfg, _params(cfg), prompt, chunks=(0, 16, 37))
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        # 16 of the prompt; then the prefix window of 16 and a rung of 24
        assert c["mla.prefill_rows_expanded"] == 3 * (16 + 16 + 24)
        assert c["moe.prefill_tokens"] == 37


class TestServedPath:
    """Through ``JAX_GENERATIVE``'s own objects."""

    def _component(self, **kw):
        from seldon_core_tpu.models.registry import build_generative_component

        return build_generative_component(
            "kimi_k2", preset="tiny", max_seq=64, n_slots=2, decode_block=4,
            kv_block_size=4, dtype=jnp.bfloat16, rng=5, **kw,
        )

    @pytest.mark.parametrize("seq_impl,kernel", [
        ("dense", False), ("flash", True),
    ])
    def test_generates_what_the_family_computes(self, prompt, seq_impl, kernel):
        from seldon_core_tpu.utils.device import xla_compile_count

        comp = self._component(seq_impl=seq_impl, decode_kernel=kernel)
        model = comp.model
        assert model.family is m and model.params["ln_f"].dtype == jnp.bfloat16
        assert model._pool_names == ("c", "kr")
        assert "k" not in model._cache and "v" not in model._cache
        # the pool's bytes: 3 layers x (16 + 8) values x 2 B a token
        per_token = 3 * (16 + 8) * 2
        assert model.kv_bytes_per_block() == 4 * per_token
        assert model.kv_bytes_per_slot() == 64 * per_token
        assert model.pool_snapshot()["bytes"]["kv_pool"] == model.kv_blocks * 4 * per_token
        assert model.spec_snapshot()["kv_dtype"] == "bfloat16"
        model.warmup()
        warmed = xla_compile_count()
        if seq_impl == "flash":  # each rung's compile left its tile plan
            plans = model.program_snapshot()["tile_plans"]
            for b in model.prefill_buckets:
                assert plans[f"S{b}:Sk{b}:{b}x{b}:wNone"] == {
                    "stepped": 1, "live": 1, "masked": 1,
                }
            assert plans["admitted"] == {"stepped": 0, "live": 0}  # not warm-up's
        tok = model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=12)
        if seq_impl == "flash":  # the prompt's one tile, live
            assert model.program_snapshot()["tile_plans"]["admitted"] == {
                "stepped": 1, "live": 1,
            }
        cur, active = np.zeros(2, np.int32), np.zeros(2, bool)
        cur[0], active[0] = int(tok), True
        toks, emitted = model.step_k(
            cur, active, np.zeros(2, np.float32), 0,
            np.full(2, -1, np.int32), np.full(2, 12, np.int32), 4,
        )
        assert emitted[:, 0].all()
        assert xla_compile_count() == warmed  # nothing compiled after warm-up
        served = [int(tok)] + [int(t) for t in toks[:, 0]]
        want = np.asarray(ref.logits(
            model.params, np.concatenate([prompt, served[:-1]]),
            **_ref_kw(model.cfg),
        ))[len(prompt) - 1:]
        deficit = want.max(-1) - want[np.arange(len(served)), served]
        assert deficit.max() < 0.5 and (deficit > 0.05).sum() <= 2
        snap = model.spec_snapshot()["counters"]
        assert snap["moe.steps"] >= 4
        assert snap["mla.rows_read"] >= 4 * 3 * 37  # steps x layers x the context
        assert snap["mla.prefill_rows_expanded"] >= 3 * 37

    def test_prefix_reuse_shares_the_latents_with_the_blocks(self, prompt):
        comp = self._component(kv_prefix_reuse=True)
        model = comp.model
        first = model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=4)
        model.release_slot(0)
        again = model.admit(1, prompt.astype(np.int32), 0.0, 0, reserve_tokens=4)
        assert model.prefills_reused == 1
        assert int(first) == int(again)

    def test_what_the_family_does_not_have_is_refused_by_name(self, prompt):
        from seldon_core_tpu.graph.units import GraphUnitError

        cfg = _cfg()
        with pytest.raises(TypeError, match="kimi_k2 has no int8 latent pool"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_dtype="int8")
        with pytest.raises(GraphUnitError, match="kimi_k2.*kv_cache_dtype"):
            self._component(kv_cache_dtype="int8")
        with pytest.raises(TypeError, match="kimi_k2 has no pool split over a mesh"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_sharded=True)
        # no LoRA path: the pool is switched off with a warning, and a
        # program handed adapters raises
        assert self._component(lora_rank=4).model.lora_rank == 0
        params = _params(cfg)
        cache = m.init_paged_cache(cfg, 2, 40, BS)
        with pytest.raises(TypeError, match="kimi_k2 has no LoRA"):
            m.decode_slots_paged(
                params, jnp.zeros(2, jnp.int32), cache, jnp.ones(2, bool), cfg,
                lora={},
            )
        # no speculative verify pass
        with pytest.raises(GraphUnitError, match="kimi_k2 has no decode_slots_spec_paged"):
            self._component(spec_draft=2)
        # what moves K/V out of the pool carries k and v alone
        with pytest.raises(TypeError, match="kimi_k2 keeps c, kr and no K/V.*prefix_dram_gb"):
            self._component(kv_prefix_reuse=True, prefix_dram_gb=0.01)
        comp = self._component()
        model = comp.model
        model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=4)
        with pytest.raises(TypeError, match="kimi_k2 keeps c, kr.*export"):
            model.export_slot_kv(0, len(prompt))
        z = np.zeros((3, 10, 4, 4, 8), np.float32)
        with pytest.raises(TypeError, match="kimi_k2 keeps c, kr.*import"):
            model.attach_imported(1, prompt.astype(np.int32), z, z)

    def test_preemption_is_refused_by_name(self):
        from seldon_core_tpu.executor.generation import GenerationScheduler

        sched = GenerationScheduler(self._component().model)
        with pytest.raises(TypeError, match="kimi_k2 keeps c, kr.*SuspendStore"):
            sched.request_preempt()

    def test_the_families_that_were_there_count_the_bytes_they_counted(self):
        """``llama`` names no pool arrays and gets ``k`` and ``v``."""
        from seldon_core_tpu.models.registry import build_generative_component

        model = build_generative_component(
            "llama", preset="tiny", max_seq=64, n_slots=2, kv_block_size=4,
        ).model
        assert model._pool_names == ("k", "v")
        cfg = model.cfg
        per_token = cfg.n_layers * 2 * cfg.n_kv_heads * (cfg.hidden // cfg.n_heads) * 4
        assert model.kv_bytes_per_block() == 4 * per_token
        assert model.pool_snapshot()["bytes"]["kv_pool"] == model.kv_blocks * 4 * per_token


class TestAPromptsRealLength:
    """PR 58: the tiled kernel is handed the prompt's real length, and the
    query tiles of the rung's padding are not computed."""

    @pytest.mark.parametrize("length", [
        600,     # the second tile of 512 straddles it; the third is dead
        1024,    # on a tile's edge: a decode step's block is the dead tile's first
    ])
    def test_the_real_rows_are_what_they_were(self, length, monkeypatch):
        from seldon_core_tpu.models import layers

        cfg, bs, rung = m.Config.tiny(max_seq=2048), 64, 1536
        params = _params(cfg)
        tokens = np.zeros((1, rung), np.int32)
        tokens[0, :length] = np.random.default_rng(length).integers(1, 256, length)
        row = np.zeros(cfg.max_seq // bs, np.int32)
        row[: rung // bs + 1] = np.arange(1, rung // bs + 2)[::-1]

        def prefill():
            cache = m.init_paged_cache(cfg, 2, 40, bs, jnp.float32)
            return jax.jit(functools.partial(m.prefill_slot_paged, cfg=cfg, seq_impl="flash"))(
                params, jnp.asarray(tokens), jnp.int32(length), jnp.int32(1),
                jnp.asarray(row), cache,
            )

        logits, cache = prefill()
        monkeypatch.setattr(  # the parent's program: the kernel never hears of the length
            m, "flash_prompt",
            lambda q, k, v, **kw: layers.flash_prompt(q, k, v, **{**kw, "length": None}),
        )
        want_logits, want = prefill()
        assert np.array_equal(np.asarray(logits), np.asarray(want_logits))
        edge = -(-length // 512) * 512
        for name in m.POOL_ARRAYS:
            def by_token(c):  # (layers, rung rows, values)
                a = np.asarray(m._kr_by_token(c[name]) if name == "kr" else c[name])
                return a[:, row[: rung // bs]].reshape(cfg.n_layers, rung, -1)

            got, was = by_token(cache), by_token(want)
            assert np.array_equal(got[:, :edge], was[:, :edge])
            assert np.isfinite(got).all()
            # the dead tile's rows did change after the first layer: it engaged
            assert np.array_equal(got[0], was[0]) and not np.array_equal(got[1:], was[1:])
        # a decode step over the slot's last, partly padded block is finite
        lg, _ = _jitted(cfg, "decode", window=cfg.max_seq, kernel=True)(
            params, jnp.asarray([0, int(np.argmax(logits))], jnp.int32), cache,
            jnp.asarray([False, True]),
        )
        assert np.isfinite(np.asarray(lg[1])).all()

    def test_the_engine_adds_up_the_tiles_of_what_it_admits(self):
        from seldon_core_tpu.models.registry import build_generative_component
        from seldon_core_tpu.ops.flash_attention import tile_plan

        model = build_generative_component(
            "kimi_k2", preset="tiny", max_seq=2048, n_slots=2, decode_block=4,
            kv_block_size=64, dtype=jnp.bfloat16, rng=5, seq_impl="flash",
        ).model
        assert model.program_snapshot()["tile_plans"]["admitted"] == {"stepped": 0, "live": 0}
        rng = np.random.default_rng(2)
        for slot, n in enumerate((1100, 300)):   # the rungs 2,048 and 512
            model.admit(slot, rng.integers(1, 256, n).astype(np.int32), 0.0, 0, reserve_tokens=8)
        plans = model.program_snapshot()["tile_plans"]
        assert plans["S2048:Sk2048:512x512:wNone"] == {"stepped": 10, "live": 10, "masked": 4}
        # kimi_k2's prompt program traces the kernel at two call sites of one
        # shape (the dense layer and the expert layers' scan): one plan
        a = tile_plan(2048, 2048, 512, 512, length=1100)
        b = tile_plan(512, 512, 512, 512, length=300)
        assert (a[:2], b[:2]) == ((10, 6), (1, 1))
        assert plans["admitted"] == {"stepped": a[0] + b[0], "live": a[1] + b[1]}


class TestEngineRoutes:
    """``examples/kimi-k2-generative/graph.json`` through the engine's own
    app: both routes give the same tokens, and the latent read's counters
    are in ``/stats/summary``."""

    def test_the_example_graph_serves_both_routes(self):
        import asyncio
        import json

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        path = os.path.join(
            os.path.dirname(__file__), "..", "examples",
            "kimi-k2-generative", "graph.json",
        )
        with open(path) as f:
            predictor = json.load(f)
        prompt = list(range(3, 40))

        async def go():
            service = PredictionService(PredictorSpec.model_validate(predictor))
            client = TestClient(TestServer(EngineApp(service).build()))
            await client.start_server()
            try:
                resp = await client.post(
                    "/api/v0.1/predictions",
                    json={"strData": json.dumps(
                        {"tokens": prompt, "max_new_tokens": 20})},
                )
                assert resp.status == 200, await resp.text()
                expected = json.loads((await resp.json())["strData"])["tokens"]
                assert len(expected) == 20
                resp = await client.post(
                    "/api/v0.1/predictions/stream",
                    json={"tokens": prompt, "max_new_tokens": 20},
                )
                assert resp.status == 200, await resp.text()
                events = [
                    json.loads(line[len("data: "):])
                    for line in (await resp.text()).splitlines()
                    if line.startswith("data: ")
                ]
                assert [e["token"] for e in events if "token" in e] == expected
                stats = await (await client.get("/stats/summary")).json()
                c = stats["breakdown"]["generation"]["kimi_k2:tiny"]["counters"]
                assert c["moe.steps"] > 0 and c["moe.pairs_routed"] > 0
                assert c["mla.rows_read"] > 0 and c["mla.prefill_rows_expanded"] > 0
            finally:
                await client.close()

        asyncio.run(go())
