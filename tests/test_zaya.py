"""The ``zaya`` family (models/zaya.py) against the benchmark's plain reference
(benchmark/reference/zaya_decoder.py), at a small size on the CPU: hidden 64,
4 query heads on 2 key-value heads of 16 (latents 64 | 32 | 32), two taps a
convolution, rotary on 8 of 16, 4 experts and the no-op behind a router 16
wide, 3 blocks, vocabulary 256; the values that decide whether a mechanism
matters seeded so that it does.  Logits, not tokens."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import moe
from seldon_core_tpu.models import zaya as m

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "benchmark", "reference")
)
import zaya_decoder as ref  # noqa: E402

BS = 4  # pool block
RUNG = 16  # what a short prompt is padded to
# float32 against float32: summation order only.  Each mechanism taken out
# below moves a logit by hundredths or more
TOL = 5e-5


def _cfg(**kw):
    return m.Config.tiny(max_seq=64, **kw)


@functools.lru_cache(maxsize=None)
def _params(cfg, seed=3, dtype=jnp.float32):
    return jax.jit(lambda key: m.init_params(key, cfg, dtype))(jax.random.PRNGKey(seed))


def _call(cfg, which, **static):
    fn = {"prefill": m.prefill_slot_paged, "decode": m.decode_slots_paged}[which]
    cfg_at = {"prefill": 6, "decode": 4}[which]

    def call(*args):
        return fn(*args[:cfg_at], cfg, *args[cfg_at:], **static)

    return jax.jit(call)


# the programs as the module has them; a test that patches the module traces
# its own (``fresh``)
_jitted = functools.lru_cache(maxsize=None)(_call)


def _slot_row(n_blocks=14, width=16, first=1):
    """A table row whose blocks are out of order (block 0 is the sink)."""
    row = np.zeros(width, np.int32)
    row[:n_blocks] = np.arange(first, first + n_blocks)[::-1]
    return row


def _fresh(cfg, params, n_slots=2, blocks=40):
    return m.init_paged_cache(cfg, n_slots, blocks, BS, params["ln_f"].dtype)


def _prefill(cfg, params, prompt, *, cache=None, seq_impl="dense", slot=1,
             rung=None, row=None, fresh=False):
    cache = _fresh(cfg, params) if cache is None else cache
    rung = rung or -(-len(prompt) // RUNG) * RUNG
    padded = np.zeros((1, rung), np.int32)
    padded[0, : len(prompt)] = prompt
    return (_call if fresh else _jitted)(cfg, "prefill", seq_impl=seq_impl)(
        params, jnp.asarray(padded), jnp.int32(len(prompt)), jnp.int32(slot),
        jnp.asarray(_slot_row() if row is None else row), cache,
    )


def _decode(cfg, params, cache, feed, *, slot=1, fresh=False, **kw):
    """Teacher-forced decode of ``slot`` over ``feed`` -> (logits of every
    step, cache)."""
    n = cache["pos"].shape[0]
    active = jnp.arange(n) == slot
    kw.setdefault("window", cfg.max_seq)
    step = (_call if fresh else _jitted)(cfg, "decode", **kw)
    out = []
    for t in feed:
        toks = jnp.zeros((n,), jnp.int32).at[slot].set(int(t))
        lg, cache = step(params, toks, cache, active)
        out.append(np.asarray(lg[slot]))
    return np.stack(out), cache


def _reference(cfg, params, seq):
    return np.asarray(ref.logits(
        params, seq, rotary_dim=cfg.rotary_dim, theta=cfg.rope_theta, eps=cfg.norm_eps,
    ))


@pytest.fixture(scope="module")
def seq():
    return np.random.default_rng(0).integers(1, 256, 40)


@pytest.fixture(scope="module")
def want(seq):
    cfg = _cfg()
    return _reference(cfg, _params(cfg), seq)


def _served(cfg, params, seq, L, *, seq_impl="dense", fresh=False, **kw):
    """Logits at every position from ``L - 1`` on: the prompt's last, then
    the rest of ``seq`` fed to decode steps."""
    last, cache = _prefill(cfg, params, seq[:L], seq_impl=seq_impl, fresh=fresh)
    steps, cache = _decode(cfg, params, cache, seq[L:], fresh=fresh, **kw)
    return np.concatenate([np.asarray(last)[None], steps]), cache


def _with(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


class TestAgainstReference:
    def test_forward(self, seq, want):
        cfg = _cfg()
        got = m.forward(_params(cfg), jnp.asarray(seq)[None], cfg)[0]
        assert np.abs(np.asarray(got) - want).max() < TOL

    # length 1: every tail is the prompt's only token behind zeros; 13:
    # mid-rung; 16: a whole rung; 21: into a second rung
    @pytest.mark.parametrize("L,seq_impl,kernel", [
        (1, "dense", False), (13, "flash", True), (16, "flash", False),
        (21, "dense", True),
    ])
    def test_prefill_then_decode(self, seq, want, L, seq_impl, kernel):
        cfg = _cfg()
        got, cache = _served(cfg, _params(cfg), seq, L, seq_impl=seq_impl, kernel=kernel)
        assert got.shape[0] == len(seq) - L + 1
        assert np.abs(got - want[L - 1:]).max() < TOL
        ctr = dict(zip(m.COUNTERS, np.asarray(cache["counters"])))
        steps = len(seq) - L
        assert ctr["moe.prefill_tokens"] == L and ctr["zaya.steps"] == ctr["moe.steps"] == steps
        # top-1: a pair a token-layer, the no-op's among the routed and not the held
        assert ctr["moe.prefill_pairs_routed"] == 3 * L and ctr["moe.pairs_routed"] == 3 * steps
        assert ctr["moe.pairs_held"] + ctr["moe.tokens_skipped"] == 3 * steps
        assert ctr["moe.prefill_pairs_held"] + ctr["moe.prefill_tokens_skipped"] == 3 * L
        # every block, a slot at position p attends p + 1 rows
        assert ctr["attn.rows_live"] == 3 * sum(range(L + 1, len(seq) + 1))

    def test_the_paged_read_at_several_blocks_a_step(self):
        """The family's call site (``models/paged.py::attend_paged``) at a
        tile the row's BYTES size: 2 key-value heads of 128 in float32 are
        1-KB rows, so a step of the kernel attends 512 rows, eight blocks of
        64, and the contexts here run from 500 to 529: the last steps' read
        is a whole tile and one live block of the next."""
        from seldon_core_tpu.ops.paged_attention import blocks_per_step

        cfg, bs, L = m.Config.tiny(max_seq=640, head_dim=128), 64, 500
        params = _params(cfg)
        cache = m.init_paged_cache(cfg, 2, 24, bs, jnp.float32)
        tile = bs * blocks_per_step(bs, cache["k"].shape[-1] * 4)
        seq = np.random.default_rng(1).integers(1, 256, 530)
        assert cache["k"].shape[2:] == (bs, 256) and tile == 512 and L < tile < len(seq)
        last, cache = _prefill(
            cfg, params, seq[:L], cache=cache, seq_impl="flash",
            row=_slot_row(n_blocks=10, width=10, first=3))
        steps, cache = _decode(cfg, params, cache, seq[L:], kernel=True)
        got = np.concatenate([np.asarray(last)[None], steps])
        assert np.abs(got - _reference(cfg, params, seq)[L - 1:]).max() < TOL

    def test_the_no_op_is_chosen_and_counted(self, seq):
        cfg = _cfg()
        _, cache = _served(cfg, _params(cfg), seq, 13)
        ctr = dict(zip(m.COUNTERS, np.asarray(cache["counters"])))
        assert 0 < ctr["moe.tokens_skipped"] < ctr["moe.pairs_routed"]
        assert 0 < ctr["moe.prefill_tokens_skipped"]
        # the touched-only kernel read what the tokens chose and no other
        assert ctr["moe.experts_read"] == ctr["moe.experts_touched"] <= ctr["moe.pairs_held"]

    def test_bfloat16_as_served(self, seq):
        """The served dtype: the tails and the pool in bfloat16, and the
        program's own two paths (prompt then steps; the full forward) within
        bfloat16 of each other on the rows where their routers chose alike."""
        cfg = _cfg()
        params = _params(cfg, dtype=jnp.bfloat16)
        got, cache = _served(cfg, params, seq, 13, seq_impl="flash", kernel=True)
        assert all(cache[n].dtype == jnp.bfloat16 for n in m.SLOT_ARRAYS + m.POOL_ARRAYS)
        full = np.asarray(m.forward(params, jnp.asarray(seq)[None], cfg)[0], np.float32)[12:]
        close = np.abs(got.astype(np.float32) - full).max(-1) < 0.12
        assert close.mean() > 0.7


class TestEachMechanismMatters:
    """Each of the block's mechanisms taken out of the PROGRAM (its weights
    set to what leaves it out, or the module's own function replaced) fails
    the comparison the sound program passes."""

    L = 13

    def _off(self, seq, want, params=None, **kw):
        cfg = _cfg()
        got, _ = _served(cfg, _params(cfg) if params is None else params, seq, self.L, **kw)
        return np.abs(got - want[self.L - 1:]).max()

    def test_zero_the_tail(self, seq, want):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, seq[: self.L])
        for lost in m.SLOT_ARRAYS:
            got, _ = _decode(
                cfg, params, dict(cache, **{lost: jnp.zeros_like(cache[lost])}),
                seq[self.L:],
            )
            assert np.abs(got - want[self.L:]).max() > 0.01, lost
            # one token's q, k and v are corrupted; the rows behind it heal
            # but for what that token left in the pool
            assert np.abs(got[0] - want[self.L]).max() > 0.01, lost

    @pytest.mark.parametrize("leaf,value", [
        ("r_gam", 0.0),  # drop z: nothing goes from a block's router to the next
        ("r_bal", 0.0),  # leave out the balancing biases
        ("res_a", (1.0, 0.0, 1.0, 0.0)), ("res_m", (1.0, 0.0, 1.0, 0.0)),  # a plain add
    ], ids=["drop-z", "no-bal", "no-res-scale-attn", "no-res-scale-moe"])
    def test_a_learned_vector_left_out(self, seq, want, leaf, value):
        params = _params(_cfg())
        like = params["layers"][leaf]
        flat = jnp.broadcast_to(
            jnp.asarray(value, like.dtype).reshape((1, -1, 1) if like.ndim == 3 else ()),
            like.shape,
        )
        assert self._off(seq, want, _with(params, **{leaf: flat})) > 0.01

    def test_the_no_op_taken_out(self, seq, want):
        """A router that may not choose the no-op sends its tokens to an
        expert: the 17th choice adds nothing, and it is taken."""
        params = _params(_cfg())
        bal = params["layers"]["r_bal"].at[:, -1].set(-1e9)
        assert self._off(seq, want, _with(params, r_bal=bal)) > 0.01

    @pytest.mark.parametrize("name,without", [
        ("_shift", lambda now, before: now),  # every value head from this token
        ("_qk_mean", lambda u, cfg: (0.0, 0.0)),
        ("_rotary", lambda x, positions, cfg: x),
    ], ids=["value-shift", "qk-mean", "rotary"])
    def test_a_function_of_the_module_replaced(self, seq, want, monkeypatch, name, without):
        monkeypatch.setattr(m, name, without)
        assert self._off(seq, want, fresh=True) > 0.01


class TestTheSlotsTails:
    def test_a_short_prompt_leaves_the_tails_of_its_real_last_token(self, seq):
        """One prompt at two rungs leaves the same tails and logits, and the
        tails are the reference's ``u``, ``c0`` and ``h Wv2`` at the last
        REAL token."""
        cfg = _cfg()
        params = _params(cfg)
        a, ca = _prefill(cfg, params, seq[:13], rung=16, seq_impl="flash")
        b, cb = _prefill(cfg, params, seq[:13], rung=32, seq_impl="flash")
        for name in m.SLOT_ARRAYS:
            np.testing.assert_allclose(
                np.asarray(ca[name]), np.asarray(cb[name]), rtol=1e-4, atol=1e-5,
                err_msg=name,
            )
            assert np.asarray(ca[name][:, ..., 1, :]).any()
            assert not np.asarray(ca[name][:, ..., 0, :]).any()  # the other slot's
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < TOL
        lp = {k: v[0] for k, v in params["layers"].items()}
        h = ref.rmsnorm(ref.f32(params["tok_emb"][seq[:13]]), lp["ln_a"], cfg.norm_eps)
        u = np.asarray(h @ lp["wqk"])
        c0 = lp["conv0_w"][0] * u[11] + lp["conv0_w"][1] * u[12] + lp["conv0_b"]
        assert np.abs(np.asarray(ca["tail_u"][0, 0, 1]) - u[12]).max() < 1e-5
        assert np.abs(np.asarray(ca["tail_c"][0, 0, 1]) - np.asarray(c0)).max() < 1e-5
        half = cfg.n_kv_heads * cfg.head_dim // 2
        hv = np.asarray(h @ lp["wv"])[12, half:]
        assert np.abs(np.asarray(ca["tail_v"][0, 1]) - hv).max() < 1e-5

    def test_two_slots_of_different_lengths_do_not_read_each_others_tails(self, seq):
        """Slots stepped together equal each stepped alone; a slot that goes
        inactive mid-block changes no other's logits and keeps its tails."""
        cfg = _cfg()
        params = _params(cfg)
        n, lens = 4, [1, 5, 13, 16]
        cache = _fresh(cfg, params, n_slots=n, blocks=1 + n * 8)
        for s, L in enumerate(lens):
            row = np.zeros(16, np.int32)
            row[:8] = 1 + 8 * s + np.arange(8)
            _, cache = _prefill(cfg, params, seq[s:s + L], cache=cache, slot=s, row=row)
        dec = _jitted(cfg, "decode", window=cfg.max_seq)
        feed = np.random.default_rng(1).integers(1, 256, (4, n)).astype(np.int32)
        together, c = [], cache
        for i, toks in enumerate(feed):
            active = np.ones(n, bool)
            active[2] = i < 2  # slot 2 goes inactive after two steps
            lg, c = dec(params, jnp.asarray(toks), c, jnp.asarray(active))
            together.append(np.asarray(lg))
            if i == 1:
                kept = {name: np.asarray(c[name][:, ..., 2, :]) for name in m.SLOT_ARRAYS}
        for name in m.SLOT_ARRAYS:
            assert np.array_equal(np.asarray(c[name][:, ..., 2, :]), kept[name]), name
        for s in range(n):
            alone = cache
            for i, toks in enumerate(feed[: 2 if s == 2 else None]):
                lg, alone = dec(params, jnp.asarray(toks), alone, jnp.arange(n) == s)
                assert np.abs(together[i][s] - np.asarray(lg[s])).max() < TOL, (s, i)

    def test_a_slot_is_overwritten_not_accumulated(self, seq, want):
        """A long request, then a short one in the same slot, equals the
        short one in a fresh cache."""
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, seq[::-1][:29].copy())
        _, cache = _decode(cfg, params, cache, seq[:6])
        last, cache = _prefill(cfg, params, seq[:5], cache=cache)
        got, _ = _decode(cfg, params, cache, seq[5:20])
        fresh_last, fresh = _prefill(cfg, params, seq[:5])
        fresh_got, _ = _decode(cfg, params, fresh, seq[5:20])
        assert np.array_equal(np.asarray(last), np.asarray(fresh_last))
        assert np.array_equal(got, fresh_got)
        assert np.abs(got - want[5:20]).max() < TOL


class TestThePublishedShape:
    def test_the_published_keys_are_accepted(self):
        catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
        published = {
            "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_size": 2048,
            "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
            "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
            "num_hidden_layers": 40, "num_key_value_heads": 2,
            "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
            "rope_parameters": {"hybrid": {"rope_theta": 5000000}},
            "router_hidden_size": 256, "tie_word_embeddings": True, "vocab_size": 262272,
        }
        if os.path.exists(catalog):
            import json

            with open(catalog) as f:
                published = next(
                    r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")["config"]
        assert m.Config.from_published(published) == m.Config()
        cut = m.Config.from_published(published, n_layers=20, max_seq=4096)
        assert (cut.n_layers, cut.max_seq, cut.latent, cut.rotary_dim) == (20, 4096, 1280, 64)

    def test_partial_rotary_touches_64_of_128(self):
        cfg = m.Config()
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 8, 128))
        y = np.asarray(m._rotary(x, jnp.arange(5) + 3, cfg))
        moved = np.abs(y - np.asarray(x)).max(axis=(0, 1)) > 0
        assert moved[:64].all() and not moved[64:].any()
        want = ref.rotate_half(x[..., :64], jnp.arange(5) + 3, cfg.rope_theta)
        assert np.abs(y[..., :64] - np.asarray(want)).max() < 1e-5

    @pytest.mark.parametrize("bad", [
        dict(n_heads=3), dict(cca_time0=1), dict(cca_time1=1), dict(experts_per_tok=2),
        dict(tie_word_embeddings=False), dict(partial_rotary_factor=1.5),
    ], ids=lambda b: next(iter(b)))
    def test_what_is_not_served_is_refused(self, bad):
        with pytest.raises(ValueError):
            m.Config(**bad)

    def test_the_published_sizes_count_the_issues_parameters(self):
        cfg = m.Config()
        shapes = jax.eval_shape(
            lambda key: m.init_params(key, cfg, jnp.bfloat16), jax.random.PRNGKey(0)
        )
        count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
        lay = shapes["layers"]
        block = count(lay) // 40
        assert block == 5_242_880 + 332_800 + 20_482 + 661_009 + 201_326_592 == 207_583_763
        assert count({k: lay[k] for k in ("conv0_w", "conv0_b", "conv1_w", "conv1_b")}) == 40 * 332_800
        assert count({k: v for k, v in lay.items() if k.startswith("r_")}) == 40 * 661_009
        assert count(shapes["tok_emb"]) == 262_272 * 2048 == 537_133_056
        assert abs(count(shapes) - 8.84e9) < 0.005e9
        assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes))

    def test_the_seeded_router_routes_evenly(self):
        """Assumed (g): the balancing biases even the choices' shares in one
        pass, so on fresh router states every one of a block's 17 choices
        takes near its even share; without them a seeded MLP's favourites
        take most tokens.  The key temperature lies in [4, 8]."""
        cfg = m.Config.tiny(n_experts=16, router_hidden_size=64)
        lay = _params(cfg)["layers"]
        tau = np.asarray(lay["tau"])
        assert (tau >= m.TAU_MIN).all() and (tau <= m.TAU_MAX).all()
        z = jax.random.normal(jax.random.PRNGKey(9), (4096, cfg.router_hidden_size))
        for l in range(cfg.n_layers):
            lp = {k: v[l] for k, v in lay.items() if k.startswith("r_")}
            p = m._router_probs(z, lp, cfg)
            even = np.bincount(np.asarray(jnp.argmax(p + lp["r_bal"], -1)), minlength=17) / 4096
            raw = np.bincount(np.asarray(jnp.argmax(p, -1)), minlength=17) / 4096
            assert even.max() < 1.5 / 17 and even.min() > 0.6 / 17, even
            assert raw.max() > 2 * even.max(), raw

    def test_both_kinds_of_state_on_every_layer(self):
        import dataclasses

        cfg = dataclasses.replace(m.Config(), n_layers=20, max_seq=4096)
        cache = jax.eval_shape(lambda: m.init_paged_cache(cfg, 48, 769, 256, jnp.bfloat16))
        assert cache["k"].shape == cache["v"].shape == (20, 769, 256, 256)
        assert cache["tail_u"].shape == cache["tail_c"].shape == (20, 1, 48, 1280)
        assert cache["tail_v"].shape == (20, 48, 128)
        nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize  # noqa: E731
        assert sum(nbytes(cache[n]) for n in m.SLOT_ARRAYS) == 48 * 20 * 5376
        assert m.slot_tail_bytes(cfg, "bfloat16") == 20 * 5376 == 107_520
        # 20,480 B a token: 20 blocks x (256 + 256) x 2 B
        assert m.paged_kv_slot_bytes(cfg, 256, dtype="bfloat16") == 4096 * 20_480 + 107_520
        assert nbytes(cache["k"]) + nbytes(cache["v"]) == 769 * 256 * 20_480
        assert m.POOL_ARRAYS == ("k", "v")
        assert m.SLOT_ARRAYS == ("tail_u", "tail_c", "tail_v")


class TestTop1WithTheNoOp:
    """``models/moe.py::routed_experts`` at one expert a token, one of the
    choices an index no share holds."""

    T, X, E, F = 12, 4, 32, 16

    def _layer(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        lp = {
            "we_gate": jax.random.normal(ks[0], (self.X, self.E, self.F)) / 6,
            "we_up": jax.random.normal(ks[1], (self.X, self.E, self.F)) / 6,
            "we_down": jax.random.normal(ks[2], (self.X, self.F, self.E)) / 4,
        }
        h2 = jax.random.normal(ks[3], (self.T, self.E))
        # expert 2 is chosen by nobody; tokens 1, 5 and 9 choose the no-op
        idx = jnp.asarray([0, 4, 1, 3, 0, 4, 1, 1, 3, 4, 0, 3], jnp.int32)[:, None]
        w = jax.random.uniform(ks[4], (self.T, 1), minval=0.2, maxval=0.9)
        return lp, h2, idx, w

    def _want(self, lp, h2, idx, w, mask):
        out = np.zeros((self.T, self.E), np.float32)
        for t in range(self.T):
            e = int(idx[t, 0])
            if e < self.X and mask[t]:
                y = ref.expert(h2[t:t + 1], lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e])
                out[t] = float(w[t, 0]) * np.asarray(y[0])
        return out

    @pytest.mark.parametrize("plan", ["dense", "touched", "grouped"])
    def test_the_three_products_agree_and_count(self, monkeypatch, plan):
        lp, h2, idx, w = self._layer()
        mask = jnp.arange(self.T) != 10  # one row is padding
        monkeypatch.setattr(moe, "experts_plan", lambda n, kernel=True: plan)
        stacks = {k: jnp.stack([jnp.zeros_like(v), v]) for k, v in lp.items()}  # layer 1 of 2
        out, ctr = moe.routed_experts(
            h2, lp, idx, w, (0, self.X), mask, jnp.zeros((len(moe.COUNTERS),), jnp.uint32),
            decode=True, kernel=True, stacks=stacks, li=1,
        )
        with jax.default_matmul_precision("highest"):
            want = self._want(lp, h2, idx, w, np.asarray(mask))
        assert np.abs(np.asarray(out) - want).max() < 2e-5
        assert not np.asarray(out)[[1, 5, 9, 10]].any()  # the no-op, and padding, add 0
        c = dict(zip(moe.COUNTERS, np.asarray(ctr)))
        # 11 real tokens routed, 3 of them to the no-op: 8 held, on 3 of 4 experts
        assert (c["moe.pairs_routed"], c["moe.pairs_held"]) == (11, 8)
        assert (c["moe.experts_touched"], c["moe.max_tokens_on_expert"]) == (3, 3)
        assert c["moe.experts_read"] == (3 if plan == "touched" else 4)

    def test_the_comparisons_rule_for_a_top_1_choice(self):
        """A served choice is held by how far under the reference's best it
        lies (with ``bal``): 0 where they choose alike, the reference's own
        gap where they differ."""
        lp = {"r_bal": jnp.asarray([0.0, 0.03, 0.0])}
        p = jnp.asarray([[0.5, 0.48, 0.02], [0.5, 0.48, 0.02], [0.2, 0.7, 0.1]])
        # p + bal: the reference chooses 1 (0.51 over 0.50), 1 and 1
        got = np.asarray(ref.choice_deficit(p, jnp.asarray([1, 0, 2]), lp))
        assert np.allclose(got, [0.0, 0.01, 0.63], atol=1e-6)
        e, w = ref.choose(p, lp)
        assert list(np.asarray(e)) == [1, 1, 1] and np.allclose(w, [0.48, 0.48, 0.7])


class TestServedPath:
    """Through ``JAX_GENERATIVE``'s own objects."""

    def _component(self, **kw):
        from seldon_core_tpu.models.registry import build_generative_component

        return build_generative_component(
            "zaya", preset="tiny", max_seq=64, n_slots=2, decode_block=4,
            kv_block_size=4, dtype=jnp.float32, rng=5, **kw,
        )

    def test_generates_what_the_family_computes(self, seq):
        from seldon_core_tpu.ops.paged_attention import blocks_per_step
        from seldon_core_tpu.utils.device import xla_compile_count

        prompt = seq[:37]
        comp = self._component(seq_impl="flash", decode_kernel=True)
        model = comp.model
        assert model.family is m
        assert model._pool_names == ("k", "v") and model._slot_names == m.SLOT_ARRAYS
        cfg = model.cfg
        # the pool: 3 blocks x (32 + 32) values x 4 B a token; a slot's
        # tails: 3 blocks x (96 + 96 + 16) x 4 B
        per_token, tails = 3 * 64 * 4, 3 * 208 * 4
        assert m.slot_tail_bytes(cfg, "float32") == tails
        assert model.kv_bytes_per_slot() == 64 * per_token + tails
        snap = model.pool_snapshot()["bytes"]
        assert snap["slot_state"] == 2 * tails and snap["per_slot"] == 64 * per_token + tails
        model.warmup()
        warmed = xla_compile_count()
        tok = model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=12)
        cur, active = np.zeros(2, np.int32), np.zeros(2, bool)
        cur[0], active[0] = int(tok), True
        toks, emitted = model.step_k(
            cur, active, np.zeros(2, np.float32), 0,
            np.full(2, -1, np.int32), np.full(2, 12, np.int32), 4,
        )
        assert emitted[:, 0].all()
        assert xla_compile_count() == warmed  # nothing compiled after warm-up
        served = [int(tok)] + [int(t) for t in toks[:, 0]]
        want = _reference(cfg, model.params, np.concatenate([prompt, served[:-1]]))
        want = want[len(prompt) - 1:]
        assert list(want.argmax(-1)) == served
        snap = model.spec_snapshot()
        # the tile the kernel ran: float32 rows of 32 values are 128 B, so
        # the rule's cap, in blocks of 4
        assert snap["decode_read"] == "kernel"
        assert snap["decode_tile_rows"] == 4 * blocks_per_step(4, 32 * 4) == 2048
        ctr = snap["counters"]
        assert ctr["moe.prefill_tokens"] >= 37 and ctr["zaya.steps"] >= 4
        assert ctr["attn.rows_live"] >= 3 * 4 * 38

    def test_what_the_family_does_not_have_is_refused_by_name(self, seq, caplog):
        import logging

        from seldon_core_tpu.graph.units import GraphUnitError

        cfg = _cfg()
        with pytest.raises(TypeError, match="zaya has no int8 pool"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_dtype="int8")
        with pytest.raises(TypeError, match="zaya has no cache split over a mesh.*tail_u"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_sharded=True)
        params = _params(cfg)
        with pytest.raises(TypeError, match="zaya has no LoRA"):
            m.decode_slots_paged(
                params, jnp.zeros(2, jnp.int32), _fresh(cfg, params),
                jnp.ones(2, bool), cfg, lora={},
            )
        with pytest.raises(GraphUnitError, match="zaya has no decode_slots_spec_paged"):
            self._component(spec_draft=2)
        with caplog.at_level(logging.WARNING):
            model = self._component(kv_prefix_reuse=True, prefill_chunk=8).model
        assert model.prefix_index is None and model.prefill_chunk == 0
        said = " ".join(r.getMessage() for r in caplog.records)
        assert "no prefill_suffix_paged; KV prefix reuse disabled" in said
        assert "no prefill_suffix_paged; chunked prefill disabled" in said
        prompt = seq[:20].astype(np.int32)
        model.admit(0, prompt, 0.0, 0, reserve_tokens=4)
        with pytest.raises(TypeError, match="zaya keeps tail_u, tail_c, tail_v per slot.*export"):
            model.export_slot_kv(0, len(prompt))
        z = np.zeros((3, 5, 4, 2, 16), np.float32)
        with pytest.raises(TypeError, match="zaya keeps tail_u, tail_c, tail_v per slot.*import"):
            model.attach_imported(1, prompt, z, z)


class TestEngineRoutes:
    """``examples/zaya-generative/graph.json`` through the engine's own app:
    both routes give the same tokens, and the family's counters are in
    ``/stats/summary``."""

    def test_the_example_graph_serves_both_routes(self):
        import asyncio
        import json

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        path = os.path.join(
            os.path.dirname(__file__), "..", "examples", "zaya-generative", "graph.json",
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
                unit = stats["breakdown"]["generation"]["zaya:tiny"]
                c = unit["counters"]
                assert set(m.COUNTERS) <= set(c)
                assert c["moe.prefill_tokens"] >= 2 * 37 and c["zaya.steps"] >= 19
                assert c["moe.pairs_routed"] == c["moe.pairs_held"] + c["moe.tokens_skipped"]
                assert unit["kv_bytes_per_slot"] > 0
                # the CPU's read is the gather: no kernel, so no tile
                assert unit["decode_read"] == "gather"
                assert unit["decode_tile_rows"] is None
            finally:
                await client.close()

        asyncio.run(go())
