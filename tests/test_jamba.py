"""The ``jamba`` family (models/jamba.py) against the benchmark's plain
reference (benchmark/reference/jamba_decoder.py), at a small size on the CPU:
hidden 64, d_inner 128, d_state 16, dt_rank 8, 8 layers of which layers 1 and
5 attend (period 4, offset 1), 4 query heads on 1 key-value head, an MLP 96
wide, vocabulary 256; the state-space parameters seeded by Mamba's published
initialisation.  Logits, not tokens."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import jamba as m

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "benchmark", "reference")
)
import jamba_decoder as ref  # noqa: E402

BS = 4  # pool block
RUNG = 16  # what a short prompt is padded to
# float32 against float32: summation order only (the state's index leads in
# the program and trails in the reference).  Each control below moves a logit
# by hundredths or more
TOL = 5e-5
SERVED = dict(
    ssm_state_dtype="float32", ssm_product_dtype="float32", ssm_padding="still",
    conv_tail_at="length", dt_bias="on",
)


def _cfg(**kw):
    return m.Config.tiny(max_seq=64, **kw)


def _params(cfg, seed=3, dtype=jnp.float32):
    """The weights: no control changes them, so one init serves them all."""
    return _made(dataclasses.replace(cfg, **SERVED), seed, dtype)


@functools.lru_cache(maxsize=None)
def _made(cfg, seed, dtype):
    return jax.jit(lambda key: m.init_params(key, cfg, dtype))(jax.random.PRNGKey(seed))


@functools.lru_cache(maxsize=None)
def _jitted(cfg, which, **static):
    fn = {"prefill": m.prefill_slot_paged, "decode": m.decode_slots_paged}[which]
    cfg_at = {"prefill": 6, "decode": 4}[which]

    def call(*args):
        return fn(*args[:cfg_at], cfg, *args[cfg_at:], **static)

    return jax.jit(call)


def _slot_row(n_blocks=14, width=16, first=1):
    """A table row whose blocks are out of order (block 0 is the sink)."""
    row = np.zeros(width, np.int32)
    row[:n_blocks] = np.arange(first, first + n_blocks)[::-1]
    return row


def _fresh(cfg, params, n_slots=2, blocks=40):
    return m.init_paged_cache(cfg, n_slots, blocks, BS, params["ln_f"].dtype)


def _prefill(cfg, params, prompt, *, cache=None, seq_impl="dense", slot=1,
             rung=None, row=None):
    cache = _fresh(cfg, params) if cache is None else cache
    rung = rung or -(-len(prompt) // RUNG) * RUNG
    padded = np.zeros((1, rung), np.int32)
    padded[0, : len(prompt)] = prompt
    return _jitted(cfg, "prefill", seq_impl=seq_impl)(
        params, jnp.asarray(padded), jnp.int32(len(prompt)), jnp.int32(slot),
        jnp.asarray(_slot_row() if row is None else row), cache,
    )


def _decode(cfg, params, cache, feed, *, slot=1, **kw):
    """Teacher-forced decode of ``slot`` over ``feed`` -> (logits of every
    step, cache)."""
    n = cache["pos"].shape[0]
    active = jnp.arange(n) == slot
    kw.setdefault("window", cfg.max_seq)
    out = []
    for t in feed:
        toks = jnp.zeros((n,), jnp.int32).at[slot].set(int(t))
        lg, cache = _jitted(cfg, "decode", **kw)(params, toks, cache, active)
        out.append(np.asarray(lg[slot]))
    return np.stack(out), cache


def _reference(cfg, params, seq):
    return np.asarray(ref.logits(
        params, seq, period=cfg.attn_layer_period, offset=cfg.attn_layer_offset,
        eps=cfg.norm_eps,
    ))


@pytest.fixture(scope="module")
def seq():
    return np.random.default_rng(0).integers(1, 256, 40)


@pytest.fixture(scope="module")
def want(seq):
    cfg = _cfg()
    return _reference(cfg, _params(cfg), seq)


def _served(cfg, params, seq, L, **kw):
    """Logits at every position from ``L - 1`` on: the prompt's last, then
    the rest of ``seq`` fed to decode steps."""
    seq_impl = kw.pop("seq_impl", "dense")
    last, cache = _prefill(cfg, params, seq[:L], seq_impl=seq_impl)
    steps, cache = _decode(cfg, params, cache, seq[L:], **kw)
    return np.concatenate([np.asarray(last)[None], steps]), cache


class TestAgainstReference:
    def test_forward(self, seq, want):
        cfg = _cfg()
        got = m.forward(_params(cfg), jnp.asarray(seq)[None], cfg)[0]
        assert np.abs(np.asarray(got) - want).max() < TOL

    # lengths 1, 2, 3: a tail shorter than the taps; 13: mid-rung; 16: a
    # whole rung; 21: into a second rung (two chunks of the kernel's)
    @pytest.mark.parametrize("L,seq_impl,kernel", [
        (1, "dense", False), (2, "flash", True), (3, "dense", False),
        (13, "flash", True), (16, "flash", False), (21, "dense", True),
    ])
    def test_prefill_then_decode(self, seq, want, L, seq_impl, kernel):
        cfg = _cfg()
        got, cache = _served(cfg, _params(cfg), seq, L, seq_impl=seq_impl, kernel=kernel)
        assert got.shape[0] == len(seq) - L + 1
        assert np.abs(got - want[L - 1:]).max() < TOL
        ctr = dict(zip(m.COUNTERS, np.asarray(cache["counters"])))
        steps = len(seq) - L
        assert ctr["ssm.prefill_tokens"] == L and ctr["ssm.prefill_rows"] % RUNG == 0
        assert ctr["ssm.steps"] == ctr["ssm.slot_steps"] == steps
        # two attention layers, a slot at position p attends p + 1 rows
        assert ctr["attn.rows_live"] == 2 * sum(range(L + 1, len(seq) + 1))

    def test_the_paged_read_at_several_blocks_a_step(self):
        """The family's call site (``models/paged.py::attend_paged``) at a
        tile the row's BYTES size: one key-value head of 256 in float32 is
        a 1-KB row, so a step of the kernel attends 512 rows, eight blocks
        of 64, and the contexts here run from 500 to 529: the last steps'
        read is a whole tile and one live block of the next."""
        from seldon_core_tpu.ops.paged_attention import blocks_per_step

        cfg = m.Config.tiny(max_seq=640, hidden=256, n_heads=1, n_layers=4)
        bs, L = 64, 500
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

    @pytest.mark.parametrize("control,least", [
        (dict(dt_bias="off"), 0.05),
        (dict(ssm_padding="moves"), 0.01),
        (dict(conv_tail_at="rung"), 0.01),
        (dict(ssm_state_dtype="bfloat16"), 1e-3),
        (dict(ssm_product_dtype="bfloat16"), 1e-3),
    ], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()) if isinstance(c, dict) else None)
    def test_a_control_fails_the_same_tolerance(self, seq, want, control, least):
        cfg = _cfg(**control)
        got, _ = _served(cfg, _params(cfg), seq, 13, seq_impl="flash")
        assert np.abs(got - want[12:]).max() > least

    def test_the_state_matters(self, seq, want):
        """A state lost between the prompt and its decode steps is seen: with
        ``b_dt`` by Mamba's initialisation a channel remembers tens of
        tokens and more."""
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, seq[:13])
        lost = dict(cache, ssm=jnp.zeros_like(cache["ssm"]))
        got, _ = _decode(cfg, params, lost, seq[13:])
        assert np.abs(got - want[13:]).max() > 0.05

    def test_bfloat16_as_served(self, seq):
        cfg = _cfg()
        params = _params(cfg, dtype=jnp.bfloat16)
        want = _reference(cfg, params, seq)
        got, cache = _served(cfg, params, seq, 13, seq_impl="flash", kernel=True)
        assert cache["ssm"].dtype == jnp.float32 and cache["conv"].dtype == jnp.bfloat16
        deficit = want[12:].max(-1) - want[12:][np.arange(len(got)), got.argmax(-1)]
        assert deficit.max() < 0.25
        assert np.abs(got - want[12:]).max() < 0.25


class TestTheSlotsState:
    def test_padding_moves_nothing(self, seq):
        """One prompt at two rungs leaves the same state, tail and logits."""
        cfg = _cfg()
        params = _params(cfg)
        a, ca = _prefill(cfg, params, seq[:13], rung=16, seq_impl="flash")
        b, cb = _prefill(cfg, params, seq[:13], rung=32, seq_impl="flash")
        # (two rungs are two shapes of every product: the last bits differ)
        for name in m.SLOT_ARRAYS:
            np.testing.assert_allclose(
                np.asarray(ca[name], np.float32), np.asarray(cb[name], np.float32),
                rtol=1e-4, atol=1e-5, err_msg=name,
            )
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < TOL
        assert np.asarray(ca["ssm"][:, 1]).any() and not np.asarray(ca["ssm"][:, 0]).any()

    @pytest.mark.parametrize("L", [1, 2, 3, 13])
    def test_the_tail_is_the_last_inputs_and_zeros_before_the_start(self, seq, L):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, seq[:L])
        lp = {k: v[0] for k, v in params["ssm_layers"].items()}
        h = ref.rmsnorm(ref.f32(params["tok_emb"][seq[:L]]), lp["ln1"], cfg.norm_eps)
        u = np.asarray(h @ lp["win"])[:, : cfg.d_inner]
        tail = np.zeros((3, cfg.d_inner), np.float32)
        tail[max(0, 3 - L):] = u[max(0, L - 3):]
        assert np.abs(np.asarray(cache["conv"][0, :, 1]) - tail).max() < 1e-5

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

    def test_the_same_request_before_and_after_others(self, seq):
        """The probes' "same output before and after the window" in
        miniature: a request, other requests through both slots, the request
        again in the slot it had."""
        cfg = _cfg()
        params = _params(cfg)
        last0, cache = _prefill(cfg, params, seq[:9])
        got0, cache = _decode(cfg, params, cache, seq[9:17])
        for slot, lo in ((0, 3), (1, 7)):
            _, cache = _prefill(
                cfg, params, seq[lo:lo + 22], cache=cache, slot=slot,
                row=_slot_row(first=1 + 16 * slot),
            )
            _, cache = _decode(cfg, params, cache, seq[:5], slot=slot)
        last1, cache = _prefill(cfg, params, seq[:9], cache=cache)
        got1, _ = _decode(cfg, params, cache, seq[9:17])
        assert np.array_equal(np.asarray(last0), np.asarray(last1))
        assert np.array_equal(got0, got1)

    def test_slots_do_not_leak(self, seq):
        """8 slots stepped together equal each stepped alone, and a slot
        that goes inactive mid-block changes no other's logits."""
        cfg = _cfg()
        params = _params(cfg)
        n = 8
        cache = _fresh(cfg, params, n_slots=n, blocks=1 + n * 8)
        lens = [1, 2, 3, 5, 8, 11, 13, 16]
        for s, L in enumerate(lens):
            row = np.zeros(16, np.int32)
            row[:8] = 1 + 8 * s + np.arange(8)
            _, cache = _prefill(
                cfg, params, seq[s:s + L], cache=cache, slot=s, row=row
            )
        dec = _jitted(cfg, "decode", window=cfg.max_seq)
        rng = np.random.default_rng(1)
        feed = rng.integers(1, 256, (6, n)).astype(np.int32)
        together, alone = [], [[] for _ in range(n)]
        c = cache
        for i, toks in enumerate(feed):
            # slot 3 goes inactive after two steps, mid-block
            active = np.ones(n, bool)
            active[3] = i < 2
            lg, c = dec(params, jnp.asarray(toks), c, jnp.asarray(active))
            together.append(np.asarray(lg))
        for s in range(n):
            c = cache
            for i, toks in enumerate(feed):
                if s == 3 and i >= 2:
                    break
                only = np.zeros(n, bool)
                only[s] = True
                lg, c = dec(params, jnp.asarray(toks), c, jnp.asarray(only))
                alone[s].append(np.asarray(lg[s]))
        for s in range(n):
            for i, row in enumerate(alone[s]):
                assert np.abs(together[i][s] - row).max() < TOL, (s, i)

    def test_an_inactive_slots_state_stays(self, seq):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, seq[:9])
        _, after = _decode(cfg, params, cache, seq[9:12], slot=0)
        assert np.array_equal(np.asarray(after["ssm"][:, 1]), np.asarray(cache["ssm"][:, 1]))
        assert int(after["pos"][1]) == 9


class TestThePattern:
    def test_the_published_pattern_and_keys(self):
        cfg = m.Config()
        assert cfg.n_layers == 28 and cfg.attn_layers == (7, 21)
        assert cfg.n_ssm_layers == 26 and cfg.d_inner == 5120 and cfg.head_dim == 128
        assert cfg.ordinals == tuple(range(7)) + (0,) + tuple(range(7, 20)) + (1,) + tuple(range(20, 26))
        assert cfg.runs == (
            (False, 0, 7), (True, 0, 1), (False, 7, 13), (True, 1, 1), (False, 20, 6),
        )
        published = {
            "attn_layer_offset": 7, "attn_layer_period": 14, "mamba_conv_bias": True,
            "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
            "mamba_expand": 2, "mamba_proj_bias": False,
        }
        assert dataclasses.replace(cfg, **published) == cfg
        for l in range(28):
            assert cfg.is_attention(l) == ref.is_attention(l, 14, 7)

    def test_the_tiny_pattern(self):
        cfg = _cfg()
        assert cfg.attn_layers == (1, 5)
        assert cfg.ordinals == (0, 0, 1, 2, 3, 1, 4, 5)
        assert [r[0] for r in cfg.runs] == [False, True, False, True, False]

    @pytest.mark.parametrize("bad", [
        dict(attn_layer_offset=14), dict(attn_layer_period=64, attn_layer_offset=40), dict(mamba_proj_bias=True), dict(n_heads=3),
        dict(ssm_state_dtype="float16"), dict(dt_bias="maybe"),
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
        ssm = count(shapes["ssm_layers"]) // 26
        attn = count(shapes["attn_layers"]) // 2
        assert abs(ssm - 104.16e6) < 0.01e6 and abs(attn - 76.68e6) < 0.01e6
        total = count(shapes)
        assert abs(total - 3.029e9) < 0.001e9
        assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes))

    def test_the_seeded_state_remembers(self):
        """Assumed (c): the step at a zero input lies in [0.001, 0.1], so a
        channel's slowest state forgets in tens to a thousand tokens."""
        cfg = _cfg()
        p = _params(cfg)["ssm_layers"]
        step = np.asarray(jax.nn.softplus(p["b_dt"]))
        assert step.min() >= 0.9 * m.DT_MIN and step.max() <= 1.1 * m.DT_MAX
        a = -np.exp(np.asarray(p["a_log"]))
        assert np.allclose(a[0, :, 0], -np.arange(1, 17)) and (np.asarray(p["d_skip"]) == 1).all()


class TestCache:
    def test_two_kinds_of_state_for_one_slot(self):
        cfg = dataclasses.replace(m.Config(), max_seq=4096)
        cache = jax.eval_shape(
            lambda: m.init_paged_cache(cfg, 128, 1153, 256, jnp.bfloat16)
        )
        assert cache["k"].shape == cache["v"].shape == (2, 1153, 256, 128)
        assert cache["ssm"].shape == (26, 128, 16, 5120) and cache["ssm"].dtype == jnp.float32
        assert cache["conv"].shape == (26, 3, 128, 5120) and cache["conv"].dtype == jnp.bfloat16
        nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize  # noqa: E731
        state = sum(nbytes(cache[n]) for n in m.SLOT_ARRAYS)
        assert state == 128 * m.slot_state_bytes(cfg, "bfloat16")
        assert abs(m.slot_state_bytes(cfg, "bfloat16") - 9.32e6) < 0.01e6
        # 1,024 B a token: two layers x (128 + 128) x 2 B
        assert m.paged_kv_slot_bytes(cfg, 256, dtype="bfloat16") == (
            4096 * 1024 + m.slot_state_bytes(cfg, "bfloat16")
        )
        assert nbytes(cache["k"]) + nbytes(cache["v"]) == 1153 * 256 * 1024
        assert m.POOL_ARRAYS == ("k", "v") and m.SLOT_ARRAYS == ("ssm", "conv")

    def test_only_the_attention_layers_leave_rows(self, seq):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, seq[:13])
        row = _slot_row()
        k = np.asarray(cache["k"])
        assert k.shape[0] == 2
        for ai in range(2):
            held = k[ai, row[:4]].reshape(16, -1)
            assert np.abs(held[:13]).min(axis=-1).max() > 0  # every real row written


class TestServedPath:
    """Through ``JAX_GENERATIVE``'s own objects."""

    def _component(self, **kw):
        from seldon_core_tpu.models.registry import build_generative_component

        return build_generative_component(
            "jamba", preset="tiny", max_seq=64, n_slots=2, decode_block=4,
            kv_block_size=4, dtype=jnp.bfloat16, rng=5, **kw,
        )

    @pytest.mark.parametrize("seq_impl,kernel", [("dense", False), ("flash", True)])
    def test_generates_what_the_family_computes(self, seq, seq_impl, kernel):
        from seldon_core_tpu.ops.paged_attention import blocks_per_step
        from seldon_core_tpu.utils.device import xla_compile_count

        prompt = seq[:37]
        comp = self._component(seq_impl=seq_impl, decode_kernel=kernel)
        model = comp.model
        assert model.family is m and model.params["ln_f"].dtype == jnp.bfloat16
        assert model._pool_names == ("k", "v") and model._slot_names == ("ssm", "conv")
        cfg = model.cfg
        # the pool: 2 layers x (16 + 16) values x 2 B a token; the slot's
        # state: 6 layers x (16 x 128 x 4 B + 3 x 128 x 2 B)
        per_token, state = 2 * 32 * 2, 6 * (16 * 128 * 4 + 3 * 128 * 2)
        assert m.slot_state_bytes(cfg, "bfloat16") == state
        assert model.kv_bytes_per_block() == 4 * per_token
        assert model.kv_bytes_per_slot() == 64 * per_token + state
        snap = model.pool_snapshot()["bytes"]
        assert snap["kv_pool"] == model.kv_blocks * 4 * per_token
        assert snap["slot_state"] == 2 * state and snap["per_slot"] == 64 * per_token + state
        assert model.memory.snapshot()["owners"][model._mem_key]["slot_state"] == 2 * state
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
        deficit = want.max(-1) - want[np.arange(len(served)), served]
        assert deficit.max() < 0.5 and (deficit > 0.05).sum() <= 2
        snap = model.spec_snapshot()
        # the tile the kernel ran: bfloat16 rows of 16 values are 32 B, so
        # the rule's cap, in blocks of 4; no kernel, no tile
        assert snap["decode_read"] == ("kernel" if kernel else "gather")
        assert snap["decode_tile_rows"] == (
            4 * blocks_per_step(4, 16 * 2) if kernel else None)
        ctr = snap["counters"]
        assert ctr["ssm.prefill_tokens"] >= 37 and ctr["ssm.prefill_rows"] >= 40
        assert ctr["ssm.steps"] >= 4 and ctr["ssm.slot_steps"] >= 4
        assert ctr["attn.rows_live"] >= 2 * 4 * 38

    def test_prefix_reuse_and_chunks_are_warned_off(self, seq, caplog):
        import logging

        with caplog.at_level(logging.WARNING):
            model = self._component(kv_prefix_reuse=True, prefill_chunk=8).model
        assert model.prefix_index is None and model.prefill_chunk == 0
        said = " ".join(r.getMessage() for r in caplog.records)
        assert "no prefill_suffix_paged; KV prefix reuse disabled" in said
        assert "no prefill_suffix_paged; chunked prefill disabled" in said
        first = model.admit(0, seq[:20].astype(np.int32), 0.0, 0, reserve_tokens=4)
        model.release_slot(0)
        again = model.admit(1, seq[:20].astype(np.int32), 0.0, 0, reserve_tokens=4)
        assert model.prefills_reused == 0 and int(first) == int(again)

    def test_what_the_family_does_not_have_is_refused_by_name(self, seq):
        from seldon_core_tpu.graph.units import GraphUnitError

        cfg = _cfg()
        with pytest.raises(TypeError, match="jamba has no int8 pool"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_dtype="int8")
        with pytest.raises(GraphUnitError, match="jamba.*kv_cache_dtype"):
            self._component(kv_cache_dtype="int8")
        with pytest.raises(TypeError, match="jamba has no cache split over a mesh.*ssm, conv"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_sharded=True)
        assert self._component(lora_rank=4).model.lora_rank == 0
        params = _params(cfg)
        with pytest.raises(TypeError, match="jamba has no LoRA"):
            m.decode_slots_paged(
                params, jnp.zeros(2, jnp.int32), _fresh(cfg, params),
                jnp.ones(2, bool), cfg, lora={},
            )
        with pytest.raises(GraphUnitError, match="jamba has no decode_slots_spec_paged"):
            self._component(spec_draft=2)
        model = self._component().model
        prompt = seq[:20].astype(np.int32)
        model.admit(0, prompt, 0.0, 0, reserve_tokens=4)
        with pytest.raises(TypeError, match="jamba keeps ssm, conv per slot.*export"):
            model.export_slot_kv(0, len(prompt))
        z = np.zeros((2, 5, 4, 1, 16), np.float32)
        with pytest.raises(TypeError, match="jamba keeps ssm, conv per slot.*import"):
            model.attach_imported(1, prompt, z, z)
        with pytest.raises(TypeError, match="jamba keeps ssm, conv per slot.*peer prefix install"):
            model.install_prefix_chain(prompt, z, z)

    def test_a_mesh_is_refused_at_build(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
        with pytest.raises(TypeError, match="jamba has no cache split over a mesh"):
            self._component(mesh=mesh)

    def test_preemption_is_refused_by_name(self):
        from seldon_core_tpu.executor.generation import GenerationScheduler

        sched = GenerationScheduler(self._component().model)
        with pytest.raises(TypeError, match="jamba keeps ssm, conv per slot.*SuspendStore"):
            sched.request_preempt()

    def test_the_families_that_were_there_name_no_slot_state(self):
        from seldon_core_tpu.models.registry import build_generative_component

        model = build_generative_component(
            "llama", preset="tiny", max_seq=64, n_slots=2, kv_block_size=4,
        ).model
        assert model._slot_names == () and model._slot_state_bytes() == 0
        assert "slot_state" not in model.pool_snapshot()["bytes"]
        assert "slot_state" not in model.memory.snapshot()["owners"][model._mem_key]


class TestEngineRoutes:
    """``examples/jamba-generative/graph.json`` through the engine's own
    app: both routes give the same tokens, and the state's counters are in
    ``/stats/summary``."""

    def test_the_example_graph_serves_both_routes(self):
        import asyncio
        import json

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        path = os.path.join(
            os.path.dirname(__file__), "..", "examples", "jamba-generative",
            "graph.json",
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
                unit = stats["breakdown"]["generation"]["jamba:tiny"]
                c = unit["counters"]
                assert set(m.COUNTERS) <= set(c)
                assert c["ssm.prefill_tokens"] >= 2 * 37 and c["ssm.slot_steps"] >= 2 * 19
                assert unit["kv_bytes_per_slot"] > 0
                # the CPU's read is the gather: no kernel, so no tile
                assert unit["decode_read"] == "gather"
                assert unit["decode_tile_rows"] is None
            finally:
                await client.close()

        asyncio.run(go())
