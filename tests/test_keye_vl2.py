"""The ``keye_vl2`` family (models/keye_vl2.py) against the benchmark's plain
reference (benchmark/reference/keye_vl2_decoder.py), at a small size on the
CPU: hidden 64, 8 heads / 2 kv of 8, an indexer of 4 heads of 8 that picks
8 keys a query, 16 experts top-4 of width 32, 2 layers, vocabulary 256.
Contexts of 24 to 50 tokens, 3 to 6 times ``index_topk``: the selection is
engaged on every judged position unless a test says otherwise.  Logits,
not tokens."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import keye_vl2 as m
from seldon_core_tpu.models import moe, paged

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "benchmark", "reference")
)
import keye_vl2_decoder as ref  # noqa: E402

BS = 4  # KV block
# float32 against float32: summation order only.  The selection itself is
# discrete: were a key swapped, a logit would move by tenths (the control
# below moves them by 4)
TOL = 2e-5


def _cfg(**kw):
    return m.Config.tiny(max_seq=64, **kw)


def _params(cfg, seed=3, dtype=jnp.float32):
    return m.init_params(jax.random.PRNGKey(seed), cfg, dtype)


def _ref_kw(cfg):
    return dict(
        theta=cfg.rope_theta, eps=cfg.norm_eps, topk=cfg.index_topk,
        top_k=cfg.experts_per_tok, held=cfg.held,
    )


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


def _reference(cfg, params, seq, n, **control):
    full = np.asarray(ref.logits(params, seq, **_ref_kw(cfg), **control))
    return full[len(seq) - n:]


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(1, 256, 37)


class TestAgainstReference:
    @pytest.mark.parametrize("seq_impl,kernel", [
        ("dense", False), ("flash", True),  # the XLA paths; the Pallas paths
    ])
    def test_prefill_then_decode(self, prompt, seq_impl, kernel):
        cfg = _cfg()
        params = _params(cfg)
        seq, got = _served_logits(
            cfg, params, prompt, seq_impl=seq_impl, kernel=kernel
        )
        want = _reference(cfg, params, seq, len(got))
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    def test_dense_over_all_keys_fails_the_same_tolerance(self, prompt):
        """The control: the program with the selection switched off
        (``select="off"``) is another model by whole logits, and the
        reference with it off agrees with THAT program."""
        cfg = _cfg(select="off")
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, prompt)
        want = _reference(cfg, params, seq, len(got))
        assert np.abs(got - want).max() > 0.1
        off = _reference(cfg, params, seq, len(got), select=False)
        np.testing.assert_allclose(got, off, atol=TOL, rtol=0)

    def test_selection_idle_under_topk(self):
        """A context under ``index_topk`` selects every key: the program
        agrees with the reference, and with itself with the selection off."""
        short = np.random.default_rng(1).integers(1, 256, 9)
        cfg = _cfg(index_topk=32)
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, short, steps=8)
        want = _reference(cfg, params, seq, len(got))
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        _, off = _served_logits(
            dataclasses.replace(cfg, select="off"), params, short, steps=8
        )
        np.testing.assert_allclose(got, off, atol=TOL, rtol=0)

    @pytest.mark.parametrize("length", [5, 13])
    def test_the_selection_kernel_gives_what_the_xla_lines_give(self, length):
        """The decode step with ``kernel`` (the selection as one Pallas
        kernel over the slot's live blocks) against the same step the XLA
        way (the window gathered, scored and sorted), over steps that cross
        ``index_topk`` (8: from 5) and block boundaries (every 4)."""
        short = np.random.default_rng(4).integers(1, 256, length)
        cfg = _cfg()
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, short, steps=14, kernel=True)
        seq_x, want = _served_logits(cfg, params, short, steps=14, kernel=False)
        np.testing.assert_array_equal(seq, seq_x)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    @pytest.mark.parametrize("window", [8, 32])
    def test_a_static_window_of_topk_or_fewer_goes_the_dense_way(self, window):
        """``window`` 8 (= ``index_topk``) reads every row through the
        dense read, kernel and gather alike; 32 scores and selects."""
        short = np.random.default_rng(2).integers(1, 256, 5)
        cfg = _cfg()
        params = _params(cfg)
        for kernel in (False, True):
            seq, got = _served_logits(
                cfg, params, short, steps=3, window=window, kernel=kernel
            )
            want = _reference(cfg, params, seq, len(got))
            np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    @pytest.mark.parametrize("chunks", [(0, 16, 37), (0, 8, 24, 37)])
    def test_suffix_over_a_reused_prefix(self, prompt, chunks):
        """The suffix program reads the prefix's K/V AND index keys from the
        pool: a prompt prefilled in spans gives what it gives whole."""
        cfg = _cfg()
        params = _params(cfg)
        seq, got = _served_logits(cfg, params, prompt, steps=6, chunks=chunks)
        want = _reference(cfg, params, seq, len(got))
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    def test_bfloat16_as_served(self, prompt):
        """bfloat16 weights, activations and pool against the float32
        reference on the same weights: the served token lies within 0.25 of
        the reference's top logit (rounding of activations moves logits by
        hundredths; a key or an expert swapped at a near-tie by a tenth)."""
        cfg = _cfg()
        params = _params(cfg, dtype=jnp.bfloat16)
        seq, got = _served_logits(cfg, params, prompt, seq_impl="flash", kernel=True)
        want = _reference(cfg, params, seq, len(got))
        served = np.concatenate([seq[len(prompt):], [np.argmax(got[-1])]])
        deficit = want.max(-1) - want[np.arange(len(served)), served]
        assert deficit.max() < 0.25


class TestSelectionProbe:
    """The benchmark kind's ``mechanism`` (benchmark/reference/kinds/
    keye_vl2_decoder.py): a layer's parts held to the reference on the
    program's OWN inputs, which is what a run's ``correct`` sees of the
    selection.  576 tokens at ``index_topk`` 8, float32: given the same
    inputs the float32 scores pick the reference's keys to the last one."""

    GRAPH = {"seq_impl": "dense", "dtype": "float32", "kv_block_size": BS}

    def _found(self, dtype="float32", **control):
        import frame

        kind = frame.named_module("kinds", "keye_vl2_decoder")
        cfg = _cfg(**control)
        params = _params(cfg)
        head = {k: params[k] for k in ("tok_emb", "head", "ln_f")}
        return kind.mechanism(
            cfg, {**self.GRAPH, "dtype": dtype}, head,
            lambda: ref.layers_of(params["layers"]), 5, 576, chain=True,
        )

    def test_the_served_selection_is_the_references_on_the_same_inputs(self):
        found = self._found()
        assert found["selection_rows_judged"] == 2 * 2 * 256
        assert found["selection_swaps_max"] == 0
        for name in ("projection", "attention", "decode_read"):
            assert found[name + "_rel_err_max"] < 1e-5, name
        # and on their own hidden states the two stay together in float32
        assert max(found["chain_keys_parted_max_by_layer"]) == 0
        assert found["argmax_agree_share_chain"] == 1.0

    @pytest.mark.parametrize("control,least", [
        ({"index_dtype": "bfloat16"}, 1), ({"select": "off"}, 576 - 8 - 1),
    ], ids=["bfloat16 index scores", "selection off"])
    def test_a_control_leaves_keys_of_the_references_set_out(self, control, least):
        found = self._found(**control)
        assert found["selection_swaps_max"] >= least
        assert found["attention_rel_err_max"] > 0.01
        assert found["decode_read_rel_err_max"] > 0.01
        assert found["projection_rel_err_max"] < 1e-5  # the projections are not the control's


class TestShareTiesToTheModel:
    def test_eight_shares_add_up_to_the_uncut_layer(self):
        """The parts that all 8 shares of a layer give (2 of the 16 experts
        each; there is no shared expert) add up to the uncut reference
        layer."""
        whole = _cfg()
        wp = _params(whole)
        h = jax.random.normal(jax.random.PRNGKey(9), (21, whole.hidden))
        lp0 = {k: v[0] for k, v in wp["layers"].items()}
        with jax.default_matmul_precision("highest"):
            want = ref.moe(h, lp0, top_k=whole.experts_per_tok, held=(0, 16))
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
            if k in (0, 5):  # a share's own init makes the same experts
                own = _params(cfg)["layers"]
                for name in lp:
                    np.testing.assert_array_equal(own[name][0], lp[name])
            routed = part(cfg, lp)
            total = total + routed
        np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
        assert np.abs(np.asarray(routed - want)).max() > 1e-2

    def test_grouped_products_give_what_dense_gives(self, monkeypatch):
        cfg = _cfg()
        lp = {k: v[0] for k, v in _params(cfg)["layers"].items()}
        h = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.hidden))
        mask = jnp.arange(40) < 33
        dense, _ = m._moe(h, lp, cfg, mask, None, decode=False)
        # the one rule every family's ``_moe`` asks, and this family's chunk
        monkeypatch.setattr(moe, "GROUPED_FROM", 8)
        monkeypatch.setattr(moe, "GROUP_CHUNK_WHOLE", 64)
        grouped, _ = m._moe(h, lp, cfg, mask, None, decode=False)
        np.testing.assert_allclose(grouped, dense, atol=TOL, rtol=0)


class TestCache:
    def test_a_third_array_under_the_same_table(self):
        cfg = _cfg()
        cache = m.init_paged_cache(cfg, 2, 40, BS, jnp.bfloat16)
        assert cache["ik"].shape == (2, 40, cfg.index_dim, BS)  # a block transposed
        assert cache["ik"].dtype == cache["k"].dtype == jnp.bfloat16
        assert cache["k"].shape == (2, 40, BS, cfg.n_kv_heads * cfg.head_dim)
        # the published sizes: 2 x 4 x 128 + 64 values a token a layer
        real = m.Config(n_layers=6)
        per_token = m.paged_kv_slot_bytes(real, 256, dtype="bfloat16") // real.max_seq
        assert per_token == 6 * 2176

    def test_every_program_writes_the_index_key_where_it_writes_kv(self, prompt):
        cfg = _cfg()
        params = _params(cfg)
        _, whole = _prefill(cfg, params, prompt)
        _, spans = _prefill(cfg, params, prompt, chunks=(0, 16, 37))
        row = _slot_row()

        def by_token(cache, name):  # (layers, blocks, block, ...)
            a = cache[name]
            return np.asarray(paged.by_token(a) if name == "ik" else a)

        for name in ("k", "v", "ik"):
            a = by_token(whole, name)[:, row[:9]].reshape(2, 36, -1)
            b = by_token(spans, name)[:, row[:9]].reshape(2, 36, -1)
            assert np.abs(a[:, :30]).min(axis=-1).max() > 0
            np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
        # a decode step's token lands at its position, on every array, and
        # nowhere else in its block
        _, _, after = _decode(cfg, params, whole, 5, 1)
        for name in ("k", "v", "ik"):
            was = by_token(whole, name)[:, row[9]]
            now = by_token(after, name)[:, row[9]]
            assert np.abs(now[:, 1] - was[:, 1]).max() > 0
            np.testing.assert_array_equal(now[:, [0, 2, 3]], was[:, [0, 2, 3]])

    @pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
    def test_the_by_token_call_is_the_served_parts_on_the_pool_as_carried(
            self, prompt, kernel):
        """``_decode_attention`` takes its index keys by token (the
        benchmark's reference kind builds them so): it gives what the two
        parts the served step calls give on the pool as it is carried."""
        cfg = _cfg()
        _, cache = _prefill(cfg, _params(cfg), prompt)
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (2, 1, cfg.n_heads, cfg.head_dim))
        qi = jax.random.normal(ks[1], (2, 1, cfg.index_heads, cfg.index_dim))
        wi = jax.random.normal(ks[2], (2, 1, cfg.index_heads))
        pos = jnp.asarray([0, len(prompt) - 1])
        active = jnp.asarray([False, True])
        n_sel = jnp.where(active, jnp.minimum(pos + 1, cfg.index_topk), 0)
        at = (1, cache["table"], pos, active)
        got = m._decode_attention(
            q, qi, wi, cache["k"], cache["v"], paged.by_token(cache["ik"]), *at,
            n_sel, cfg, sparse=True, kernel=kernel,
        )
        rows, read = m._select_rows(qi, wi, cache["ik"], *at, cfg, kernel=kernel)
        want = m._decode_read(
            q, cache["k"], cache["v"], *at, n_sel, rows, kernel=kernel
        )
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        assert np.abs(np.asarray(got[1])).max() > 0
        # 37 tokens in blocks of 4: 10 live blocks of the active slot, none
        # of the other; the XLA lines gather the table's 16 for both
        assert np.asarray(read).tolist() == ([0, 10] if kernel else [16, 16])


class TestCounters:
    def test_the_selection_is_counted_on_the_device(self, prompt):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, prompt)
        _, _, cache = _decode(cfg, params, cache, 5, 3)
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        assert m.COUNTERS[:8] == (
            "moe.pairs_routed", "moe.pairs_held", "moe.experts_touched",
            "moe.max_tokens_on_expert", "moe.steps",
            "moe.prefill_pairs_routed", "moe.prefill_pairs_held",
            "moe.prefill_tokens",
        )
        assert c["moe.steps"] == 3 and c["moe.prefill_tokens"] == 37
        # 2 layers x (38 + 39 + 40) keys scored, 2 x 3 x 8 attended
        assert c["dsa.keys_scored"] == 2 * 117
        assert c["dsa.keys_selected"] == 2 * 3 * 8
        assert c["moe.pairs_routed"] == 2 * 3 * 4

    @pytest.mark.parametrize("kernel,blocks", [
        # the kernel visits the live blocks of the one active slot: positions
        # 37, 38, 39 in blocks of 4 are 10 each, on 2 layers
        (True, 2 * 3 * 10),
        # the XLA way gathers the window of both slots: 2 x 16 blocks
        (False, 2 * 3 * 2 * 16),
    ])
    def test_index_key_blocks_read_are_counted_by_the_way_taken(
            self, prompt, kernel, blocks):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, prompt)
        _, _, cache = _decode(cfg, params, cache, 5, 3, kernel=kernel)
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        assert m.COUNTERS[-1] == "dsa.key_blocks_read"
        assert c["dsa.key_blocks_read"] == blocks
        # what a step has to score is what it was, whichever way
        assert c["dsa.keys_scored"] == 2 * 117
        assert c["dsa.keys_selected"] == 2 * 3 * 8

    def test_a_window_that_selects_nothing_reads_no_index_keys(self, prompt):
        cfg = _cfg()
        params = _params(cfg)
        _, cache = _prefill(cfg, params, np.asarray(prompt[:5]))
        _, _, cache = _decode(cfg, params, cache, 5, 2, window=8, kernel=True)
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        assert c["dsa.key_blocks_read"] == 0 and c["dsa.keys_scored"] == 2 * (6 + 7)

    def test_a_prompt_counts_its_pairs_in_units_of_1024(self):
        ctr = jnp.zeros((len(m.COUNTERS),), jnp.uint32)
        cfg = m.Config(n_layers=6)
        out = np.asarray(m._count_prompt(ctr, cfg, 0, jnp.int32(24576)))
        c = dict(zip(m.COUNTERS, out.tolist()))
        assert c["moe.prefill_tokens"] == 24576
        assert c["dsa.prefill_keys_scored"] == 6 * (24576 * 24577 // 2 // 1024)
        assert c["dsa.prefill_keys_selected"] == 6 * (
            (2048 * 2049 // 2 + (24576 - 2048) * 2048) // 1024
        )


class TestServedPath:
    """Through ``JAX_GENERATIVE``'s own objects."""

    def _component(self, **kw):
        from seldon_core_tpu.models.registry import build_generative_component

        return build_generative_component(
            "keye_vl2", preset="tiny", max_seq=64, n_slots=2, decode_block=4,
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
        assert model._cache["ik"].dtype == jnp.bfloat16
        # the pool's bytes count the third array
        per_token = 2 * (2 * 2 * 8 + 8) * 2  # layers x (K + V + index key) x 2 B
        assert model.kv_bytes_per_block() == 4 * per_token
        assert model.kv_bytes_per_slot() == 64 * per_token
        assert model.pool_snapshot()["bytes"]["kv_pool"] == model.kv_blocks * 4 * per_token
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
        want = np.asarray(ref.logits(
            model.params, np.concatenate([prompt, served[:-1]]),
            **_ref_kw(model.cfg),
        ))[len(prompt) - 1:]
        deficit = want.max(-1) - want[np.arange(len(served)), served]
        assert deficit.max() < 0.25
        snap = model.spec_snapshot()["counters"]
        assert snap["moe.steps"] >= 4
        assert snap["dsa.keys_selected"] >= 4 * 2 * 8  # steps x layers x topk
        assert snap["dsa.keys_scored"] > snap["dsa.keys_selected"]

    def test_a_long_prompt_leaves_the_masked_kernels_tile_plan(self, prompt, monkeypatch):
        """A prompt longer than ``index_topk`` through ``seq_impl="flash"``:
        each chunk's ``masked_flash_attention`` shape leaves the grid it was
        compiled with — live tiles alone, by the shapes — where
        ``breakdown.generation.<unit>.programs.tile_plans`` shows it."""
        monkeypatch.setattr(m, "QUERY_CHUNK", 16)  # the rung of 64 in four chunks
        model = self._component(seq_impl="flash", decode_kernel=True).model
        assert len(prompt) > model.cfg.index_topk
        model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=4)
        plans = model.program_snapshot()["tile_plans"]
        for q0 in (0, 16, 32, 48):
            lq, lk = 16, q0 + 16
            bq, bk = min(512, lq), min(512, lk)  # the kernel's own tiles
            live = sum(
                ki * bk <= q0 + qi * bq + bq - 1
                for qi in range(lq // bq) for ki in range(lk // bk)
            )
            assert plans[f"masked:S{lq}:Sk{lk}:{bq}x{bk}:q{q0}"] == {
                "stepped": live, "live": live,
            }

    def test_prefix_reuse_shares_index_keys_with_the_blocks(self, prompt):
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
        with pytest.raises(TypeError, match="keye_vl2 has no int8 KV"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_dtype="int8")
        with pytest.raises(GraphUnitError, match="keye_vl2.*kv_cache_dtype"):
            self._component(kv_cache_dtype="int8")
        with pytest.raises(TypeError, match="keye_vl2 has no pool split"):
            m.init_paged_cache(cfg, 2, 40, BS, kv_sharded=True)
        # no LoRA path: the pool is switched off with a warning, and a
        # program handed adapters raises
        assert self._component(lora_rank=4).model.lora_rank == 0
        params = _params(cfg)
        cache = m.init_paged_cache(cfg, 2, 40, BS)
        with pytest.raises(TypeError, match="keye_vl2 has no LoRA"):
            m.decode_slots_paged(
                params, jnp.zeros(2, jnp.int32), cache, jnp.ones(2, bool), cfg,
                lora={},
            )
        # no speculative verify pass (each draft position would select anew)
        with pytest.raises(GraphUnitError, match="keye_vl2 has no decode_slots_spec_paged"):
            self._component(spec_draft=2)
        # what moves K/V out of the pool carries k and v alone
        with pytest.raises(TypeError, match="keye_vl2 keeps ik.*prefix_dram_gb"):
            self._component(kv_prefix_reuse=True, prefix_dram_gb=0.01)
        comp = self._component()
        model = comp.model
        model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=4)
        with pytest.raises(TypeError, match="keye_vl2 keeps ik.*export"):
            model.export_slot_kv(0, len(prompt))
        z = np.zeros((2, 10, 4, 2, 8), np.float32)
        with pytest.raises(TypeError, match="keye_vl2 keeps ik.*import"):
            model.attach_imported(1, prompt.astype(np.int32), z, z)

    def test_preemption_is_refused_by_name(self):
        from seldon_core_tpu.executor.generation import GenerationScheduler

        sched = GenerationScheduler(self._component().model)
        with pytest.raises(TypeError, match="keye_vl2 keeps ik.*SuspendStore"):
            sched.request_preempt()


class TestEngineRoutes:
    """``examples/keye-vl2-generative/graph.json`` through the engine's own
    app: both routes give the same tokens, and the selection's counters are
    in ``/stats/summary``."""

    def test_the_example_graph_serves_both_routes(self):
        import asyncio
        import json

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        path = os.path.join(
            os.path.dirname(__file__), "..", "examples",
            "keye-vl2-generative", "graph.json",
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
                c = stats["breakdown"]["generation"]["keye_vl2:tiny"]["counters"]
                assert c["moe.steps"] > 0 and c["moe.pairs_routed"] > 0
                assert 0 < c["dsa.keys_selected"] < c["dsa.keys_scored"]
            finally:
                await client.close()

        asyncio.run(go())
