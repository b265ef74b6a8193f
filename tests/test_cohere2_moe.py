"""The ``cohere2_moe`` family (models/cohere2_moe.py) against the benchmark's
plain reference (benchmark/reference/cohere2_moe_decoder.py), at a small
size on the CPU: hidden 64, 8 heads / 2 kv, 16 experts top-4 of width 32,
2 shared, window 8, 4 layers (sliding, sliding, sliding, full), vocabulary
256.  Logits, not tokens."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import cohere2_moe as m
from seldon_core_tpu.models import moe

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "benchmark", "reference")
)
import cohere2_moe_decoder as ref  # noqa: E402

BS = 4  # KV block
TOL = 2e-5  # float32 against float32: summation order only


def _cfg(**kw):
    return m.Config.tiny(max_seq=64, experts_held="4:8", **kw)


def _params(cfg, seed=3, dtype=jnp.float32):
    return m.init_params(jax.random.PRNGKey(seed), cfg, dtype)


def _ref_kw(cfg):
    return dict(
        pattern=cfg.layer_pattern, theta=cfg.rope_theta, eps=cfg.norm_eps,
        window=cfg.sliding_window, top_k=cfg.experts_per_tok, held=cfg.held,
    )


def _plan():
    """The expert plan's thresholds in force (a test shrinks them)."""
    return moe.GROUPED_FROM, moe.GROUP_CHUNK


@functools.lru_cache(maxsize=None)
def _jitted(cfg, which, plan=None, **static):
    """One compiled program for each (configuration, entry point): the tests
    share them, as serving does.  ``plan`` (:func:`_plan`) is part of the
    key alone: a program traced under other thresholds is another program."""
    fn = {
        "prefill": m.prefill_slot_paged, "suffix": m.prefill_suffix_paged,
        "decode": m.decode_slots_paged, "multi": m._decode_paged_multi,
        "spec": m.decode_slots_spec_paged,
    }[which]
    # where ``cfg`` stands among each entry point's positional arguments
    cfg_at = {"prefill": 6, "suffix": 8, "decode": 4, "multi": 5, "spec": 5}[which]

    def call(*args):
        return fn(*args[:cfg_at], cfg, *args[cfg_at:], **static)

    return jax.jit(call)


def _slot_row(n_blocks=14, width=16):
    """A table row whose blocks are out of order (block 0 is the sink)."""
    row = np.zeros(width, np.int32)
    row[:n_blocks] = np.arange(1, n_blocks + 1)[::-1]
    return row


def _prefill(cfg, params, prompt, *, seq_impl="dense", chunks=None, slot=1,
             rung=None):
    """Prompt -> (last logits, cache), whole or in ``chunks`` (the first
    through ``prefill_slot_paged``, the others through the suffix program
    over the slot's own blocks, as the scheduler's chunked prefill does).
    ``rung``: the length each span is padded to (the whole blocks it fills,
    if not given)."""
    cache = m.init_paged_cache(cfg, 2, 40, BS, params["ln_f"].dtype)
    row = jnp.asarray(_slot_row())
    L = len(prompt)
    spans = [(0, L)] if not chunks else list(zip(chunks[:-1], chunks[1:]))
    logits = None
    for a, b in spans:
        bucket = rung or -(-(b - a) // BS) * BS
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : b - a] = prompt[a:b]
        if a == 0:
            logits, cache = _jitted(cfg, "prefill", _plan(), seq_impl=seq_impl)(
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
                cfg, "suffix", _plan(), prefix_window=min(pw, cfg.max_seq)
            )(
                params, jnp.asarray(padded), jnp.int32(a), jnp.int32(b),
                jnp.int32(slot), row, jnp.asarray(sb), cache,
            )
    return logits, cache


def _decode(cfg, params, cache, first, steps, **kw):
    """Greedy decode of slot 1 -> (tokens fed, logits of every step, cache)."""
    active = jnp.asarray([False, True])
    fed, out, nxt = [], [], int(first)
    for _ in range(steps):
        fed.append(nxt)
        lg, cache = _jitted(cfg, "decode", _plan(), window=cfg.max_seq, **kw)(
            params, jnp.asarray([0, nxt], jnp.int32), cache, active,
        )
        out.append(np.asarray(lg[1]))
        nxt = int(np.argmax(out[-1]))
    return fed, out, cache


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(1, 256, 37)


class TestAgainstReference:
    """Prefill, then decode through the paged cache, against the reference's
    full forward pass: contexts of 3 to 6 windows (37 to 49 tokens, window
    8), so both layer kinds, the window's edge and the position-free layers
    are all crossed."""

    @pytest.mark.parametrize("seq_impl", ["dense", "flash"])
    @pytest.mark.parametrize("experts", ["dense", "grouped"])
    def test_prefill_then_decode(self, monkeypatch, prompt, seq_impl, experts):
        if experts == "grouped":
            # the prefill's formulation at a prompt of 37: sorted pairs,
            # grouped products, three passes of 32 rows
            monkeypatch.setattr(moe, "GROUPED_FROM", 8)
            monkeypatch.setattr(moe, "GROUP_CHUNK", 32)
            monkeypatch.setattr(moe, "GROUP_ROWS_AN_EXPERT", 1)
        cfg = _cfg()
        params = _params(cfg)
        logits, cache = _prefill(cfg, params, prompt, seq_impl=seq_impl)
        want = ref.logits(params, prompt, **_ref_kw(cfg))
        np.testing.assert_allclose(logits, want[-1], atol=TOL, rtol=0)
        fed, got, _ = _decode(cfg, params, cache, np.argmax(logits), 12)
        want = ref.logits(params, np.concatenate([prompt, fed]), **_ref_kw(cfg))
        np.testing.assert_allclose(
            np.stack(got), want[len(prompt):], atol=TOL, rtol=0
        )

    @pytest.mark.parametrize("seq_impl", ["dense", "flash"])
    def test_a_rung_of_three_eighths_gives_what_the_next_double_gives(
        self, monkeypatch, prompt, seq_impl
    ):
        """The prefill ladder's rungs above 4,096 are 3 x a power of two
        (6,144 in the served cell): here a prompt of 37 in a rung of 48
        and in one of 64, grouped experts on.  Padding is masked, so the
        logits, the first token and the prompt's K/V rows are the same,
        and the float32 reference's."""
        monkeypatch.setattr(moe, "GROUPED_FROM", 8)
        monkeypatch.setattr(moe, "GROUP_CHUNK", 32)
        monkeypatch.setattr(moe, "GROUP_ROWS_AN_EXPERT", 1)
        cfg = _cfg()
        params = _params(cfg)
        (got, c48), (want, c64) = (
            _prefill(cfg, params, prompt, seq_impl=seq_impl, rung=r)
            for r in (48, 64)
        )
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert int(np.argmax(got)) == int(np.argmax(want))
        ref_logits = ref.logits(params, prompt, **_ref_kw(cfg))
        np.testing.assert_allclose(got, ref_logits[-1], atol=TOL, rtol=0)
        held = _slot_row()[:10]  # the 10 blocks the prompt's 37 rows lie in
        for name in ("k", "v"):
            a, b = (
                np.asarray(c[name])[:, held].reshape(cfg.n_layers, 40, -1)[:, :37]
                for c in (c48, c64)
            )
            np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
        # and the decode that follows reads what the rung of 48 wrote
        fed, out, _ = _decode(cfg, params, c48, np.argmax(got), 4)
        after = ref.logits(params, np.concatenate([prompt, fed]), **_ref_kw(cfg))
        np.testing.assert_allclose(
            np.stack(out), after[len(prompt):], atol=TOL, rtol=0
        )

    @pytest.mark.parametrize("chunks", [[0, 16, 37], [0, 8, 24, 32, 37]])
    def test_prefill_in_chunks(self, prompt, chunks):
        """Through ``prefill_suffix_paged``: chunk boundaries inside and
        past the window, the prefix read from the slot's own blocks."""
        cfg = _cfg()
        params = _params(cfg)
        whole, _ = _prefill(cfg, params, prompt)
        logits, cache = _prefill(cfg, params, prompt, chunks=chunks)
        np.testing.assert_allclose(logits, whole, atol=TOL, rtol=0)
        fed, got, _ = _decode(cfg, params, cache, np.argmax(logits), 4)
        want = ref.logits(params, np.concatenate([prompt, fed]), **_ref_kw(cfg))
        np.testing.assert_allclose(
            np.stack(got), want[len(prompt):], atol=TOL, rtol=0
        )

    def test_bfloat16_as_served(self, prompt):
        """The served dtype: bfloat16 weights and activations, the router in
        float32, against the float32 reference on the same weights."""
        cfg = _cfg()
        params = _params(cfg, dtype=jnp.bfloat16)
        logits, cache = _prefill(cfg, params, prompt, seq_impl="flash")
        fed, got, _ = _decode(cfg, params, cache, np.argmax(logits), 8)
        want = np.asarray(
            ref.logits(params, np.concatenate([prompt, fed]), **_ref_kw(cfg))
        )[len(prompt):]
        got = np.stack(got).astype(np.float32)
        # bfloat16 at hidden 64 is coarse (8 bits of mantissa, little to
        # average over): the bulk of the logits agrees closely, the worst one
        # loosely; a wrong layer (TestNegativeControls) is off by far more
        err = np.abs(got - want)
        assert err.mean() < 0.03 and err.max() < 0.6
        # each token served lies within a margin of the reference's top
        served = np.asarray(fed[1:])
        rows = want[: len(served)]
        assert (rows.max(-1) - rows[np.arange(len(served)), served]).max() < 0.5

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_decode_through_the_paged_kernel(self, prompt, dtype):
        """``kernel=True``: the Pallas paged decode-attention kernel (in
        interpret mode here) with the window inside it, against the XLA
        gather path, on both layer kinds past the window."""
        cfg = _cfg()
        params = _params(cfg, dtype=dtype)
        logits, cache = _prefill(cfg, params, prompt)
        first = np.argmax(logits)
        fed, xla, _ = _decode(cfg, params, dict(cache), first, 6)
        fed_k, ker, _ = _decode(cfg, params, dict(cache), first, 6, kernel=True)
        tol = TOL if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(
            np.stack(ker[:1]).astype(np.float32),
            np.stack(xla[:1]).astype(np.float32), atol=tol, rtol=0,
        )
        if dtype == jnp.float32:
            assert fed_k == fed
            np.testing.assert_allclose(np.stack(ker), np.stack(xla), atol=TOL, rtol=0)

    def test_spec_verify_positions_equal_single_steps(self, prompt):
        """``decode_slots_spec_paged`` (L queries a slot) scores the same
        positions as L single steps."""
        cfg = _cfg()
        params = _params(cfg)
        logits, cache = _prefill(cfg, params, prompt)
        fed, got, _ = _decode(cfg, params, dict(cache), np.argmax(logits), 3)
        q = jnp.asarray([[0, 0, 0], fed], jnp.int32)
        active = jnp.asarray([False, True])
        lg, _ = _jitted(cfg, "spec", window=cfg.max_seq)(
            params, q, dict(cache), active,
            jnp.broadcast_to(active[:, None], (2, 3)),
        )
        np.testing.assert_allclose(lg[1], np.stack(got), atol=TOL, rtol=0)


class TestShareTiesToTheModel:
    def test_eight_shares_add_up_to_the_uncut_layer(self):
        """The parts that all 8 shares of a layer give (2 of the 16 experts
        each), the shared mean counted once, add up to the uncut reference
        layer: the routed experts' weights are the same in every share that
        holds them, the router and its normalisation are the whole model's."""
        whole = m.Config.tiny(max_seq=64)
        wp = _params(whole)
        h = jax.random.normal(jax.random.PRNGKey(9), (21, whole.hidden))
        lp0 = {k: v[0] for k, v in wp["layers"].items()}
        with jax.default_matmul_precision("highest"):
            want = ref.moe(h, lp0, top_k=whole.experts_per_tok, held=(0, 16))
        mask = jnp.ones((21,), bool)

        @functools.partial(jax.jit, static_argnums=0)
        def part(cfg, layers):
            lp = {k: v[0] for k, v in layers.items()}
            out, _ = m._moe(h, lp, cfg, mask, None, decode=True,
                            stacks=layers, li=0)
            g = jnp.einsum("te,jef->jtf", h, lp["ws_gate"])
            u = jnp.einsum("te,jef->jtf", h, lp["ws_up"])
            shared = jnp.einsum(
                "jtf,jfe->te", jax.nn.silu(g) * u, lp["ws_down"]
            ) / cfg.n_shared_experts
            return out - shared, shared

        total = 0.0
        for k in range(8):
            cfg = dataclasses.replace(whole, experts_held=f"{2 * k}:2")
            # a share's weights ARE the whole model's experts 2k, 2k + 1
            layers = {
                name: a[:, 2 * k: 2 * k + 2] if name.startswith("we_") else a
                for name, a in wp["layers"].items()
            }
            if k in (0, 5):  # ... which is what its own init makes
                own = _params(cfg)["layers"]
                for name in layers:
                    np.testing.assert_array_equal(own[name], layers[name])
            routed, shared = part(cfg, layers)
            total = total + routed
        np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=0)
        # and one share alone is NOT the layer
        assert np.abs(np.asarray(routed + shared - want)).max() > 1e-2


class TestWindowRead:
    """A sliding layer reads the blocks that hold its window
    (``window_read``), not the slot's first blocks; the rows it reads are the
    full read's rows at the same positions, bit for bit, and the step's
    logits are those of a full read under the window mask."""

    def test_rows_read_are_the_full_reads_rows(self, prompt):
        cfg = _cfg()
        params = _params(cfg, dtype=jnp.bfloat16)
        _, cache = _prefill(cfg, params, prompt)
        pos, table = cache["pos"], cache["table"]
        assert m.window_blocks(cfg, BS) == 3 < cfg.max_seq // BS
        phys, kpos = m.window_read(table, pos, cfg, BS)
        rows = np.asarray(cache["k"][0, phys]).reshape(2, -1, 2 * 8)
        full = np.asarray(cache["k"][0, table]).reshape(2, -1, 2 * 8)
        p, kp = int(pos[1]), np.asarray(kpos[1])
        inside = (kp <= p) & (kp > p - cfg.sliding_window)
        assert inside.sum() == cfg.sliding_window
        np.testing.assert_array_equal(rows[1][inside], full[1][kp[inside]])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_step_equals_full_read_under_the_mask(self, prompt, dtype):
        cfg = _cfg()
        params = _params(cfg, dtype=dtype)
        logits, cache = _prefill(cfg, params, prompt)
        nxt = jnp.asarray([0, int(np.argmax(logits))], jnp.int32)
        active = jnp.asarray([False, True])
        args = (params, nxt[:, None], dict(cache), active, active[:, None])
        windowed, c1 = _jitted(cfg, "multi", window=cfg.max_seq)(*args)
        full, c2 = _jitted(
            cfg, "multi", window=cfg.max_seq, window_read_off=True
        )(*args)
        # the same rows under the same mask: equal but for the order the
        # softmax sums them in (the full read sums the masked rows' zeros too)
        tol = 1e-5 if dtype == jnp.float32 else 0.02
        np.testing.assert_allclose(
            np.asarray(windowed[1], np.float32), np.asarray(full[1], np.float32),
            atol=tol, rtol=0,
        )
        np.testing.assert_array_equal(c1["k"][0], c2["k"][0])


class TestNegativeControls:
    """Each of the four ways to get the layer wrong fails the comparison the
    tests above pass: the reference, made wrong in that one way, no longer
    agrees with the program."""

    @pytest.mark.parametrize("wrong", [
        {"window": None},              # the window mask dropped
        {"rope_on_full": True},        # RoPE on the position-free layers
        {"shared_mean": False},        # a plain sum of the shared experts
        {"norm_over": "held"},         # normalised over the held picks only
    ], ids=["no-window", "rope-on-full", "shared-sum", "norm-over-held"])
    def test_wrong_layer_disagrees(self, prompt, wrong):
        cfg = _cfg()
        params = _params(cfg)
        logits, _ = _prefill(cfg, params, prompt)
        kw = {**_ref_kw(cfg), **wrong}
        bad = ref.logits(params, prompt, **kw)[-1]
        assert np.abs(np.asarray(logits - bad)).max() > 100 * TOL


class TestCounters:
    def test_routing_is_counted_on_the_device(self, prompt):
        cfg = _cfg()
        params = _params(cfg)
        logits, cache = _prefill(cfg, params, prompt)
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        n, k, layers = len(prompt), cfg.experts_per_tok, cfg.n_layers
        assert c["moe.prefill_tokens"] == n
        assert c["moe.prefill_pairs_routed"] == n * k * layers
        assert 0 < c["moe.prefill_pairs_held"] < n * k * layers
        assert c["moe.steps"] == 0
        _, _, cache = _decode(cfg, params, cache, np.argmax(logits), 5)
        c = dict(zip(m.COUNTERS, np.asarray(cache["counters"]).tolist()))
        assert c["moe.steps"] == 5
        assert c["moe.pairs_routed"] == 5 * k * layers  # one active slot
        assert c["moe.pairs_held"] <= c["moe.pairs_routed"]
        # one token a step: every expert it touches holds exactly that token
        assert c["moe.experts_touched"] == c["moe.pairs_held"]
        assert c["moe.max_tokens_on_expert"] <= 5 * layers


class TestPackedProjections:
    """``pack_params``: the four attention projections with their heads
    folded and the contracted axis last, as an engine holds them.  Every
    entry point takes either tree and computes the same."""

    def test_pack_moves_no_value_and_is_idempotent(self):
        cfg = _cfg()
        params = _params(cfg)
        packed = m.pack_params(params)
        lay, was = packed["layers"], params["layers"]
        L, E = cfg.n_layers, cfg.hidden
        for name in ("wq", "wk", "wv"):
            want = np.asarray(was[name]).reshape(L, E, -1).transpose(0, 2, 1)
            np.testing.assert_array_equal(lay[name], want)
        np.testing.assert_array_equal(
            lay["wo"], np.asarray(was["wo"]).reshape(L, -1, E)
        )
        assert set(m.PACKED) == {"layers/wq", "layers/wk", "layers/wv", "layers/wo"}
        for name in set(was) - {"wq", "wk", "wv", "wo"}:
            assert lay[name] is was[name]
        assert packed["tok_emb"] is params["tok_emb"]
        assert m.pack_params(packed) is packed
        # the canonical tree is left as it was
        assert params["layers"]["wq"].shape == (L, E, cfg.n_heads, cfg.head_dim)

    @pytest.mark.parametrize("packed", [False, True], ids=["canonical", "packed"])
    def test_every_leaf_has_its_logical_axes(self, packed):
        cfg = _cfg()
        params = _params(cfg)
        if packed:
            params = m.pack_params(params)
        axes = m.param_logical_axes(params)["layers"]
        for name, split in (("wq", "heads"), ("wk", "kv_heads"),
                            ("wv", "kv_heads"), ("wo", "heads")):
            assert len(axes[name]) == params["layers"][name].ndim
            assert axes[name][0] == "layers" and split in axes[name]
            if packed:  # the axis a mesh splits comes first after the layers
                assert axes[name] == ("layers", split, "embed")

    def test_on_a_mesh_the_packed_leaves_split_their_heads(self):
        """Placed by the family's own axes on tp=2, as ``GenerativeModel``
        places what it packed: the folded heads axis is the one split, a
        chip's half of ``wq`` its own heads' rows, and ``forward`` gives
        what one device gives."""
        from seldon_core_tpu.parallel import best_mesh
        from seldon_core_tpu.parallel.sharding import shard_params

        cfg = _cfg()
        packed = m.pack_params(_params(cfg))
        mesh = best_mesh(2, tp=2)
        placed = shard_params(packed, mesh, m.param_logical_axes(packed))
        wq = placed["layers"]["wq"]
        assert wq.sharding.spec[1] == "tp"
        half = cfg.n_heads * cfg.head_dim // 2
        shapes = {s.data.shape for s in wq.addressable_shards}
        assert shapes == {(cfg.n_layers, half, cfg.hidden)}
        toks = jnp.asarray(np.arange(1, 17)[None])
        fwd = jax.jit(functools.partial(m.forward, cfg=cfg))
        np.testing.assert_allclose(
            np.asarray(fwd(placed, toks)), np.asarray(fwd(packed, toks)),
            atol=TOL, rtol=0,
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_forward_takes_either_tree(self, prompt, dtype):
        cfg = _cfg()
        params = _params(cfg, dtype=dtype)
        fwd = jax.jit(functools.partial(m.forward, cfg=cfg))
        toks = jnp.asarray(prompt[None])
        a = np.asarray(fwd(params, toks), np.float32)
        b = np.asarray(fwd(m.pack_params(params), toks), np.float32)
        np.testing.assert_allclose(b, a, atol=TOL if dtype == jnp.float32 else 0.05, rtol=0)
        assert (a.argmax(-1) == b.argmax(-1)).mean() >= (1.0 if dtype == jnp.float32 else 0.9)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_the_paged_entry_points_take_either_tree(self, prompt, dtype):
        """``prefill_slot_paged``, ``prefill_suffix_paged`` (the prompt in
        chunks) and ``decode_slots_paged``: the same greedy tokens and
        logits from the canonical and the packed tree."""
        cfg = _cfg()
        params = _params(cfg, dtype=dtype)
        tol = TOL if dtype == jnp.float32 else 0.05
        runs = []
        for tree in (params, m.pack_params(params)):
            whole, _ = _prefill(cfg, tree, prompt)
            logits, cache = _prefill(cfg, tree, prompt, chunks=[0, 16, 28, len(prompt)])
            fed, out, _ = _decode(cfg, tree, cache, np.argmax(logits), 6)
            runs.append((np.asarray(whole, np.float32), np.asarray(logits, np.float32),
                         fed, np.stack(out).astype(np.float32)))
        (w0, l0, fed0, o0), (w1, l1, fed1, o1) = runs
        np.testing.assert_allclose(w1, w0, atol=tol, rtol=0)
        np.testing.assert_allclose(l1, l0, atol=tol, rtol=0)
        assert fed1[0] == fed0[0]
        np.testing.assert_allclose(o1[:1], o0[:1], atol=tol, rtol=0)
        if dtype == jnp.float32:
            assert fed1 == fed0
            np.testing.assert_allclose(o1, o0, atol=tol, rtol=0)


class TestServedPath:
    """Through ``JAX_GENERATIVE``'s own objects: the registry builds the
    family in the served dtype, ``GenerativeModel`` warms it and serves it
    with the scheduler's programs, and the counters reach the snapshot."""

    def _component(self, **kw):
        from seldon_core_tpu.models.registry import build_generative_component

        return build_generative_component(
            "cohere2_moe", preset="tiny", experts_held="4:8", max_seq=64,
            n_slots=2, decode_block=4, kv_block_size=4, dtype=jnp.bfloat16,
            rng=5, **kw,
        )

    @staticmethod
    def _canonical(model):
        """The component's weights as ``init_params`` made them (``rng=5``):
        what the plain reference reads; the model holds them packed."""
        return m.init_params(jax.random.PRNGKey(5), model.cfg, jnp.bfloat16)

    def test_the_model_holds_the_projections_packed(self):
        """``GenerativeModel`` packs once at build: ``params_packed`` names
        the four leaves with the shapes of the tree the programs are handed,
        no value changed; a family without the hook reports ``{}``."""
        from seldon_core_tpu.models.registry import build_generative_component

        model = self._component().model
        c = model.cfg
        hd, kvd = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        want = {
            "layers/wq": [c.n_layers, hd, c.hidden],
            "layers/wk": [c.n_layers, kvd, c.hidden],
            "layers/wv": [c.n_layers, kvd, c.hidden],
            "layers/wo": [c.n_layers, hd, c.hidden],
        }
        assert model.params_packed() == want
        assert model.spec_snapshot()["params_packed"] == want
        for path, shape in want.items():
            assert list(model.params["layers"][path.split("/")[1]].shape) == shape
        packed = m.pack_params(self._canonical(model))
        for a, b in zip(jax.tree.leaves(model.params), jax.tree.leaves(packed)):
            np.testing.assert_array_equal(a, b)
        llama = build_generative_component(
            "llama", preset="tiny", n_slots=2, kv_block_size=4
        ).model
        assert llama.params_packed() == {}
        assert llama.spec_snapshot()["params_packed"] == {}

    @pytest.mark.parametrize("source", ["init", "checkpoint"])
    def test_the_registry_packs_what_it_makes_itself(self, source, tmp_path):
        """A fresh init is packed inside its own jitted program and a
        checkpoint as it is loaded: the tree ``GenerativeModel`` is handed is
        packed already (nothing canonical is left on the device for it to
        replace), and holds ``init_params``' values."""
        from seldon_core_tpu.executor.checkpoint import save_params
        from seldon_core_tpu.models import registry

        fam, cfg = registry.get_family("cohere2_moe"), _cfg()
        canonical = m.init_params(jax.random.PRNGKey(5), cfg, jnp.bfloat16)
        ckpt = None
        if source == "checkpoint":
            ckpt = str(tmp_path / "w.npz")
            save_params(ckpt, canonical)
        tree = registry._resolve_params(
            fam, cfg, None, ckpt, 5, dtype=jnp.bfloat16, pack=m.pack_params
        )
        assert m.pack_params(tree) is tree
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(m.pack_params(canonical))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and without the hook, the canonical tree as before
        plain = registry._resolve_params(fam, cfg, None, ckpt, 5, dtype=jnp.bfloat16)
        assert plain["layers"]["wq"].shape == canonical["layers"]["wq"].shape

    def test_a_tree_handed_in_canonical_is_packed_at_build_and_left_alone(self):
        """``GenerativeModel`` packs a caller's canonical tree itself; the
        caller's leaves stay the caller's, and a leaf the pack does not
        touch is shared."""
        from seldon_core_tpu.executor.generation import GenerativeModel

        cfg = _cfg()
        tree = _params(cfg, dtype=jnp.bfloat16)
        model = GenerativeModel(
            cfg, tree, family_mod=m, n_slots=2, kv_block_size=4, dtype=jnp.bfloat16,
        )
        assert set(model.params_packed()) == set(m.PACKED)
        assert model.params["layers"]["wq"].ndim == 3
        assert tree["layers"]["wq"].ndim == 4 and not tree["layers"]["wq"].is_deleted()
        assert model.params["layers"]["we_up"] is tree["layers"]["we_up"]

    def test_weights_are_made_in_the_served_dtype(self):
        from seldon_core_tpu.models import registry

        fam = registry.get_family("cohere2_moe")
        cfg = registry.resolve_config("cohere2_moe", "tiny")
        params = registry._resolve_params(
            fam, cfg, None, None, 7, dtype=jnp.bfloat16
        )
        assert {str(a.dtype) for a in jax.tree.leaves(params)} == {"bfloat16"}
        again = m.init_params(jax.random.PRNGKey(7), cfg, jnp.bfloat16)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
            np.testing.assert_array_equal(a, b)

    def test_llama_init_is_left_as_it_was(self):
        from seldon_core_tpu.models import llama, registry

        cfg = llama.Config.tiny()
        params = registry._resolve_params(
            registry.get_family("llama"), cfg, None, None, 11,
            dtype=jnp.bfloat16,
        )
        want = llama.init_params(jax.random.PRNGKey(11), cfg)
        assert params["tok_emb"].dtype == jnp.float32
        np.testing.assert_array_equal(params["layers"]["wq"], want["layers"]["wq"])

    @pytest.mark.parametrize("seq_impl,kernel", [
        ("dense", False), ("flash", True),  # the XLA paths; the Pallas paths
    ])
    def test_generates_what_the_family_computes(self, prompt, seq_impl, kernel):
        from seldon_core_tpu.utils.device import xla_compile_count

        comp = self._component(seq_impl=seq_impl, decode_kernel=kernel)
        model = comp.model
        assert model.family is m and model.params["ln_f"].dtype == jnp.bfloat16
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
        # teacher-forced on the served tokens, the float32 reference puts
        # each of them within a small margin of its top logit
        want = np.asarray(ref.logits(
            self._canonical(model), np.concatenate([prompt, served[:-1]]),
            **_ref_kw(model.cfg),
        ))[len(prompt) - 1:]
        deficit = want.max(-1) - want[np.arange(len(served)), served]
        assert deficit.max() < 0.25
        snap = model.spec_snapshot()["counters"]
        assert snap["moe.steps"] >= 4
        assert snap["moe.pairs_routed"] >= 4 * 4 * 4  # steps x top-4 x layers
        assert snap["moe.prefill_tokens"] >= len(prompt)

    def test_a_rung_between_doubles_is_warmed_and_served(self, monkeypatch, prompt):
        """The ladder with its midpoints (from 16 up here, from 4,096 up as
        served): the prompt of 37 runs in ``prefill:b48``, warmed before
        it came, and ``prefill_rows`` says so."""
        from seldon_core_tpu.executor import generation
        from seldon_core_tpu.utils.device import xla_compile_count

        monkeypatch.setattr(generation, "HALF_RUNGS_FROM", 16)
        model = self._component(seq_impl="flash", decode_kernel=True).model
        assert model.prefill_buckets == (16, 24, 32, 48, 64)
        model.warmup()
        assert "prefill:b48[kernel]" in model.warmup_programs
        warmed = xla_compile_count()
        tok = model.admit(0, prompt.astype(np.int32), 0.0, 0, reserve_tokens=4)
        assert xla_compile_count() == warmed
        want = np.asarray(ref.logits(
            self._canonical(model), prompt, **_ref_kw(model.cfg)
        ))[-1]
        assert want.max() - want[int(tok)] < 0.25
        assert model.spec_snapshot()["prefill_rows"] == {
            "real": 37, "padded": 48, "by_rung": {"48": 1},
        }

    def test_what_the_family_does_not_have_is_refused(self):
        from seldon_core_tpu.graph.units import GraphUnitError

        with pytest.raises(GraphUnitError, match="kv_cache_dtype"):
            self._component(kv_cache_dtype="int8")
        # no LoRA path either: the pool is switched off with a warning
        assert self._component(lora_rank=4).model.lora_rank == 0


class TestAPromptsRealLength:
    """PR 58: the tiled kernel is handed the prompt's real length (with the
    layer's window), and the query tiles of the rung's padding are not
    computed."""

    @pytest.mark.parametrize("length", [
        600,     # the second tile of 512 straddles it; the third is dead
        1024,    # on a tile's edge: a decode step's block is the dead tile's first
    ])
    def test_the_real_rows_are_what_they_were(self, length, monkeypatch):
        from seldon_core_tpu.models import layers

        cfg = m.Config.tiny(max_seq=2048, experts_held="4:8")
        bs, rung = 64, 1536
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
        for name in ("k", "v"):
            got, was = (  # (layers, rung rows, values)
                np.asarray(c[name])[:, row[: rung // bs]].reshape(cfg.n_layers, rung, -1)
                for c in (cache, want)
            )
            assert np.array_equal(got[:, :edge], was[:, :edge])
            assert np.isfinite(got).all()
            # the dead tile's rows did change after the first layer: it engaged
            assert np.array_equal(got[0], was[0]) and not np.array_equal(got[1:], was[1:])
        # a decode step over the slot's last, partly padded block is finite
        lg, _ = jax.jit(functools.partial(
            m.decode_slots_paged, cfg=cfg, window=cfg.max_seq, kernel=True
        ))(
            params, jnp.asarray([0, int(np.argmax(logits))], jnp.int32), cache,
            jnp.asarray([False, True]),
        )
        assert np.isfinite(np.asarray(lg[1])).all()


class TestEngineRoutes:
    """``examples/cohere2-moe-generative/graph.json`` through the engine's
    own app: ``/predictions`` and ``/predictions/stream`` give the same
    tokens, and the routing counters are in ``/stats/summary``."""

    def test_the_example_graph_serves_both_routes(self):
        import asyncio
        import json

        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        path = os.path.join(
            os.path.dirname(__file__), "..", "examples",
            "cohere2-moe-generative", "graph.json",
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
                unit = stats["breakdown"]["generation"]["cohere2_moe:tiny"]
                c = unit["counters"]
                assert c["moe.steps"] > 0 and c["moe.pairs_routed"] > 0
                assert 0 < c["moe.pairs_held"] <= c["moe.pairs_routed"]
                assert c["moe.prefill_tokens"] >= 2 * len(prompt)
            finally:
                await client.close()

        asyncio.run(go())
