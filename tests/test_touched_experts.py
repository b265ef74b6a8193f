"""The touched-only expert kernel (ops/touched_experts.py, Pallas interpret
mode on the CPU) against ``moe.experts_dense`` on the same hidden
state, weights and routing; the one rule of static shapes that chooses
between them; and the counter that says which ran (``moe.experts_read``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import cohere2_moe as cm
from seldon_core_tpu.models import keye_vl2 as kv
from seldon_core_tpu.models import moe
from seldon_core_tpu.ops import touched_experts as te

E, F = 64, 256


def _routing(kind, T, K, X, rng):
    """-> (local (T, K), held (T, K) bool) of one kind of routing."""
    local = np.stack([rng.permutation(X)[:K] for _ in range(T)])
    held = np.ones((T, K), bool)
    if kind == "no live token":
        held[:] = False
    elif kind == "one expert":
        local[:] = X - 3
    elif kind == "a share":
        # ids as _moe forms them for a share: idx - first, some outside
        local = local - X // 2
        local[:, 0] = X + 5
        held = (local >= 0) & (local < X)
    elif kind == "inactive slots":
        held[T // 2] = False
        held[0] = False
    return jnp.asarray(local, jnp.int32), jnp.asarray(held)


# (case, routing, tokens, top-k, held experts, layers, layer, bytes a grid step may take)
CASES = [
    ("random routing at the cell's ratio", "random", 8, 4, 32, 1, 0, None),  # 8 x 4 of 32 ~ 8 x 8 of 128 x 2
    ("no live token", "no live token", 8, 4, 32, 1, 0, None),
    ("every pair on one expert", "one expert", 8, 4, 32, 1, 0, None),
    ("every held expert touched", "random", 8, 4, 8, 1, 0, None),  # T x K >= X
    ("pairs whose expert is not held", "a share", 8, 4, 16, 1, 0, None),
    ("an inactive slot's pairs masked out", "inactive slots", 8, 4, 32, 1, 0, None),
    ("a layer other than the first of several", "random", 8, 4, 16, 3, 2, None),
    ("an F of more than one tile", "random", 8, 4, 16, 2, 1, 3 * E * 128 * 4),
    ("rows that are no whole tile", "random", 5, 2, 16, 1, 0, None),
    # since PR 47 the rule sends a prompt rung under GROUPED_FROM rows here too
    ("32 slots over 16 held", "a share", 32, 4, 16, 2, 1, None),
    ("64 rows, every held expert touched", "random", 64, 4, 16, 1, 0, None),
    ("64 rows, some touched", "a share", 64, 2, 16, 2, 1, None),
    ("64 rows, no live token", "no live token", 64, 2, 16, 1, 0, None),
    ("128 rows, every held expert touched", "random", 128, 2, 16, 2, 1, None),
    ("128 rows, some touched", "a share", 128, 2, 16, 1, 0, None),
    ("128 rows, no live token", "no live token", 128, 2, 16, 1, 0, None),
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernel_gives_what_the_dense_products_give(case, dtype, monkeypatch):
    name, routing, T, K, X, L, li, step_bytes = case
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(len(name) + T + X)
    if step_bytes is not None:
        monkeypatch.setattr(te, "STEP_BYTES", step_bytes * dt.itemsize // 4)
        assert te.f_tile(E, F, dt.itemsize) == 128
    stacks = {
        k: jnp.asarray(rng.normal(size=(L, X) + shape) / np.sqrt(shape[0]), dt)
        for k, shape in (("we_gate", (E, F)), ("we_up", (E, F)), ("we_down", (F, E)))
    }
    h2 = jnp.asarray(rng.normal(size=(T, E)), dt)
    w = rng.random((T, K)).astype(np.float32)
    w = jnp.asarray(w / w.sum(-1, keepdims=True))
    local, held = _routing(routing, T, K, X, rng)
    lp = {k: v[li] for k, v in stacks.items()}
    want = np.asarray(moe.experts_dense(h2, lp, local, held, w))
    got = np.asarray(moe.experts_touched(h2, stacks, li, local, held, w))
    assert got.shape == (T, E) and got.dtype == np.float32
    if routing == "no live token":
        assert not got.any() and not want.any()
        return
    assert np.abs(want).max() > 0.05
    if dtype == "float32":
        # both in float32: the same terms, summed in another order
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * np.abs(want).max())
    else:
        # the dense way rounds gate, up, their product and the down product
        # to bfloat16 (2**-8 each); the kernel keeps float32 until the
        # operand of the down product
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-2 * np.abs(want).max())


@pytest.mark.parametrize("touched", [
    [], [3], [0, 1, 2, 3, 4, 5, 6, 7], [1, 6], [7],
], ids=["none", "one", "all", "two", "the last"])
def test_the_list_holds_the_touched_first_and_the_last_of_them_after(touched):
    mask = np.zeros(8, bool)
    mask[touched] = True
    ids, n = te.touched_list(jnp.asarray(mask), 6 if len(touched) < 7 else 8)
    ids = np.asarray(ids).tolist()
    assert int(n) == len(touched)
    assert ids[: len(touched)] == touched
    assert set(ids[len(touched):]) <= {touched[-1] if touched else 0}


# (tokens, the kernel can be handed the stacks, the plan)
PLANS = [
    (8, True, "touched"),    # Keye-VL-2.0's cell: 8 slots over 128 held
    (32, True, "touched"),   # Command A+'s: 32 slots over 16 held, 12-14 touched
    (1, True, "touched"),
    (8, False, "dense"),     # stacks over a mesh, or not at hand
    (moe.GROUPED_FROM - 1, True, "touched"),  # the largest prompt rung under the grouped ones
    (moe.GROUPED_FROM - 1, False, "dense"),
    (moe.GROUPED_FROM, True, "grouped"),
    (24576, False, "grouped"),
]


@pytest.mark.parametrize("T,kernel,plan", PLANS)
def test_one_rule_of_static_shapes_chooses_the_plan(T, kernel, plan):
    assert moe.experts_plan(T, kernel=kernel) == plan


class TestTheServedStep:
    """``keye_vl2.decode_slots_paged`` at a small size by either plan."""

    BS = 4

    def _steps(self, monkeypatch, kernel, steps=3):
        if not kernel:
            # what a caller without the stacks tells the rule
            plan = moe.experts_plan
            monkeypatch.setattr(
                moe, "experts_plan", lambda T, kernel: plan(T, kernel=False)
            )
        cfg = kv.Config.tiny(max_seq=64)
        params = kv.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
        cache = kv.init_paged_cache(cfg, 2, 40, self.BS, jnp.float32)
        prompt = np.random.default_rng(0).integers(1, 256, (1, 24))
        row = np.zeros(16, np.int32)
        row[:14] = np.arange(1, 15)[::-1]
        logits, cache = kv.prefill_slot_paged(
            params, jnp.asarray(prompt, jnp.int32), jnp.int32(22), jnp.int32(1),
            jnp.asarray(row), cache, cfg,
        )
        assert len(kv.COUNTERS) == 14 and cache["counters"].shape == (14,)
        toks, out = [], []
        nxt = int(np.argmax(logits))
        for _ in range(steps):
            toks.append(nxt)
            lg, cache = kv.decode_slots_paged(
                params, jnp.asarray([0, nxt], jnp.int32), cache,
                jnp.asarray([False, True]), cfg, window=cfg.max_seq,
            )
            out.append(np.asarray(lg[1]))
            nxt = int(np.argmax(out[-1]))
        counters = dict(zip(kv.COUNTERS, np.asarray(cache["counters"]).tolist()))
        return cfg, toks, np.stack(out), counters

    def test_the_same_tokens_and_logits_by_either_plan(self, monkeypatch):
        cfg, toks_k, logits_k, c_k = self._steps(monkeypatch, True)
        _, toks_d, logits_d, c_d = self._steps(monkeypatch, False)
        assert toks_k == toks_d
        np.testing.assert_allclose(logits_k, logits_d, rtol=0, atol=2e-5)
        # the kernel read the experts touched and no other; the dense
        # products every held expert of every layer in every step
        assert c_k["moe.steps"] == c_d["moe.steps"] == 3
        assert c_k["moe.experts_touched"] == c_d["moe.experts_touched"] > 0
        assert c_k["moe.experts_read"] == c_k["moe.experts_touched"]
        assert c_d["moe.experts_read"] == cfg.n_experts * cfg.n_layers * 3
        for name in kv.COUNTERS:
            if name != "moe.experts_read":
                assert c_k[name] == c_d[name], name

    def test_the_ninth_counter_sits_behind_the_eight_and_before_the_selections(self):
        assert cm.COUNTERS[8] == "moe.experts_read" and len(cm.COUNTERS) == 9
        assert kv.COUNTERS[:9] == cm.COUNTERS
        assert kv.COUNTERS[9:] == (
            "dsa.keys_scored", "dsa.keys_selected", "dsa.prefill_keys_scored",
            "dsa.prefill_keys_selected", "dsa.key_blocks_read",
        )
        assert (kv._STEPS, kv._P_TOKENS, kv._SCORED) == (4, 7, 9)


class TestCommandAPlusAt32Slots:
    """``cohere2_moe.decode_slots_paged`` at 32 slots over 8 held experts:
    the kernel on one device, ``moe.experts_dense`` for stacks over a mesh."""

    S, STEPS = 32, 2

    @staticmethod
    @functools.cache
    def _steps(sharded):
        S, STEPS = TestCommandAPlusAt32Slots.S, TestCommandAPlusAt32Slots.STEPS
        cfg = cm.Config.tiny(max_seq=64, experts_held="4:8")
        params = cm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
        cache = cm.init_paged_cache(cfg, S, 2 * S + 1, 4, jnp.float32)
        table = np.zeros((S, 64 // 4), np.int32)
        table[:, :2] = np.arange(1, 2 * S + 1).reshape(S, 2)  # two blocks a slot
        cache["table"] = jnp.asarray(table)
        active = jnp.asarray(np.arange(S) % 4 != 0)
        nxt, toks, out = jnp.arange(S, dtype=jnp.int32) + 1, [], []
        for _ in range(STEPS):
            lg, cache = cm.decode_slots_paged(
                params, nxt, cache, active, cfg, window=8, kv_sharded=sharded,
            )
            out.append(np.asarray(lg)[np.asarray(active)])
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            toks.append(np.asarray(nxt)[np.asarray(active)].tolist())
        counters = dict(zip(cm.COUNTERS, np.asarray(cache["counters"]).tolist()))
        return cfg, toks, np.stack(out), counters

    def test_the_kernel_reads_the_experts_touched_and_no_other(self):
        cfg, _, _, c = self._steps(sharded=False)
        assert c["moe.steps"] == self.STEPS
        assert 0 < c["moe.experts_touched"] <= 8 * cfg.n_layers * self.STEPS
        assert c["moe.experts_read"] == c["moe.experts_touched"]

    def test_stacks_over_a_mesh_read_every_held_expert_to_the_same_tokens(self):
        cfg, toks_d, logits_d, c_d = self._steps(sharded=True)
        _, toks_k, logits_k, c_k = self._steps(sharded=False)
        assert c_d["moe.experts_read"] == 8 * cfg.n_layers * self.STEPS
        assert toks_k == toks_d
        np.testing.assert_allclose(logits_k, logits_d, rtol=0, atol=2e-5)
        for name in cm.COUNTERS:
            if name != "moe.experts_read":
                assert c_k[name] == c_d[name], name
