"""Which read the decode programs are built with, and what says so.

``decode_kernel`` unset, the program chooses: the paged kernel where the
pool is on one device, the family's decode takes ``kernel=`` and the
backend compiles Pallas; the XLA gather elsewhere.  Set, it is honoured.
``/stats/summary`` names the read (``decode_read``) and counts, per decode
dispatch, the pool blocks the live slots hold against the blocks the
window spans.  Whatever row shape the pool is carried in, the blocks that
leave the device are the five-dimensional frame.
"""

from __future__ import annotations

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.executor.generation import (
    GenerationScheduler,
    GenerativeModel,
)
from seldon_core_tpu.models import llama
from seldon_core_tpu.ops.paged_attention import STEP_ROWS_MAX


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = llama.Config.tiny(max_seq=128)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _mesh():
    from seldon_core_tpu.parallel import best_mesh

    return best_mesh(2, tp=2)


class TestTheProgramChooses:
    @pytest.mark.parametrize(
        "backend,mesh,param,env,read",
        [
            ("cpu", False, None, None, "gather"),  # the interpreter is no kernel
            ("tpu", False, None, None, "kernel"),
            ("tpu", True, None, None, "gather"),  # a sharded pool keeps its read
            ("cpu", False, True, None, "kernel"),
            ("tpu", False, False, None, "gather"),
            ("cpu", False, None, "1", "kernel"),
            ("tpu", False, None, "0", "gather"),
            ("tpu", False, None, "", "kernel"),
            ("tpu", False, False, "1", "gather"),  # the parameter over the variable
        ],
    )
    def test_decode_read(self, tiny, monkeypatch, backend, mesh, param, env, read):
        import jax

        cfg, params = tiny
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if env is None:
            monkeypatch.delenv("SCT_DECODE_KERNEL", raising=False)
        else:
            monkeypatch.setenv("SCT_DECODE_KERNEL", env)
        kw = {}
        if mesh:
            kw = dict(mesh=_mesh(), param_axes=llama.param_logical_axes(params))
        model = GenerativeModel(cfg, params, n_slots=2, decode_kernel=param, **kw)
        assert model.decode_kernel is (read == "kernel")
        assert model.spec_snapshot()["decode_read"] == read
        assert ("kernel" in model.variant_sfx) == (read == "kernel")
        # the tile the kernel runs at: the tiny pool's float32 rows of 2 kv
        # heads x 8 are 64 B, so the rule's cap; no kernel, no tile
        assert model.spec_snapshot()["decode_tile_rows"] == (
            STEP_ROWS_MAX if read == "kernel" else None)
        # one device: a row holds its heads side by side; a mesh: a head axis
        assert model._cache["k"].ndim == (5 if mesh else 4)

    def test_a_family_without_the_kernel_keeps_the_gather(self, tiny, monkeypatch):
        import types

        import jax

        cfg, params = tiny
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("SCT_DECODE_KERNEL", raising=False)
        fam = {n: getattr(llama, n) for n in dir(llama) if not n.startswith("__")}
        fam["decode_slots_paged"] = (
            lambda params, tokens, cache, active, cfg, *, window=None,
            lora=None, adapter_ids=None, kv_sharded=False:
            llama.decode_slots_paged(
                params, tokens, cache, active, cfg, window=window, lora=lora,
                adapter_ids=adapter_ids, kv_sharded=kv_sharded)
        )
        model = GenerativeModel(
            cfg, params, n_slots=2,
            family_mod=types.SimpleNamespace(__name__="no_kernel", **fam),
        )
        assert model.decode_kernel is False


class TestTheTileOfEachCellsPool:
    """``decode_tile_rows`` on the benchmark's own graphs: each generative
    configuration's pool as its family makes it at the cell's slots, blocks
    and dtype (shapes alone: nothing is allocated), asked what
    ``/stats/summary`` asks.  On the chip an unset ``decode_kernel`` is the
    kernel on one device and the gather on a mesh (``TestTheProgramChooses``)."""

    @pytest.mark.parametrize("config,rows", [
        ("mistral-7b-l8", 256),  # 2-KB rows, blocks of 16: PR 29's tile
        ("mistral-7b-tp4", None),  # a mesh reads by gather
        ("command-a-plus-l4-ep8", 256),  # 2-KB rows, blocks of 256
        ("keye-vl-2-30b-a3b-l6", 512),  # 1-KB rows (read under ``topk`` only)
        ("kimi-k2-6-l5-ep32", None),  # latent rows: no K by head
        ("ai21-jamba2-3b", 2048),  # 256-B rows: the cap
        ("zaya1-8b-l20", 1024),  # 512-B rows: 512 KB a pool
    ])
    def test_the_tile_follows_the_cells_own_pool(self, config, rows):
        import inspect
        import os
        import types

        from seldon_core_tpu.models import registry

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
            p = json.load(f)["graph"]["parameters"]
        serving = set(inspect.signature(registry.build_generative_component).parameters)
        cfg = registry.resolve_config(p["family"], None, **{
            k: v for k, v in p.items() if k not in serving and k != "family"})
        mod = registry.GENERATIVE_FAMILIES[p["family"]]
        mesh = "mesh" in p
        cache = jax.eval_shape(lambda: mod.init_paged_cache(
            cfg, p["n_slots"], p.get("kv_blocks", 64), p["kv_block_size"],
            jnp.dtype(p["dtype"]), **({"kv_sharded": True} if mesh else {})))
        unit = types.SimpleNamespace(
            _cache=cache, decode_kernel=p.get("decode_kernel", not mesh))
        assert GenerativeModel.decode_tile_rows(unit) == rows


class TestOneProgramForEveryWindow:
    """The kernel reads by each slot's own position, so its decode program
    is one, at ``max_seq``; the gather keeps a program a window."""

    def test_window_buckets(self, tiny):
        cfg, params = tiny
        gather = GenerativeModel(cfg, params, n_slots=2)
        kernel = GenerativeModel(cfg, params, n_slots=2, decode_kernel=True)
        assert gather._window_buckets() == [64, 128]
        assert kernel._window_buckets() == [128]
        active = np.asarray([True, False])
        assert gather._window_for(active, 4) == 64
        assert kernel._window_for(active, 4) == 128

    @pytest.mark.parametrize("spec", [{}, {"spec_draft": 2, "spec_method": "draft",
                                           "spec_draft_model": "truncate:1"}])
    def test_the_same_tokens_either_read(self, tiny, spec):
        """Greedy streams through the scheduler, the draft model's decode
        included: the kernel's one program against the gather's windows."""
        cfg, params = tiny
        prompts = [np.arange(3, 3 + n, dtype=np.int32) for n in (5, 37, 70)]
        got = []
        for kernel in (False, True):
            model = GenerativeModel(
                cfg, params, n_slots=4, decode_block=4, decode_kernel=kernel,
                **spec)
            sched = GenerationScheduler(model)

            async def go():
                try:
                    return await asyncio.gather(
                        *(sched.submit(p, max_new_tokens=20) for p in prompts))
                finally:
                    await sched.close()

            got.append([np.asarray(t).tolist() for t in asyncio.run(go())])
            if kernel:
                assert {k[1] for k in model._decode_k_jit} == {cfg.max_seq}
        assert got[0] == got[1]


class TestStatsSummary:
    def test_the_read_and_its_block_counters(self):
        """``breakdown.generation.<unit>`` of ``/stats/summary``:
        ``decode_read`` and the two block counters, which move with every
        decode block: live blocks are a share of the window's."""
        from aiohttp.test_utils import TestClient, TestServer

        from seldon_core_tpu.engine.app import EngineApp
        from seldon_core_tpu.engine.service import PredictionService
        from seldon_core_tpu.graph.spec import PredictorSpec

        predictor = {
            "name": "p",
            "graph": {
                "name": "gen", "type": "MODEL",
                "implementation": "JAX_GENERATIVE",
                "parameters": [
                    {"name": "family", "value": "llama", "type": "STRING"},
                    {"name": "preset", "value": "tiny", "type": "STRING"},
                    {"name": "n_slots", "value": "4", "type": "INT"},
                    {"name": "decode_block", "value": "4", "type": "INT"},
                ],
            },
        }

        async def go():
            service = PredictionService(PredictorSpec.model_validate(predictor))
            client = TestClient(TestServer(EngineApp(service).build()))
            await client.start_server()
            try:
                seen = []
                for prompt in (list(range(3, 40)), list(range(5, 9))):
                    resp = await client.post(
                        "/api/v0.1/predictions",
                        json={"strData": json.dumps(
                            {"tokens": prompt, "max_new_tokens": 12})},
                    )
                    assert resp.status == 200, await resp.text()
                    stats = await (await client.get("/stats/summary")).json()
                    (unit,) = stats["breakdown"]["generation"].values()
                    assert unit["decode_read"] == "gather"  # the CPU backend
                    assert unit["decode_kernel"] is False
                    seen.append(
                        (unit["kv_blocks_live"], unit["kv_blocks_window"]))
                (l0, w0), (l1, w1) = seen
                assert 0 < l0 < w0 and l0 < l1 and w0 < w1
                # 37 + 12 tokens hold 4 blocks of 16 in the end, one slot of
                # four, and the window is 64 at the least: 4 x 4 blocks
                assert l0 <= 3 * 4 and w0 >= 3 * 16
            finally:
                await client.close()

        asyncio.run(go())

    def test_counters_follow_the_scheduler(self, tiny):
        cfg, params = tiny
        model = GenerativeModel(cfg, params, n_slots=4, decode_block=4)
        sched = GenerationScheduler(model)

        async def go():
            try:
                return await sched.submit(
                    np.arange(1, 20, dtype=np.int32), max_new_tokens=9)
            finally:
                await sched.close()

        out = asyncio.run(go())
        assert len(out) == 9
        blocks = model.kv_blocks_window // (model.n_slots * (64 // 16))
        assert blocks >= 2  # 8 tokens after the first: two blocks of 4 at least
        # one live slot, its ceiling inside the second 16-token block
        assert model.kv_blocks_live == 2 * blocks


class TestFrames:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_export_and_import_in_the_frame_shape(self, tiny, kv_dtype):
        """The pool's rows hold their heads side by side; what
        ``export_slot_kv`` hands out and ``attach_imported`` takes is
        ``(layers, blocks, block, kv_heads, head_dim)``, and a round trip
        through it leaves the second pool's blocks equal byte for byte."""
        cfg, params = tiny
        kw = {"kv_cache_dtype": kv_dtype} if kv_dtype else {}
        src = GenerativeModel(cfg, params, n_slots=2, **kw)
        dst = GenerativeModel(cfg, params, n_slots=2, **kw)
        assert src._cache["k"].ndim == 4
        prompt = np.arange(1, 40, dtype=np.int32)
        src.admit(0, prompt, 0.0, 0, reserve_tokens=8)
        frame = src.export_slot_kv(0, prompt.size)
        nb = -(-prompt.size // 16)
        shape = (cfg.n_layers, nb, 16, cfg.n_kv_heads, cfg.head_dim)
        assert frame[0].shape == frame[1].shape == shape
        scales = {}
        if kv_dtype:
            assert frame[2].shape == frame[3].shape == shape[:4]
            scales = dict(k_scale=frame[2], v_scale=frame[3])
        dst.attach_imported(1, prompt, frame[0], frame[1], reserve_tokens=8, **scales)
        back = dst.export_slot_kv(1, prompt.size)
        for a, b in zip(frame, back):
            assert a.dtype == b.dtype and np.array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
        # the rows in the pool are the frame's, heads side by side
        phys = np.asarray(dst._slot_row[1][:nb])
        assert np.array_equal(
            np.asarray(dst._cache["k"][:, phys], np.float32),
            np.asarray(frame[0], np.float32).reshape(shape[:3] + (-1,)),
        )


class TestLlamaDecodeReads:
    """``llama``'s decode on the pool as one device carries it (a row holds
    its heads side by side) and as a mesh splits it (a head axis): the
    paged kernel, the XLA read and the plain forward pass agree."""

    def _prefilled(self, kv_dtype, kv_sharded):
        from seldon_core_tpu.models import llama

        cfg = llama.Config.tiny(max_seq=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        cache = llama.init_paged_cache(
            cfg, 3, 13, 16, kv_dtype=kv_dtype, kv_sharded=kv_sharded)
        assert cache["k"].ndim == (5 if kv_sharded else 4)
        prompts = {0: np.arange(1, 28), 2: np.arange(40, 49)}
        for slot, toks in prompts.items():
            row = np.zeros(4, np.int32)
            row[:] = 1 + 4 * slot + np.arange(4)
            padded = np.zeros((1, 32), np.int32)
            padded[0, : toks.size] = toks
            _, cache = llama.prefill_slot_paged(
                params, jnp.asarray(padded), jnp.int32(toks.size),
                jnp.int32(slot), jnp.asarray(row), cache, cfg)
        return llama, cfg, params, cache, prompts

    @pytest.mark.parametrize(
        "L,kv_dtype,kv_sharded",
        [(1, None, False), (3, None, False), (1, "int8", False),
         (3, "int8", False), (1, None, True), (3, None, True)],
    )
    def test_kernel_gather_and_forward_agree(self, L, kv_dtype, kv_sharded):
        llama, cfg, params, cache, prompts = self._prefilled(
            kv_dtype, kv_sharded)
        act = jnp.asarray([True, False, True])
        qtok = jnp.asarray([[7, 8, 9], [0, 0, 0], [3, 4, 5]], jnp.int32)[:, :L]
        got = {}
        for kernel in (False, True):
            if L == 1:
                logits, new = llama.decode_slots_paged(
                    params, qtok[:, 0], dict(cache), act, cfg, window=64,
                    kernel=kernel, kv_sharded=kv_sharded)
                logits = logits[:, None]
            else:
                logits, new = llama.decode_slots_spec_paged(
                    params, qtok, dict(cache), act, act[:, None] & (qtok > 0),
                    cfg, window=64, kernel=kernel, kv_sharded=kv_sharded)
            got[kernel] = np.asarray(logits)
            assert new["k"].shape == cache["k"].shape
        np.testing.assert_allclose(
            got[True][[0, 2]], got[False][[0, 2]], rtol=2e-5, atol=2e-5)
        if kv_dtype is None:
            for slot, toks in prompts.items():
                full = np.concatenate([toks, np.asarray(qtok[slot])])
                want = llama.forward(params, jnp.asarray(full[None]), cfg)[0]
                np.testing.assert_allclose(
                    got[True][slot], np.asarray(want[toks.size:]),
                    rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_the_two_pool_shapes_hold_the_same_bytes(self, kv_dtype):
        """Prefill, a decode step and a suffix prefill leave the one-device
        pool and the pool with a head axis equal byte for byte."""
        sides = []
        for kv_sharded in (False, True):
            llama, cfg, params, cache, _ = self._prefilled(kv_dtype, kv_sharded)
            _, cache = llama.decode_slots_paged(
                params, jnp.asarray([7, 0, 3], jnp.int32), cache,
                jnp.asarray([True, False, True]), cfg, window=32,
                kv_sharded=kv_sharded)
            logits, cache = llama.prefill_suffix_paged(
                params, jnp.asarray(np.arange(16)[None, :] + 1, jnp.int32),
                jnp.int32(16), jnp.int32(16 + 11), jnp.int32(1),
                cache["table"][0], jnp.asarray([6], jnp.int32), cache, cfg,
                prefix_window=16, kv_sharded=kv_sharded)
            sides.append((np.asarray(logits), cache))
        (l0, c0), (l1, c1) = sides
        assert np.array_equal(l0, l1)
        for name in c0:
            assert np.array_equal(
                np.asarray(c0[name]).reshape(c1[name].shape), np.asarray(c1[name])
            ), name

    def test_the_pool_reaches_the_kernel_as_it_is_carried(self):
        """On the decode program's jaxpr: the K and V operands of the
        ``pallas_call`` are reshapes of the scan's carried pool (layers
        folded into blocks, rows untouched), and nothing else in a layer
        makes or takes a pool-sized array but the step's own row write: no
        copy, transpose, gather source cut out or dynamic-slice of it."""
        llama, cfg, params, cache, _ = self._prefilled(None, False)
        jaxpr = jax.make_jaxpr(
            lambda p, c: llama.decode_slots_paged(
                p, jnp.asarray([7, 0, 3], jnp.int32), c,
                jnp.asarray([True, False, True]), cfg, window=64, kernel=True)
        )(params, cache)
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1
        body = scans[0].params["jaxpr"].jaxpr
        pool_size = cache["k"].size
        producer = {v: e for e in body.eqns for v in e.outvars}
        calls = [e for e in body.eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        big = [v for v in calls[0].invars if v.aval.size == pool_size]
        assert len(big) == 2  # K and V
        for v in big:
            eqn = producer[v]
            assert eqn.primitive.name == "reshape"
            src = eqn.invars[0]
            assert src.aval.shape == cache["k"].shape
            assert v.aval.shape == (
                cache["k"].shape[0] * cache["k"].shape[1],) + cache["k"].shape[2:]
            # the carried pool after this step's rows were written into it
            assert producer[src].primitive.name == "scatter"
            assert producer[src].invars[0] in body.invars
        touching = {
            e.primitive.name for e in body.eqns
            if any(getattr(v.aval, "size", 0) >= pool_size
                   for v in list(e.invars) + list(e.outvars)
                   if hasattr(v, "aval"))
        }
        assert touching == {"scatter", "reshape", "pallas_call"}
