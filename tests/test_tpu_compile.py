"""The paged decode kernel, the prompt's tiled kernel, the selection's
kernels (a prompt's and a decode step's) the touched-only expert kernel and the selective scan through the TPU's own compiler, at the widths the benchmark's cells serve, for a v5e that is described and not attached
(no chip time; nothing runs).  The interpreter the other tests use accepts
what Mosaic refuses: a copy or slice off the tiling, too much fast memory.

All in this one file, the topology described inside a fixture: only the
worker that is given the file loads the TPU's library (see the
``on-chip-measurement`` guide, section 2).
"""

from __future__ import annotations

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (name, slots, queries, heads, head_dim, pool blocks, block, kv heads,
#  table columns, int8, sliding window)
CASES = [
    ("mistral-7b-l8 w2048", 32, 1, 32, 128, 8 * 4097, 16, 8, 128, False, None),
    ("mistral-7b-l8 w64", 32, 1, 32, 128, 8 * 4097, 16, 8, 4, False, None),
    ("mistral-7b-l8 verify of 5", 32, 5, 32, 128, 8 * 4097, 16, 8, 128, False, None),
    ("mistral-7b-l8 int8 w2048", 32, 1, 32, 128, 8 * 4097, 16, 8, 128, True, None),
    ("mistral-7b-l8 int8 w64", 32, 1, 32, 128, 8 * 4097, 16, 8, 4, True, None),
    ("command-a-plus full layer", 32, 1, 128, 128, 4 * 769, 256, 8, 32, False, None),
    ("command-a-plus window layer", 32, 1, 128, 128, 4 * 769, 256, 8, 17, False, 4096),
    # 8 query rows on 2 key-value heads of 128: a pool row 256 wide
    # (512-B rows: four blocks a step since PR 51)
    ("zaya1-8b-l20", 48, 1, 8, 128, 20 * 769, 256, 2, 16, False, None),
    # 20 query rows on one key-value head: 256-B rows, eight blocks a step
    ("ai21-jamba2-3b", 128, 1, 20, 128, 2 * 1153, 256, 1, 16, False, None),
    # 1-KB rows (4 kv heads), a context under ``topk``: two blocks a step
    ("keye-vl-2 under topk", 8, 1, 32, 128, 6 * 793, 256, 4, 128, False, None),
    ("zaya1-8b-l20 int8", 48, 1, 8, 128, 20 * 769, 256, 2, 16, True, None),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_paged_kernel_compiles_for_v5e(one_chip, case):
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.paged_attention import paged_decode_attention

    _, S, L, H, D, NB, BS, KV, WB, quant, window = case

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    dt = jnp.bfloat16
    pool = sds((NB, BS, KV * D), jnp.int8 if quant else dt)
    args = [
        sds((S, L, H, D), dt), pool, pool, sds((S, WB), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.bool_),
    ]
    if quant:
        args += [sds((NB, BS, KV), dt)] * 2

    def f(q, k, v, table, pos, active, ks=None, vs=None):
        kw = {}
        if window:
            first = jnp.maximum(pos - window + 1, 0) // BS * BS
            kw = dict(first=first, window=window)
        return paged_decode_attention(
            q, k, v, table, pos, k_scale=ks, v_scale=vs, active=active,
            interpret=False, **kw)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool goes in as it is: no copy of it among the temporaries
    pool_bytes = NB * BS * KV * D * (1 if quant else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_the_tiled_kernel_compiles_at_a_rung_of_6144(one_chip, window):
    """Command A+'s prompt attention at the ladder's rung between 4,096 and
    8,192: 12 x 12 tiles of 512, a count that is no power of two."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import flash_attention

    def sds(heads):
        return jax.ShapeDtypeStruct((1, heads, 6144, 128), jnp.bfloat16, sharding=one_chip)

    def f(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512, window=window,
            interpret=False,
        )

    compiled = jax.jit(f).lower(sds(128), sds(8), sds(8)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q_offset,chunk", [(0, 4096), (24576, 8192)],
                         ids=["first chunk of 12,288", "last chunk of 32,768"])
def test_the_selection_kernels_compile_at_keye_vl2s_widths(one_chip, q_offset, chunk):
    """``ops/sparse_attention.py`` at the published sizes (16 index heads of
    64, top-2,048, 32 / 4 heads of 128): a strip's scores against 32,768
    keys in VMEM, the mask as int8, the tiled attention under it."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops import sparse_attention as sa

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lk = q_offset + chunk

    def select(qi, wi, ki):
        return sa.select_topk_mask(
            qi, wi, ki, topk=2048, q_offset=q_offset, interpret=False
        )

    def attend(q, k, v, mask):
        return sa.masked_flash_attention(
            q, k, v, mask, q_offset=q_offset, interpret=False
        )

    for fn, args in (
        (select, (sds((chunk, 16, 64)), sds((chunk, 16), jnp.float32), sds((lk, 64)))),
        (attend, (sds((32, chunk, 128)), sds((4, lk, 128)), sds((4, lk, 128)),
                  sds((chunk, lk), jnp.int8))),
    ):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        # nothing the size of the mask among the temporaries
        assert compiled.memory_analysis().temp_size_in_bytes < chunk * lk // 8


# (name, table columns, pool blocks, pool dtype)
DECODE_SELECT_CASES = [
    ("the cell's window of 32,768", 128, 6 * 793, "bfloat16"),
    ("the reference kind's 12,288", 48, 48, "bfloat16"),
    ("the reference kind's float32 diagnosis", 48, 48, "float32"),
]


@pytest.mark.parametrize(
    "case", DECODE_SELECT_CASES, ids=[c[0] for c in DECODE_SELECT_CASES]
)
def test_the_decode_selection_kernel_compiles_at_keye_vl2s_widths(one_chip, case):
    """``select_decode_topk`` at the published sizes (8 slots, 16 index
    heads of 64, blocks of 256 tokens, top-2,048): whole blocks copied from
    the pool as it is carried (a block's tokens along the lanes: Mosaic
    refuses a slice of 64 lanes), dynamic rows of the score scratch, the
    shifts that pack the selection; the pool goes in as it is."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops import sparse_attention as sa

    _, wb, nb, dt = case
    dt = jnp.dtype(dt)

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(qi, wi, ikt, table, pos):
        return sa.select_decode_topk(qi, wi, ikt, table, pos, topk=2048, interpret=False)

    compiled = jax.jit(f).lower(
        sds((8, 16, 64)), sds((8, 16), jnp.float32), sds((nb, 64, 256)),
        sds((8, wb), jnp.int32), sds((8,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2048 * 4 * 4


# (name, tokens, held experts, hidden, expert width, layers, columns of F a step)
EXPERT_CASES = [
    ("keye-vl-2 a whole expert a step", 8, 128, 2048, 768, 6, 768),
    ("command-a-plus F in tiles", 32, 16, 4096, 4096, 4, 512),
    # the largest prompt rung under the grouped products (PR 47)
    ("command-a-plus a prompt rung of 128 rows", 128, 16, 4096, 4096, 4, 512),
    ("kimi-k2.6 a prompt rung of 128 rows", 128, 12, 7168, 2048, 4, 256),
    # top-1: an expert of 25.2 MB is two grid steps
    ("zaya1-8b two tiles an expert", 48, 16, 2048, 2048, 20, 1024),
]


@pytest.mark.parametrize("case", EXPERT_CASES, ids=[c[0] for c in EXPERT_CASES])
def test_the_touched_only_expert_kernel_compiles_for_v5e(one_chip, case):
    """``ops/touched_experts.py`` at the widths of the expert cells: three
    blocks of 3.1 MB double-buffered under a stated VMEM limit, and ``F`` in
    tiles where one matrix is 33.5 MB; the stacks go in as they are."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.touched_experts import f_tile, touched_expert_products

    _, T, X, E, F, L, tile = case

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    G = min(X, T * 8)

    def f(h2, cw, ids, n, gate, up, down):
        return touched_expert_products(
            h2, cw, ids, n, gate, up, down, base=X, interpret=False
        )

    compiled = jax.jit(f).lower(
        sds((T, E)), sds((T, X), jnp.float32), sds((G,), jnp.int32),
        sds((), jnp.int32), sds((L * X, E, F)), sds((L * X, E, F)),
        sds((L * X, F, E)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert f_tile(E, F, 2) == tile
    assert compiled.memory_analysis().temp_size_in_bytes < E * F * 2


# (name, sorted rows a pass, held experts, contraction, columns, layers, columns a step)
GROUPED_CASES = [
    ("zaya1-8b gate at the cell's rung", 2048, 16, 2048, 2048, 20, 2048),
    ("zaya1-8b the shortest grouped rung", 256, 16, 2048, 2048, 20, 2048),
    ("command-a-plus gate", 4096, 16, 4096, 4096, 4, 1024),
    ("kimi-k2.6 gate", 4096, 12, 7168, 2048, 4, 512),
    ("kimi-k2.6 down", 4096, 12, 2048, 7168, 4, 1792),
    ("zaya1-8b the longest rung", 4096, 16, 2048, 2048, 20, 2048),
]


@pytest.mark.parametrize("case", GROUPED_CASES, ids=[c[0] for c in GROUPED_CASES])
def test_the_grouped_expert_kernel_compiles_for_v5e(one_chip, monkeypatch, case):
    """``ops/grouped_experts.py`` at the prompt shapes of the expert cells
    whose programs run it:
    a weight block that spans the contraction, double-buffered under a
    stated VMEM limit, ``F`` in tiles where a matrix is over 12 MB; the stack
    goes in as it is carried, and nothing the size of a matrix is copied."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.grouped_experts import col_tile, grouped_product, walk

    # the op asks the backend whether to interpret: this process runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, R, X, K, F, L, tile = case

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def f(x, sizes, w, li):
        return grouped_product(x, walk(sizes, R, li * X), w)

    compiled = jax.jit(f).lower(
        sds((R, K)), sds((X,), jnp.int32), sds((L * X, K, F)), sds((), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert col_tile(K, F, 2) == tile
    assert compiled.memory_analysis().temp_size_in_bytes < K * F * 2


def test_keye_vl2s_decode_program_holds_no_copy_of_a_layers_experts(one_chip, monkeypatch):
    """The whole decode step at the served shapes (8 slots, six layers, a
    pool of 793 blocks of 256, window 32,768): the expert kernel is in it,
    handed the stack of every layer, and the program's temporaries are not
    the size of a layer's experts (1.2 GB: XLA fuses no slice into a
    kernel's operand, so a layer cut out first would be a copy a step)."""
    import functools

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import keye_vl2 as m

    # the ops ask the backend whether to interpret: this process runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = m.Config(n_layers=6)

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    params = shapes(jax.eval_shape(
        lambda: m.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    ))
    cache = shapes(jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 8, 793, 256, jnp.bfloat16)
    ))
    step = jax.jit(
        functools.partial(m.decode_slots_paged, cfg=cfg, window=32768, kernel=True),
        donate_argnums=(2,),
    )
    compiled = step.lower(
        params, jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip), cache,
        jax.ShapeDtypeStruct((8,), jnp.bool_, sharding=one_chip),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2  # the experts, the selection
    # nor of the index keys' pool (156 MB), nor of the window's keys and scores
    layer_experts = 128 * 3 * 2048 * 768 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_experts // 64


def test_keye_vl2s_prompt_program_cuts_a_layer_out_for_xlas_grouped_product(one_chip, monkeypatch):
    """``prefill:b8192`` whole at the served shapes (six layers of 128 held
    experts, a pool of 793 blocks of 256): Keye-VL-2.0's prompt programs hand
    ``_moe`` no stacks, so the grouped products are ``lax.ragged_dot``'s over
    the layer alone, three in the scan, and the temporaries hold the cut of
    a layer's experts as they did before PR 52 (1.83 GB; with the kernel
    over the carried stack they were 0.58 GB, and the cell read 1.0 % fewer
    tokens: PERF.md §6, PR 52)."""
    import functools

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import keye_vl2 as m

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = m.Config(n_layers=6)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = shapes(jax.eval_shape(
        lambda: m.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    ))
    cache = shapes(jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 8, 793, 256, jnp.bfloat16)
    ))
    prefill = jax.jit(
        functools.partial(m.prefill_slot_paged, cfg=cfg, seq_impl="flash"),
        donate_argnums=(5,),
    )
    compiled = prefill.lower(
        params, arg((1, 8192)), arg(()), arg(()), arg((128,)), cache
    ).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 3 and "jit(_product)" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.84e9


def _command_a_plus_as_served(one_chip, monkeypatch, packed=True):
    """The family, the cell's config and the shapes of its weights (as the
    engine holds them: ``pack_params``; or the canonical tree) and pool."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import cohere2_moe as m

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = m.Config(vocab_size=32768, n_layers=4, experts_held="0:16", max_seq=8192)

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    def weights():
        params = m.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
        return m.pack_params(params) if packed else params

    params = shapes(jax.eval_shape(weights))
    cache = shapes(jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 32, 769, 256, jnp.bfloat16)
    ))
    return m, cfg, params, cache


def _hlo_instructions(text):
    """{computation: [(name, shape, op, operand names, the rest)]} of an
    optimised HLO module's text."""
    import re

    def close(s, i):  # the index of the parenthesis that closes s[i]
        depth = 0
        for j in range(i, len(s)):
            depth += (s[j] == "(") - (s[j] == ")")
            if depth == 0:
                return j
        raise ValueError(s[:80])

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ", line)
        if not m or cur is None:
            continue
        rest = line[m.end():]
        end = close(rest, 0) + 1 if rest.startswith("(") else rest.index(" ")
        shape, rest = rest[:end], rest[end:].lstrip()
        op = rest[:rest.index("(")]
        args = close(rest, len(op))
        cur.append((m.group(1), shape, op,
                    re.findall(r"%([\w.\-]+)", rest[len(op):args + 1]), rest[args + 1:]))
    return comps


def _weight_relayouts(text, pattern, at_least=16 << 20):
    """The instructions of an optimised HLO module that take an entry
    parameter whose ``op_name`` matches ``pattern`` — followed through
    tuples, loops, bitcasts and what such an instruction made of it — are
    no product (a dot, a convolution, a fusion that holds one) and write
    ``at_least`` bytes or more to HBM: a weight cut out of its stack or
    re-tiled on its way to a product.  (A prefetch into the fast memory,
    ``S(1)``, in the parameter's own tiling is none.)  -> [(name, op, MB)]"""
    import math
    import re

    comps = _hlo_instructions(text)
    entry = re.search(r"ENTRY %([\w.\-]+)", text).group(1)
    sizes = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1}

    def nbytes(shape):
        return sum(
            sizes.get(dt, 0) * math.prod(int(d) for d in dims.split(",") if d)
            for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape)
        )

    def calls(rest):
        return re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", rest)

    def is_product(op, rest):
        return op in ("dot", "convolution") or any(
            is_product(o, r) for c in calls(rest) for _, _, o, _, r in comps[c]
        )

    found = []

    def walk(comp, labels):
        for name, shape, op, operands, rest in comps[comp]:
            hit = [labels[o] for o in operands if o in labels]
            if not hit:
                continue
            if op == "tuple":
                labels[name] = {
                    k: labels[o] for k, o in enumerate(operands) if o in labels
                }
            elif op == "get-tuple-element":
                k = int(re.search(r"index=(\d+)", rest).group(1))
                if isinstance(hit[0], dict) and k in hit[0]:
                    labels[name] = hit[0][k]
            elif op in ("bitcast", "optimization-barrier"):
                labels[name] = hit[0]
            elif op == "while":
                labels[name] = hit[0]
                for c in calls(rest):
                    arg = next(i[0] for i in comps[c] if i[2] == "parameter")
                    walk(c, {arg: hit[0]})
            elif not is_product(op, rest):
                labels[name] = True  # what it made of the weight is the weight still
                if (not op.endswith("-start") and "S(1)" not in shape
                        and nbytes(shape) >= at_least):
                    found.append((name, op, nbytes(shape) >> 20))

    walk(entry, {
        name: True for name, _, op, _, rest in comps[entry]
        if op == "parameter" and re.search(pattern, rest.replace("\\'", "'"))
    })
    return found


ATTENTION_WEIGHTS = r"op_name=\"params\['layers'\]\['w[qkvo]'\]"


def _decode_block(m, cfg):
    """Sixteen decode steps under one loop, as ``_decode_k``
    (``executor/generation.py``) scans them, greedy."""
    import jax.numpy as jnp
    from jax import lax

    def block(params, tokens, active, cache):
        def body(carry, _):
            tokens, cache = carry
            logits, cache = m.decode_slots_paged(
                params, tokens, cache, active, cfg, window=8192, kernel=True
            )
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            return (tokens, cache), tokens

        (tokens, cache), ys = lax.scan(body, (tokens, cache), None, length=16)
        return ys, tokens, cache

    return block


def test_command_a_plus_decode_program_streams_its_experts_through_the_kernel(one_chip, monkeypatch):
    """The whole decode step at the served shapes (32 slots, four layers of
    16 held experts, a pool of 769 blocks of 256, window 8,192), on the
    weights as the engine holds them (``pack_params``): every layer's
    experts run through the touched-only kernel (PR 47), handed the stack of
    every layer, and neither a matrix of a layer's experts nor a layer's
    attention projection is copied."""
    import functools

    import jax
    import jax.numpy as jnp

    m, cfg, params, cache = _command_a_plus_as_served(one_chip, monkeypatch)
    step = jax.jit(
        functools.partial(m.decode_slots_paged, cfg=cfg, window=8192, kernel=True),
        donate_argnums=(2,),
    )
    compiled = step.lower(
        params, jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip), cache,
        jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip),
    ).compile()
    # one period of four layers, unrolled: a paged read and the experts in each
    assert compiled.as_text().count("tpu_custom_call") == 2 * cfg.n_layers
    # what a step of 32 rows needs (this compile of PR 56: 3.4 MB; the
    # canonical tree's relayout of wq / wo made it 0.41 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "canonical"])
def test_command_a_plus_decode_block_takes_its_projections_as_they_lie(one_chip, monkeypatch, packed):
    """Sixteen steps under one loop, as the engine's ``decode_k:k16:w8192``
    scans them.  On the packed tree no instruction but the products takes
    ``wq`` / ``wk`` / ``wv`` / ``wo`` (the canonical tree's block copied
    ``wq`` and ``wo`` whole before its loop, 1.07 GB, and cut a layer of
    ``wq`` out and re-tiled it in every step: PERF.md section 6, PR 56) and
    the block's temporaries are megabytes.  The canonical tree still
    compiles: the pack happens inside the program, at that old cost."""
    import jax
    import jax.numpy as jnp

    m, cfg, params, cache = _command_a_plus_as_served(one_chip, monkeypatch, packed)
    compiled = jax.jit(_decode_block(m, cfg), donate_argnums=(3,)).lower(
        params, jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip), cache,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 * cfg.n_layers
    relayouts = _weight_relayouts(text, ATTENTION_WEIGHTS)
    if packed:
        assert relayouts == []
        assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
    else:  # the helper sees what the packed tree is rid of
        assert relayouts, relayouts


def test_command_a_plus_prompt_program_reads_its_experts_in_place(one_chip, monkeypatch):
    """``prefill:b4096`` whole at the served shapes (four layers of 16 held
    experts, a pool of 769 blocks of 256, the weights as the engine holds
    them): one period of four layers unrolled, each layer's grouped
    products through ``ops/grouped_experts.py`` over the carried stack
    (PR 52; no ``ragged-dot`` left), and the temporaries no larger than with
    ``lax.ragged_dot`` (1,432,391,680 B by this compile of PR 51's tree)."""
    import functools

    import jax
    import jax.numpy as jnp

    m, cfg, params, cache = _command_a_plus_as_served(one_chip, monkeypatch)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    prefill = jax.jit(
        functools.partial(m.prefill_slot_paged, cfg=cfg, seq_impl="flash"),
        donate_argnums=(5,),
    )
    compiled = prefill.lower(
        params, arg((1, 4096)), arg(()), arg(()), arg((32,)), cache
    ).compile()
    text = compiled.as_text()
    # gate, up and down in each of the four layers
    assert text.count("jit(_pass_products)/jit(_product)/pallas_call") == 3 * cfg.n_layers
    assert "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.44e9


@pytest.mark.parametrize("follows", [False, True], ids=["the rung", "the prompt's length"])
def test_the_tiled_kernel_compiles_at_kimi_k2s_widths(one_chip, follows):
    """A prompt's expanded latent attention at the 12,288 rung: 64 heads,
    keys 192 wide (128 + the 64-wide rotary key) under values 128 wide, the
    softmax scale stated; and with the prompt's real length, a traced scalar
    that the three prefetched lists are made from (PR 58)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import flash_attention

    def sds(width):
        return jax.ShapeDtypeStruct((1, 64, 12288, width), jnp.bfloat16, sharding=one_chip)

    def f(q, k, v, *length):
        return flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512, scale=0.14468,
            interpret=False, length=length[0] if follows else None,
        )

    length = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)] if follows else []
    compiled = jax.jit(f).lower(sds(192), sds(192), sds(128), *length).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kimi_k2_as_served(one_chip, monkeypatch):
    """``(module, cfg, params, cache, arg)`` of the cell's deployment as
    shapes on the described chip: five layers, 12 held experts, 32 slots over
    a latent pool of 1,633 blocks of 256."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import kimi_k2 as m

    # the ops ask the backend whether to interpret: this process runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = m.Config(vocab_size=20480, n_layers=5, experts_held="0:12", max_seq=16384)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = shapes(jax.eval_shape(
        lambda: m.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    ))
    cache = shapes(jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 32, 1633, 256, jnp.bfloat16)
    ))
    return m, cfg, params, cache, arg


def test_kimi_k2s_prompt_program_fits_at_the_12288_rung(one_chip, monkeypatch):
    """``prefill:b12288`` whole at the served shapes (five layers, 64 heads,
    the latent pool of 1,633 blocks): the tiled kernel is in it at both call
    sites, the grouped products run through ``ops/grouped_experts.py`` over
    the carried stack (two bodies: gate and up, down; no ``ragged-dot``
    left), and the temporaries hold no copy of a layer's experts (1.06 GB):
    1.80 GB where they were the 1.60 GB recorded at PR 43, the same
    instructions of that size with the buffers of the pass's loop assigned
    apart (PERF.md §6, PR 52)."""
    import functools

    import jax

    m, cfg, params, cache, arg = _kimi_k2_as_served(one_chip, monkeypatch)
    prefill = jax.jit(
        functools.partial(m.prefill_slot_paged, cfg=cfg, seq_impl="flash"),
        donate_argnums=(5,),
    )
    compiled = prefill.lower(
        params, arg((1, 12288)), arg(()), arg(()), arg((64,)), cache
    ).compile()
    # the dense layer's call site and the expert layers' scan
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "jit(_pass_products)/jit(_product)/pallas_call" in text and "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.81e9


def test_kimi_k2s_decode_program_makes_no_key_or_value_by_head(one_chip, monkeypatch):
    """The whole decode step at the served shapes (32 slots, five layers, a
    latent pool of 1,633 blocks of 256, window 16,384): the latent read and
    the touched-only expert kernel are in it, the pool goes in as it lies
    (a block of rotary keys 64 x 256, transposed), and the program's
    temporaries are megabytes — the window's keys or values by head would be
    32 x 16,384 x 64 x 128 x 2 B = 8.6 GB, a copy of the pool 2.4 GB."""
    import functools

    import jax
    import jax.numpy as jnp

    m, cfg, params, cache, arg = _kimi_k2_as_served(one_chip, monkeypatch)
    step = jax.jit(
        functools.partial(m.decode_slots_paged, cfg=cfg, window=16384, kernel=True),
        donate_argnums=(2,),
    )
    compiled = step.lower(params, arg((32,)), cache, arg((32,), jnp.bool_)).compile()
    # the dense layer's read; the expert layers' read and their experts (one scan)
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rung", [256, 4096], ids=["rung 256", "rung 4096"])
def test_the_selective_scan_kernel_compiles_at_jambas_widths(one_chip, rung):
    """``ops/selective_scan.py`` at AI21-Jamba2-3B's widths (5,120 channels,
    a state of 16, bfloat16 activations under a float32 ``D_t``), the
    smallest and the largest rung of the served ladder: the dynamic group of
    sixteen rows, the static lane of a token's ``B`` and ``C`` and the sum
    down the sublanes are Mosaic's to refuse."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.selective_scan import selective_scan

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def f(c, dt, b, cc, a, d, length):
        return selective_scan(c, dt, b, cc, a, d, length, interpret=False)

    compiled = jax.jit(f).lower(
        sds((rung, 5120), jnp.bfloat16), sds((rung, 5120)), sds((rung, 16)),
        sds((rung, 16)), sds((16, 5120)), sds((5120,)), sds((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # nothing of (tokens, state, channels) is made: that is 335 MB at 1,024
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def _jamba_as_served(one_chip, monkeypatch):
    """``(module, cfg, params, cache, arg)`` of the cell's deployment as
    shapes on the described chip: the whole model, 28 layers at the
    published widths, 128 slots over a pool of 1,153 blocks of 256."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import jamba as m

    # the ops ask the backend whether to interpret: this process runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(m.Config(), max_seq=4096)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = shapes(jax.eval_shape(
        lambda: m.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    ))
    cache = shapes(jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 128, 1153, 256, jnp.bfloat16)
    ))
    return m, cfg, params, cache, arg


def test_jambas_prompt_program_fits_and_names_its_kernels_once(one_chip, monkeypatch):
    """``prefill:b1024`` whole at the served shapes: the recurrence's kernel
    and the tiled attention are in it once each (one scan over the 28 layers,
    branching on the layer's kind), the arguments are the issue's 7.55 GB
    and the temporaries tens of MB: no copy of the slots' state (1.09 GB),
    of the convolution tails (102 MB: a tail written a tap at a time) or of
    the pool through a branch."""
    import functools

    import jax

    m, cfg, params, cache, arg = _jamba_as_served(one_chip, monkeypatch)
    prefill = jax.jit(
        functools.partial(m.prefill_slot_paged, cfg=cfg, seq_impl="flash"),
        donate_argnums=(5,),
    )
    compiled = prefill.lower(
        params, arg((1, 1024)), arg(()), arg(()), arg((16,)), cache
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "ssm.scan" in text
    memory = compiled.memory_analysis()
    assert 7.5e9 < memory.argument_size_in_bytes < 7.6e9
    assert memory.temp_size_in_bytes < 64 << 20


def test_jambas_decode_program_updates_the_slots_state_in_place(one_chip, monkeypatch):
    """The whole decode step at the served shapes (128 slots, 28 layers, the
    state of 26 of them 1.09 GB, window 4,096): the paged kernel reads 20
    query rows on one 128-lane key-value head at each attention layer, the
    update kernel takes the whole carried array aliased to its result, and
    the program's temporaries are megabytes — a copy of one layer's state for
    all slots would be 42 MB, of the whole state 1.09 GB."""
    import functools

    import jax
    import jax.numpy as jnp

    m, cfg, params, cache, arg = _jamba_as_served(one_chip, monkeypatch)
    step = jax.jit(
        functools.partial(m.decode_slots_paged, cfg=cfg, window=4096, kernel=True),
        donate_argnums=(2,),
    )
    compiled = step.lower(params, arg((128,)), cache, arg((128,), jnp.bool_)).compile()
    # the two attention layers' reads, and the update in place in each of
    # the three runs of state-space layers
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5 and text.count("ssm.update") >= 3
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def _zaya_as_served(one_chip, monkeypatch):
    """``(module, cfg, params, cache, arg)`` of the cell's deployment as
    shapes on the described chip: 20 of 40 blocks at the published widths,
    every expert and the whole vocabulary, 48 slots over a pool of 769 blocks
    of 256."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import zaya as m

    # the ops ask the backend whether to interpret: this process runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = m.Config(n_layers=20, max_seq=4096)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shapes(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = shapes(jax.eval_shape(
        lambda: m.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    ))
    cache = shapes(jax.eval_shape(
        lambda: m.init_paged_cache(cfg, 48, 769, 256, jnp.bfloat16)
    ))
    return m, cfg, params, cache, arg


def test_zayas_prompt_program_fits_at_the_2048_rung(one_chip, monkeypatch):
    """``prefill:b2048`` whole at the served shapes: the tiled attention is
    in it once (one scan over the 20 blocks) beside the grouped kernel's
    three calls (gate, up, down: ``ops/grouped_experts.py`` over the carried
    stack, no ``ragged-dot`` left), the arguments are the issue's 13.4 GB,
    and the temporaries hold no copy of a block's experts (403 MB) or of
    the pool (4.03 GB)."""
    import functools

    import jax

    m, cfg, params, cache, arg = _zaya_as_served(one_chip, monkeypatch)
    prefill = jax.jit(
        functools.partial(m.prefill_slot_paged, cfg=cfg, seq_impl="flash"),
        donate_argnums=(5,),
    )
    compiled = prefill.lower(
        params, arg((1, 2048)), arg(()), arg(()), arg((16,)), cache
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4 and text.count("flash_attention)/pallas_call") == 1
    assert text.count("moe.experts/while/body/jit(_pass_products)/jit(_product)/pallas_call") == 3
    assert "ragged" not in text
    for scope in ("cca.qk", "cca.conv", "cca.mix", "cca.v", "attn.prompt", "cca.out",
                  "router.down", "router.mlp", "moe.route", "moe.experts", "res.scale", "head"):
        assert scope in text, scope
    memory = compiled.memory_analysis()
    assert 13.3e9 < memory.argument_size_in_bytes < 13.5e9
    assert memory.temp_size_in_bytes < 400 << 20


def test_zayas_decode_program_reads_the_pool_and_the_experts_through_kernels(one_chip, monkeypatch):
    """The whole decode step at the served shapes (48 slots, 20 blocks,
    window 4,096): the paged kernel reads 8 query rows on 2 key-value heads
    and the touched-only kernel streams the chosen experts, each once in the
    scan's body; the temporaries are the float32 logits (50 MB) and little
    more — no copy of the pool, of a block's experts or of the tails."""
    import functools

    import jax
    import jax.numpy as jnp

    m, cfg, params, cache, arg = _zaya_as_served(one_chip, monkeypatch)
    step = jax.jit(
        functools.partial(m.decode_slots_paged, cfg=cfg, window=4096, kernel=True),
        donate_argnums=(2,),
    )
    compiled = step.lower(params, arg((48,)), cache, arg((48,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "attn.paged" in text and "moe.experts" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 160 << 20


def test_sampling_over_zayas_vocabulary_compiles(one_chip):
    """Arg-max and top-k over rows of 262,272 float32 logits, four times the
    longest row another cell has."""
    import functools

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.zaya import sample_tokens

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for top_k in (0, 40):
        compiled = jax.jit(functools.partial(sample_tokens, top_k=top_k)).lower(
            arg((48, 262272), jnp.float32), arg((48,), jnp.float32), arg((2,), jnp.uint32)
        ).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
