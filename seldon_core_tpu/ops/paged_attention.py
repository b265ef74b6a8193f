"""Paged decode-attention as a Pallas TPU kernel.

The fused decode step (``models/llama.py::_decode_paged_multi``) spends its
HBM budget reading each slot's keys and values out of the paged KV pool.
The XLA path does that as gather -> (dequant) -> einsum -> softmax -> einsum,
which materializes the gathered ``(S, W, kv, hd)`` window of EVERY slot at
the batch-wide window (and, under int8, its dequantized copy) in HBM between
ops.  This kernel fuses the whole read side and reads what a live slot
holds: one grid step is one slot; the slot's live blocks are copied by their
(scalar-prefetched) table entries from the pool in HBM into a VMEM tile
sized in BYTES (:func:`blocks_per_step`: as many whole blocks as make about
:data:`STEP_BYTES` a pool, never fewer than :data:`STEP_ROWS` key rows nor
more than :data:`STEP_ROWS_MAX` — 16 blocks of 16 tokens or one of 256 at
2-KB rows, four of 256 at 512-B rows: read off the pool's own shape and
dtype), the next step's blocks — this slot's next tile or the next live
slot's first — in flight while this one is attended;
int8 blocks dequantize in VMEM against their per-(position, head) scales;
attention runs the online-softmax recurrence over one tile at a time.  Pool
bytes are read once, nothing intermediate touches HBM, a block past the
slot's last query or before a sliding window's lower edge is not fetched,
and an inactive slot reads nothing (guide: /opt/skills/guides/pallas_guide.md;
manual double-buffered copies are the standard TPU paged-attention pattern,
the recurrence is flash decoding).

Layout.  The pool is ``(NB, BS, KV*D)``: a row holds every KV head of one
position side by side, which is how the programs carry it on one device
(``models/llama.py::init_paged_cache``), so the carried pool — every layer's
blocks, the layer an offset into the table — is the kernel's operand as it
is; a pool with a head axis would be re-tiled whole on the way in.  All heads
share ONE matmul per step: the queries are laid out block-diagonally, row
``h*R + r`` holding head ``h``'s query in lanes ``[h*D, (h+1)*D)`` and zeros
elsewhere, so ``Q_bd @ K_tile^T`` is every head's scores at once and ``P @
V_tile`` carries head ``h``'s output in the same lanes of row ``h*R + r``
(the wrapper keeps that diagonal).  The zeros cost MXU passes the
memory-bound decode step has to spare, and buy a kernel with no per-head
slicing of packed tiles.

Query shapes are the decode step's: ``L = 1`` for the plain step,
``L = 1 + spec_draft`` for the fused speculative verify pass.  Grouped
queries attend the *un-repeated* KV heads (GQA), exactly like the XLA path.

The kernel is compiled by Mosaic on every backend but the CPU, where it
runs in Pallas interpret mode so the equivalence tests pin it to the dense
reference; :func:`paged_decode_attention_reference` is the XLA-path math
factored out for those tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-but-finite: -inf * 0 = nan would poison the rescale
# A step's tile (PERF.md §6, PR 29 and PR 51).  What a step costs beside its
# bytes is fixed — two copies started and awaited a block, the scalar reads,
# the loop, a score tile's latency — and a copy is only as fast as what is
# queued behind it, so the tile is a size in bytes and its rows follow the
# pool's row.  On the chip (PR 50's sweep, the kernel alone at the slots and
# contexts of the cells that have such rows): 512-B rows read 398 GB/s by
# live rows at 256 rows a step, 637 at 1,024 and 646 at 2,048; 256-B rows
# 195 at 256, 418 at 2,048 and 394 at 4,096, where the score tile is mostly
# masked rows
STEP_BYTES = 512 * 1024  # one pool's tile a step (K and V: twice that in flight)
STEP_ROWS = 256  # the floor: key rows a step attends at 2-KB rows and wider
STEP_ROWS_MAX = 2048  # the cap: a wider score tile stops paying
# rows of the kernel's second scalar operand, one column a slot (+ one)
_POS, _FIRST, _BLO, _BHI, _WLO, _NW, _NXT = range(7)


def mxu_operands(dtype) -> tuple:
    """``(operand dtype, precision)`` for a kernel matmul fed ``dtype``
    data: bfloat16 goes to the MXU as it is (exact products, float32
    accumulation); anything else is computed in float32 at HIGHEST
    precision, because Mosaic's default rounds float32 operands on the way
    in and the float32 references would not be met."""
    if dtype == jnp.bfloat16:
        return jnp.bfloat16, None
    return jnp.float32, jax.lax.Precision.HIGHEST


def blocks_per_step(block_size: int, row_bytes: int) -> int:
    """Pool blocks one step of the kernel attends together, from what the
    operand shows: its block size and the bytes of a pool row (``KV * D`` x
    the pool dtype's item size).  As many whole blocks as make a tile of
    :data:`STEP_BYTES` a pool, never fewer than :data:`STEP_ROWS` key rows
    (so 2-KB rows keep PR 29's 256: 16 blocks of 16 tokens, one of 256) and
    never more than :data:`STEP_ROWS_MAX`; at least one block.  512-B rows
    (2 kv heads of 128 in bfloat16) travel four blocks of 256 a step, 1 MB of
    K and V in flight as at 2-KB rows.  The sweep behind the target and the
    cap: PERF.md §6, PR 51 (the table of PR 50's sweep)."""
    rows = min(max(STEP_ROWS, STEP_BYTES // int(row_bytes)), STEP_ROWS_MAX)
    return max(1, rows // int(block_size))


def _paged_kernel(
    table_ref,  # (S, WB) int32 scalar-prefetch: physical block per column
    meta_ref,  # (7, S + 1) int32 scalar-prefetch: rows _POS .. _NXT
    q_ref,  # (1, KV*R, KV*D) block-diagonal, pre-scaled queries of one slot
    k_hbm,  # (NB, BS, KV*D) the whole pool, left in HBM
    v_hbm,
    *refs,  # [ks_ref, vs_ref,] o_ref, kbuf, vbuf, sem, cnt, m_scr, l_scr,
    #         acc_scr; the scales are (1, KV, steps * T): the table's rows
    bs,
    g_blocks,
    groups,
    rows,
    n_cols,
    quant,
    window=None,
):
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, kbuf, vbuf, sem, cnt, m_scr, l_scr, acc_scr = refs
    pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    s_i = pl.program_id(0)
    n_slots = pl.num_programs(0)
    G = g_blocks
    T = G * bs  # key rows a step
    HR = q_ref.shape[1]  # KV * rows

    def copies(slot, w, buf, go):
        """Start (``go``) or await the copies of step ``w`` of ``slot``:
        its live blocks, each by its table entry, into tile ``buf``."""
        lo = jnp.maximum(meta_ref[_BLO, slot], w * G)
        hi = jnp.minimum(meta_ref[_BHI, slot], w * G + G - 1)

        def one(b, carry):
            blk = table_ref[slot, b]
            for o, (src, dst) in enumerate(pools):
                cp = pltpu.make_async_copy(
                    src.at[blk], dst.at[buf, b - w * G], sem.at[buf, o]
                )
                cp.start() if go else cp.wait()
            return carry

        jax.lax.fori_loop(lo, hi + 1, one, 0)

    @pl.when(s_i == 0)
    def _prime():
        # rows of a tile that no copy fills are masked out of the scores,
        # and 0 * (what fast memory held before) must still be 0
        for _, dst in pools:
            dst[...] = jnp.zeros_like(dst)
        cnt[0] = 0
        head = meta_ref[_NXT, 0]

        @pl.when(head < n_slots)
        def _head():
            copies(head, meta_ref[_WLO, head], 0, True)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    base = meta_ref[_POS, s_i]
    first = meta_ref[_FIRST, s_i]
    w_lo = meta_ref[_WLO, s_i]
    n_w = meta_ref[_NW, s_i]
    # int8 pool values are exact in bfloat16, so a quantized pool rides
    # the queries' operand dtype too
    cdt, prec = mxu_operands(q_ref.dtype)

    def step(i, carry):
        w = w_lo + i
        cur = cnt[0] % 2
        # the next step's blocks travel while this one is attended: this
        # slot's next group, else the first group of the next slot that
        # has any (none: the last work of the call).  Their copies start
        # BEFORE this step's are awaited (the other tile is free since the
        # step before this one was attended), so the copy engine always
        # has the next tile queued: 14 % a call on the chip (PERF.md §6)
        last = i == n_w - 1
        nslot = jnp.where(last, meta_ref[_NXT, s_i + 1], s_i)
        nw = jnp.where(last, meta_ref[_WLO, nslot], w + 1)

        @pl.when(nslot < n_slots)
        def _ahead():
            copies(nslot, nw, 1 - cur, True)

        copies(s_i, w, cur, False)

        q = q_ref[0].astype(cdt)  # (HR, KV*D)
        k = kbuf[cur].astype(cdt).reshape(T, -1)  # (T, KV*D)
        v = vbuf[cur].astype(cdt).reshape(T, -1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (HR, T): row h*rows + r is head h's scores
        if quant:
            # per-(position, head) symmetric scales: the dequant the XLA
            # path pays as a separate HBM-resident op folds into the scores
            # and probabilities here.  The one-hot matmul spreads the
            # step's (KV, T) scales to the (HR, T) score layout.
            kv = ks_ref.shape[1]
            sdt, sprec = mxu_operands(ks_ref.dtype)
            onehot = (
                jax.lax.broadcasted_iota(jnp.int32, (HR, kv), 0) // rows
                == jax.lax.broadcasted_iota(jnp.int32, (HR, kv), 1)
            ).astype(sdt)
            at = pl.ds(pl.multiple_of(w * T, T), T)

            def spread(ref):
                return jax.lax.dot_general(
                    onehot, ref[0, :, at].astype(sdt),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=sprec,
                )

            s = s * spread(ks_ref)
        # row h*rows + r belongs to query position j = r // groups and may
        # see pool rows [0, base + j] — the causal-speculation window.  A
        # block the step did not fetch lies past the last query or before
        # the window's lower edge, so the same test hides its stale rows
        rows_j = (
            jax.lax.broadcasted_iota(jnp.int32, (HR, T), 0) % rows
        ) // groups
        col = w * T + jax.lax.broadcasted_iota(jnp.int32, (HR, T), 1)
        cols = first + col
        seen = cols <= base + rows_j
        if window is not None:
            seen = seen & (cols > base + rows_j - window)
        if n_cols % T:
            seen = seen & (col < n_cols)  # columns the table does not have
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + p.sum(axis=-1)
        if quant:
            # the scales come by table entry, a dead block's too
            p = p * jnp.where(seen, spread(vs_ref), 0.0)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_scr[:] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)
        cnt[0] = cnt[0] + 1
        return carry

    jax.lax.fori_loop(0, n_w, step, 0)
    l = l_scr[:, 0]
    safe_l = jnp.where(l == 0.0, 1.0, l)  # nothing read or seen -> zeros
    o_ref[0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
    first: jax.Array | None = None,
    window: int | None = None,
    active: jax.Array | None = None,
) -> jax.Array:
    """Attention for ``L`` decode queries per slot over the paged KV pool.

    ``q (S, L, H, D)`` post-RoPE queries (``H = kv_heads * groups``);
    ``k_pages``/``v_pages (NB, BS, KV * D)`` the pool as it is carried,
    every layer's blocks in one row of blocks (``(NB, BS, KV, D)`` is taken
    too, and is re-tiled on the way by a backend that tiles its memory);
    float, or int8 with ``k_scale``/``v_scale (NB, BS, KV)``.  ``table (S,
    WB)`` the physical blocks each slot's attention window reads; ``pos
    (S,)`` the slot's base position — query ``j`` sees pool rows ``[0, pos +
    j]``.  Returns ``(S, L, H, D)`` in the query dtype.  Semantics are
    exactly :func:`paged_decode_attention_reference` (the XLA gather path)
    for every slot that is ``active (S,)`` (default: all); an inactive slot
    reads nothing and gets zeros.

    A sliding-window layer hands ``table`` the blocks that hold its window
    and ``first (S,)`` the position of the first row of the first of them
    (a multiple of ``BS``; default 0: the slot's first blocks), and
    ``window`` (static): query ``j`` then sees rows at positions
    ``(pos + j - window, pos + j]``.  Blocks wholly outside what any query
    of the slot sees are not fetched.

    ``interpret`` defaults to True on the CPU backend only; every other
    backend compiles the kernel, and a shape Mosaic refuses is an error —
    nothing falls back to the interpreter or the XLA path in its name.
    """
    S, L, H, D = q.shape
    if k_pages.ndim == 4:
        k_pages = k_pages.reshape(k_pages.shape[:2] + (-1,))
        v_pages = v_pages.reshape(v_pages.shape[:2] + (-1,))
    NB, BS, KVD = k_pages.shape
    KV = KVD // D
    WB = table.shape[1]
    if H % KV:
        raise ValueError(f"H {H} must be a multiple of kv heads {KV}")
    groups = H // KV
    R = L * groups
    G = blocks_per_step(BS, KVD * k_pages.dtype.itemsize)
    quant = k_scale is not None
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = jnp.asarray(1.0 / math.sqrt(D), q.dtype)
    # row r = j * groups + g: query-major so r // groups recovers j
    qr = (
        q.reshape(S, L, KV, groups, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(S, KV, R, D)
    )
    # block-diagonal queries: (S, KV, R, KV', D) is zero off KV == KV'
    eye = jnp.eye(KV, dtype=q.dtype)
    q_bd = (
        (qr * scale)[:, :, :, None, :] * eye[None, :, None, :, None]
    ).reshape(S, KV * R, KV * D)
    # which table columns a slot's queries can see, and the steps of G
    # columns that hold them: all of it scalar work, done once out here
    pos = jnp.asarray(pos, jnp.int32)
    first = (
        jnp.zeros((S,), jnp.int32) if first is None
        else jnp.asarray(first, jnp.int32)
    )
    b_hi = jnp.minimum((pos + L - 1 - first) // BS, WB - 1)
    b_lo = jnp.zeros((S,), jnp.int32)
    if window is not None:
        b_lo = jnp.maximum((pos - int(window) + 1 - first) // BS, 0)
    if active is not None:
        b_hi = jnp.where(active, b_hi, -1)
    has = b_hi >= b_lo
    w_lo = b_lo // G
    n_w = jnp.where(has, b_hi // G - w_lo + 1, 0)
    nxt = jax.lax.cummin(
        jnp.where(has, jnp.arange(S, dtype=jnp.int32), S), reverse=True
    )
    meta = jnp.stack([pos, first, b_lo, b_hi, w_lo, n_w, nxt])  # _POS .. _NXT
    meta = jnp.pad(meta, ((0, 0), (0, 1)), constant_values=S).astype(jnp.int32)
    kernel = functools.partial(
        _paged_kernel, bs=BS, g_blocks=G, groups=groups, rows=R,
        n_cols=WB * BS, quant=quant,
        window=None if window is None else int(window),
    )

    def slot_block(s, t, m):
        return (s, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, KV * R, KV * D), slot_block), hbm, hbm]
    args = [q_bd, k_pages, v_pages]
    tiles = [
        pltpu.VMEM((2, G, BS, KVD), k_pages.dtype),
        pltpu.VMEM((2, G, BS, KVD), v_pages.dtype),
    ]
    if quant:
        # one scale a (row, head) is a 64th of the rows' bytes and too
        # narrow a slice to copy by block: the table's rows of scales are
        # gathered out here, heads by rows, a slot's at a time in VMEM
        cols = -(-WB // G) * G * BS

        def by_rows(scales):
            rows_ = scales[table].reshape(S, WB * BS, KV)
            rows_ = jnp.pad(rows_, ((0, 0), (0, cols - WB * BS), (0, 0)))
            return rows_.transpose(0, 2, 1)

        in_specs += [pl.BlockSpec((1, KV, cols), slot_block)] * 2
        args += [by_rows(k_scale), by_rows(v_scale)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV * R, KV * D), slot_block),
            scratch_shapes=tiles + [
                pltpu.SemaphoreType.DMA((2, len(tiles))),
                pltpu.SMEM((1,), jnp.int32),  # steps done: the tile in turn
                pltpu.VMEM((KV * R, 128), jnp.float32),  # running max (col 0)
                pltpu.VMEM((KV * R, 128), jnp.float32),  # running denom (col 0)
                pltpu.VMEM((KV * R, KV * D), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, KV * R, KV * D), q.dtype),
        # one slot's tiles are filled while the slot before it is attended
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(jnp.asarray(table, jnp.int32), meta, *args)
    # head h's output sits in lanes [h*D, (h+1)*D) of its own rows
    out = jnp.diagonal(
        out.reshape(S, KV, R, KV, D), axis1=1, axis2=3
    )  # (S, R, D, KV)
    return (
        out.transpose(0, 3, 1, 2)
        .reshape(S, KV, L, groups, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(S, L, H, D)
    )


def paged_decode_attention_reference(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """The XLA gather path, factored out of ``_decode_paged_multi``: the
    pure-JAX fallback and the pin the kernel equivalence tests hold to."""
    S, L, H, D = q.shape
    NB, BS, KV, _ = k_pages.shape
    WB = table.shape[1]
    W = WB * BS
    groups = H // KV
    kw = k_pages[table]  # (S, WB, BS, KV, D)
    vw = v_pages[table]
    if k_scale is not None:
        kw = kw.astype(jnp.float32) * k_scale[table][..., None].astype(
            jnp.float32
        )
        vw = vw.astype(jnp.float32) * v_scale[table][..., None].astype(
            jnp.float32
        )
        kw = kw.astype(q.dtype)
        vw = vw.astype(q.dtype)
    kw = kw.reshape(S, W, KV, D)
    vw = vw.reshape(S, W, KV, D)
    positions = pos[:, None] + jnp.arange(L)[None, :]  # (S, L)
    valid = jnp.arange(W)[None, None, :] <= positions[:, :, None]  # (S, L, W)
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(S, L, KV, groups, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kw) * scale
    s = jnp.where(valid[:, None, None, :, :], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vw)
    return o.reshape(S, L, H, D)
