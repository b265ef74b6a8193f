"""Paged decode-attention as a Pallas TPU kernel.

The fused decode step (``models/llama.py::_decode_paged_multi``) spends its
HBM budget reading each slot's attention window out of the paged KV pool.
The XLA path does that as gather -> (dequant) -> einsum -> softmax -> einsum,
which materializes the gathered ``(S, W, kv, hd)`` window (and, under int8,
its dequantized copy) in HBM between ops.  This kernel fuses the whole read
side: the block-table gather is the BlockSpec index map (scalar-prefetched
table entries steer each grid step's DMA straight at the right pool block),
int8 blocks dequantize in VMEM against their per-(position, head) scales,
and attention runs the online-softmax recurrence over one KV block at a
time — pool bytes are read once, nothing intermediate touches HBM
(guide: /opt/skills/guides/pallas_guide.md; the gather idiom is the
standard TPU paged-attention pattern, the recurrence is flash decoding).

Layout.  The pool is ``(NB, BS, KV, D)`` and stays that way; Mosaic takes
a block whose last two dimensions are whole tiles or the whole dimension,
so one grid step reads a pool block as ``(BS, KV*D)`` — every KV head of
``BS`` positions, a free reshape of contiguous memory — and the scales as
``(BS, KV)``.  All heads then share ONE matmul per step: the queries are
laid out block-diagonally, row ``h*R + r`` holding head ``h``'s query in
lanes ``[h*D, (h+1)*D)`` and zeros elsewhere, so ``Q_bd @ K_blk^T`` is
every head's scores at once and ``P @ V_blk`` carries head ``h``'s output
in the same lanes of row ``h*R + r`` (the wrapper keeps that diagonal).
The zeros cost MXU passes the memory-bound decode step has to spare, and
buy a kernel with no per-head slicing of packed tiles.

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


def mxu_operands(dtype) -> tuple:
    """``(operand dtype, precision)`` for a kernel matmul fed ``dtype``
    data: bfloat16 goes to the MXU as it is (exact products, float32
    accumulation); anything else is computed in float32 at HIGHEST
    precision, because Mosaic's default rounds float32 operands on the way
    in and the float32 references would not be met."""
    if dtype == jnp.bfloat16:
        return jnp.bfloat16, None
    return jnp.float32, jax.lax.Precision.HIGHEST


def _paged_kernel(
    table_ref,  # (S, WB) int32 scalar-prefetch: physical block per grid step
    pos_ref,  # (S,) int32 scalar-prefetch: per-slot base position
    first_ref,  # (S,) int32 scalar-prefetch: position of the first row read
    q_ref,  # (1, KV*R, KV*D) block-diagonal, pre-scaled queries of one slot
    k_ref,  # (1, BS, KV*D) one gathered KV block, every head
    v_ref,
    *refs,  # [k_scale_ref, v_scale_ref,] o_ref, m_scr, l_scr, acc_scr
    bs,
    groups,
    rows,
    n_w,
    quant,
    window=None,
):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    s_i = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    HR = q_ref.shape[1]  # KV * rows
    base = pos_ref[s_i]
    # grid step w reads the rows at positions col0 .. col0 + bs - 1: the
    # table's blocks are the slot's first ones (first = 0) or, under a
    # sliding window, the ones from the block that holds its lower edge on
    col0 = first_ref[s_i] + w * bs
    # key blocks entirely past every query position are dead weight: the
    # furthest query sits at base + L - 1 (each head's last row is query
    # L-1's last group); under a window so are blocks that end at or
    # before the first query's ``base - window``
    live = col0 <= base + (rows - 1) // groups
    if window is not None:
        live = live & (col0 + bs - 1 > base - window)

    # int8 pool values are exact in bfloat16, so a quantized pool rides
    # the queries' operand dtype too
    cdt, prec = mxu_operands(q_ref.dtype)

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(cdt)  # (HR, KV*D)
        k = k_ref[0].astype(cdt)  # (BS, KV*D)
        v = v_ref[0].astype(cdt)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (HR, BS): row h*rows + r is head h's scores
        if quant:
            # per-(position, head) symmetric scales: the dequant the XLA
            # path pays as a separate HBM-resident op folds into the scores
            # and probabilities here.  The one-hot matmul spreads the
            # (BS, KV) scale block to the (HR, BS) score layout.
            kv = ks_ref.shape[2]
            sdt, sprec = mxu_operands(ks_ref.dtype)
            onehot = (
                jax.lax.broadcasted_iota(jnp.int32, (HR, kv), 0) // rows
                == jax.lax.broadcasted_iota(jnp.int32, (HR, kv), 1)
            ).astype(sdt)
            spread = functools.partial(
                jax.lax.dot_general,
                onehot,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=sprec,
            )
            s = s * spread(ks_ref[0].astype(sdt))
        # row h*rows + r belongs to query position j = r // groups and may
        # see pool rows [0, base + j] — the causal-speculation window
        rows_j = (
            jax.lax.broadcasted_iota(jnp.int32, (HR, bs), 0) % rows
        ) // groups
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (HR, bs), 1)
        seen = cols <= base + rows_j
        if window is not None:
            seen = seen & (cols > base + rows_j - window)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + p.sum(axis=-1)
        if quant:
            p = p * spread(vs_ref[0].astype(sdt))
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_scr[:] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(w == n_w - 1)
    def _emit():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
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
) -> jax.Array:
    """Attention for ``L`` decode queries per slot over the paged KV pool.

    ``q (S, L, H, D)`` post-RoPE queries (``H = kv_heads * groups``);
    ``k_pages``/``v_pages (NB, BS, KV, D)`` ONE layer's pool (float, or
    int8 with ``k_scale``/``v_scale (NB, BS, KV)``); ``table (S, WB)`` the
    physical blocks each slot's attention window reads; ``pos (S,)`` the
    slot's base position — query ``j`` sees pool rows ``[0, pos + j]``.
    Returns ``(S, L, H, D)`` in the query dtype.  Semantics are exactly
    :func:`paged_decode_attention_reference` (the XLA gather path).

    A sliding-window layer hands ``table`` the blocks that hold its window
    and ``first (S,)`` the position of the first row of the first of them
    (a multiple of ``BS``; default 0: the slot's first blocks), and
    ``window`` (static): query ``j`` then sees rows at positions
    ``(pos + j - window, pos + j]``, and blocks wholly outside are skipped.

    ``interpret`` defaults to True on the CPU backend only; every other
    backend compiles the kernel, and a shape Mosaic refuses is an error —
    nothing falls back to the interpreter or the XLA path in its name.
    """
    S, L, H, D = q.shape
    NB, BS, KV, _ = k_pages.shape
    WB = table.shape[1]
    if H % KV:
        raise ValueError(f"H {H} must be a multiple of kv heads {KV}")
    groups = H // KV
    R = L * groups
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
    kernel = functools.partial(
        _paged_kernel, bs=BS, groups=groups, rows=R, n_w=WB, quant=quant,
        window=None if window is None else int(window),
    )
    if first is None:
        first = jnp.zeros((S,), jnp.int32)

    def slot_block(s, w, t, p, f):
        return (s, 0, 0)

    def pool_block(s, w, t, p, f):
        # the gather: scalar-prefetched table entries drive the DMA source
        return (t[s, w], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KV * R, KV * D), slot_block),
        pl.BlockSpec((1, BS, KV * D), pool_block),
        pl.BlockSpec((1, BS, KV * D), pool_block),
    ]
    args = [
        q_bd,
        k_pages.reshape(NB, BS, KV * D),
        v_pages.reshape(NB, BS, KV * D),
    ]
    if quant:
        in_specs += [
            pl.BlockSpec((1, BS, KV), pool_block),
            pl.BlockSpec((1, BS, KV), pool_block),
        ]
        args += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, WB),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV * R, KV * D), slot_block),
            scratch_shapes=[
                pltpu.VMEM((KV * R, 128), jnp.float32),  # running max (col 0)
                pltpu.VMEM((KV * R, 128), jnp.float32),  # running denom (col 0)
                pltpu.VMEM((KV * R, KV * D), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, KV * R, KV * D), q.dtype),
        interpret=interpret,
    )(
        jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32),
        jnp.asarray(first, jnp.int32), *args,
    )
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
