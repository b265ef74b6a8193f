"""A decode step's read of a LATENT paged cache as a Pallas TPU kernel.

Multi-head latent attention (``models/kimi_k2.py``) leaves two things of a
token in the pool: the normed latent ``c`` (512 values) and ONE rotary key
``kr`` (64), shared by every head.  A decode step never makes keys or values
by head: it carries each head's query into the latent space (``ql = qn
W_UK``, the model's lines) and attends the pool's rows as they lie — one
"key head" 576 wide under all the query heads, the same 512 values serving
as the key part and as the value::

    s[a, u]  = scale * (ql[a] . c[u] + qr[a] . kr[u])      u <= pos
    ol[a]    = sum_u softmax_u(s[a, u]) c[u]               (H, 512)

One grid step is one slot.  The slot's live blocks are copied by their
(scalar-prefetched) table entries from the pool in HBM into a VMEM tile of
:data:`STEP_ROWS` rows, the next tile — this slot's next or the next live
slot's first — in flight while this one is attended, as
``ops/paged_attention.py`` does it; the tile that made the scores makes the
weighted sum, so a latent row is read from HBM ONCE a slot and step (fed
the latent as ``k`` and again as ``v`` the K/V kernel would read every row
twice).  All heads are the M dimension of two matmuls a tile (``ql`` against
the tile's ``c``, ``qr`` against its ``kr``) and of the weighted sum; scores,
the online-softmax recurrence and the sum are float32.  A block past the
slot's position is not fetched and an inactive slot reads nothing.  The
kernel counts the rows its awaited copies brought in, and returns the count.

Layout.  ``c_pages (NB, BS, C)``: a row a token.  ``krt_pages (NB, R, BS)``:
a block of rotary keys TRANSPOSED, its tokens along the lanes — 64 wide by
tokens it is no whole 128-lane tile (the pool would be padded to twice its
bytes, and Mosaic copies whole tiles: PERF.md §6, PR 42), and this way round
it is the right operand of ``qr @ krt`` as it lies.  576 values a token, no
padding.

Compiled by Mosaic on every backend but the CPU, where it runs in Pallas
interpret mode; :func:`mla_decode_attention_reference` is the same
mathematics in XLA lines (the whole static window gathered): what the tests
hold the kernel to, and what ``decode_kernel: false`` serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seldon_core_tpu.ops.paged_attention import NEG_INF, mxu_operands

STEP_ROWS = 1024  # latent rows attended in one step (PERF.md §6, PR 43)
# rows of the kernel's second scalar operand, one column a slot (+ one)
_POS, _BHI, _NW, _NXT = range(4)


def _mla_kernel(
    table_ref,  # (S, WB) int32 scalar-prefetch: physical block per column
    meta_ref,  # (4, S + 1) int32 scalar-prefetch: rows _POS .. _NXT
    ql_ref,  # (1, H, C) pre-scaled latent queries of one slot
    qr_ref,  # (1, H, R) pre-scaled rotary queries
    c_hbm,  # (NB, BS, C) the latents, left in HBM
    krt_hbm,  # (NB, R, BS) the rotary keys, a block transposed
    o_ref,  # (1, H, C)
    read_ref,  # (1, 1, 128) int32: the blocks this slot's copies brought in
    cbuf,  # (2, G, BS, C)
    rbuf,  # (2, G, R, BS)
    sem,  # DMA (2, 2)
    cnt,  # SMEM (1,): steps done, the tile in turn
    m_scr, l_scr, acc_scr,
    *, n_cols, rope, score_dtype,
):
    pools = ((c_hbm, cbuf), (krt_hbm, rbuf))
    s_i = pl.program_id(0)
    n_slots = pl.num_programs(0)
    G, BS = cbuf.shape[1], cbuf.shape[2]
    T = G * BS
    H = ql_ref.shape[1]

    def copies(slot, w, buf, go):
        """Start (``go``) or await the copies of step ``w`` of ``slot``: its
        live blocks, each by its table entry, into tile ``buf``.  Returns
        how many blocks."""
        hi = jnp.minimum(meta_ref[_BHI, slot], w * G + G - 1)

        def one(b, n):
            blk = table_ref[slot, b]
            for o, (src, dst) in enumerate(pools):
                cp = pltpu.make_async_copy(
                    src.at[blk], dst.at[buf, b - w * G], sem.at[buf, o]
                )
                cp.start() if go else cp.wait()
            return n + 1

        return jax.lax.fori_loop(w * G, hi + 1, one, jnp.int32(0))

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
            copies(head, 0, 0, True)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = meta_ref[_POS, s_i]
    n_w = meta_ref[_NW, s_i]
    cdt, prec = mxu_operands(ql_ref.dtype)

    def step(w, read):
        cur = cnt[0] % 2
        # the next tile travels while this one is attended: this slot's
        # next, else the first of the next slot that has any; its copies
        # start before this tile's are awaited (ops/paged_attention.py)
        last = w == n_w - 1
        nslot = jnp.where(last, meta_ref[_NXT, s_i + 1], s_i)
        nw = jnp.where(last, 0, w + 1)

        @pl.when(nslot < n_slots)
        def _ahead():
            copies(nslot, nw, 1 - cur, True)

        read = read + copies(s_i, w, cur, False)

        c = cbuf[cur].astype(cdt).reshape(T, -1)  # (T, C)
        # the MXU accumulates in float32 whatever is asked of it: the
        # control rounds each product as it leaves
        s = jax.lax.dot_general(
            ql_ref[0].astype(cdt), c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ).astype(score_dtype)  # (H, T)
        if rope:
            qr = qr_ref[0].astype(cdt)
            s = s + jnp.concatenate([
                jax.lax.dot_general(
                    qr, rbuf[cur, g].astype(cdt), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec,
                )
                for g in range(G)
            ], axis=1).astype(score_dtype)
        s = s.astype(jnp.float32)
        # a block the step did not fetch lies past the slot's position, so
        # the same test hides its stale rows
        col = w * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
        seen = col <= pos
        if n_cols % T:
            seen = seen & (col < n_cols)  # columns the table does not have
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + p.sum(axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(cdt), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_scr[:] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)
        cnt[0] = cnt[0] + 1
        return read

    read = jax.lax.fori_loop(0, n_w, step, jnp.int32(0))
    read_ref[0] = jnp.zeros(read_ref.shape[1:], jnp.int32) + read
    l = l_scr[:, 0]
    safe_l = jnp.where(l == 0.0, 1.0, l)  # nothing read or seen -> zeros
    o_ref[0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)


def mla_decode_attention(
    ql: jax.Array, qr: jax.Array, c_pages: jax.Array, krt_pages: jax.Array,
    table: jax.Array, pos: jax.Array, *, scale: float,
    active: jax.Array | None = None, rope: bool = True,
    score_dtype=jnp.float32, step_rows: int = STEP_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(ol (S, H, C), rows_read (S,) int32)``: one decode query a slot,
    carried into the latent space, over the latent paged pool, and the pool
    rows the kernel's awaited copies brought in for the slot (its live
    blocks x the block size).  ``ql (S, H, C)`` the absorbed queries ``qn
    W_UK``, ``qr (S, H, R)`` the rotary ones; ``c_pages (NB, BS, C)`` and
    ``krt_pages (NB, R, BS)`` the pool as it is carried, every layer's
    blocks in one row of blocks; ``table (S, WB)`` the physical block of
    each of a slot's columns; ``pos (S,)``: the slot sees rows ``[0, pos]``.
    ``scale`` is the softmax scale (static; the queries are scaled in their
    own dtype on the way in).  A slot that is not ``active`` reads nothing
    and gets zeros.  ``rope=False`` (the rotary part left out) and
    ``score_dtype`` (the scores rounded as the MXU hands them over) are
    negative controls', never served.  Semantics are
    :func:`mla_decode_attention_reference`'s for every active slot."""
    S, H, C = ql.shape
    R = qr.shape[2]
    NB, BS, _ = c_pages.shape
    WB = table.shape[1]
    G = max(1, min(int(step_rows) // BS, WB))
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    pos = jnp.asarray(pos, jnp.int32)
    b_hi = jnp.minimum(pos // BS, WB - 1)
    if active is not None:
        b_hi = jnp.where(active, b_hi, -1)
    has = b_hi >= 0
    n_w = jnp.where(has, b_hi // G + 1, 0)
    nxt = jax.lax.cummin(
        jnp.where(has, jnp.arange(S, dtype=jnp.int32), S), reverse=True
    )
    meta = jnp.stack([pos, b_hi, n_w, nxt])  # _POS .. _NXT
    meta = jnp.pad(meta, ((0, 0), (0, 1)), constant_values=S).astype(jnp.int32)
    kernel = functools.partial(
        _mla_kernel, n_cols=WB * BS, rope=bool(rope), score_dtype=score_dtype,
    )

    def slot_block(s, t, m):
        return (s, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, read = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, H, C), slot_block),
                pl.BlockSpec((1, H, R), slot_block),
                hbm, hbm,
            ],
            out_specs=[
                pl.BlockSpec((1, H, C), slot_block),
                pl.BlockSpec((1, 1, 128), slot_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, G, BS, C), c_pages.dtype),
                pltpu.VMEM((2, G, R, BS), krt_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, 128), jnp.float32),  # running max (col 0)
                pltpu.VMEM((H, 128), jnp.float32),  # running denom (col 0)
                pltpu.VMEM((H, C), jnp.float32),  # accumulator
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, H, C), ql.dtype),
            jax.ShapeDtypeStruct((S, 1, 128), jnp.int32),
        ],
        # one slot's tiles are filled while the slot before it is attended
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        jnp.asarray(table, jnp.int32), meta,
        ql * jnp.asarray(scale, ql.dtype), qr * jnp.asarray(scale, qr.dtype),
        c_pages, krt_pages,
    )
    return out, read[:, 0, 0] * BS


def mla_decode_attention_reference(
    ql: jax.Array, qr: jax.Array, c_pages: jax.Array, krt_pages: jax.Array,
    table: jax.Array, pos: jax.Array, *, scale: float,
    active: jax.Array | None = None, rope: bool = True,
    score_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """The same read in XLA lines: the whole static window of every slot
    gathered from the pool, scored and summed.  ``rows_read`` is what it
    gathers: the window's rows, of every slot."""
    S, H, C = ql.shape
    BS = c_pages.shape[1]
    WB = table.shape[1]
    W = WB * BS
    c = c_pages[table].reshape(S, W, C)
    s = jnp.einsum(
        "shc,swc->shw", ql * jnp.asarray(scale, ql.dtype), c,
        preferred_element_type=score_dtype,
    )
    if rope:
        kr = jnp.swapaxes(krt_pages[table], -1, -2).reshape(S, W, -1)
        s = s + jnp.einsum(
            "shr,swr->shw", qr * jnp.asarray(scale, qr.dtype), kr,
            preferred_element_type=score_dtype,
        )
    seen = jnp.arange(W)[None, :] <= jnp.asarray(pos, jnp.int32)[:, None]
    if active is not None:
        seen = seen & active[:, None]
    s = jnp.where(seen[:, None, :], s.astype(jnp.float32), NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(seen[:, None, :], p, 0.0)  # a slot that sees nothing: zeros
    ol = jnp.einsum(
        "shw,swc->shc", p.astype(c.dtype), c,
        preferred_element_type=jnp.float32,
    )
    return ol.astype(ql.dtype), jnp.full((S,), W, jnp.int32)
