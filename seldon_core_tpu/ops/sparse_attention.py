"""Learned sparse attention (a DeepSeek-style indexer over a GQA model,
``models/keye_vl2.py``) as Pallas TPU kernels: a query attends only the
``topk`` keys its index scores rank highest,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          (float32)

and the two kernels here are the parts of a prompt's attention which XLA
alone would pay for in HBM traffic; the decode read is XLA's.

* :func:`sparse_decode_attention` — the decode read: the slot's selected
  rows gathered from the paged pool (``(rows, KV * D)``: every layer's
  blocks flattened, a row one token's kv heads side by side) and attended.
  Pool rows that were not selected are never read.  (Its docstring says
  why Mosaic cannot copy one row of the pool.)
* :func:`select_topk_mask` — a prompt's selection.  For a strip of queries
  the index scores against every key up to the strip's last are made tile
  by tile into VMEM and never leave it; the ``topk``-th largest score of a
  row is found exactly by bisection on the scores' bit patterns (31 passes
  of compare-and-count over the strip), ties at it are given to the lower
  positions as ``argsort`` would, and what goes to HBM is the selection
  itself, one int8 a (query, key).
* :func:`masked_flash_attention` — the tiled attention of
  ``ops/flash_attention.py`` under that mask, the query heads of one kv
  head together in a step so that the mask and the keys are read once a
  group, not once a head.

Each kernel has its XLA reference (``*_reference``) of the same mathematics;
on the CPU the kernels run in Pallas interpret mode and the tests hold them
to the references (``tests/test_ops.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seldon_core_tpu.ops.paged_attention import NEG_INF, mxu_operands

INT_MIN = -(2**31)
_VMEM_LIMIT = 96 << 20


def _interpret(flag):
    return jax.default_backend() == "cpu" if flag is None else flag


# ---------------------------------------------------------------------------
# index scores
# ---------------------------------------------------------------------------

def index_scores(qi, wi, ki, dtype=jnp.float32):
    """``I (Lq, Lk)``: ``qi (Lq, HI, DI)``, ``wi (Lq, HI)``, ``ki (Lk, DI)``.
    Products of the operands as they are stored, accumulated, rectified and
    weighted in ``dtype`` (float32; bfloat16 is the control's)."""
    s = jnp.einsum(
        "qjd,kd->jqk", qi.astype(dtype), ki.astype(dtype),
        preferred_element_type=dtype, precision=jax.lax.Precision.HIGHEST,
    )
    w = wi.astype(dtype).T[:, :, None]
    return jnp.sum(w * jnp.maximum(s, 0), axis=0).astype(jnp.float32)


def _score_key(x):
    """float32 -> int32 with the same order (``-0.0`` as ``0.0``)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = jnp.where(b == INT_MIN, 0, b)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


# ---------------------------------------------------------------------------
# decode: attention over selected rows of the paged pool
# ---------------------------------------------------------------------------

def sparse_decode_attention(q, k_rows, v_rows, rows, count):
    """One decode query a slot over the rows it selected, in XLA.

    ``q (S, H, D)`` post-RoPE queries; ``k_rows`` / ``v_rows (NR, KV * D)``
    the paged pool by rows (every layer's blocks flattened: a reshape of the
    pool as it is carried); ``rows (S, K)`` the pool row of each selected
    key; ``count (S,)`` how many of them, from the front, are real (0: the
    slot gets zeros).  All ``H`` heads share the slot's set.  Only the rows
    named are read from the pool: a gather of ``S * K`` rows, which XLA
    writes out and the attention reads back.

    Why no kernel reads the rows in place: a bfloat16 pool lies in HBM in
    tiles of 16 rows by 128 lanes with two rows to a 32-bit word, so ONE
    token's row is not contiguous, and Mosaic takes no copy whose rows are
    not whole tiles ("slice shape along dimension 0 must be aligned to
    tiling"); copying the 16-row tile around each selected row would read
    16 times the bytes, as much as the whole context."""
    S, H, D = q.shape
    KV = k_rows.shape[1] // D
    K = rows.shape[1]
    kw = k_rows[rows].reshape(S, K, KV, D)
    vw = v_rows[rows].reshape(S, K, KV, D)
    qg = q.reshape(S, KV, H // KV, D)
    s = jnp.einsum(
        "bkgd,bskd->bkgs", qg, kw, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    real = jnp.arange(K)[None, :] < count[:, None]
    s = jnp.where(real[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(count[:, None, None, None] > 0, p, 0.0)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(vw.dtype), vw)
    return o.reshape(S, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# prefill: the selection of a strip of queries, as a mask
# ---------------------------------------------------------------------------

def _select_kernel(
    qi_ref,  # (HI, bq, DI) the strip's index queries
    wi_ref,  # (bq, HI) float32 head weights
    kit_ref,  # (DI, Lk) every index key, transposed: resident
    mask_ref,  # (bq, Lk) int8 out
    keys_scr,  # (bq, Lk) int32: the strip's scores as ordered ints
    *, bq, bk, topk, q_offset, score_dtype,
):
    HI = qi_ref.shape[0]
    n_t = kit_ref.shape[1] // bk
    first = q_offset + pl.program_id(0) * bq  # the strip's first position
    hi = jnp.minimum((first + bq - 1) // bk + 1, n_t)  # tiles with a seen key
    t = first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    cdt, prec = mxu_operands(qi_ref.dtype)
    wi = wi_ref[...].astype(score_dtype)

    def score(kt, carry):
        at = pl.ds(pl.multiple_of(kt * bk, bk), bk)
        kit = kit_ref[:, at].astype(cdt)
        acc = jnp.zeros((bq, bk), score_dtype)
        for j in range(HI):
            # (the MXU accumulates in float32; the control rounds after)
            s = jax.lax.dot_general(
                qi_ref[j].astype(cdt), kit, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ).astype(score_dtype)
            acc = acc + wi[:, j:j + 1] * jnp.maximum(s, 0)
        key = _score_key(acc.astype(jnp.float32))
        keys_scr[:, at] = jnp.where(kt * bk + lane <= t, key, INT_MIN)
        return carry

    jax.lax.fori_loop(0, hi, score, 0)

    def count(test):
        """(bq, 1) int32: keys of each row that pass ``test``."""

        def one(kt, acc):
            at = pl.ds(pl.multiple_of(kt * bk, bk), bk)
            return acc + test(keys_scr[:, at]).astype(jnp.int32)

        acc = jax.lax.fori_loop(0, hi, one, jnp.zeros((bq, bk), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the topk-th largest key of each row, bit by bit from the top: the
    # largest value with at least topk keys at or above it (INT_MIN where
    # the row has fewer: everything it may see is selected)
    thr = jnp.where(
        count(lambda k: k >= 0) >= topk,
        jnp.zeros((bq, 1), jnp.int32), jnp.full((bq, 1), INT_MIN, jnp.int32),
    )

    def bit(i, thr):
        cand = thr | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda k: k >= cand) >= topk, cand, thr)

    thr = jax.lax.fori_loop(0, 31, bit, thr)
    ties = topk - count(lambda k: k > thr)  # places left for keys AT thr
    # exclusive running count of a tile's ties, by a triangular product
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
    ).astype(jnp.bfloat16)

    def emit(kt, seen):
        at = pl.ds(pl.multiple_of(kt * bk, bk), bk)
        key = keys_scr[:, at]
        eq = key == thr
        rank = seen + jax.lax.dot_general(
            eq.astype(jnp.bfloat16), tri, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            # zeros and ones: exact in one pass, whatever precision the
            # caller's context asks of its matmuls
            precision=jax.lax.Precision.DEFAULT,
        ).astype(jnp.int32)
        sel = ((key > thr) | (eq & (rank < ties))) & (kt * bk + lane <= t)
        mask_ref[:, at] = sel.astype(jnp.int8)
        return seen + jnp.sum(eq.astype(jnp.int32), axis=1, keepdims=True)

    jax.lax.fori_loop(0, hi, emit, jnp.zeros((bq, 1), jnp.int32))

    def blank(kt, carry):
        at = pl.ds(pl.multiple_of(kt * bk, bk), bk)
        mask_ref[:, at] = jnp.zeros((bq, bk), jnp.int8)
        return carry

    jax.lax.fori_loop(hi, n_t, blank, 0)


@functools.partial(
    jax.jit,
    static_argnames=("topk", "q_offset", "block_q", "block_k", "score_dtype",
                     "interpret"),
)
def select_topk_mask(
    qi: jax.Array, wi: jax.Array, ki: jax.Array, *, topk: int,
    q_offset: int = 0, block_q: int = 64, block_k: int = 512,
    score_dtype=jnp.float32, interpret: bool | None = None,
) -> jax.Array:
    """``(Lq, Lk) int8``: 1 where the query at position ``q_offset + t``
    selects the key at position ``s``: ``s`` is seen (``s <= q_offset +
    t``) and its index score is among the row's ``topk`` largest seen
    (ties: lower ``s`` first).  ``qi (Lq, HI, DI)``, ``wi (Lq, HI)``,
    ``ki (Lk, DI)`` at positions ``0 .. Lk - 1``."""
    Lq, HI, DI = qi.shape
    Lk = ki.shape[0]
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    if Lq % bq or Lk % bk:
        raise ValueError(f"({Lq}, {Lk}) is not whole tiles of ({bq}, {bk})")
    kernel = functools.partial(
        _select_kernel, bq=bq, bk=bk, topk=int(topk), q_offset=int(q_offset),
        score_dtype=score_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=(Lq // bq,),
        in_specs=[
            pl.BlockSpec((HI, bq, DI), lambda i: (0, i, 0)),
            pl.BlockSpec((bq, HI), lambda i: (i, 0)),
            pl.BlockSpec((DI, Lk), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, Lk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Lq, Lk), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bq, Lk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_interpret(interpret),
    )(qi.transpose(1, 0, 2), wi.astype(jnp.float32), ki.T)


def select_topk_mask_reference(qi, wi, ki, *, topk, q_offset=0,
                               score_dtype=jnp.float32):
    """The same selection by ``lax.top_k`` (XLA): best first, the lower
    index first among equals; every seen key where there are fewer."""
    Lq, Lk = qi.shape[0], ki.shape[0]
    seen = jnp.arange(Lk)[None, :] <= q_offset + jnp.arange(Lq)[:, None]
    scores = jnp.where(seen, index_scores(qi, wi, ki, score_dtype), -jnp.inf)
    _, idx = jax.lax.top_k(scores, min(int(topk), Lk))
    chosen = jnp.zeros((Lq, Lk), bool).at[jnp.arange(Lq)[:, None], idx].set(True)
    return (chosen & seen).astype(jnp.int8)


# ---------------------------------------------------------------------------
# prefill: tiled attention under the selection
# ---------------------------------------------------------------------------

def _masked_flash_kernel(
    q_ref,  # (1, G, bq, D): the query heads of one kv head
    k_ref,  # (1, bk, D)
    v_ref,
    mask_ref,  # (bq, bk) int8
    o_ref, m_scr, l_scr, acc_scr,
    *, bq, bk, n_k, q_offset, scale,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    G, D = q_ref.shape[1], q_ref.shape[3]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    cdt, prec = mxu_operands(q_ref.dtype)

    # a tile wholly after the strip's last query selects nothing
    @pl.when(ki * bk <= q_offset + qi * bq + bq - 1)
    def _tile():
        q = (q_ref[0] * scale).astype(cdt).reshape(G * bq, D)
        k = k_ref[0].astype(cdt)
        v = v_ref[0].astype(cdt)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ).reshape(G, bq, bk)
        sel = (mask_ref[...] != 0)[None]
        s = jnp.where(sel, s, NEG_INF).reshape(G * bq, bk)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        # a row with nothing selected so far keeps m at NEG_INF, where
        # exp(s - m) would be 1 for every masked key
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_cur[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + p.sum(axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_scr[:] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _emit():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l[:, None]).reshape(G, bq, D).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("q_offset", "block_q", "block_k", "interpret")
)
def masked_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, *,
    q_offset: int = 0, block_q: int = 256, block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``q (H, Lq, D)`` at positions ``q_offset ..`` over ``k``, ``v (KV,
    Lk, D)`` at ``0 .. Lk - 1`` where ``mask (Lq, Lk) int8`` is set — a
    mask that selects nothing after a query's own position (the causal
    bound lets whole tiles be skipped).  All heads share the mask; a row
    with nothing selected gives zeros.  Returns ``(H, Lq, D)``."""
    H, Lq, D = q.shape
    KV, Lk = k.shape[:2]
    G = H // KV
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    if Lq % bq or Lk % bk:
        raise ValueError(f"({Lq}, {Lk}) is not whole tiles of ({bq}, {bk})")
    n_k = Lk // bk
    q_offset = int(q_offset)

    def last(qi):  # the last key tile a strip of queries can select from
        return jnp.minimum((q_offset + qi * bq + bq - 1) // bk, n_k - 1)

    kernel = functools.partial(
        _masked_flash_kernel, bq=bq, bk=bk, n_k=n_k, q_offset=q_offset,
        scale=1.0 / math.sqrt(D),
    )
    out = pl.pallas_call(
        kernel,
        grid=(KV, Lq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda h, qi, ki: (h, 0, qi, 0)),
            # past a strip's last tile the block stays the same: no copy
            pl.BlockSpec((1, bk, D), lambda h, qi, ki: (h, jnp.minimum(ki, last(qi)), 0)),
            pl.BlockSpec((1, bk, D), lambda h, qi, ki: (h, jnp.minimum(ki, last(qi)), 0)),
            pl.BlockSpec((bq, bk), lambda h, qi, ki: (qi, jnp.minimum(ki, last(qi)))),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D), lambda h, qi, ki: (h, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((KV, G, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, 128), jnp.float32),
            pltpu.VMEM((G * bq, 128), jnp.float32),
            pltpu.VMEM((G * bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_interpret(interpret),
    )(q.reshape(KV, G, Lq, D), k, v, mask)
    return out.reshape(H, Lq, D)


def masked_attention_reference(q, k, v, mask):
    """Dense attention under ``mask`` (XLA): ``q (H, Lq, D)``, ``k``, ``v
    (KV, Lk, D)``, ``mask (Lq, Lk)``; float32 scores and softmax."""
    H, Lq, D = q.shape
    KV = k.shape[0]
    qg = q.reshape(KV, H // KV, Lq, D)
    s = jnp.einsum(
        "kgqd,ksd->kgqs", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    sel = (mask != 0)[None, None]
    s = jnp.where(sel, s, jnp.finfo(jnp.float32).min)
    p = jnp.where(sel, jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("kgqs,ksd->kgqd", p.astype(v.dtype), v)
    return o.reshape(H, Lq, D).astype(q.dtype)
