"""Learned sparse attention (a DeepSeek-style indexer over a GQA model,
``models/keye_vl2.py``) as Pallas TPU kernels: a query attends only the
``topk`` keys its index scores rank highest,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          (float32)

and the kernels here are the parts which XLA alone would pay for in HBM
traffic: a prompt's selection and attention, and a decode step's selection;
the decode read of the selected rows is XLA's.

* :func:`sparse_decode_attention` — the decode read: the slot's selected
  rows gathered from the paged pool (``(rows, KV * D)``: every layer's
  blocks flattened, a row one token's kv heads side by side) and attended.
  Pool rows that were not selected are never read.  (Its docstring says
  why Mosaic cannot copy one row of the pool.)
* :func:`select_decode_topk` — a decode step's selection, a slot a grid
  step: the slot's live blocks of index keys are copied from the pool by
  table entry and scored in VMEM, the ``topk``-th largest score is found by
  the same bisection, and the selected positions are packed to the front
  of the row and leave as pool rows; the window is not gathered and nothing
  is sorted.
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
  group, not once a head.  **A masked tile costs what a selected tile
  needs** (PR 46, as PR 44 did for the tiled kernel; PERF.md §6): the grid
  is ``(KV, steps)`` and a step is one LIVE tile — a key tile that starts
  at or before the strip's last query, from the list
  ``flash_attention._tile_steps`` makes of the static shapes and
  ``q_offset``, by scalar prefetch; the running max and denominator are
  ``(G * block_q, 128)`` with every lane of a row the same and stay
  two-dimensional from the scores' reduction to the rescale, whole vregs
  in and out (cutting a one-lane column out and broadcasting it back,
  three times a tile of 2,048 rows, was 38 % of the kernel's time); and a
  score is selected once: the running max starts at
  ``M_INIT``, above the mask's value, so a score left out has the exponent
  0 whatever its row has seen and a row with nothing selected ends at
  zeros.  A step works its group's heads in strips of ``_HEADS_A_STRIP``
  inside a ``fori_loop``, a head's ``(block_q, block_k)`` scores at a time:
  within a strip the compiler runs one head's products under another's
  softmax, where the whole group's 2,048 x 512 scores as one array left the
  MXU waiting on the vector passes.  The sums and their order are the
  kernel's as it stood — a row's scores, its max, its exponents and sums
  over the same key tile of ``block_k`` — so the output has the same bits
  whatever ``block_q`` and the strips are.

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

from seldon_core_tpu.ops.flash_attention import (
    _FIRST,
    _LAST,
    M_INIT,
    TILE_PLANS,
    _lanes,
    _tile_steps,
    tile_plan,
)
from seldon_core_tpu.ops.paged_attention import NEG_INF, mxu_operands

INT_MIN = -(2**31)
_VMEM_LIMIT = 96 << 20
# the query heads of a kv head that one iteration of the masked kernel's loop
# works: measured at 8 heads of 512 x 512 (PERF.md §6, PR 46)
_HEADS_A_STRIP = 4


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


def _kth_largest_key(counts, topk: int, shape, bits: int):
    """``(thr, ties)``: the ``topk``-th largest of the ordered-int keys
    (``_score_key``) that ``counts`` sees, and the places left for keys AT
    it.  ``counts(*tests)`` gives, for each test of a tile of keys, how many
    keys pass, an int32 array of ``shape`` (a row each, or a slot's one).
    The value is found from the top, ``bits`` of it a sweep (``2 ** bits -
    1`` candidates ride one sweep of the scratch): the largest candidate
    with ``topk`` keys at or above it, ``INT_MIN`` where fewer are seen
    (everything seen is selected).  Both selection kernels' search: a
    prompt's strip takes a bit a sweep (a sweep is a strip's rows by tiles
    of keys: the compares are the cost), a decode slot two (its sweep is a
    chain of a reduction and a broadcast: the sweeps are)."""
    thr = jnp.where(
        counts(lambda k: k >= 0)[0] >= topk,
        jnp.zeros(shape, jnp.int32), jnp.full(shape, INT_MIN, jnp.int32),
    )

    def sweep(thr, low, nbits):
        cands = [
            thr | jnp.left_shift(jnp.int32(j), low) for j in range(1, 1 << nbits)
        ]
        found = counts(*[lambda k, c=c: k >= c for c in cands])
        for c, n in zip(cands, found):
            thr = jnp.where(n >= topk, c, thr)  # ascending: the last that holds
        return thr

    whole, rest = divmod(31, bits)
    thr = jax.lax.fori_loop(
        0, whole, lambda i, thr: sweep(thr, 31 - bits * (i + 1), bits), thr
    )
    if rest:
        thr = sweep(thr, 0, rest)
    return thr, topk - counts(lambda k: k > thr)[0]


# ---------------------------------------------------------------------------
# decode: the selection of one query a slot, over the slot's live index keys
# ---------------------------------------------------------------------------

# pool blocks scored together: the sublanes of one int32 tile, so that a
# group's scores are whole tiles of the scratch
_GROUP = 8


def _flat_rank(m):
    """``(R, B) int32``: how many of ``m (R, B) bool`` are set before each
    place, rows after one another: within a row by a triangular product, the
    rows before by a second one over the rows' totals.  Zeros and ones and
    totals of at most ``B``: exact in one bfloat16 pass up to 256, in
    float32 at the highest precision past it."""
    R, B = m.shape
    dims = (((1,), (0,)), ((), ()))

    def before(n, transposed):
        i, j = (jax.lax.broadcasted_iota(jnp.int32, (n, n), d) for d in (0, 1))
        return j < i if transposed else i < j

    within = jax.lax.dot_general(
        m.astype(jnp.bfloat16), before(B, False).astype(jnp.bfloat16), dims,
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.DEFAULT,
    )
    dt, prec = (
        (jnp.bfloat16, jax.lax.Precision.DEFAULT) if B <= 256
        else (jnp.float32, jax.lax.Precision.HIGHEST)
    )
    total = jnp.sum(m.astype(jnp.float32), axis=1, keepdims=True)
    rows = jax.lax.dot_general(
        before(R, True).astype(dt), jnp.broadcast_to(total, (R, B)).astype(dt),
        dims, preferred_element_type=jnp.float32, precision=prec,
    )
    return (within + rows).astype(jnp.int32)


def _shift_flat(x, s):
    """``y[i] = x[i + s]`` over ``x (R, B)`` read row after row (what falls
    off the front comes round to the back, where the caller has nothing)."""
    R, B = x.shape
    if s % B == 0:
        return pltpu.roll(x, (R - s // B) % R, 0)
    z = pltpu.roll(x, B - s, 1)  # z[r, l] = x[r, (l + s) % B]
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, B), 1)
    return jnp.where(lane < B - s, z, pltpu.roll(z, R - 1, 0))


def _decode_select_kernel(
    table_ref,  # (S, WB) int32 scalar-prefetch: the pool block of each column
    pos_ref,  # (S,) int32 scalar-prefetch: the slot's position, under 0: none
    qi_ref,  # (1, HI, DI) the slot's index queries
    wi_ref,  # (1, HI, 1) float32 head weights
    ikt_hbm,  # (NB, DI, BS) the pool's index keys, transposed: left in HBM
    out_ref,  # (1, KR, BS) int32: the pool rows of the selected keys
    read_ref,  # (1, 1, 128) int32: the pool blocks this slot's copies brought in
    kbuf,  # (2, G, DI, BS)
    sem,  # DMA (2,)
    keys_scr,  # (R, BS) int32: the slot's scores as ordered ints, a block a row
    *, topk, score_dtype,
):
    s_i = pl.program_id(0)
    G, BS = kbuf.shape[1], kbuf.shape[3]
    R = keys_scr.shape[0]
    pos = pos_ref[s_i]
    n_live = jnp.minimum(pos // BS + 1, table_ref.shape[1])  # blocks with a seen key
    n_g = (n_live + G - 1) // G
    cdt, prec = mxu_operands(qi_ref.dtype)
    q = qi_ref[0].astype(cdt)
    wi = wi_ref[0].astype(score_dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)

    def copies(w, buf, go):
        """Start (``go``) or await the copies of group ``w``: its live
        blocks, each by its table entry, into tile ``buf``.  Returns how
        many it started or awaited."""

        def one(b, n):
            cp = pltpu.make_async_copy(
                ikt_hbm.at[table_ref[s_i, b]], kbuf.at[buf, b - w * G], sem.at[buf]
            )
            cp.start() if go else cp.wait()
            return n + 1

        return jax.lax.fori_loop(
            w * G, jnp.minimum(w * G + G, n_live), one, jnp.int32(0)
        )

    @pl.when(n_g > 0)
    def _prime():
        copies(0, 0, True)

    def score(w, read):
        cur = w % 2

        @pl.when(w + 1 < n_g)
        def _ahead():
            copies(w + 1, 1 - cur, True)

        read = read + copies(w, cur, False)
        for g in range(G):
            # (the MXU accumulates in float32; the control rounds after)
            s = jax.lax.dot_general(
                q, kbuf[cur, g].astype(cdt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ).astype(score_dtype)  # (HI, BS)
            acc = jnp.sum(wi * jnp.maximum(s, 0), axis=0, keepdims=True)
            key = _score_key(acc.astype(jnp.float32))
            # a block of the group past the live ones was not copied: what
            # the tile held before is hidden by the same test
            at = (w * G + g) * BS + lane
            keys_scr[pl.ds(w * G + g, 1), :] = jnp.where(at <= pos, key, INT_MIN)
        return read

    read = jax.lax.fori_loop(0, n_g, score, jnp.int32(0))
    read_ref[0] = jnp.zeros(read_ref.shape[1:], jnp.int32) + read

    def counts(*tests):
        """(1, 1) int32 each: the slot's keys that pass each of ``tests``,
        in one sweep."""

        def one(w, accs):
            key = keys_scr[pl.ds(pl.multiple_of(w * G, G), G), :]
            return tuple(a + t(key).astype(jnp.int32) for a, t in zip(accs, tests))

        zero = jnp.zeros((G, BS), jnp.int32)
        accs = jax.lax.fori_loop(0, n_g, one, (zero,) * len(tests))
        return [jnp.sum(a, keepdims=True) for a in accs]

    thr, ties = _kth_largest_key(counts, topk, (1, 1), bits=2)

    row = jax.lax.broadcasted_iota(jnp.int32, (R, BS), 0)
    at = row * BS + jax.lax.broadcasted_iota(jnp.int32, (R, BS), 1)
    seen = at <= pos  # rows past the live groups hold another slot's keys
    key = keys_scr[...]
    eq = (key == thr) & seen
    sel = ((key > thr) & seen) | (eq & (_flat_rank(eq) < ties))
    # a selected key moves forward by the places not selected before it, one
    # bit of that distance a pass from the lowest (the order is kept, so no
    # two meet); it carries the distance, and where it lands says the rest
    code = jnp.where(sel, 2 * (at - _flat_rank(sel)) + 1, 0)
    for k in range((R * BS - 1).bit_length()):
        moving = (code & (2 << k)) != 0
        code = jnp.where(moving, 0, code) | _shift_flat(
            jnp.where(moving, code, 0), 1 << k
        )
    KR = out_ref.shape[1]
    took = code[:KR] != 0
    p = at[:KR] + (code[:KR] >> 1)  # the position that landed at each place
    blk = p // BS

    def entry(b, base):
        return jnp.where(blk == b, table_ref[s_i, b], base)

    # the pool row of a position, through the table: only a live block holds one
    base = jax.lax.fori_loop(0, n_live, entry, jnp.zeros((KR, BS), jnp.int32))
    out_ref[0] = jnp.where(took, base * BS + p % BS, 0)


def select_decode_topk(
    qi: jax.Array, wi: jax.Array, ikt_pages: jax.Array, table: jax.Array,
    pos: jax.Array, *, topk: int, score_dtype=jnp.float32,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(rows (S, topk) int32, blocks_read (S,) int32)``: the pool rows of
    the keys each slot's decode query selects, and the pool blocks of index
    keys the kernel brought in for the slot (its own count of the copies it
    awaited: the live blocks).  The rows come in the order of their
    positions — the ``min(topk, pos + 1)`` seen keys (``s <= pos``, inside the table's window) with the largest
    index scores, ties to the lower positions; the rest of a row is 0 (a row
    of the pool: in bounds).  The pool row of position ``s`` is ``table[s //
    BS] * BS + s % BS``, what ``sparse_decode_attention`` takes.  ``qi (S,
    HI, DI)``, ``wi (S, HI)``;
    ``ikt_pages (NB, DI, BS)`` the pool's index keys as they are carried,
    every layer's blocks in one row of blocks and a block transposed (its
    ``BS`` tokens along the lanes: a block of 64-wide keys by tokens is no
    whole tile, and Mosaic copies whole tiles); ``table (S, WB)`` the pool
    block of each of the window's columns.  A slot whose ``pos`` is under 0
    (one that is not active) reads nothing and selects nothing.

    One grid step a slot: its live blocks are copied by table entry into
    VMEM, ``_GROUP`` at a time with the next group in flight, and scored
    there; the threshold is the prompt kernel's search over the scores' bit
    patterns (``_kth_largest_key``); the selected positions are packed to the front in VMEM by
    shifts of powers of two and turned into pool rows through the table.
    Nothing but those rows' numbers goes to HBM, and blocks past ``pos`` are
    not read."""
    S, HI, DI = qi.shape
    BS = ikt_pages.shape[2]
    WB = table.shape[1]
    G = _GROUP
    R = -(-WB // G) * G
    KR = -(-int(topk) // BS)
    if KR > R:
        raise ValueError(f"topk {topk} is more than the window's {WB * BS} keys")
    pos = jnp.minimum(jnp.asarray(pos, jnp.int32), WB * BS - 1)
    kernel = functools.partial(
        _decode_select_kernel, topk=int(topk), score_dtype=score_dtype
    )

    def slot_block(s, t, p):
        return (s, 0, 0)

    out, read = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, HI, DI), slot_block),
                pl.BlockSpec((1, HI, 1), slot_block),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, KR, BS), slot_block),
                pl.BlockSpec((1, 1, 128), slot_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, G, DI, BS), ikt_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((R, BS), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, KR, BS), jnp.int32),
            jax.ShapeDtypeStruct((S, 1, 128), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=_interpret(interpret),
    )(
        jnp.asarray(table, jnp.int32), pos, qi,
        wi.astype(jnp.float32)[:, :, None], ikt_pages,
    )
    return out.reshape(S, KR * BS)[:, : int(topk)], read[:, 0, 0]


def select_decode_topk_reference(qi, wi, ikt_pages, table, pos, *, topk,
                                 score_dtype=jnp.float32):
    """The XLA way: the whole window's index keys gathered, scored, masked
    past ``pos`` and sorted (``lax.top_k``): ``(S, topk)`` pool rows, best
    first, the lower position first among equals; and the blocks gathered a
    slot, the window's ``WB`` whatever ``pos`` says."""
    S = qi.shape[0]
    BS = ikt_pages.shape[2]
    W = table.shape[1] * BS
    keys = jnp.swapaxes(ikt_pages[table], 2, 3).reshape(S, W, -1)
    scores = jax.vmap(
        lambda a, b, c: index_scores(a, b, c, score_dtype)
    )(qi[:, None], wi[:, None], keys)[:, 0]
    scores = jnp.where(jnp.arange(W)[None, :] <= pos[:, None], scores, -jnp.inf)
    idx = jax.lax.top_k(scores, int(topk))[1]
    rows = jnp.take_along_axis(table, idx // BS, axis=1) * BS + idx % BS
    return rows, jnp.full((S,), table.shape[1], jnp.int32)


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

    def counts(*tests):
        """(bq, 1) int32 each: keys of each row that pass each of ``tests``."""

        def one(kt, accs):
            at = pl.ds(pl.multiple_of(kt * bk, bk), bk)
            key = keys_scr[:, at]
            return tuple(a + t(key).astype(jnp.int32) for a, t in zip(accs, tests))

        zero = jnp.zeros((bq, bk), jnp.int32)
        accs = jax.lax.fori_loop(0, hi, one, (zero,) * len(tests))
        return [jnp.sum(a, axis=1, keepdims=True) for a in accs]

    thr, ties = _kth_largest_key(counts, topk, (bq, 1), bits=1)
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
    q_of, k_of, kind,  # the live tiles: ``flash_attention._tile_steps``
    q_ref,  # (1, G, bq, D): the query heads of one kv head
    k_ref,  # (1, bk, D)
    v_ref,
    mask_ref,  # (bq, bk) int8
    o_ref, m_scr, l_scr, acc_scr,
    *, scale,
):
    what = kind[pl.program_id(1)]
    G, bq, D = q_ref.shape[1:]
    bk = k_ref.shape[1]

    @pl.when((what & _FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cdt, prec = mxu_operands(q_ref.dtype)
    # every step runs its tile (a strip's first key tile holds position 0)
    sel = mask_ref[...] != 0  # once a tile, for every head of the group

    def head(g):
        rows = pl.ds(pl.multiple_of(g * bq, bq), bq)
        q = (q_ref[0, g] * scale).astype(cdt)  # (bq, D)
        k = k_ref[0].astype(cdt)
        v = v_ref[0].astype(cdt)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bq, bk)
        # the one select: the running max starts at M_INIT, above NEG_INF, so
        # a score left out has the exponent 0 whatever its row has seen
        s = jnp.where(sel, s, NEG_INF)
        m_prev = m_scr[rows, :]  # (bq, w): a row's lanes all equal, as l_scr's
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_cur, bk))
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[rows, :] = alpha * l_scr[rows, :] + p.sum(axis=-1, keepdims=True)
        m_scr[rows, :] = m_cur
        acc_scr[rows, :] = acc_scr[rows, :] * _lanes(alpha, D) + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    # the group's heads in strips: within a strip one head's products run
    # under another's softmax, and the loop keeps the body (and its compile)
    # at a strip's size
    per = math.gcd(G, _HEADS_A_STRIP)

    def strip(i, carry):
        for j in range(per):
            head(i * per + j)
        return carry

    jax.lax.fori_loop(0, G // per, strip, 0)

    @pl.when((what & _LAST) != 0)
    def _emit():
        l = _lanes(l_scr[...], D)
        safe_l = jnp.where(l == 0.0, 1.0, l)  # nothing selected -> zeros
        o_ref[0] = (acc_scr[...] / safe_l).reshape(G, bq, D).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("q_offset", "block_q", "block_k", "interpret")
)
def masked_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, *,
    q_offset: int = 0, block_q: int = 512, block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``q (H, Lq, D)`` at positions ``q_offset ..`` over ``k``, ``v (KV,
    Lk, D)`` at ``0 .. Lk - 1`` where ``mask (Lq, Lk) int8`` is set — a
    mask that selects nothing after a query's own position (the causal
    bound: a tile wholly after a strip's last query is no grid step).  All
    heads share the mask; a row with nothing selected gives zeros.  Returns
    ``(H, Lq, D)``."""
    H, Lq, D = q.shape
    KV, Lk = k.shape[:2]
    G = H // KV
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    if Lq % bq or Lk % bk:
        raise ValueError(f"({Lq}, {Lk}) is not whole tiles of ({bq}, {bk})")
    q_offset = int(q_offset)

    steps = _tile_steps(Lq, Lk, bq, bk, True, None, q_offset)
    stepped, live, _ = tile_plan(Lq, Lk, bq, bk, True, None, q_offset)
    # traced once a shape, beside the tiled kernel's
    TILE_PLANS[f"masked:S{Lq}:Sk{Lk}:{bq}x{bk}:q{q_offset}"] = {
        "stepped": stepped, "live": live,
    }

    lanes = 128 if bk % 128 == 0 else bk  # the statistics' width
    out = pl.pallas_call(
        functools.partial(_masked_flash_kernel, scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(KV, stepped),
            in_specs=[
                pl.BlockSpec((1, G, bq, D), lambda h, t, qo, ko, kd: (h, 0, qo[t], 0)),
                pl.BlockSpec((1, bk, D), lambda h, t, qo, ko, kd: (h, ko[t], 0)),
                pl.BlockSpec((1, bk, D), lambda h, t, qo, ko, kd: (h, ko[t], 0)),
                pl.BlockSpec((bq, bk), lambda h, t, qo, ko, kd: (qo[t], ko[t])),
            ],
            out_specs=pl.BlockSpec(
                (1, G, bq, D), lambda h, t, qo, ko, kd: (h, 0, qo[t], 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((G * bq, lanes), jnp.float32),  # running max, every lane
                pltpu.VMEM((G * bq, lanes), jnp.float32),  # running denom, every lane
                pltpu.VMEM((G * bq, D), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KV, G, Lq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_interpret(interpret),
    )(*(jnp.asarray(a) for a in steps), q.reshape(KV, G, Lq, D), k, v, mask)
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
