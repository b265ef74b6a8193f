"""Flash attention as a Pallas TPU kernel.

The dense causal attention in the model zoo materializes the full
``(B, H, S, S)`` score matrix in HBM — at seq 8k and bf16 that is 128MB per
head-batch and all of it HBM traffic.  This kernel computes attention in
``(block_q, block_k)`` tiles resident in VMEM with the online-softmax
recurrence, so scores never touch HBM and the MXU is fed back-to-back
tiles: memory drops from O(S²) to O(S·D) and the arithmetic intensity
matches the hardware (guide: /opt/skills/guides/pallas_guide.md; the
technique is the standard flash-attention tiling).

Layout: ``(B, H, S, D)``.  **A causal tile costs what it needs** (PR 44).
The grid is ``(B, H, steps)``, and a step is one LIVE tile: shapes, ``causal``
and ``window`` are static, so :func:`_tile_steps` lists the ``(qi, ki)`` pairs
that hold a visible key — query tile by query tile, key tiles ascending — and
the list reaches the ``BlockSpec``s' index maps by scalar prefetch.  A tile
wholly above the diagonal (and, under a sliding ``window`` — key ``j`` visible
to query ``i`` iff ``i - window < j <= i`` — one wholly before it) is no grid
step: its K and V are never fetched.  A query tile accumulates over its key
tiles in VMEM scratch, starts on the step flagged first and writes its output
on the step flagged last.  Of the live tiles only those that STRADDLE the
diagonal or the window's lower edge build a mask (two iotas, the compares, a
select); a tile wholly inside runs the products and the softmax alone.
:func:`tile_plan` counts the three kinds: a prompt of 12,288 at 512 x 512
steps 300 tiles of the square's 576 and masks 24 of them.

**A prompt's tiles end at its real length** (PR 58).  A prompt runs in a rung
of the ladder, its tail padding; a caller that knows the ``length`` hands it
over (a traced scalar), and :func:`_steps_at` makes the three prefetched
lists from the static ones and it: a step whose query tile starts at or past
``length`` is no longer live, and its key tile is the one the step before it
left in VMEM, so it multiplies nothing and copies nothing.  Such a tile still
starts and still writes its zeros, as a tile that sees no key does (a later
layer writes those rows' K/V into the slot's last block, and the decode read
multiplies ``0 x V`` over that block's unseen rows: they have to be finite).
The grid, the body and so the compiled kernel are what they are without a
``length``; 8,704 tokens in the 12,288 rung multiply 153 of its 300 tiles.

The running max and denominator are ``(block_q, 128)`` float32 with every lane
of a row the same, and stay two-dimensional from the scores' reduction to the
rescale: whole vregs in and out.  Cutting a one-lane column out of them and
broadcasting it back across lanes every tile (``m_scr[:, 0]``,
``m_cur[:, None]``) was half the kernel's time, more than the mask and the
dead steps together (PERF.md §6, PR 44).  The arithmetic and its order are the
parent's: at the same tile the results are bit for bit what they were, but for
a row that sees no key, which now gives zeros wherever it lies.

``k``/``v`` may carry fewer heads than ``q`` (grouped-query attention): query
head ``h`` reads key head ``h // (H // Hk)`` through the block index, so the
keys are never repeated in HBM.

The kernel is compiled by Mosaic on every backend but the CPU, where it
runs in Pallas interpret mode so the equivalence tests pin it to the dense
reference.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seldon_core_tpu.ops.paged_attention import mxu_operands

log = logging.getLogger(__name__)

NEG_INF = -1e30  # large-but-finite: -inf * 0 = nan would poison the rescale
# the running max starts ABOVE the mask's value: a masked score's exponent is
# then 0 whatever the row has seen, and a row that sees no key ends at zeros
M_INIT = NEG_INF / 2

# what a grid step is, as bits of its entry in the prefetched list
_FIRST, _LAST, _LIVE, _MASKED = 1, 2, 4, 8

# the tile plans of the calls traced in this process, by shape: what
# ``breakdown.generation.<unit>.programs.tile_plans`` shows
TILE_PLANS: dict[str, dict[str, int]] = {}
# of those calls, the ones handed a prompt's real length: the arguments
# :func:`tile_plan` counts their live tiles from at any length
FOLLOWS_LENGTH: set[tuple] = set()


@functools.lru_cache(maxsize=None)
def _tile_steps(S, Sk, block_q, block_k, causal, window, q_offset=0):
    """The grid's steps as three int32 arrays ``(q tile, key tile, kind)``:
    every tile that holds a visible key, query tile by query tile, key tiles
    ascending; a query tile that sees no key at all keeps one step that is
    not live, which writes its zeros.  Query ``i`` stands at the keys'
    position ``q_offset + i`` (a later chunk of a prompt over every key so
    far: ``ops/sparse_attention.py::masked_flash_attention``)."""
    q_of, k_of, kind = [], [], []
    for qi in range(S // block_q):
        r0 = q_offset + qi * block_q
        r1 = r0 + block_q - 1
        row = []
        for ki in range(Sk // block_k):
            c0, c1 = ki * block_k, ki * block_k + block_k - 1
            if causal and c0 > r1:
                break  # wholly above the diagonal, and so is every later one
            if window is not None and c1 <= r0 - window:
                continue  # wholly before the window of the tile's first query
            masked = causal and c1 > r0
            if window is not None:
                masked = masked or c0 <= r1 - window
            row.append((ki, _LIVE | (_MASKED if masked else 0)))
        row = row or [(0, 0)]
        for n, (ki, what) in enumerate(row):
            q_of.append(qi)
            k_of.append(ki)
            kind.append(
                what | (_FIRST if n == 0 else 0) | (_LAST if n == len(row) - 1 else 0)
            )
    return tuple(np.asarray(a, np.int32) for a in (q_of, k_of, kind))


def _past(q_of, block_q, length):
    """Which steps' query tiles start at or past ``length``."""
    return q_of * block_q >= length


def tile_plan(S, Sk, block_q, block_k, causal=True, window=None, q_offset=0,
              length=None):
    """``(stepped, live, masked)``: the grid steps a head takes, those that
    run the products, and those of them that build a mask — static in the
    shapes, and what the kernel's grid and bodies are made from.  With a
    prompt's real ``length`` the last two are what :func:`_steps_at` leaves
    of them: the steps of the query tiles that start before ``length``."""
    q_of, _, kind = _tile_steps(S, Sk, block_q, block_k, causal, window, q_offset)
    if length is not None:
        kind = np.where(_past(q_of, block_q, length), kind & (_FIRST | _LAST), kind)
    return (
        len(kind),
        int(np.count_nonzero(kind & _LIVE)),
        int(np.count_nonzero(kind & _MASKED)),
    )


def _steps_at(steps, block_q, length):
    """The prefetched lists for a prompt ``length`` tokens long in its rung
    (a traced int32 scalar; None: the static lists).  A step whose query tile
    starts at or past ``length`` keeps its ``_FIRST`` / ``_LAST`` bits alone,
    so it runs no product and its tile still writes zeros; such steps are the
    list's tail, and their key tile is the last live step's, so that the
    pipeline finds the block it holds and copies nothing."""
    q_of, k_of, kind = (jnp.asarray(a) for a in steps)
    if length is None:
        return q_of, k_of, kind
    dead = _past(q_of, block_q, length)
    held = k_of[jnp.maximum(jnp.sum(~dead) - 1, 0)]
    return (
        q_of,
        jnp.where(dead, held, k_of),
        jnp.where(dead, kind & (_FIRST | _LAST), kind),
    )


def admitted_tiles(S, length):
    """``(stepped, live)`` a head, summed over the calls traced in this
    process with ``S`` query rows and a ``length`` to follow, for a prompt of
    ``length`` tokens: what the engine adds up as it admits one."""
    plans = [
        tile_plan(*args, length=length) for args in FOLLOWS_LENGTH if args[0] == S
    ]
    return sum(p[0] for p in plans), sum(p[1] for p in plans)


def _lanes(x, n):
    """``x (rows, w)``, its lanes all equal, at width ``n``."""
    w = x.shape[1]
    if n == w:
        return x
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _flash_kernel(
    q_of, k_of, kind, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
    block_q, block_k, causal, scale, window=None, score_dtype=None
):
    t = pl.program_id(2)
    qi, ki, what = q_of[t], k_of[t], kind[t]

    @pl.when((what & _FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cdt, prec = mxu_operands(q_ref.dtype)

    def _tile(masked):
        q = (q_ref[0, 0] * scale).astype(cdt)  # (bq, D)
        k = k_ref[0, 0].astype(cdt)  # (bk, D)
        v = v_ref[0, 0].astype(cdt)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bq, bk)
        if score_dtype is not None:  # a negative control: the scores rounded
            s = s.astype(score_dtype).astype(jnp.float32)
        if masked:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = rows >= cols
            if window is not None:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, w): a row's lanes all equal, as l_scr's
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_cur, block_k))
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_cur
        acc_scr[...] = acc_scr[...] * _lanes(alpha, acc_scr.shape[1]) + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    # a tile wholly inside the diagonal (and the window) builds no mask
    if causal:
        pl.when((what & _MASKED) != 0)(lambda: _tile(True))
    pl.when((what & (_LIVE | _MASKED)) == _LIVE)(lambda: _tile(False))

    @pl.when((what & _LAST) != 0)
    def _emit():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "interpret", "window", "scale",
        "score_dtype",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    window: int | None = None,
    scale: float | None = None,
    score_dtype=None,
    length: jax.Array | None = None,
) -> jax.Array:
    """``(B, H, S, D)`` attention; blocks clamp to S and must divide it.
    ``window`` (static, causal only) keeps keys ``j > i - window``;
    ``k`` of shape ``(B, Hk, Sk, D)`` with ``Hk`` dividing ``H`` is read
    grouped, never repeated.  ``v (B, Hk, Sk, Dv)`` may have a width of its
    own (latent attention: 192-wide keys under 128-wide values), and the
    output is ``(B, H, S, Dv)``; ``scale`` (static) is the softmax scale,
    ``D ** -0.5`` unless given.  ``score_dtype`` (static; a negative control,
    never served) rounds a tile's scores to that type as they leave the MXU;
    unset, they stay float32.  A row that sees no key gives zeros.
    ``length`` (a traced int32 scalar) says that only the first ``length``
    query rows are a prompt's and the rest its rung's padding: a query tile
    that starts at or past it runs no product, fetches no key and gives
    zeros; every row before it, and the rest of the tile that straddles it,
    is what it is without."""
    B, H, S, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    if k.shape[3] != D or v.shape[:3] != k.shape[:3]:
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}: q and k share their "
            "width, k and v their heads and length"
        )
    if H % Hk:
        raise ValueError(f"{H} query heads do not group over {Hk} key heads")
    if window is not None and not causal:
        raise ValueError("a sliding window needs causal=True")
    g = H // Hk
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"seq lengths ({S}, {Sk}) must be divisible by blocks "
            f"({block_q}, {block_k})"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = 1.0 / math.sqrt(D) if scale is None else float(scale)
    window = None if window is None else int(window)

    steps = _tile_steps(S, Sk, block_q, block_k, causal, window)
    stepped, live, masked = tile_plan(S, Sk, block_q, block_k, causal, window)
    # traced once a shape: the line a warm-up's compile of a rung leaves
    TILE_PLANS[f"S{S}:Sk{Sk}:{block_q}x{block_k}:w{window}"] = {
        "stepped": stepped, "live": live, "masked": masked,
    }
    if length is not None:
        FOLLOWS_LENGTH.add((S, Sk, block_q, block_k, causal, window))
    log.info(
        "flash_attention S=%d Sk=%d H=%d D=%d Dv=%d tile %dx%d window=%s: "
        "tile plan stepped=%d live=%d masked=%d of %d",
        S, Sk, H, D, Dv, block_q, block_k, window, stepped, live, masked,
        (S // block_q) * (Sk // block_k),
    )

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        scale=scale,
        score_dtype=score_dtype,
        window=window,
    )
    lanes = 128 if block_k % 128 == 0 else block_k  # the statistics' width
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, stepped),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), lambda b, h, t, qo, ko, kd: (b, h, qo[t], 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, h, t, qo, ko, kd: (b, h // g, ko[t], 0)),
                pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, t, qo, ko, kd: (b, h // g, ko[t], 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, block_q, Dv), lambda b, h, t, qo, ko, kd: (b, h, qo[t], 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, lanes), jnp.float32),  # running max, every lane
                pltpu.VMEM((block_q, lanes), jnp.float32),  # running denom, every lane
                pltpu.VMEM((block_q, Dv), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        interpret=interpret,
    )(*_steps_at(steps, block_q, length), q, k, v)


def _fit_block(s: int, preferred: int = 128) -> int:
    """Largest divisor of ``s`` that is <= ``preferred`` — a length that is
    not a multiple of the preferred tile still runs (a 192-token bucket
    tiles at 96).  On the chip the divisor must also be a tile Mosaic
    takes (a multiple of 8, or the whole length); one that is not — a
    prime length, say — is a compile error there, not a fallback."""
    b = min(preferred, s)
    while s % b:
        b -= 1
    return b


def flash_causal_attention_blhd(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Adapter for the model zoo's ``(B, L, H, D)`` attention contract
    (``models/llama.py::_layer``): transpose, pick tile sizes that divide
    the actual sequence lengths, run the kernel, transpose back.  Callers
    choose flash via ``seq_impl``."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(
        qt, kt, vt, causal=True,
        block_q=_fit_block(qt.shape[2]),
        block_k=_fit_block(kt.shape[2]),
    )
    return out.transpose(0, 2, 1, 3)
