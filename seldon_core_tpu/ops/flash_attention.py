"""Flash attention as a Pallas TPU kernel.

The dense causal attention in the model zoo materializes the full
``(B, H, S, S)`` score matrix in HBM — at seq 8k and bf16 that is 128MB per
head-batch and all of it HBM traffic.  This kernel computes attention in
``(block_q, block_k)`` tiles resident in VMEM with the online-softmax
recurrence, so scores never touch HBM and the MXU is fed back-to-back
tiles: memory drops from O(S²) to O(S·D) and the arithmetic intensity
matches the hardware (guide: /opt/skills/guides/pallas_guide.md; the
technique is the standard flash-attention tiling).

Layout: ``(B, H, S, D)``.  The grid is ``(B, H, Sq/bq, Sk/bk)`` — TPU
iterates the last axis fastest, so each query tile accumulates over its
key tiles in VMEM scratch and writes its output once on the final key
step.  Causal masking is per-tile (fully-masked tiles skip the matmul
entirely): tiles wholly after a query tile, and with a sliding ``window``
(key ``j`` visible to query ``i`` iff ``i - window < j <= i``) tiles wholly
before it too.  ``k``/``v`` may carry fewer heads than ``q`` (grouped-query
attention): query head ``h`` reads key head ``h // (H // Hk)`` through the
block index, so the keys are never repeated in HBM.

The kernel is compiled by Mosaic on every backend but the CPU, where it
runs in Pallas interpret mode so the equivalence tests pin it to the dense
reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seldon_core_tpu.ops.paged_attention import mxu_operands

NEG_INF = -1e30  # large-but-finite: -inf * 0 = nan would poison the rescale


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, block_q, block_k,
    n_k, causal, scale, window=None, score_dtype=None
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # tiles where every key position is after every query position are
    # fully masked: skip their FLOPs entirely; under a window so are tiles
    # whose last key lies at or before the first query's ``i - window``
    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    if window is not None:
        live = live & (ki * block_k + block_k - 1 > qi * block_q - window)

    cdt, prec = mxu_operands(q_ref.dtype)

    @pl.when(live)
    def _tile():
        q = (q_ref[0, 0] * scale).astype(cdt)  # (bq, D)
        k = k_ref[0, 0].astype(cdt)  # (bk, D)
        v = v_ref[0, 0].astype(cdt)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bq, bk)
        if score_dtype is not None:  # a negative control: the scores rounded
            s = s.astype(score_dtype).astype(jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = rows >= cols
            if window is not None:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
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
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0, 0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)


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
) -> jax.Array:
    """``(B, H, S, D)`` attention; blocks clamp to S and must divide it.
    ``window`` (static, causal only) keeps keys ``j > i - window``;
    ``k`` of shape ``(B, Hk, Sk, D)`` with ``Hk`` dividing ``H`` is read
    grouped, never repeated.  ``v (B, Hk, Sk, Dv)`` may have a width of its
    own (latent attention: 192-wide keys under 128-wide values), and the
    output is ``(B, H, S, Dv)``; ``scale`` (static) is the softmax scale,
    ``D ** -0.5`` unless given.  ``score_dtype`` (static; a negative control,
    never served) rounds a tile's scores to that type as they leave the MXU;
    unset, they stay float32."""
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
    n_q = S // block_q
    n_k = Sk // block_k
    scale = 1.0 / math.sqrt(D) if scale is None else float(scale)

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        causal=causal,
        scale=scale,
        score_dtype=score_dtype,
        window=None if window is None else int(window),
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max (col 0)
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom (col 0)
            pltpu.VMEM((block_q, Dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)


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
