"""What every decoder family shares: the norms, the rotary embeddings, the
float32 residual add, a prompt's plain causal attention, the RMSNorm head
over a ``(vocab, hidden)`` weight and on-device sampling.  A family module (``models/<family>.py``) builds from
here, from ``models/paged.py`` (the frame of a paged cache) and from
``models/moe.py`` (the routed expert layer); it imports no other family.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, eps):
    """Cohere's LayerNorm: mean subtracted, no bias; statistics in float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x, positions, theta):
    """Rotate-half rotary embedding.  x: (..., L, H, D); positions: (..., L)
    int32."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def rope_pairs(x, positions, theta, freqs=None):
    """Interleaved-pair rotary embedding (``rope_gptj``): dims (2i, 2i+1)
    rotate together, all ``head_dim`` of them.  x: (..., L, H, D);
    positions: (..., L).  ``freqs (D / 2,)`` replaces ``theta``'s own
    (``kimi_k2``'s YaRN)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xp[..., 0], xp[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def add(x, y):
    """The residual stream's add: in float32, back in the stream's dtype."""
    return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)


def flash_prompt(q, k, v, **kw):
    """A prompt's own causal attention through the tiled Pallas kernel
    (``ops/flash_attention.py``): q (L, H, D); k (L, KV, D), v (L, KV, Dv)
    at positions 0..L-1, keys read grouped -> (L, H, Dv).  ``kw``: the
    kernel's ``window``, ``scale``, ``score_dtype`` or ``length`` (the
    prompt's real length in its rung: the query tiles wholly past it are not
    computed and give zeros)."""
    from seldon_core_tpu.ops.flash_attention import flash_attention

    blk = min(512, q.shape[0])
    out = flash_attention(
        q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
        v.transpose(1, 0, 2)[None], causal=True, block_q=blk, block_k=blk, **kw,
    )
    return out[0].transpose(1, 0, 2)


def attend_prompt(q, k, v, seq_impl: str, length=None):
    """A whole prompt's causal grouped-query attention under ``attn.prompt``:
    ``q (T, H, D)`` over ``k``, ``v (T, KV, D)`` at positions ``0..T-1``,
    through the tiled kernel (``seq_impl="flash"``; with the prompt's real
    ``length`` it leaves out the rung's padded query tiles) or in plain XLA,
    scores and softmax float32.  -> (T, H, D).  For a family whose keys need
    no window and no selection (``jamba``, ``zaya``)."""
    T, H, D = q.shape
    with jax.named_scope("attn.prompt"):
        if seq_impl == "flash":
            return flash_prompt(q, k, v, length=length)
        kv = k.shape[1]
        qg = q.reshape(T, kv, H // kv, D)
        s = jnp.einsum(
            "tkgd,ukd->kgtu", qg, k, preferred_element_type=jnp.float32
        ) / math.sqrt(D)
        seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(jnp.float32).min), axis=-1)
        return jnp.einsum("kgtu,ukd->tkgd", p.astype(v.dtype), v).reshape(T, H, D)


def rms_head(h, norm_w, vocab_w, eps):
    """The final RMSNorm and the vocabulary projection over ``vocab_w
    (vocab, hidden)``, read transposed: an untied head or the embedding
    itself.  -> ``(logits, hidden)``."""
    with jax.named_scope("head"):
        h = rmsnorm(h, norm_w, eps)
        return jnp.einsum("...e,ve->...v", h, vocab_w), h


def sample_tokens(
    logits: jax.Array, temperature: jax.Array, key: jax.Array, top_k: int = 0
) -> jax.Array:
    """Per-row sampling, fused into the compiled device step: ``temperature
    (S,)`` <= 0 means greedy; ``top_k`` (STATIC — one compiled program per
    value) restricts sampling to the k highest logits.

    This runs inside the jitted prefill/decode programs so only ``(S,)``
    token ids ever cross the host boundary — never ``(S, vocab)`` logits.
    ``top_k=1`` reduces to greedy (a pinned-equal test holds it there).
    """
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    f32 = logits.astype(jnp.float32)
    if top_k and int(top_k) > 0:
        k = min(int(top_k), logits.shape[-1])
        vals, idx = lax.top_k(f32, k)  # (S, k) descending
        local = jax.random.categorical(key, vals / temp, axis=-1)  # (S,)
        sampled = jnp.take_along_axis(idx, local[:, None], axis=-1)[:, 0]
    else:
        sampled = jax.random.categorical(key, f32 / temp, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
