"""Plain reference for the dense RoPE / GQA / SwiGLU / RMSNorm decoder
(Mistral-7B-v0.3's block; Llama-2/3's too): the full forward pass in
straightforward ``jax.numpy``, float32, highest matmul precision, one layer
after the other, no cache, no batching tricks, no kernels.

Follows the published description (arXiv:2310.06825 section 2 with the
v0.3 config: no sliding window; rotary embedding in the "rotate-half"
layout of the HuggingFace implementation, which is the layout the served
weights are in).  Departure: none in the mathematics; weights are random.

``params`` is the served tree (``tok_emb``, ``layers/{wq,wk,wv,wo,w_gate,
w_up,w_down,ln_att,ln_mlp}`` stacked on a leading layer axis, ``ln_f``,
``head``) in the dtype it is served in; each tensor is raised to float32
where it is used, so the reference sees exactly the served weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: (L, H, D); rotate-half rotary embedding at positions 0..L-1."""
    L, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, lp, n_heads, n_kv_heads, theta, eps):
    """One block on one sequence ``x (L, E)``; ``lp`` float32."""
    L = x.shape[0]
    h = _rmsnorm(x, lp["ln_att"], eps)
    q = jnp.einsum("le,ehd->lhd", h, lp["wq"])
    k = jnp.einsum("le,ehd->lhd", h, lp["wk"])
    v = jnp.einsum("le,ehd->lhd", h, lp["wv"])
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("qhd,hde->qe", o, lp["wo"])
    h = _rmsnorm(x, lp["ln_mlp"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def layers_of(stacked: dict):
    """One dict of weights per layer from the tree stacked on a layer axis."""
    for i in range(stacked["wq"].shape[0]):
        yield {k: v[i] for k, v in stacked.items()}


def logits(params, tokens, *, n_heads, n_kv_heads, rope_theta, norm_eps,
           layers=None):
    """Next-token logits ``(L, vocab)`` at every position of one sequence.
    ``layers`` may hand the layers' weights one by one (a model spread over
    several devices); each layer runs where its weights are."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    step = jax.jit(
        lambda x, lp: layer(x, {k: f32(v) for k, v in lp.items()},
                            n_heads, n_kv_heads, rope_theta, norm_eps)
    )
    home = next(iter(params["head"].devices()))
    with jax.default_matmul_precision("highest"):
        x = f32(params["tok_emb"][jnp.asarray(tokens, jnp.int32)])
        for lp in layers if layers is not None else layers_of(params["layers"]):
            x = step(jax.device_put(x, next(iter(lp["wq"].devices()))), lp)
        x = _rmsnorm(jax.device_put(x, home), f32(params["ln_f"]), norm_eps)
        return x @ f32(params["head"])
