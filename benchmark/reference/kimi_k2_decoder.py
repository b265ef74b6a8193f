"""Plain reference for the Kimi-K2 decoder (``model_type: kimi_k2``,
Kimi-K2.6, the language model; the layer equations are DeepSeek-V3's, whose
modelling code ``kimi_k2`` inherits): the full forward pass in
straightforward ``jax.numpy``, float32, highest matmul precision, one layer
after the other, no cache, no batching, no kernels, no grouped products —
and in the EXPANDED form only: keys and values by head at every position, so
that the served decode step, which never makes them (it attends the latents
themselves, the query carried into their space), is held to an independent
formulation.

Follows the published config (huggingface.co/moonshotai/Kimi-K2.6
config.json) as ISSUE 43 wrote the layer down, ``x (T, 7168)``::

    h    = RMSNorm(x; g1, eps 1e-5)
    cq   = RMSNorm(h Wqa; gq)                                    (T, 1536)
    [qn | qr] = cq Wqb                                           (T, 64, 128 | 64)      qr = RoPE_y(qr)
    [ckv | kr] = h Wkva                                          (T, 512 | 64)          one rotary key a token
    c    = RMSNorm(ckv; gkv)                                     kr = RoPE_y(kr)
    sigma = 192^-1/2 * m^2,   m = 0.1 * mscale_all_dim * ln(factor) + 1 = 1.41589   (sigma 0.14468)
    [kn | v] = c Wkvb                                            (T, 64, 128 | 128)
    s[t,u,a] = sigma * (qn[t,a].kn[u,a] + qr[t,a].kr[u])         u <= t
    o[t,a]   = sum_u softmax_u(s[t,u,a]) v[u,a]                  (T, 64, 128)
    x    = x + o Wo                                              (8192 -> 7168), no bias anywhere

    RoPE_y: 32 adjacent pairs (2i, 2i+1), theta 50,000, YaRN: f_i = theta^(-2i/64);
       low = floor(64 ln(4096 / (32 * 2 pi)) / (2 ln theta)) = 8,  high = ceil(64 ln(4096 / (1 * 2 pi)) / (2 ln theta)) = 20
       r_i = clip((i - low) / (high - low), 0, 1);   f'_i = (1 - r_i) f_i + r_i f_i / 64
       cos and sin carry mscale / mscale_all_dim's ratio of attention factors = 1.0

    h2   = RMSNorm(x; g2)
    layer 0:        x = x + Wd(silu(Wg h2) * Wu h2)              18,432 wide, no router
    layers 1..:     s = sigmoid(h2 Wr) over all 384
       E_t = top-8 of (s + b)          b: the learned per-expert bias: chooses, never weighs
       w_e = 2.827 * s_e / sum_{e' in E_t} s_e'
       x = x + sum_{e in E_t, e held} w_e * Wd_e(silu(Wg_e h2) * Wu_e h2) + Wsd(silu(Wsg h2) * Wsu h2)
    logits = RMSNorm(x_L; gf) W_head                                                   untied

Assumed, as the configuration file lists: (a) adjacent rotary pairs; (b)
RMSNorm on both latents; (c) ``b`` drawn from the seed, small against the
scores' spread; (d) ``ep_size``, ``seq_aux``, ``moe_layer_freq`` say nothing
of a layer.  Departure: the experts this share does not hold add nothing
(``held = (first, count)``); weights are random.

A layer's weights are the served tree's (``dense_layers/*`` for the leading
layers, ``layers/*`` for the expert layers, each stacked on a leading layer
axis; ``Wkvb``'s two halves are the leaves ``wuk`` and ``wuv``), in the
dtype they are served in; each tensor is raised to float32 where it is used
— an expert's matrices one expert at a time, the attention one block of
queries at a time, so that 13,000 tokens at the published widths fit beside
the weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256  # query rows attended at once
MLP_BLOCK = 2048  # rows of the dense layer's 18,432-wide product at once


def f32(a):
    return jnp.asarray(a, jnp.float32)


def rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn(rope: dict, dim: int, theta: float):
    """``(f' (dim / 2,) float32, sigma's m)`` from a config's
    ``rope_scaling`` group (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``mscale_all_dim``)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    factor = float(rope["factor"])
    if factor <= 1:
        return jnp.asarray(f, jnp.float32), 1.0

    def at(rotations):
        return dim * math.log(
            rope["original_max_position_embeddings"] / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(at(rope["beta_fast"])), 0)
    high = min(math.ceil(at(rope["beta_slow"])), dim - 1)
    r = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    m = 0.1 * float(rope["mscale_all_dim"]) * math.log(factor) + 1.0
    return jnp.asarray((1 - r) * f + r * f / factor, jnp.float32), m


def rope_pairs(x, freqs, first=0):
    """x: (L, H, D) at positions ``first ..``; dims (2i, 2i+1) rotate
    together."""
    L, _, d = x.shape
    ang = (first + jnp.arange(L, dtype=jnp.float32))[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    p = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def project(h, lp, freqs, eps):
    """``(qn (L, H, dn), qr (L, H, dr), c (L, C), kr (L, dr))`` of the
    normed hidden state ``h``."""
    dn = lp["wuk"].shape[-1]
    cl = lp["kv_norm"].shape[-1]
    cq = rmsnorm(h @ f32(lp["wqa"]), f32(lp["q_norm"]), eps)
    q = jnp.einsum("lq,qhd->lhd", cq, f32(lp["wqb"]))
    ckv = h @ f32(lp["wkva"])
    c = rmsnorm(ckv[:, :cl], f32(lp["kv_norm"]), eps)
    kr = rope_pairs(ckv[:, None, cl:], freqs)[:, 0]
    return q[..., :dn], rope_pairs(q[..., dn:], freqs), c, kr


@jax.jit
def expand(c, kr, wuk, wuv):
    """Keys ``(L, H, dn + dr)`` and values ``(L, H, dv)`` by head, the
    token's one rotary key under every head."""
    kn = jnp.einsum("lc,chd->lhd", c, f32(wuk))
    v = jnp.einsum("lc,chd->lhd", c, f32(wuv))
    krh = jnp.broadcast_to(kr[:, None, :], kn.shape[:2] + kr.shape[-1:])
    return jnp.concatenate([kn, krh], axis=-1), v


@functools.partial(jax.jit, static_argnames=("sigma",))
def attend_rows(q, k, v, first_row, sigma):
    """Rows ``first_row ..`` of the causal attention: ``q (B, H, D)`` over
    ``k (L, H, D)``, ``v (L, H, dv)``."""
    s = jnp.einsum("qhd,khd->hqk", q, k) * sigma
    t = first_row + jnp.arange(q.shape[0])[:, None]
    s = jnp.where((jnp.arange(k.shape[0])[None, :] <= t)[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


@jax.jit
def project_out(o, wo):
    return jnp.einsum("qhd,hde->qe", o, f32(wo))


@jax.jit
def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ f32(wg)) * (h @ f32(wu))) @ f32(wd)


def route(h, w_router, b_router, top_k, scale):
    """(L, n_experts) weights: ``scale * s_e / sum over the chosen``, 0
    elsewhere; the chosen are the top-k of score plus bias."""
    s = jax.nn.sigmoid(h @ f32(w_router))
    _, idx = jax.lax.top_k(s + f32(b_router), top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    w = scale * vals / jnp.sum(vals, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros(s.shape, jnp.float32).at[rows, idx].set(w)


def moe(h, lp, *, top_k, held, scale):
    """The held experts' part of the routed sum, plus the shared experts."""
    first, count = held
    cw = route(h, lp["w_router"], lp["b_router"], top_k, scale)
    out = jnp.zeros_like(h)
    for x in range(count):
        y = swiglu(h, lp["we_gate"][x], lp["we_up"][x], lp["we_down"][x])
        out = out + cw[:, first + x, None] * y
    for j in range(lp["ws_gate"].shape[0]):
        out = out + swiglu(h, lp["ws_gate"][j], lp["ws_up"][j], lp["ws_down"][j])
    return out


def attention(q, k, v, sigma):
    """The causal attention of a whole sequence, ``Q_BLOCK`` rows at once."""
    return jnp.concatenate([
        attend_rows(q[a:a + Q_BLOCK], k, v, a, sigma)
        for a in range(0, q.shape[0], Q_BLOCK)
    ])


def layer(x, lp, *, freqs, sigma, eps, top_k, held, scale):
    """One block on one sequence ``x (L, E)`` float32; ``lp`` as served: a
    leading dense layer where it has ``w_gate``, else an expert layer."""
    h = rmsnorm(x, f32(lp["ln1"]), eps)
    qn, qr, c, kr = project(h, lp, freqs, eps)
    k, v = expand(c, kr, lp["wuk"], lp["wuv"])
    o = attention(jnp.concatenate([qn, qr], axis=-1), k, v, sigma)
    x = x + project_out(o, lp["wo"])
    h2 = rmsnorm(x, f32(lp["ln2"]), eps)
    if "w_gate" in lp:
        return x + jnp.concatenate([
            swiglu(h2[a:a + MLP_BLOCK], lp["w_gate"], lp["w_up"], lp["w_down"])
            for a in range(0, h2.shape[0], MLP_BLOCK)
        ])
    return x + moe(h2, lp, top_k=top_k, held=held, scale=scale)


def layers_of(params: dict):
    """One dict of weights per layer, the leading dense layers first, from
    the served tree's two stacks."""
    for name in ("dense_layers", "layers"):
        stack = params[name]
        for i in range(stack["wqa"].shape[0]):
            yield {k: v[i] for k, v in stack.items()}


def logits(params, tokens, *, rope, theta, eps, top_k, held, scale,
           layers=None, rows=None):
    """Next-token logits ``(L, vocab)`` at every position of one sequence
    (``rows``: only those positions' logits).  ``rope`` is the config's
    ``rope_scaling`` group; ``layers`` may hand the layers' weights one by
    one."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["tok_emb"][jnp.asarray(tokens, jnp.int32)])
        for lp in (layers if layers is not None else layers_of(params)):
            dr = lp["wqb"].shape[-1] - lp["wuk"].shape[-1]
            freqs, m = yarn(rope, dr, float(theta))
            sigma = lp["wqb"].shape[-1] ** -0.5 * m * m
            x = layer(
                x, lp, freqs=freqs, sigma=float(sigma), eps=float(eps),
                top_k=top_k, held=held, scale=float(scale),
            )
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rmsnorm(x, f32(params["ln_f"]), eps)
        return x @ f32(params["head"]).T
