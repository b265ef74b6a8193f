"""Plain reference for the Cohere2-MoE decoder (``model_type: cohere2_moe``,
Command A+): the full forward pass in straightforward ``jax.numpy``,
float32, highest matmul precision, one layer after the other, no cache, no
batching, no kernels, no grouped products.

Follows the published config (huggingface.co/CohereLabs/
command-a-plus-05-2026 config.json) as ISSUE 28 wrote the layer down.  With
``h = LayerNorm(x)`` (mean subtracted, variance + eps, a weight, no bias)
and ``use_parallel_block``::

    x' = x + Attn_l(h) + MoE(h)
    sliding layers (l % pattern != pattern - 1): q, k rotated by RoPE in
        interleaved pairs (rope_gptj: dims 2i, 2i+1), every dim; key j is
        visible to query i iff  i - window < j <= i
    full layers: no position encoding at all; j <= i
    o = softmax(q k^T / sqrt(head_dim)) v, 16 query heads a key head; Wo
    MoE: s = sigmoid(h Wr) over all experts; T = the experts_per_tok largest
        w_e = s_e / sum_{e' in T} s_e'
        routed = sum_{e in T and held} w_e Wd_e(silu(Wg_e h) * (Wu_e h))
        shared = (1 / n_shared) sum_j Wd'_j(silu(Wg'_j h) * (Wu'_j h))
    logits = logit_scale * LayerNorm_f(x_L) E^T     (E the tied embedding)

Assumed, as the configuration file lists: ``intermediate_size`` is one
expert's width; "average" is the mean over the shared experts, added
unweighted; full layers carry no position encoding.  Departure: the experts
this share does not hold add nothing (``held = (first, count)``: the same
cut the program is given); weights are random.

``params`` is the served tree (``tok_emb``, ``layers/{ln,wq,wk,wv,wo,
w_router,we_*,ws_*}`` stacked on a leading layer axis, ``ln_f``) in the
dtype it is served in; each tensor is raised to float32 where it is used —
an expert's three matrices one expert at a time, the attention's scores one
block of queries at a time, so that a 5,000-token sequence at the published
widths fits beside the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # query rows scored at once


def f32(a):
    return jnp.asarray(a, jnp.float32)


def layernorm(x, w, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w


def rope_pairs(x, theta):
    """x: (L, H, D) at positions 0..L-1; dims (2i, 2i+1) rotate together."""
    L, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


@jax.jit
def _qkv(h, wq, wk, wv):
    return (jnp.einsum("le,ehd->lhd", h, f32(wq)),
            jnp.einsum("le,ehd->lhd", h, f32(wk)),
            jnp.einsum("le,ehd->lhd", h, f32(wv)))


@jax.jit
def _attend_rows(q, k, v, first_row, window):
    """Rows ``first_row ..`` of the attention: q (B, H, D) against every key
    (L, KV, D); ``window`` <= 0 means none."""
    rep = q.shape[1] // k.shape[1]
    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kr) / math.sqrt(q.shape[-1])
    i = first_row + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = (j <= i) & ((window <= 0) | (j > i - window))
    s = jnp.where(seen[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vr)


@jax.jit
def _project_out(o, wo):
    return jnp.einsum("qhd,hde->qe", o, f32(wo))


@jax.jit
def _expert(h, wg, wu, wd):
    return (jax.nn.silu(h @ f32(wg)) * (h @ f32(wu))) @ f32(wd)


def route(h, w_router, top_k, dtype=jnp.float32):
    """(L, n_experts) weights: s_e / sum over the chosen, 0 elsewhere.
    ``dtype`` other than float32 is the control's, never the reference's."""
    s = jax.nn.sigmoid(h.astype(dtype) @ jnp.asarray(w_router, dtype))
    vals, idx = jax.lax.top_k(s, top_k)
    w = (vals / jnp.sum(vals, axis=-1, keepdims=True)).astype(jnp.float32)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros(s.shape, jnp.float32).at[rows, idx].set(w)


def moe(h, lp, *, top_k, held, shared_mean=True, norm_over="chosen"):
    """routed (the held experts' part) + shared.  ``shared_mean`` and
    ``norm_over`` exist for the tests' negative controls."""
    first, count = held
    cw = route(h, lp["w_router"], top_k)
    if norm_over == "held":  # WRONG on purpose: normalise over held picks only
        part = cw[:, first:first + count]
        cw = cw.at[:, first:first + count].set(
            part / jnp.maximum(part.sum(-1, keepdims=True), 1e-30)
        )
    out = jnp.zeros_like(h)
    for x in range(count):
        y = _expert(h, lp["we_gate"][x], lp["we_up"][x], lp["we_down"][x])
        out = out + cw[:, first + x, None] * y
    n_shared = lp["ws_gate"].shape[0]
    shared = jnp.zeros_like(h)
    for j in range(n_shared):
        shared = shared + _expert(
            h, lp["ws_gate"][j], lp["ws_up"][j], lp["ws_down"][j]
        )
    return out + (shared / n_shared if shared_mean else shared)


def layer(x, lp, *, full, theta, eps, window, top_k, held, rope_on_full=False,
          **wrong):
    """One block on one sequence ``x (L, E)`` float32; ``lp`` as served."""
    L = x.shape[0]
    h = layernorm(x, f32(lp["ln"]), eps)
    q, k, v = _qkv(h, lp["wq"], lp["wk"], lp["wv"])
    if not full or rope_on_full:
        q, k = rope_pairs(q, theta), rope_pairs(k, theta)
    w = 0 if full or window is None else int(window)
    o = jnp.concatenate([
        _attend_rows(q[a:a + Q_BLOCK], k, v, a, w)
        for a in range(0, L, Q_BLOCK)
    ])
    return x + _project_out(o, lp["wo"]) + moe(h, lp, top_k=top_k, held=held, **wrong)


def layers_of(stacked: dict):
    """One dict of weights per layer from the tree stacked on a layer axis."""
    for i in range(stacked["wq"].shape[0]):
        yield {k: v[i] for k, v in stacked.items()}


def logits(params, tokens, *, pattern, theta, eps, window, top_k, held,
           logit_scale=1.0, layers=None, rows=None, **wrong):
    """Next-token logits ``(L, vocab)`` at every position of one sequence
    (``rows``: only those positions' logits).  ``layers`` may hand the
    layers' weights one by one; each layer runs where its weights are."""
    home = next(iter(params["tok_emb"].devices()))
    with jax.default_matmul_precision("highest"):
        x = f32(params["tok_emb"][jnp.asarray(tokens, jnp.int32)])
        each = layers if layers is not None else layers_of(params["layers"])
        for li, lp in enumerate(each):
            x = jax.device_put(x, next(iter(lp["wq"].devices())))
            x = layer(
                x, lp, full=li % pattern == pattern - 1, theta=theta, eps=eps,
                window=window, top_k=top_k, held=held, **wrong,
            )
        x = jax.device_put(x, home)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = layernorm(x, f32(params["ln_f"]), eps)
        return logit_scale * (x @ f32(params["tok_emb"]).T)
