"""Plain reference for the Keye-VL-2.0 decoder (``model_type: KeyeVL2``,
Keye-VL-2.0-30B-A3B, the language model): the full forward pass in
straightforward ``jax.numpy``, float32, highest matmul precision, one layer
after the other, no cache, no batching, no kernels, no grouped products.

Follows the published config (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B
config.json) as ISSUE 38 wrote the layer down, ``x (T, hidden)``::

    h   = RMSNorm(x; g1, eps)
    q   = RoPE(RMSNorm_head(h Wq))   (T, H, D)     k = RoPE(RMSNorm_head(h Wk))  (T, KV, D)
    v   = h Wv                       (T, KV, D)    rotate-half over all D dims, theta
    qI  = RoPE(h WqI)                (T, HI, DI)   kI = RoPE(LayerNorm(h WkI))   (T, DI)  one index key a token
    wI  = h Ww                       (T, HI)
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])              for s <= t
    S_t = the min(topk, t + 1) keys s <= t with the largest I[t, s]   (ties: lower s first)
    o[t, a] = sum_{s in S_t} softmax_{s in S_t}(q[t, a] . k[s, a // G] / sqrt(D)) v[s, a // G]
    x   = x + o Wo
    h2  = RMSNorm(x; g2)
    p   = softmax(h2 Wr) over all experts;  E_t = top-k of p;  w_e = p_e / sum_{e' in E_t} p_e'
    x   = x + sum_{e in E_t, e held} w_e * Wd_e(silu(Wg_e h2) * Wu_e h2)    no shared expert
    logits = RMSNorm(x_L; gf) W_head                                         untied

On text the three components of M-RoPE (``mrope_section [16, 24, 24]``) are
equal, so it is one-dimensional RoPE at the token's position.  Assumed, as
the configuration file lists: (a) QK-norm per head; (b) the indexer reads
``h``; (c) LayerNorm on the index key, rotate-half RoPE over all its dims;
(d) ``topk`` counts tokens; (e) no Hadamard rotation, no FP8.  A positive
factor on ``I`` does not change ``S_t`` and is left out.  Departure: the
experts this share does not hold add nothing (``held = (first, count)``);
weights are random.

``params`` is the served tree (``tok_emb``, ``head``, ``ln_f`` and
``layers/*`` stacked on a leading layer axis) in the dtype it is served in;
each tensor is raised to float32 where it is used — an expert's matrices
one expert at a time, the index scores, the explicit ``argsort`` and the
attention one block of queries at a time, so that 25,000 tokens at the
published widths fit beside the weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # query rows scored, sorted and attended at once


def f32(a):
    return jnp.asarray(a, jnp.float32)


def rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layernorm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def rope_half(x, theta):
    """x: (L, H, D) at positions 0..L-1; dim i rotates with dim i + D/2."""
    L, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _project(h, lp, theta, eps):
    q = rmsnorm(jnp.einsum("le,ehd->lhd", h, f32(lp["wq"])), f32(lp["q_norm"]), eps)
    k = rmsnorm(jnp.einsum("le,ehd->lhd", h, f32(lp["wk"])), f32(lp["k_norm"]), eps)
    v = jnp.einsum("le,ehd->lhd", h, f32(lp["wv"]))
    qi = jnp.einsum("le,ejd->ljd", h, f32(lp["wqi"]))
    ki = layernorm(h @ f32(lp["wki"]), f32(lp["ki_norm_w"]), f32(lp["ki_norm_b"]), eps)
    wi = h @ f32(lp["wwi"])
    return (rope_half(q, theta), rope_half(k, theta), v,
            rope_half(qi, theta), rope_half(ki[:, None, :], theta)[:, 0], wi)


def selected(qi, wi, ki, first_row, topk, score_dtype=jnp.float32):
    """``(B, L)`` bool: the keys each of the queries at positions
    ``first_row ..`` attends: those it sees (``s <= t``), and past ``topk``
    of them the ``topk`` it scores highest.  ``qi (B, HI, DI)``, ``wi (B,
    HI)``, ``ki (L, DI)``.  ``score_dtype`` is a negative control's."""
    L = ki.shape[0]
    t = first_row + jnp.arange(qi.shape[0])[:, None]
    chosen = jnp.arange(L)[None, :] <= t
    if L <= topk:
        return chosen
    dot = jnp.einsum("qjd,kd->qjk", qi.astype(score_dtype), ki.astype(score_dtype))
    scores = jnp.sum(
        wi.astype(score_dtype)[:, :, None] * jax.nn.relu(dot), axis=1
    ).astype(jnp.float32)
    scores = jnp.where(chosen, scores, -jnp.inf)
    # descending and stable: among equal scores the lower s comes first
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return chosen & (rank < topk)


def attend(q, k, v, chosen):
    """``q (B, H, D)`` over the keys ``(L, KV, D)`` that ``chosen (B, L)``
    names, every head of a group over the same ones."""
    rep = q.shape[1] // k.shape[1]
    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    a = jnp.einsum("qhd,khd->hqk", q, kr) / math.sqrt(q.shape[-1])
    a = jnp.where(chosen[None], a, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(a, axis=-1), vr)


@functools.partial(jax.jit, static_argnames=("topk", "select", "score_dtype"))
def _attend_rows(q, k, v, qi, wi, ki, first_row, topk, select=True,
                 score_dtype=jnp.float32):
    """Rows ``first_row ..`` of the attention: q (B, H, D) against every key
    (L, KV, D) it selects.  ``select=False`` (every seen key) and
    ``score_dtype`` exist for the negative controls."""
    chosen = selected(
        qi, wi, ki, first_row, topk if select else ki.shape[0], score_dtype
    )
    return attend(q, k, v, chosen)


@jax.jit
def _project_out(o, wo):
    return jnp.einsum("qhd,hde->qe", o, f32(wo))


@jax.jit
def _expert(h, wg, wu, wd):
    return (jax.nn.silu(h @ f32(wg)) * (h @ f32(wu))) @ f32(wd)


def route(h, w_router, top_k, dtype=jnp.float32):
    """(L, n_experts) weights: p_e / sum over the chosen, 0 elsewhere.
    ``dtype`` other than float32 is a control's, never the reference's."""
    p = jax.nn.softmax(h.astype(dtype) @ jnp.asarray(w_router, dtype), axis=-1)
    vals, idx = jax.lax.top_k(p, top_k)
    w = (vals / jnp.sum(vals, axis=-1, keepdims=True)).astype(jnp.float32)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros(p.shape, jnp.float32).at[rows, idx].set(w)


def moe(h, lp, *, top_k, held):
    """The held experts' part of the routed sum."""
    first, count = held
    cw = route(h, lp["w_router"], top_k)
    out = jnp.zeros_like(h)
    for x in range(count):
        y = _expert(h, lp["we_gate"][x], lp["we_up"][x], lp["we_down"][x])
        out = out + cw[:, first + x, None] * y
    return out


def layer(x, lp, *, theta, eps, topk, top_k, held, **control):
    """One block on one sequence ``x (L, E)`` float32; ``lp`` as served."""
    L = x.shape[0]
    h = rmsnorm(x, f32(lp["ln1"]), eps)
    q, k, v, qi, ki, wi = _project(h, lp, theta, eps)
    o = jnp.concatenate([
        _attend_rows(
            q[a:a + Q_BLOCK], k, v, qi[a:a + Q_BLOCK], wi[a:a + Q_BLOCK], ki,
            a, topk, **control,
        )
        for a in range(0, L, Q_BLOCK)
    ])
    x = x + _project_out(o, lp["wo"])
    h2 = rmsnorm(x, f32(lp["ln2"]), eps)
    return x + moe(h2, lp, top_k=top_k, held=held)


def layers_of(stacked: dict):
    """One dict of weights per layer from the tree stacked on a layer axis."""
    for i in range(stacked["wq"].shape[0]):
        yield {k: v[i] for k, v in stacked.items()}


def logits(params, tokens, *, theta, eps, topk, top_k, held, layers=None,
           rows=None, **control):
    """Next-token logits ``(L, vocab)`` at every position of one sequence
    (``rows``: only those positions' logits).  ``layers`` may hand the
    layers' weights one by one; each layer runs where its weights are."""
    home = next(iter(params["tok_emb"].devices()))
    with jax.default_matmul_precision("highest"):
        x = f32(params["tok_emb"][jnp.asarray(tokens, jnp.int32)])
        each = layers if layers is not None else layers_of(params["layers"])
        for lp in each:
            x = jax.device_put(x, next(iter(lp["wq"].devices())))
            x = layer(
                x, lp, theta=float(theta), eps=float(eps), topk=int(topk),
                top_k=top_k, held=held, **control,
            )
        x = jax.device_put(x, home)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rmsnorm(x, f32(params["ln_f"]), eps)
        return x @ f32(params["head"]).T
