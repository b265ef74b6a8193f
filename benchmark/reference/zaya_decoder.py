"""Plain reference for the ZAYA1 decoder (``model_type: zaya``, ZAYA1-8B): the
full forward pass in straightforward ``jax.numpy``, float32, highest matmul
precision, one block after the other: the convolutions as shifted sums over
the whole sequence, the attention as a dense masked softmax, the experts as a
loop over 16 — no kernel, no cache, no slots, no padding, no tails.  It takes
the number of blocks from the tree and nothing else of a cut.

Follows the published config (huggingface.co/Zyphra/ZAYA1-8B config.json),
Compressed Convolutional Attention in its grouped form (arXiv:2510.04476) and
the ZAYA1 router (arXiv:2511.17127) as ISSUE 49 wrote the block down, ``x (T,
2048)``; ``eps`` 1e-5 everywhere, no bias on any projection::

    x   = Emb[tokens]                      Emb (262272, 2048); the head is Emb^T (tied, lm_head_bias false)
    z   = none                              the router's depth state: nothing enters layer 0
    block l = 0..39 ("hybrid": an attention sublayer, then an expert sublayer)

    residual add, both sublayers (learned residual scaling, scale_residual_merge):
      x <- (a_r * x + b_r) + (a_o * f(RMSNorm(x; g)) + b_o)        a_r, b_r, a_o, b_o (2048,) a sublayer

    attention sublayer  f = CCA     (8 query heads, 2 key-value heads, head 128: latents 1024 | 256 | 256)
      h      = RMSNorm(x; g_a)
      u_t    = [h_t Wq | h_t Wk]                       (1280) = 10 heads of 128     Wq (2048,1024), Wk (2048,256)
      c0_t   = w0[:,0] * u_{t-1} + w0[:,1] * u_t + b0                 depthwise, causal, cca_time0 = 2 taps
      c1_t[g]= W1[g,0] c0_{t-1}[g] + W1[g,1] c0_t[g] + b1[g]          a head g: (128 -> 128), causal, cca_time1 = 2 taps
                 left of a prompt's first token each convolution reads zeros: u_{-1} = 0 and c0_{-1} = 0 (not b0)
      qp, kp = u as (8,128) | (2,128)                  the latents before the convolutions
      mq[a]  = (qp[a] + kp[a // 4]) / 2                mk[b] = mean over the 4 query heads a of group b of mq[a]
      q[a]   = c1[a] + mq[a]                           k[b] = c1[8 + b] + mk[b]                       the q-k mean
      q[a]   = sqrt(128) q[a] / |q[a]|_2               k[b] = tau[b] sqrt(128) k[b] / |k[b]|_2        float32; tau (2,)
      v_t    = [h_t Wv1 | h_{t-1} Wv2]                 head 0 from this token, head 1 from the one before; h_{-1} = 0
      q, k   : rotate-half rotary on the first 64 of each head's 128, theta 5e6, absolute positions
      s[t,j,a] = 128^-1/2 q[t,a].k[j,a//4], j <= t     o[t,a] = sum_j softmax_j(s) v[j,a//4]     f = concat_a(o) Wo   Wo (1024,2048)

    expert sublayer  f = MoE        (16 experts of 2048 -> 2048 -> 2048, top-1, no shared expert)
      h   = RMSNorm(x; g_m)
      z_l = h Wd + bd                                   Wd (2048,256)                            router_hidden_size 256
      z_l = z_l + gam_l * z_{l-1}      (l > 0)          exponential depth averaging: gam_l (256,); z_l, as summed, goes on to block l+1
      p   = softmax(W3 gelu(W2 gelu(W1 RMSNorm(z_l; g_r) + b1r) + b2r))     W1, W2 (256,256), W3 (256,17); float32, over 17
      e   = argmax(p + bal)                             bal (17,): balancing biases, in the choice alone
      w   = p[e]                                        top-1: the chosen probability, not renormalised
      f   = w * (silu(h Wg_e) * (h Wu_e)) Wd_e          for e < 16
      f   = 0                                           for e = 16: the no-op, this token skips the sublayer (mixture of depths)
    logits = RMSNorm(x_L; g_f) Emb^T

Departures from the published code, each noted: (1) the weights are random,
from the seed, with the values that decide whether a mechanism matters
seeded so that it does and so that the model routes as a trained one does
(the configuration's ``assumed`` (g): a key temperature that makes
attention peaked, balancing biases that even the choices' shares in one pass); (2) everything
is float32 where the published code runs the projections in bfloat16: this
is the reference the served precisions are held to; (3) the GELU is the
exact (erf) one; (4) the served tree carries ``[Wq | Wk]`` as one matrix
``wqk (2048, 1280)``, ``[Wv1 | Wv2]`` as ``wv (2048, 256)``, the depthwise
taps as ``conv0_w (2, 1280)`` (tap ``j`` on ``u_{t-1+j}``), the per-head taps
as ``conv1_w (10, 2, 128, 128)`` and a sublayer's ``[a_r, b_r, a_o, b_o]`` as
``res_a`` / ``res_m (4, 2048)``: read here by those names.

A block's weights are the served tree's (``layers/*``, stacked on a leading
layer axis), in the dtype they are served in, raised to float32 where they
are used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # query rows attended at once
VOCAB_BLOCKS = 8  # the head runs over the vocabulary in this many parts


def f32(a):
    return jnp.asarray(a, jnp.float32)


def rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def shifted(a, by: int):
    """``a (T, ...)`` moved ``by`` tokens later, zeros in front."""
    if by == 0:
        return a
    return jnp.concatenate([jnp.zeros_like(a[:by]), a[:-by]])


def rotate_half(x, positions, theta):
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("rotary_dim", "theta"))
def cca_qkv(h, lp, *, rotary_dim: int, theta: float):
    """``(q (T, H, D), k (T, KV, D), v (T, KV, D))`` of one whole sequence
    ``h (T, E)`` at positions ``0..T-1``, as attended."""
    T = h.shape[0]
    w1 = f32(lp["conv1_w"])  # (g, taps, d, d)
    g, k1, d, _ = w1.shape
    kv = lp["tau"].shape[-1]
    heads = g - kv
    u = h @ f32(lp["wqk"])  # (T, g * d)
    w0 = f32(lp["conv0_w"])  # (taps, g * d), tap j on u_{t - (taps - 1) + j}
    k0 = w0.shape[0]
    c0 = sum(w0[j] * shifted(u, k0 - 1 - j) for j in range(k0)) + f32(lp["conv0_b"])
    c0 = c0.reshape(T, g, d)
    c1 = sum(
        jnp.einsum("tgc,gcd->tgd", shifted(c0, k1 - 1 - j), w1[:, j])
        for j in range(k1)
    ) + f32(lp["conv1_b"])
    up = u.reshape(T, g, d)
    qp, kp = up[:, :heads], up[:, heads:]
    group = heads // kv
    mq = (qp + jnp.repeat(kp, group, axis=1)) / 2
    mk = mq.reshape(T, kv, group, d).mean(axis=2)
    q = c1[:, :heads] + mq
    k = c1[:, heads:] + mk
    q = d ** 0.5 * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = f32(lp["tau"])[:, None] * d ** 0.5 * k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    pos = jnp.arange(T)
    q = jnp.concatenate(
        [rotate_half(q[..., :rotary_dim], pos, theta), q[..., rotary_dim:]], axis=-1)
    k = jnp.concatenate(
        [rotate_half(k[..., :rotary_dim], pos, theta), k[..., rotary_dim:]], axis=-1)
    hv = h @ f32(lp["wv"])  # (T, kv * d): [h Wv1 | h Wv2], a half each
    half = hv.shape[1] // 2
    v = jnp.concatenate([hv[:, :half], shifted(hv, 1)[:, half:]], axis=1)
    return q, k, v.reshape(T, kv, d)


@jax.jit
def attend_rows(q, k, v, first_row):
    """Rows ``first_row ..`` of the causal attention: ``q (B, H, D)`` over
    ``k``, ``v (L, KV, D)``, query head ``a`` on key head ``a // (H / KV)``."""
    B, H, D = q.shape
    kv = k.shape[1]
    qg = q.reshape(B, kv, H // kv, D)
    s = jnp.einsum("tkgd,ukd->kgtu", qg, k) * D ** -0.5
    t = first_row + jnp.arange(B)[:, None]
    s = jnp.where(jnp.arange(k.shape[0])[None, :] <= t, s, -jnp.inf)
    return jnp.einsum("kgtu,ukd->tkgd", jax.nn.softmax(s, axis=-1), v).reshape(B, H, D)


def attend(q, k, v):
    """The dense masked softmax, ``Q_BLOCK`` rows at once.  -> (T, H, D)."""
    return jnp.concatenate([
        attend_rows(q[a:a + Q_BLOCK], k, v, a) for a in range(0, q.shape[0], Q_BLOCK)
    ])


@functools.partial(jax.jit, static_argnames=("eps",))
def router_probs(z, lp, eps):
    """The 17 probabilities of the depth state ``z (T, R)`` as summed."""
    a = rmsnorm(z, f32(lp["r_ln"]), eps)
    a = jax.nn.gelu(a @ f32(lp["r_w1"]) + f32(lp["r_b1"]), approximate=False)
    a = jax.nn.gelu(a @ f32(lp["r_w2"]) + f32(lp["r_b2"]), approximate=False)
    return jax.nn.softmax(a @ f32(lp["r_w3"]), axis=-1)


def choose(p, lp):
    """``(e (T,), w (T,))``: the balancing biases enter the choice alone."""
    e = jnp.argmax(p + f32(lp["r_bal"]), axis=-1)
    return e, jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0]


def choice_deficit(p, e, lp):
    """How far under this reference's best ``p + bal`` a served choice ``e
    (T,)`` lies: 0 where the two choose alike, and at least the reference's
    own gap between its first and second where they differ — what a top-1
    comparison holds in the place of the choice itself."""
    s = p + f32(lp["r_bal"])
    return jnp.max(s, axis=-1) - jnp.take_along_axis(s, e[:, None], axis=-1)[:, 0]


@jax.jit
def expert(h, wg, wu, wd):
    return (jax.nn.silu(h @ f32(wg)) * (h @ f32(wu))) @ f32(wd)


def experts(h, e, w, lp):
    """``f (T, E)`` under the choice ``e`` and its weight ``w``: a loop
    over the experts; the no-op (``e`` past the last expert) adds nothing."""
    out = jnp.zeros_like(h)
    for x in range(lp["we_gate"].shape[0]):
        mine = (e == x)[:, None]
        y = expert(h, lp["we_gate"][x], lp["we_up"][x], lp["we_down"][x])
        out = out + jnp.where(mine, w[:, None] * y, 0.0)
    return out


def merge(x, f, res):
    a_r, b_r, a_o, b_o = f32(res)
    return (a_r * x + b_r) + (a_o * f + b_o)


def block(x, z, lp, *, rotary_dim, theta, eps):
    """One block on one sequence: ``x (T, E)`` float32 and the router's depth
    state ``z (T, R)`` (None into block 0) -> the same, for the next."""
    h = rmsnorm(x, f32(lp["ln_a"]), eps)
    q, k, v = cca_qkv(h, lp, rotary_dim=rotary_dim, theta=theta)
    o = attend(q, k, v).reshape(x.shape[0], -1)
    x = merge(x, o @ f32(lp["wo"]), lp["res_a"])
    h = rmsnorm(x, f32(lp["ln_m"]), eps)
    zl = h @ f32(lp["r_down"]) + f32(lp["r_down_b"])
    if z is not None:
        zl = zl + f32(lp["r_gam"]) * z
    e, w = choose(router_probs(zl, lp, eps), lp)
    return merge(x, experts(h, e, w, lp), lp["res_m"]), zl


def layers_of(params: dict):
    """One dict of weights per block, in order, from the served stack."""
    stack = params["layers"]
    for l in range(stack["ln_a"].shape[0]):
        yield {k: v[l] for k, v in stack.items()}


def head(x, params, eps):
    """``RMSNorm(x; g_f) Emb^T``, the vocabulary in parts."""
    x = rmsnorm(x, f32(params["ln_f"]), eps)
    emb = params["tok_emb"]
    step = -(-emb.shape[0] // VOCAB_BLOCKS)
    return jnp.concatenate(
        [x @ f32(emb[a:a + step]).T for a in range(0, emb.shape[0], step)], axis=-1
    )


def logits(params, tokens, *, rotary_dim, theta, eps, rows=None):
    """Next-token logits ``(T, vocab)`` at every position of one sequence
    (``rows``: only those positions' logits)."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["tok_emb"][jnp.asarray(tokens, jnp.int32)])
        z = None
        for lp in layers_of(params):
            x, z = block(
                x, z, lp, rotary_dim=int(rotary_dim), theta=float(theta), eps=float(eps)
            )
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return head(x, params, float(eps))
