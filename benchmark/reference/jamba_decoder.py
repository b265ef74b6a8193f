"""Plain reference for the Jamba decoder (``model_type: jamba``,
AI21-Jamba2-3B): the full forward pass in straightforward ``jax.numpy``,
float32, highest matmul precision, one layer after the other, the recurrence
a ``lax.scan`` over the tokens with the state ``(d_inner, d_state)`` as the
papers write it — no kernel, no cache, no slots, no batching, no padding.

Follows the published config (huggingface.co/ai21labs/AI21-Jamba2-3B
config.json) and the public ``jamba`` modelling code as ISSUE 45 wrote the
layer down, ``x (T, 2560)``::

    x0 = Emb[tokens]                                        Emb (65536, 2560); the head is Emb^T (tied)
    layer l = 0..27:  attention where l % 14 == 7 (layers 7, 21), state-space elsewhere (26 layers)
    h  = RMSNorm(x; g1, eps 1e-6)

    state-space mixer (d_inner 5120 = 2 x 2560, d_state 16, d_conv 4, dt_rank 160):
      [u | z]   = h Win                                     (T, 5120 | 5120)          no bias
      c_t       = silu( sum_{j=0..3} wc[:, j] * u_{t-3+j} + bc )                      depthwise, causal, bias; u_{t<0} = 0
      [dr|B|C]  = c Wx                                      (T, 160 | 16 | 16)        no bias
      dr = RMSNorm(dr; g_dt)   B = RMSNorm(B; g_b)   C = RMSNorm(C; g_c)              Jamba's three inner norms
      D_t       = softplus(dr_t Wdt + b_dt)                 (5120)
      A         = -exp(A_log)                               (5120, 16)
      S_t       = exp(D_t[:, None] * A) * S_{t-1} + (D_t * c_t)[:, None] * B_t[None, :]     S (5120, 16), S_{-1} = 0
      y_t       = S_t C_t + Dskip * c_t                     (5120)
      o         = (y * silu(z)) Wout                        (T, 2560)                 no bias

    attention (layers 7 and 21):
      q = h Wq (T, 20, 128)   k = h Wk (T, 1, 128)   v = h Wv (T, 1, 128)             no bias, NO rotary, no position signal
      s[t,u,a] = 128^-1/2 q[t,a].k[u]   u <= t       o[t,a] = sum_u softmax_u(s) v[u]       o = concat_a(o) Wo

    x  = x + o
    h2 = RMSNorm(x; g2)
    x  = x + Wd(silu(Wg h2) * Wu h2)                        8,192 wide on all 28 layers (num_experts 1: no router)
    logits = RMSNorm(x_L; gf) Emb^T

Departures from the published code, each noted: (1) the weights are random,
from the seed, the state-space parameters by Mamba's published
initialisation (the configuration's ``assumed`` (c)); (2) everything is
float32 where the published code runs the projections in bfloat16 and the
recurrence in float32 (its ``use_mamba_kernels`` path): this is the
reference the served precisions are held to; (3) ``num_experts`` 1: every
MLP is the dense SwiGLU, the ``expert_layer_*`` keys are idle; (4) the
served tree carries ``A_log`` with the state index leading, ``(16, 5120)``,
the convolution's taps as ``(4, 5120)`` and ``Wx`` as ``(192, 5120)``: read
here transposed into the papers' ``(5120, 16)`` and ``(5120, 192)``, and by
tap.

A layer's weights are the served tree's (``ssm_layers/*`` and
``attn_layers/*``, each stacked on a leading axis of its own kind's layers,
every layer's MLP and norms in its own kind's stack), in the dtype they are
served in, raised to float32 where they are used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # query rows attended at once


def f32(a):
    return jnp.asarray(a, jnp.float32)


def rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def is_attention(layer: int, period: int, offset: int) -> bool:
    """The ``jamba`` family's convention for the order of the layer types."""
    return layer % period == offset


@jax.jit
def conv(u, lp, before=None):
    """``c (T, Di)``: the causal depthwise convolution of ``u (T, Di)`` and
    its silu.  ``before (K - 1, Di)``: the inputs ahead of ``u``'s first
    (zeros unset)."""
    w = f32(lp["conv_w"])  # (K, Di), tap j on u_{t - (K - 1) + j}
    k = w.shape[0]
    T = u.shape[0]
    if before is None:
        before = jnp.zeros((k - 1, u.shape[1]), jnp.float32)
    padded = jnp.concatenate([f32(before), u])
    acc = sum(padded[j:j + T] * w[j] for j in range(k))
    if "conv_b" in lp:
        acc = acc + f32(lp["conv_b"])
    return jax.nn.silu(acc)


@functools.partial(jax.jit, static_argnames=("eps",))
def project(c, lp, eps):
    """``(dr (T, R) normed, B (T, N) normed, C (T, N) normed, D_t (T, Di))``
    of the convolution's output ``c``."""
    r = lp["g_dt"].shape[-1]
    n = lp["g_b"].shape[-1]
    drbc = c @ f32(lp["wx"]).T  # served with the outputs leading
    dr = rmsnorm(drbc[:, :r], f32(lp["g_dt"]), eps)
    b = rmsnorm(drbc[:, r:r + n], f32(lp["g_b"]), eps)
    cc = rmsnorm(drbc[:, r + n:], f32(lp["g_c"]), eps)
    return dr, b, cc, jax.nn.softplus(dr @ f32(lp["wdt"]) + f32(lp["b_dt"]))


@jax.jit
def recurrence(c, dt, b, cc, lp, s0=None):
    """``(y (T, Di), S (Di, N))``: the recurrence over the tokens, one after
    the other, from ``s0 (Di, N)`` (zeros unset)."""
    a = -jnp.exp(f32(lp["a_log"])).T  # (Di, N)
    d_skip = f32(lp["d_skip"])

    def step(s, xs):
        ct, dtt, bt, cct = xs
        s = jnp.exp(dtt[:, None] * a) * s + (dtt * ct)[:, None] * bt[None, :]
        return s, s @ cct + d_skip * ct

    s0 = jnp.zeros(a.shape, jnp.float32) if s0 is None else f32(s0)
    s, y = jax.lax.scan(step, s0, (c, dt, b, cc))
    return y, s


@functools.partial(jax.jit, static_argnames=("eps",))
def ssm_mixer(h, lp, eps):
    """The state-space mixer of one sequence ``h (T, E)`` -> ``(o (T, E),
    S (Di, N))``."""
    di = lp["d_skip"].shape[-1]
    uz = h @ f32(lp["win"])
    u, z = uz[:, :di], uz[:, di:]
    c = conv(u, lp)
    _, b, cc, dt = project(c, lp, eps)
    y, s = recurrence(c, dt, b, cc, lp)
    return (y * jax.nn.silu(z)) @ f32(lp["wout"]), s


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


def attn_mixer(h, lp):
    """Multi-query attention with no position signal, ``Q_BLOCK`` rows at
    once."""
    q = jnp.einsum("te,ehd->thd", h, f32(lp["wq"]))
    k = jnp.einsum("te,ehd->thd", h, f32(lp["wk"]))
    v = jnp.einsum("te,ehd->thd", h, f32(lp["wv"]))
    o = jnp.concatenate([
        attend_rows(q[a:a + Q_BLOCK], k, v, a) for a in range(0, q.shape[0], Q_BLOCK)
    ])
    return jnp.einsum("thd,hde->te", o, f32(lp["wo"]))


@jax.jit
def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ f32(wg)) * (h @ f32(wu))) @ f32(wd)


def layer(x, lp, *, eps):
    """One block on one sequence ``x (T, E)`` float32; ``lp`` as served: an
    attention layer where it has ``wq``, else a state-space layer."""
    h = rmsnorm(x, f32(lp["ln1"]), eps)
    x = x + (attn_mixer(h, lp) if "wq" in lp else ssm_mixer(h, lp, eps)[0])
    h2 = rmsnorm(x, f32(lp["ln2"]), eps)
    return x + swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


def layers_of(params: dict, period: int, offset: int):
    """One dict of weights per layer in the model's order, from the served
    tree's two stacks by the family's pattern."""
    total = sum(params[s]["ln1"].shape[0] for s in ("ssm_layers", "attn_layers"))
    at = {"ssm_layers": 0, "attn_layers": 0}
    for l in range(total):
        name = "attn_layers" if is_attention(l, period, offset) else "ssm_layers"
        yield {k: v[at[name]] for k, v in params[name].items()}
        at[name] += 1


def logits(params, tokens, *, period, offset, eps, rows=None):
    """Next-token logits ``(T, vocab)`` at every position of one sequence
    (``rows``: only those positions' logits)."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["tok_emb"][jnp.asarray(tokens, jnp.int32)])
        for lp in layers_of(params, period, offset):
            x = layer(x, lp, eps=float(eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        x = rmsnorm(x, f32(params["ln_f"]), eps)
        return x @ f32(params["tok_emb"]).T
