"""ZAYA1 decoder (``model_type: zaya``, ZAYA1-8B) for generative serving: the
sixth family under the contract ``executor/generation.py::GenerativeModel``
reads, and the first whose EVERY layer keeps both kinds of state: K and V a
token in the paged pool, and a SLOT's tails — what two short causal
convolutions and a value shift need of the token before — beside them.

One block, ``x (T, 2048)``; ``eps`` 1e-5 everywhere, no bias on any
projection (``attention_bias`` false)::

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
      POOL, a token a layer:  k (2,128) as attended, v (2,128) as shifted: 1,024 B in bfloat16
      SLOT, a layer:          u_{t-1} (1280), c0_{t-1} (1280), h_{t-1} Wv2 (128): 5,376 B in bfloat16, whatever the context

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

The attention is Compressed Convolutional Attention in its grouped form
(arXiv:2510.04476), the expert layer the ZAYA1 router and its top-1 experts
(arXiv:2511.17127).  Assumed (``benchmark/configs/zaya1-8b-l20.json`` lists
the same, each with its source): (a) the switches ``zaya_use_mod``,
``zaya_use_eda``, ``scale_residual_merge`` and the router width's name, from
the sibling row ``ZAYA1-base``; (b) CCA's order of operations, the q-k mean
over groups, the value shift by key-value head and the zero left padding;
(c) the temperature as a positive scale a key head on the normalised key and
``sqrt(128)`` on both norms; (d) the router MLP's depth and biases, the exact
(erf) GELU, the depth state taken after the sum and before the norm, the
balancing biases in the choice alone; (e) the no-op's output is 0; (f)
residual scaling as scale-and-bias vectors on both arms; (g) seeded values
that make each mechanism matter and the model route as a trained one does
(``init_params``: a key temperature that makes attention peaked, balancing
biases that even the choices' shares: assumptions, no published load); (h) activations and
weights in the served dtype, every norm's mean, both L2 norms, the softmaxes
and the router from ``z_l`` on in float32; (i) ``sliding_window`` null and
``rope_parameters.hybrid_sliding`` are idle.  ``cca_time0`` and ``cca_time1``
count the taps, two or more; the value shift is always one token.

Two kinds of state for one slot on EVERY layer (``init_paged_cache``).  Under
the one block table (``POOL_ARRAYS``): ``k`` and ``v (layers, blocks, block,
kv_heads * head_dim)``, the uniform pool of ``models/llama.py``.  PER SLOT
and not by token (``SLOT_ARRAYS``): ``tail_u (layers, cca_time0 - 1, slots,
1280)``, ``tail_c (layers, cca_time1 - 1, slots, 1280)`` and ``tail_v
(layers, slots, 128)``, taps before slots so the channels lie along the
lanes.  What is written into the pool at ``t`` depends on the slot's tails.

What a program owes the tails: ``prefill_slot_paged`` writes them as of the
prompt's LAST REAL token (a rung's padding rows move nothing), overwriting
what a former request left; ``decode_slots_paged`` shifts every ACTIVE
slot's by one token and leaves an inactive slot's alone.  A stale or zeroed
tail corrupts one token's ``q``, ``k`` and ``v`` and then heals, which no
comparison of sampled tokens would see: the tests and the benchmark's
reference kind hold logits and parts.  The tails have no place in any path
that moves or shares a slot's cache: the family has no
``prefill_suffix_paged`` (prefix reuse and chunked prefill are switched off
with the contract's warning: a shared prefix would need the tails AT the
prefix's end), no speculative verify (a rejected draft would have to rewind
them), no LoRA, no int8 pool, no mesh, and ``generation.py::_kv_alone``
refuses handoff, suspend and the DRAM and peer tiers by the arrays' names.

The experts' products are ``models/moe.py``'s at one expert a token: the
17th choice is an index no share holds, so the token adds 0 and is counted
as routed and as skipped.  The layer scan carries two streams, ``x`` and
the router's ``z``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models import moe, paged
from seldon_core_tpu.models.common import annotate_params
# benchmark/reference/kinds/zaya_decoder.py reads ``_attend_prompt`` here
from seldon_core_tpu.models.layers import attend_prompt as _attend_prompt
from seldon_core_tpu.models.layers import rms_head, rmsnorm, rope
from seldon_core_tpu.models.layers import sample_tokens  # noqa: F401  (contract)

COUNTERS = moe.COUNTERS + (
    "moe.tokens_skipped",          # decode: token-layers that chose the no-op
    "moe.prefill_tokens_skipped",  # prefill: the same, of real tokens
    "attn.rows_live",              # decode: K/V rows the layers HAVE to read, from the live slots'
                                   # positions alone (a slot at position p attends p + 1 rows a
                                   # layer), layers, slots and steps summed
    "zaya.steps",                  # decode steps
)
_SKIPPED, _P_SKIPPED, _ROWS_LIVE, _STEPS = range(len(moe.COUNTERS), len(COUNTERS))
# the per-token arrays of the paged pool, under the one table
POOL_ARRAYS = ("k", "v")
# the per-SLOT arrays of the cache: the convolutions' and the value shift's
# tails.  Counted with a slot's bytes; refused by whatever moves or shares a
# slot's cache
SLOT_ARRAYS = ("tail_u", "tail_c", "tail_v")
# the published keys ``Config.from_published`` reads, and the field of each
_PUBLISHED = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "cca_time0": "cca_time0", "cca_time1": "cca_time1",
    "partial_rotary_factor": "partial_rotary_factor",
    "num_experts": "n_experts", "num_experts_per_tok": "experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "router_hidden_size": "router_hidden_size",
    "tie_word_embeddings": "tie_word_embeddings",
    "max_position_embeddings": "max_seq", "rms_norm_eps": "norm_eps",
}


# the seeded key temperature's range, and how many drawn router states the
# seeded balancing biases are read from (assumed (g): ``init_params``)
TAU_MIN, TAU_MAX = 4.0, 8.0
BALANCE_STATES = 2048


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 262272
    hidden: int = 2048
    n_layers: int = 40
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    # the published names, as published
    cca_time0: int = 2  # taps of the depthwise convolution
    cca_time1: int = 2  # taps of the per-head convolution
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6  # rope_parameters.hybrid.rope_theta
    n_experts: int = 16  # num_experts; the router has one more output, the no-op
    experts_per_tok: int = 1  # num_experts_per_tok
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    tie_word_embeddings: bool = True
    max_seq: int = 131072
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads divides n_heads")
        if self.cca_time0 < 2 or self.cca_time1 < 2:
            raise ValueError("cca_time0 and cca_time1 count the taps: two or more")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("partial_rotary_factor gives an even share of head_dim")
        if self.experts_per_tok != 1:
            raise ValueError("the ZAYA1 router is top-1: num_experts_per_tok is 1")
        if not self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings false is not served: the head is Emb^T")

    @classmethod
    def from_published(cls, config: dict, **kw) -> "Config":
        """The ``Config`` of a published ``config.json`` (the keys of
        ``_PUBLISHED`` and ``rope_parameters.hybrid.rope_theta``)."""
        found = {f: config[k] for k, f in _PUBLISHED.items() if k in config}
        theta = config.get("rope_parameters", {}).get("hybrid", {}).get("rope_theta")
        if theta is not None:
            found["rope_theta"] = float(theta)
        return cls(**{**found, **kw})

    @property
    def latent_heads(self) -> int:
        """Heads of 128 in ``u``: the query heads, then the key heads."""
        return self.n_heads + self.n_kv_heads

    @property
    def latent(self) -> int:
        return self.latent_heads * self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def held(self) -> tuple[int, int]:
        """Every expert is held: the no-op, index ``n_experts``, is no share's."""
        return 0, self.n_experts

    @classmethod
    def tiny(cls, max_seq: int = 64, **kw) -> "Config":
        """Test-scale config: same code paths, toy sizes."""
        base = dict(
            vocab_size=256, hidden=64, n_layers=3, n_heads=4, n_kv_heads=2,
            head_dim=16, n_experts=4, moe_intermediate_size=32,
            router_hidden_size=16, max_seq=max_seq, rope_theta=10000.0,
        )
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> dict:
    """Random weights IN ``dtype``, a layer (an expert leaf: one expert of
    one layer) at a time.  Projections ``N / sqrt(fan_in)``; what decides
    whether a mechanism matters is seeded so that it does, and so that the
    model routes as a trained one does (assumed (g)): the residual scales
    ``1 + 0.1 N`` and biases ``0.02 N``, ``gam = 0.5 + 0.1 N``, the
    convolutions' taps of the size of an identity's (``N / sqrt(taps)`` a
    channel, ``N / sqrt(taps * head_dim)`` a head's matrix), every other
    bias ``0.02 N``; ``tau`` log-uniform in [4, 8] (a cosine score times
    ``sqrt(head_dim)`` has a deviation of 1 over random keys: at ``tau`` 1 a
    query attends thousands of keys alike, every token's attention is the
    same mean and the router sees one token); ``bal`` evens the choices'
    shares in one pass (:func:`_balanced`: an assumption)."""
    c = cfg
    e, f, d, r = c.hidden, c.moe_intermediate_size, c.head_dim, c.router_hidden_size
    g, n = c.latent_heads, c.n_layers
    keys = iter(jax.random.split(rng, 32))
    layer_ids = jnp.arange(n)

    def stacked(shape, scale, mean=0.0):
        key = next(keys)
        return lax.map(
            lambda l: (
                mean + scale * jax.random.normal(jax.random.fold_in(key, l), shape)
            ).astype(dtype),
            layer_ids,
        )

    def experts(shape, fan_in):
        key = next(keys)

        def layer(l):
            lk = jax.random.fold_in(key, l)
            return lax.map(
                lambda x: (
                    jax.random.normal(jax.random.fold_in(lk, x), shape)
                    / math.sqrt(fan_in)
                ).astype(dtype),
                jnp.arange(c.n_experts),
            )

        return lax.map(layer, layer_ids)

    def rows(count, width):
        """A (count, width) matrix in slabs of at most 8,192 rows."""
        key = next(keys)
        slab = max(s for s in range(1, min(count, 8192) + 1) if count % s == 0)
        return lax.map(
            lambda i: (
                jax.random.normal(jax.random.fold_in(key, i), (slab, width))
                / math.sqrt(width)
            ).astype(dtype),
            jnp.arange(count // slab),
        ).reshape(count, width)

    def residual():
        """``[a_r, b_r, a_o, b_o]`` of one sublayer, every layer."""
        scale = jnp.asarray([0.1, 0.02, 0.1, 0.02], jnp.float32)[:, None]
        mean = jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)[:, None]
        return (
            mean + scale * jax.random.normal(next(keys), (n, 4, e))
        ).astype(dtype)

    ones = jnp.ones((n, e), dtype)
    tau = jnp.exp(
        jax.random.uniform(next(keys), (n, c.n_kv_heads))
        * (math.log(TAU_MAX) - math.log(TAU_MIN)) + math.log(TAU_MIN)
    )
    layers = {
        "ln_a": ones, "ln_m": ones,
        # [Wq | Wk] and [Wv1 | Wv2], each one product
        "wqk": stacked((e, c.latent), e ** -0.5),
        "wv": stacked((e, c.n_kv_heads * d), e ** -0.5),
        "wo": stacked((c.n_heads * d, e), (c.n_heads * d) ** -0.5),
        # taps before channels: the channels lie along the lanes
        "conv0_w": stacked((c.cca_time0, c.latent), c.cca_time0 ** -0.5),
        "conv0_b": stacked((c.latent,), 0.02),
        "conv1_w": stacked((g, c.cca_time1, d, d), (c.cca_time1 * d) ** -0.5),
        "conv1_b": stacked((g, d), 0.02),
        "tau": tau.astype(dtype),
        "res_a": residual(), "res_m": residual(),
        "r_down": stacked((e, r), e ** -0.5),
        "r_down_b": stacked((r,), 0.02),
        "r_gam": stacked((r,), 0.1, 0.5),
        "r_ln": jnp.ones((n, r), dtype),
        "r_w1": stacked((r, r), r ** -0.5),
        "r_b1": stacked((r,), 0.02),
        "r_w2": stacked((r, r), r ** -0.5),
        "r_b2": stacked((r,), 0.02),
        "r_w3": stacked((r, c.n_experts + 1), r ** -0.5),
        "we_gate": experts((e, f), e),
        "we_up": experts((e, f), e),
        "we_down": experts((f, e), f),
    }
    layers["r_bal"] = _balanced(layers, next(keys), cfg).astype(dtype)
    return {
        # the embedding and, read transposed, the head (tied)
        "tok_emb": rows(c.vocab_size, e),
        "layers": layers,
        "ln_f": jnp.ones((e,), dtype),
    }


def _balanced(layers: dict, key, cfg: Config):
    """Every block's balancing biases ``bal (layers, n_experts + 1)``, in one
    pass: on :data:`BALANCE_STATES` router states drawn ``N(0, I)`` a block,
    ``bal_e`` is minus the probability that choice ``e`` passes once in
    ``n_experts + 1`` states, the mean taken out — so each choice tops the
    others about as often.  AN ASSUMPTION, not a published fact: a seeded MLP
    prefers a few of its outputs whatever the token (its hidden layers' means
    are not 0) and top-1 then sends most tokens to three or four experts;
    loaded weights would bring their own ``bal`` and never run this."""
    n = cfg.n_experts + 1

    def block(xs):
        l, lp = xs
        z = jax.random.normal(
            jax.random.fold_in(key, l), (BALANCE_STATES, cfg.router_hidden_size)
        )
        bal = -jnp.quantile(_router_probs(z, lp, cfg), 1.0 - 1.0 / n, axis=0)
        return bal - jnp.mean(bal)

    router = {k: v for k, v in layers.items() if k.startswith("r_")}
    return lax.map(block, (jnp.arange(cfg.n_layers), router))


_AXIS_RULES = [
    (r"layers/we_(gate|up)", ("layers", None, "embed", "mlp")),
    (r"layers/we_down", ("layers", None, "mlp", "embed")),
    (r"layers/ln_[am]", ("layers", "embed")),
    (r"tok_emb", ("vocab", "embed")),
    (r"ln_f", ("embed",)),
]


def param_logical_axes(params):
    return annotate_params(params, _AXIS_RULES)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _latents(h, lp, cfg: Config):
    """``u = [h Wq | h Wk]`` and ``hv = [h Wv1 | h Wv2]`` of ``h (..., E)``."""
    with jax.named_scope("cca.qk"):
        u = jnp.einsum("...e,ef->...f", h, lp["wqk"])
    with jax.named_scope("cca.v"):
        hv = jnp.einsum("...e,ef->...f", h, lp["wv"])
    return u, hv


def _conv0(window, lp):
    """``c0 = sum_j w0[j] * window[j] + b0`` of the taps ``window``, a
    sequence of arrays ``(..., 1280)``, oldest first; float32 inside, the
    activations' dtype out."""
    w = _f32(lp["conv0_w"])
    acc = sum(_f32(window[j]) * w[j] for j in range(w.shape[0]))
    return (acc + _f32(lp["conv0_b"])).astype(window[0].dtype)


def _conv1(window, lp, cfg: Config):
    """``c1[g] = sum_j W1[g, j] window[j][g] + b1[g]`` of the taps ``window``
    (each ``(T, 1280)``), a head's matrix a tap.  -> ``(T, heads, 128)``
    float32."""
    g, d = cfg.latent_heads, cfg.head_dim
    taps = jnp.concatenate(
        [w.reshape(w.shape[0], g, d) for w in window], axis=-1
    )  # (T, g, taps * d)
    w1 = lp["conv1_w"].reshape(g, -1, d)  # (g, taps * d, d), tap-major
    # operands raised to float32, not ``preferred_element_type``: the same
    # product on a TPU (one bfloat16 pass, float32 sums), and the CPU's
    # batched dot takes no bfloat16 operands with a float32 result
    return jnp.einsum("tgc,gcd->tgd", _f32(taps), _f32(w1)) + _f32(lp["conv1_b"])


def _qk_mean(u, cfg: Config):
    """``(mq (T, H, D), mk (T, KV, D))`` float32 of the latents before the
    convolutions: a query head's mean with its group's key, and a key
    head's mean of its group's ``mq``."""
    T = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    up = _f32(u).reshape(T, h + kv, d)
    qp = up[:, :h].reshape(T, kv, h // kv, d)
    mq = (qp + up[:, h:, None]) / 2
    return mq.reshape(T, h, d), jnp.mean(mq, axis=2)


def _l2(x):
    """``sqrt(D) x / |x|_2`` over the last axis, float32."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _rotary(x, positions, cfg: Config):
    """Rotate-half rotary on the first ``rotary_dim`` of each head."""
    r = cfg.rotary_dim
    return jnp.concatenate(
        [rope(x[..., :r], positions, cfg.rope_theta), x[..., r:]], axis=-1
    )


def _mix(u, c1, lp, positions, cfg: Config, dtype):
    """The q-k mean, both L2 norms, the key's temperature and the rotary:
    ``u (T, 1280)``, ``c1 (T, 10, 128)`` float32 -> ``q (T, H, D)``, ``k (T,
    KV, D)`` as attended, in ``dtype``."""
    with jax.named_scope("cca.mix"):
        mq, mk = _qk_mean(u, cfg)
        q = _l2(c1[:, : cfg.n_heads] + mq)
        k = _l2(c1[:, cfg.n_heads:] + mk) * _f32(lp["tau"])[:, None]
        q = _rotary(q, positions, cfg)
        k = _rotary(k, positions, cfg)
        return q.astype(dtype), k.astype(dtype)


def _shift(now, before):
    """``v_t = [hv_t's first half | hv_{t-1}'s second]``: ``now (T, KV * D)``
    this token's ``[h Wv1 | h Wv2]`` and ``before (T, KV * D / 2)`` the
    second half of the token's before."""
    return jnp.concatenate([now[..., : before.shape[-1]], before], axis=-1)


def _cca_prompt_parts(h, lp, cfg: Config, length) -> dict:
    """CCA's ``q``, ``k`` and ``v`` over one prompt ``h (T, E)`` of
    ``length`` real tokens at positions ``0..T-1``, part by part: ``q (T,
    H, D)``, ``k (T, KV, D)``, ``v (T, KV * D)``, and the tails as of the
    last real token: ``tail_u (K0 - 1, 1280)`` (``u`` at
    ``length - K0 + 1 .. length - 1``, zeros before the start), ``tail_c (K1
    - 1, 1280)`` and ``tail_v (128)``, ``h_{length-1} Wv2``."""
    T = h.shape[0]
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    u, hv = _latents(h, lp, cfg)
    with jax.named_scope("cca.conv"):
        pu = jnp.pad(u, ((k0 - 1, 0), (0, 0)))  # u_{t<0} = 0
        c0 = _conv0([pu[j:j + T] for j in range(k0)], lp)
        pc = jnp.pad(c0, ((k1 - 1, 0), (0, 0)))  # c0_{t<0} = 0, not b0
        c1 = _conv1([pc[j:j + T] for j in range(k1)], lp, cfg)
        # pu[i] is u_{i - (K0 - 1)}: the K0 - 1 rows from `length` hold
        # u_{length-K0+1 .. length-1}
        tail_u = lax.dynamic_slice_in_dim(pu, length, k0 - 1, axis=0)
        tail_c = lax.dynamic_slice_in_dim(pc, length, k1 - 1, axis=0)
    q, k = _mix(u, c1, lp, jnp.arange(T), cfg, h.dtype)
    with jax.named_scope("cca.v"):
        second = hv[:, hv.shape[-1] // 2:]
        v = _shift(hv, jnp.pad(second, ((1, 0), (0, 0)))[:T])  # h_{-1} = 0
        tail_v = lax.dynamic_index_in_dim(second, length - 1, keepdims=False)
    return dict(q=q, k=k, v=v, tail_u=tail_u, tail_c=tail_c, tail_v=tail_v)


def _cca_step_parts(h, lp, cfg: Config, tail_u, tail_c, tail_v, pos, active) -> dict:
    """CCA's one token for every slot, part by part: ``h (S, E)`` at
    positions ``pos (S,)`` behind the slots' tails ``tail_u (K0 - 1, S,
    1280)``, ``tail_c (K1 - 1, S, 1280)``, ``tail_v (S, 128)`` -> ``q (S,
    H, D)``, ``k (S, KV, D)``, ``v (S, KV * D)`` and the tails shifted by one
    token — an inactive slot's stay."""
    u, hv = _latents(h, lp, cfg)
    with jax.named_scope("cca.conv"):
        wu = jnp.concatenate([tail_u, u[None].astype(tail_u.dtype)])
        c0 = _conv0(wu, lp)
        wc = jnp.concatenate([tail_c, c0[None].astype(tail_c.dtype)])
        c1 = _conv1(wc, lp, cfg)
    q, k = _mix(u, c1, lp, pos, cfg, h.dtype)
    with jax.named_scope("cca.v"):
        v = _shift(hv, tail_v.astype(hv.dtype))
        keep = active[:, None]
        tails = dict(
            tail_u=jnp.where(keep, wu[1:], tail_u),
            tail_c=jnp.where(keep, wc[1:], tail_c),
            tail_v=jnp.where(
                keep, hv[:, tail_v.shape[-1]:].astype(tail_v.dtype), tail_v
            ),
        )
    return dict(q=q, k=k, v=v, **tails)


def _cca_out(o, lp):
    with jax.named_scope("cca.out"):
        return jnp.einsum("...f,fe->...e", o.reshape(o.shape[:-2] + (-1,)), lp["wo"])


def _merge(x, f, res):
    """``(a_r * x + b_r) + (a_o * f + b_o)``: the residual add of either
    sublayer, ``res (4, E)`` its learned scales and biases; float32 inside,
    the stream's dtype out."""
    with jax.named_scope("res.scale"):
        a_r, b_r, a_o, b_o = _f32(res)
        return ((a_r * _f32(x) + b_r) + (a_o * _f32(f) + b_o)).astype(x.dtype)


def _router_probs(z, lp, cfg: Config):
    """The router's 17 probabilities of the depth state ``z (T, R)`` as
    summed: its norm, the MLP and the softmax, float32, the products at
    HIGHEST."""
    hp = lax.Precision.HIGHEST
    with jax.named_scope("router.mlp"):
        a = z * lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True) + cfg.norm_eps)
        a = a * _f32(lp["r_ln"])
        for w, b in (("r_w1", "r_b1"), ("r_w2", "r_b2")):
            a = jax.nn.gelu(
                jnp.dot(a, _f32(lp[w]), precision=hp) + _f32(lp[b]), approximate=False
            )
        return jax.nn.softmax(jnp.dot(a, _f32(lp["r_w3"]), precision=hp), axis=-1)


def _router_parts(h2, z_before, lp, cfg: Config) -> dict:
    """The ZAYA1 router of ``h2 (T, E)`` behind the depth state ``z_before
    (T, R)`` float32 (zeros into layer 0): ``z`` as summed (what goes on to
    the next block), the 17 probabilities ``p``, the choice ``e (T,)`` and
    its weight ``w (T,)``; float32 from ``z`` on: a near-tie flipped by
    rounding swaps the sublayer's whole output."""
    with jax.named_scope("router.down"):
        z = jnp.einsum(
            "te,er->tr", h2, lp["r_down"], preferred_element_type=jnp.float32
        ) + _f32(lp["r_down_b"])
        z = z + _f32(lp["r_gam"]) * z_before
    p = _router_probs(z, lp, cfg)
    with jax.named_scope("moe.route"):
        e = jnp.argmax(p + _f32(lp["r_bal"]), axis=-1).astype(jnp.int32)
        w = jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0]
    return dict(z=z, p=p, e=e, w=w)


def _moe(h2, z, lp, cfg: Config, tok_mask, ctr, *, decode: bool, stacks, li):
    """The expert sublayer's ``f`` of ``h2 (T, E)`` behind the depth state
    ``z``: -> ``(f (T, E) float32, the router's parts, counters)``.
    ``stacks`` are every layer's expert weights and ``li`` this layer
    (``moe.experts_grouped`` says why a kernel wants those).  A token whose
    choice is the no-op (index ``n_experts``, which no share holds) adds 0;
    it is counted as routed, and as skipped."""
    r = _router_parts(h2, z, lp, cfg)
    out, ctr = moe.routed_experts(
        h2, lp, r["e"][:, None], r["w"][:, None], cfg.held, tok_mask, ctr,
        decode=decode, kernel=True, stacks=stacks, li=li,
    )
    skipped = jnp.sum((r["e"] == cfg.n_experts) & tok_mask)
    ctr = paged.bump(ctr, _SKIPPED if decode else _P_SKIPPED, skipped)
    return out, r, ctr


def _expert_sublayer(x, z, lp, cfg: Config, tok_mask, ctr, **kw):
    """-> ``(x, the depth state on to the next block, counters)``."""
    f, r, ctr = _moe(rmsnorm(x, lp["ln_m"], cfg.norm_eps), z, lp, cfg, tok_mask, ctr, **kw)
    return _merge(x, f, lp["res_m"]), r["z"], ctr


def _head(params, x, cfg: Config):
    """Final norm and the tied head -> ``(logits, hidden)``."""
    return rms_head(x, params["ln_f"], params["tok_emb"], cfg.norm_eps)


def _scan_layers(params, carry, layer_fn):
    """``layer_fn(carry, li, lp) -> carry`` over the blocks, in one scan.
    ``lp`` is a block's weights WITHOUT its experts: those are read from the
    stacks of every layer (:func:`_stacks`), never sliced out of them."""
    thin = {k: v for k, v in params["layers"].items() if k not in moe.EXPERT_KEYS}

    def body(carry, xs):
        return layer_fn(carry, *xs), None

    n = thin["ln_a"].shape[0]
    carry, _ = lax.scan(body, carry, (jnp.arange(n), thin))
    return carry


def _stacks(params):
    return {k: params["layers"][k] for k in moe.EXPERT_KEYS}


# ---------------------------------------------------------------------------
# full forward (scoring; the registry's ``apply``)
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """Full-sequence logits ``(B, L, V)``, one sequence after the other."""
    stacks = _stacks(params)

    def one(toks):
        L = toks.shape[0]
        mask = jnp.ones((L,), bool)

        def layer(carry, li, lp):
            x, z = carry
            p = _cca_prompt_parts(rmsnorm(x, lp["ln_a"], cfg.norm_eps), lp, cfg, L)
            v = p["v"].reshape(L, cfg.n_kv_heads, -1)
            o = _attend_prompt(p["q"], p["k"], v, "dense")
            x = _merge(x, _cca_out(o, lp), lp["res_a"])
            x, z, _ = _expert_sublayer(
                x, z, lp, cfg, mask, None, decode=False, stacks=stacks, li=li
            )
            return x, z

        z = jnp.zeros((L, cfg.router_hidden_size), jnp.float32)
        x, _ = _scan_layers(params, (params["tok_emb"][toks], z), layer)
        return _head(params, x, cfg)[0]

    return lax.map(one, tokens.astype(jnp.int32))


def apply(params: dict, batch: jax.Array, cfg: Config) -> jax.Array:
    """Serving entry (``JAX_MODEL``): next-token distribution."""
    return jax.nn.softmax(forward(params, batch, cfg)[:, -1].astype(jnp.float32))


# ---------------------------------------------------------------------------
# the cache: a paged pool and a slot's tails, on every layer
# ---------------------------------------------------------------------------

def init_paged_cache(
    cfg: Config, n_slots: int, n_blocks: int, block_size: int,
    dtype=jnp.float32, kv_sharded: bool = False, kv_dtype: str | None = None,
) -> dict:
    """Under the one table: ``k`` and ``v (layers, blocks, block_size,
    kv_heads * head_dim)``, a row holding its heads side by side (the layout
    the paged kernel reads a block in).  Per SLOT, in ``dtype``: ``tail_u
    (layers, cca_time0 - 1, slots, 1280)``, ``tail_c (layers, cca_time1 - 1,
    slots, 1280)``, ``tail_v (layers, slots, 128)``.  ``counters`` are
    ``COUNTERS``, uint32, wrapping."""
    if kv_dtype is not None:
        raise TypeError(
            f"zaya has no int8 pool (kv_cache_dtype={kv_dtype!r}): its rows "
            "are 2 key-value heads a layer already, and what is written "
            "depends on the slot's tails"
        )
    if kv_sharded:
        raise TypeError(
            "zaya has no cache split over a mesh: its per-slot tails "
            f"({', '.join(SLOT_ARRAYS)}) have no placement rule"
        )
    n, d = cfg.n_layers, cfg.head_dim
    pool = (n, n_blocks, block_size, cfg.n_kv_heads * d)
    return {
        **paged.bookkeeping(cfg.max_seq, n_slots, block_size, len(COUNTERS)),
        "k": jnp.zeros(pool, dtype),
        "v": jnp.zeros(pool, dtype),
        "tail_u": jnp.zeros((n, cfg.cca_time0 - 1, n_slots, cfg.latent), dtype),
        "tail_c": jnp.zeros((n, cfg.cca_time1 - 1, n_slots, cfg.latent), dtype),
        "tail_v": jnp.zeros((n, n_slots, cfg.n_kv_heads * d // 2), dtype),
    }


def slot_tail_bytes(cfg: Config, dtype="float32") -> int:
    """HBM bytes of one slot's tails, whatever its context."""
    values = (
        (cfg.cca_time0 + cfg.cca_time1 - 2) * cfg.latent
        + cfg.n_kv_heads * cfg.head_dim // 2
    )
    return paged.slot_bytes(1, cfg.n_layers * values, dtype)


def paged_kv_slot_bytes(
    cfg: Config, block_size: int, *, kv_dtype: str | None = None, dtype="float32"
) -> int:
    """HBM bytes one max_seq slot costs: every layer's K and V of every
    token, and the slot's tails."""
    del block_size, kv_dtype
    per_token = 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers
    return paged.slot_bytes(cfg.max_seq, per_token, dtype) + slot_tail_bytes(cfg, dtype)


def prefill_slot_paged(
    params: dict, tokens: jax.Array, length: jax.Array, slot: jax.Array,
    blocks_row: jax.Array, cache: dict, cfg: Config, *, mesh=None,
    seq_impl: str = "dense", lora=None, adapter_id=None,
    return_hidden: bool = False,
):
    """Prefill ONE request's prompt (the contract of
    ``llama.prefill_slot_paged``): every layer's K (as attended) and V (as
    shifted) go to the blocks reserved for ``slot``, and its tails as of the
    last REAL token go to the SLOT, overwriting what a former request left.
    ``seq_impl="flash"`` attends through the tiled Pallas kernel;
    ``"dense"`` in plain XLA."""
    del mesh, adapter_id
    paged.no_lora("zaya", lora)
    bs = cache["k"].shape[2]
    lp_ = tokens.shape[1]
    real = jnp.arange(lp_) < length
    phys = blocks_row[: lp_ // bs]
    stacks = _stacks(params)
    x = params["tok_emb"][tokens[0]]  # (Lp, E)

    def layer(carry, li, lp):
        x, z, ck, cv, tails, ctr = carry
        p = _cca_prompt_parts(rmsnorm(x, lp["ln_a"], cfg.norm_eps), lp, cfg, length)
        # attend what the pool will hold: the rows as stored
        k, v = p["k"].astype(ck.dtype), p["v"].astype(cv.dtype)
        ck = paged.write_prompt(ck, li, phys, k, bs)
        cv = paged.write_prompt(cv, li, phys, v, bs)
        o = _attend_prompt(p["q"], k, v.reshape(k.shape), seq_impl, length=length)
        x = _merge(x, _cca_out(o, lp), lp["res_a"])
        tails = {
            # a row a tap (models/jamba.py::prefill_slot_paged says why)
            name: _write_tail(tails[name], li, slot, p[name]) for name in SLOT_ARRAYS
        }
        x, z, ctr = _expert_sublayer(
            x, z, lp, cfg, real, ctr, decode=False, stacks=stacks, li=li
        )
        return x, z, ck, cv, tails, ctr

    ctr = paged.bump(cache.get("counters"), moe.PREFILL_TOKENS, length)
    z = jnp.zeros((lp_, cfg.router_hidden_size), jnp.float32)
    x, _, ck, cv, tails, ctr = _scan_layers(
        params,
        (x, z, cache["k"], cache["v"], {n: cache[n] for n in SLOT_ARRAYS}, ctr),
        layer,
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - 1, {"k": ck, "v": cv, **tails}, ctr,
        slot, length, blocks_row, return_hidden, _head,
    )


def _write_tail(tail, li, slot, rows):
    """One slot's rows of layer ``li`` into a tail as it is carried:
    ``rows (taps, C)`` into ``(layers, taps, slots, C)``, or ``(C,)`` into
    ``(layers, slots, C)``."""
    rows = rows.astype(tail.dtype)
    if tail.ndim == 3:
        return lax.dynamic_update_slice(tail, rows[None, None], (li, slot, 0))
    for j in range(rows.shape[0]):
        tail = lax.dynamic_update_slice(tail, rows[j][None, None, None], (li, j, slot, 0))
    return tail


def decode_slots_paged(
    params: dict, tokens: jax.Array, cache: dict, active: jax.Array,
    cfg: Config, *, window: int | None = None, kernel: bool = False,
    lora=None, adapter_ids=None, kv_sharded: bool = False,
):
    """One decode step for every slot (the contract of
    ``llama.decode_slots_paged``): every layer's convolutions read the
    slot's tails and shift them, K and V are written to the pool and read
    through the table.  ``window`` (static) bounds the table's columns read;
    ``kernel`` (static) reads through the Pallas paged kernel, each slot's
    live blocks alone."""
    del adapter_ids, kv_sharded
    paged.no_lora("zaya", lora)
    pos = cache["pos"]
    S = tokens.shape[0]
    bs = cache["k"].shape[2]
    write_blk, write_off, read_blk = paged.decode_frame(
        cache, active, bs, window, cfg.max_seq
    )
    stacks = _stacks(params)
    x = params["tok_emb"][tokens]  # (S, E)

    def layer(carry, li, lp):
        x, z, ck, cv, tails, ctr = carry
        mine = {
            n: lax.dynamic_index_in_dim(t, li, keepdims=False) for n, t in tails.items()
        }
        p = _cca_step_parts(
            rmsnorm(x, lp["ln_a"], cfg.norm_eps), lp, cfg, **mine, pos=pos,
            active=active,
        )
        ck = ck.at[li, write_blk, write_off].set(p["k"].reshape(S, -1).astype(ck.dtype))
        cv = cv.at[li, write_blk, write_off].set(p["v"].astype(cv.dtype))
        o = paged.attend_paged(p["q"], ck, cv, li, read_blk, pos, active, kernel=kernel)
        x = _merge(x, _cca_out(o, lp), lp["res_a"])
        tails = {
            n: lax.dynamic_update_index_in_dim(t, p[n], li, 0) for n, t in tails.items()
        }
        x, z, ctr = _expert_sublayer(
            x, z, lp, cfg, active, ctr, decode=True, stacks=stacks, li=li
        )
        return x, z, ck, cv, tails, ctr

    ctr = paged.bump(cache.get("counters"), moe.STEPS, 1)
    ctr = paged.bump(ctr, _STEPS, 1)
    ctr = paged.bump(
        ctr, _ROWS_LIVE, cfg.n_layers * jnp.sum(jnp.where(active, pos + 1, 0))
    )
    z = jnp.zeros((S, cfg.router_hidden_size), jnp.float32)
    x, _, ck, cv, tails, ctr = _scan_layers(
        params,
        (x, z, cache["k"], cache["v"], {n: cache[n] for n in SLOT_ARRAYS}, ctr),
        layer,
    )
    out = dict(cache)
    out.update(k=ck, v=cv, **tails, pos=jnp.where(active, pos + 1, pos))
    if ctr is not None:
        out["counters"] = ctr
    return _head(params, x, cfg)[0], out
