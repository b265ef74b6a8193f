"""Kimi-K2 decoder (``model_type: kimi_k2``, Kimi-K2.6, 1.04T-A32B; the layer
equations are DeepSeek-V3's, whose modelling code ``kimi_k2`` inherits) for
generative serving: the fourth family under the contract
``executor/generation.py::GenerativeModel`` reads.  The language model only
(the vision tower, MoonViT, is out of scope); ``num_nextn_predict_layers``
is 0: no multi-token prediction.

One layer, ``x (T, 7168)`` (sequential pre-norm block, no bias anywhere)::

    h    = RMSNorm(x; g1, eps 1e-5)
    cq   = RMSNorm(h Wqa; gq)                                    (T, 1536)
    [qn | qr] = cq Wqb                                           (T, 64, 128 | 64)      qr = RoPE_y(qr)
    [ckv | kr] = h Wkva                                          (T, 512 | 64)          one rotary key a token
    c    = RMSNorm(ckv; gkv)                                     kr = RoPE_y(kr)
    POOL, a token a layer:  c (512) and kr (64), bfloat16: 1,152 B.  Nothing by head.
    sigma = 192^-1/2 * m^2,   m = 0.1 * mscale_all_dim * ln(factor) + 1 = 1.41589   (sigma 0.14468)

    a prompt (expanded):   [kn | v] = c Wkvb                     (T, 64, 128 | 128)
       s[t,u,a] = sigma * (qn[t,a].kn[u,a] + qr[t,a].kr[u])      u <= t
       o[t,a]   = sum_u softmax_u(s[t,u,a]) v[u,a]               (T, 64, 128)
    a decode step (absorbed):  Wkvb by head = [W_UK[a] (128, 512) | W_UV[a] (128, 512)]
       ql[t,a]  = qn[t,a] W_UK[a]                                (64, 512)
       s[t,u,a] = sigma * (ql[t,a].c[u] + qr[t,a].kr[u])         u over the slot's live rows of the pool
       ol[t,a]  = sum_u softmax_u(s[t,u,a]) c[u]                 (64, 512)
       o[t,a]   = W_UV[a] ol[t,a]                                (64, 128)
    x    = x + o Wo                                              (8192 -> 7168)

    RoPE_y: 32 adjacent pairs (2i, 2i+1), theta 50,000, YaRN: f_i = theta^(-2i/64);
       low = floor(64 ln(4096 / (32 * 2 pi)) / (2 ln theta)) = 8,  high = ceil(64 ln(4096 / (1 * 2 pi)) / (2 ln theta)) = 20
       r_i = clip((i - low) / (high - low), 0, 1);   f'_i = (1 - r_i) f_i + r_i f_i / 64
       cos and sin carry mscale / mscale_all_dim's ratio of attention factors = 1.0

    h2   = RMSNorm(x; g2)
    layer 0:        x = x + Wd(silu(Wg h2) * Wu h2)              18,432 wide, no router
    layers 1..:     s = sigmoid(h2 Wr) over all 384, float32
       E_t = top-8 of (s + b)          b: the learned per-expert bias (topk_method noaux_tc): chooses, never weighs;
                                       n_group 1, topk_group 1: no limit by group
       w_e = 2.827 * s_e / sum_{e' in E_t} s_e'
       x = x + sum_{e in E_t, e held here} w_e * Wd_e(silu(Wg_e h2) * Wu_e h2)         experts 2,048 wide
             + Wsd(silu(Wsg h2) * Wsu h2)                                              one shared expert, 2,048 wide
    logits = RMSNorm(x_L; gf) W_head                                                   untied

The two attentions are the same mathematics (``qn.kn = qn.(W_UK c) = (qn
W_UK).c``); ``tests/test_kimi_k2.py`` holds them to each other.  Assumed (the
configuration's file lists the same, each with its reason): (a) adjacent
rotary pairs, the checkpoint layout of the DeepSeek-V3 modelling code (with
seeded weights either layout is the same model up to a permutation of
columns); (b) RMSNorm on both latents with ``rms_norm_eps``; (c) ``b`` is
drawn from the seed, small against the scores' spread
(:data:`ROUTER_BIAS_STD`), so that it moves some choices and not all; (d) ``ep_size``, ``seq_aux``, ``moe_layer_freq`` 1
describe training or say nothing of a layer.  The router's product, sigmoid,
bias, top-8 and normalisation run in float32 (a near-tie at the 8th place
flipped by bfloat16 swaps an expert, which is not rounding noise).

The paged pool is uniform, one table, one kind of block, and holds NOTHING by
head: ``c (layers, blocks, block, kv_lora_rank)`` and ``kr (layers, blocks,
qk_rope_dim, block)`` — a block of rotary keys TRANSPOSED, its tokens along
the lanes (``init_paged_cache`` says why; only ``paged.write_transposed``
and ``paged.by_token``, here ``_kr_write`` and ``_kr_by_token``, know which
way round a block lies).  576 values a token a layer, no padding.  Prefix reuse
shares a block's latents with nothing further.

Two attentions for one model.  A prompt up-projects its latents to keys and
values by head (transient, never in the pool) and runs the tiled kernel
(``ops/flash_attention.py``: keys 192 wide, values 128, the scale ``sigma``)
with ``seq_impl="flash"``, chunked XLA otherwise; a suffix behind a reused
prefix reads the prefix's ``c`` and ``kr`` from the pool and attends in the
expanded form too, in XLA.  A decode step never makes K or V: with ``kernel``
the read is ``ops/mla_attention.py::mla_decode_attention`` (each slot's live
blocks copied by table entry, a latent row read once), else the XLA lines it
is held to.  Two kinds of layer in one stack: ``params["dense_layers"]`` (the
leading ``n_dense_layers``, a SwiGLU) and ``params["layers"]`` (the expert
layers), each a scan of its own; the pool's layer axis runs over both.
``experts_held`` means what it means in ``models/moe.py``, whose expert
products (:func:`~seldon_core_tpu.models.moe.experts_plan`) these are.
``COUNTERS`` keeps that module's names, counted over the expert layers, and
adds the latent cache's three.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models import moe, paged
from seldon_core_tpu.models.common import annotate_params
from seldon_core_tpu.models.layers import add, flash_prompt, rms_head, rope_pairs
# benchmark/reference/kinds/kimi_k2_decoder.py reads ``_rmsnorm`` and
# ``_kr_by_token`` here (a block of rotary keys lies transposed in the pool)
from seldon_core_tpu.models.layers import rmsnorm as _rmsnorm
from seldon_core_tpu.models.layers import sample_tokens  # noqa: F401  (contract)
from seldon_core_tpu.models.paged import by_token as _kr_by_token
from seldon_core_tpu.models.paged import write_transposed as _kr_write

# query rows one pass of the XLA expanded attention scores at once
ATTN_Q_CHUNK = 128
# rows of a prompt one pass of the dense layer's 18,432-wide MLP takes
MLP_CHUNK = 4096
# the drawn selection bias's standard deviation (assumed (c)): an eightieth of
# the sigmoid scores' own spread (0.21), seven tenths of the median distance
# between a token's 8th and 9th score of 384 (0.0036).  It moves the choice
# of one token in four and an expert's load by some 5 %; ten times as much
# moves 96 % of the choices and the load of a share's 12 experts by 16 % a
# layer, so that a share's work is its seed's and not the model's (PERF.md
# §6, PR 43)
ROUTER_BIAS_STD = 0.0025

COUNTERS = moe.COUNTERS + (
    "mla.rows_read",              # decode: latent rows the step's read brought in, as the read itself counts them,
                                  # layers, slots and steps summed
    "mla.prefill_rows_expanded",  # prefill: latent rows a prompt or suffix program up-projected, layers summed
    "mla.rows_live",              # decode: latent rows the step HAS to read, from the live slots' positions alone
                                  # (a slot at position p attends p + 1 rows a layer), layers, slots and steps summed
)
_STEPS, _P_TOKENS = moe.STEPS, moe.PREFILL_TOKENS
_ROWS_READ, _P_EXPANDED, _ROWS_LIVE = (len(moe.COUNTERS) + i for i in range(3))
# every per-token array of the paged pool: there is no "k" and no "v"
POOL_ARRAYS = ("c", "kr")


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 163840
    hidden: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 1  # first_k_dense_replace
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dense: int = 18432  # intermediate_size: the leading dense layers' MLP
    ffn: int = 2048  # ONE expert's width (moe_intermediate_size), routed and shared
    n_experts: int = 384  # the router's width: always the whole model's
    experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 2.827
    experts_held: str = ""  # "first:count"; empty holds all n_experts
    max_seq: int = 262144
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-5
    # negative controls, never served
    decode_rope: str = "on"  # "off" leaves qr.kr out of a decode step's score
    softmax_mscale: str = "on"  # "off" is sigma without m^2
    decode_score_dtype: str = "float32"  # "bfloat16" rounds a decode step's scores
    prompt_score_dtype: str = "float32"  # "bfloat16" rounds a prompt's scores

    def __post_init__(self):
        moe.held_range(self.experts_held, self.n_experts)  # or refused
        if self.qk_rope_dim % 2 or not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError(
                "qk_rope_dim even; n_dense_layers leading layers of n_layers, "
                "at least one expert layer behind them"
            )
        if self.decode_rope not in ("on", "off") or self.softmax_mscale not in ("on", "off"):
            raise ValueError("decode_rope and softmax_mscale are 'on' or 'off'")

    @property
    def held(self) -> tuple[int, int]:
        """(first, count) of the routed experts this share holds."""
        return moe.held_range(self.experts_held, self.n_experts)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def mscale(self) -> float:
        """YaRN's attention factor ``m`` over all dims."""
        if self.rope_factor <= 1:
            return 1.0
        return 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        """``sigma``: the key width's ``^-1/2`` times ``m^2``."""
        base = (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        return base * (self.mscale ** 2 if self.softmax_mscale == "on" else 1.0)

    @classmethod
    def tiny(cls, max_seq: int = 64, **kw) -> "Config":
        """Test-scale config: same code paths, toy sizes; a YaRN original
        length well under the contexts a test reaches."""
        base = dict(
            vocab_size=256, hidden=64, n_layers=3, n_dense_layers=1, n_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
            v_head_dim=8, ffn_dense=96, ffn=32, n_experts=16,
            experts_per_tok=4, max_seq=max_seq, rope_theta=10000.0,
            # the ramp r = [0, 1/2, 1, 1] over the four rotary pairs
            rope_factor=8.0, rope_original_max=16, rope_beta_fast=4.0,
            rope_beta_slow=0.25,
        )
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> dict:
    """Random weights IN ``dtype``, one layer (an expert leaf: one expert of
    one layer) at a time, so that the float32 temporary is never larger than
    that; expert ``e`` of expert layer ``l`` has the same values in every
    share that holds it.  The router's bias ``b`` is drawn with a
    standard deviation of :data:`ROUTER_BIAS_STD` (assumed (c))."""
    c = cfg
    first, count = c.held
    keys = jax.random.split(rng, 20)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)

    def stacked(key, n, shape, fan_in):
        return lax.map(
            lambda l: normal(jax.random.fold_in(key, l), shape, fan_in),
            jnp.arange(n),
        )

    def experts(key, shape, fan_in, ids):
        def layer(l):
            lk = jax.random.fold_in(key, l)
            return lax.map(
                lambda e: normal(jax.random.fold_in(lk, e), shape, fan_in), ids
            )

        return lax.map(layer, jnp.arange(c.n_moe_layers))

    def rows(key, n, width, fan_in):
        """An (n, width) matrix in slabs of at most 8,192 rows."""
        slab = max(s for s in range(1, min(n, 8192) + 1) if n % s == 0)
        return lax.map(
            lambda i: normal(jax.random.fold_in(key, i), (slab, width), fan_in),
            jnp.arange(n // slab),
        ).reshape(n, width)

    e, f, fd, h = c.hidden, c.ffn, c.ffn_dense, c.n_heads
    ql, cl, dn, dr, dv = (
        c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim,
    )

    def attention(key, n):
        ks = jax.random.split(key, 6)
        ones = lambda width: jnp.ones((n, width), dtype)  # noqa: E731
        return {
            "ln1": ones(e), "ln2": ones(e), "q_norm": ones(ql), "kv_norm": ones(cl),
            "wqa": stacked(ks[0], n, (e, ql), e),
            "wqb": stacked(ks[1], n, (ql, h, dn + dr), ql),
            "wkva": stacked(ks[2], n, (e, cl + dr), e),
            # Wkvb by head, its two halves apart: W_UK and W_UV
            "wuk": stacked(ks[3], n, (cl, h, dn), cl),
            "wuv": stacked(ks[4], n, (cl, h, dv), cl),
            "wo": stacked(ks[5], n, (h, dv, e), h * dv),
        }

    held_ids = first + jnp.arange(count)
    shared_ids = jnp.arange(c.n_shared_experts)
    nm = c.n_moe_layers
    return {
        "tok_emb": rows(keys[0], c.vocab_size, e, e),
        "dense_layers": {
            **attention(keys[1], c.n_dense_layers),
            "w_gate": stacked(keys[2], c.n_dense_layers, (e, fd), e),
            "w_up": stacked(keys[3], c.n_dense_layers, (e, fd), e),
            "w_down": stacked(keys[4], c.n_dense_layers, (fd, e), fd),
        },
        "layers": {
            **attention(keys[5], nm),
            "w_router": stacked(keys[6], nm, (e, c.n_experts), e),
            "b_router": (
                ROUTER_BIAS_STD * jax.random.normal(keys[7], (nm, c.n_experts))
            ).astype(dtype),
            "we_gate": experts(keys[8], (e, f), e, held_ids),
            "we_up": experts(keys[9], (e, f), e, held_ids),
            "we_down": experts(keys[10], (f, e), f, held_ids),
            "ws_gate": experts(keys[11], (e, f), e, shared_ids),
            "ws_up": experts(keys[12], (e, f), e, shared_ids),
            "ws_down": experts(keys[13], (f, e), f, shared_ids),
        },
        "ln_f": jnp.ones((e,), dtype),
        # (vocab, hidden) as the embedding is, read transposed
        "head": rows(keys[14], c.vocab_size, e, e),
    }


_AXIS_RULES = [
    (r"layers/wqa|layers/wkva|layers/w_router", ("layers", "embed", None)),
    (r"layers/wqb|layers/wu[kv]", ("layers", None, "heads", "head_dim")),
    (r"layers/wo", ("layers", "heads", "head_dim", "embed")),
    (r"layers/w[es]_(gate|up)", ("layers", None, "embed", "mlp")),
    (r"layers/w[es]_down", ("layers", None, "mlp", "embed")),
    (r"layers/w_(gate|up)", ("layers", "embed", "mlp")),
    (r"layers/w_down", ("layers", "mlp", "embed")),
    (r"layers/ln[12]", ("layers", "embed")),
    (r"layers/(q_norm|kv_norm|b_router)", ("layers", None)),
    (r"tok_emb|head", ("vocab", "embed")),
    (r"ln_f", ("embed",)),
]


def param_logical_axes(params):
    return annotate_params(params, _AXIS_RULES)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def yarn_freqs(cfg: Config) -> jax.Array:
    """The ``qk_rope_dim / 2`` rotary frequencies under YaRN (the module's
    docstring: ``f'_i``)."""
    d = cfg.qk_rope_dim
    f = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if cfg.rope_factor <= 1:
        return f

    def correction(rotations):
        return d * math.log(
            cfg.rope_original_max / (rotations * 2 * math.pi)
        ) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), d - 1)
    span = (high - low) if high != low else 0.001
    r = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / span, 0.0, 1.0)
    return (1.0 - r) * f + r * f / cfg.rope_factor


def _rope_y(x, positions, cfg: Config):
    """``RoPE_y`` of ``x (..., L, H, qk_rope_dim)``.  The ratio of attention
    factors that cos and sin carry is ``mscale / mscale_all_dim``'s: 1.0 as
    published, and a graph that states another is refused here."""
    if cfg.rope_factor > 1 and cfg.rope_mscale != cfg.rope_mscale_all_dim:
        raise ValueError("rope_mscale other than rope_mscale_all_dim is not served")
    return rope_pairs(x, positions, cfg.rope_theta, freqs=yarn_freqs(cfg))


def _latents(h, lp, cfg: Config, positions):
    """The projections of ``h (..., L, E)``: ``qn (..., L, H, dn)``, ``qr
    (..., L, H, dr)`` rotated, the normed latent ``c (..., L, C)`` and the
    token's one rotary key ``kr (..., L, dr)``, rotated."""
    dn, cl = cfg.qk_nope_dim, cfg.kv_lora_rank
    with jax.named_scope("attn.q_latent"):
        cq = _rmsnorm(
            jnp.einsum("...le,eq->...lq", h, lp["wqa"]), lp["q_norm"], cfg.norm_eps
        )
        q = jnp.einsum("...lq,qhd->...lhd", cq, lp["wqb"])
        qn, qr = q[..., :dn], _rope_y(q[..., dn:], positions, cfg)
    with jax.named_scope("attn.kv_latent"):
        ckv = jnp.einsum("...le,ec->...lc", h, lp["wkva"])
        c = _rmsnorm(ckv[..., :cl], lp["kv_norm"], cfg.norm_eps)
        kr = _rope_y(ckv[..., None, cl:], positions, cfg)[..., 0, :]
    return qn, qr, c, kr


def _expand(c, kr, lp, cfg: Config):
    """A prompt's keys and values by head from its latents: ``k (L, H, dn +
    dr)`` (the token's one rotary key under every head) and ``v (L, H,
    dv)``.  Transient: never in the pool."""
    with jax.named_scope("attn.expand"):
        kn = jnp.einsum("lc,chd->lhd", c, lp["wuk"])
        v = jnp.einsum("lc,chd->lhd", c, lp["wuv"])
        krh = jnp.broadcast_to(kr[:, None, :], kn.shape[:2] + kr.shape[-1:])
        return jnp.concatenate([kn, krh.astype(kn.dtype)], axis=-1), v


def _attend(q, k, v, qpos, kpos, kvalid, scale, score_dtype=None):
    """Expanded attention of one sequence in plain XLA, ``ATTN_Q_CHUNK``
    queries at a pass.  q: (Lq, H, D); k: (Lk, H, D); v: (Lk, H, Dv);
    scores and softmax in float32 (``score_dtype``, a negative control,
    rounds the scores first)."""
    lq, nh, d = q.shape
    cq = lq if lq <= ATTN_Q_CHUNK or lq % ATTN_Q_CHUNK else ATTN_Q_CHUNK

    def one(args):
        qc, pc = args
        s = jnp.einsum(
            "qhd,khd->hqk", qc, k, preferred_element_type=jnp.float32
        ) * scale
        if score_dtype is not None:
            s = s.astype(score_dtype).astype(jnp.float32)
        seen = (kpos[None, :] <= pc[:, None]) & kvalid[None, :]
        s = jnp.where(seen[None], s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v)

    out = lax.map(one, (q.reshape(lq // cq, cq, nh, d), qpos.reshape(-1, cq)))
    return out.reshape(lq, nh, v.shape[-1])


def _prompt_score_dtype(cfg: Config):
    """None as served; the control's type to round a prompt's scores to."""
    return jnp.bfloat16 if cfg.prompt_score_dtype == "bfloat16" else None


def _attend_prompt(qn, qr, c, kr, lp, cfg: Config, seq_impl: str, length=None):
    """A whole prompt's own attention at positions ``0 .. L - 1`` in the
    EXPANDED form: by the tiled Pallas kernel (``"flash"``; with the prompt's
    real ``length`` it leaves out the rung's padded query tiles) or in
    chunked XLA.  -> (L, H, dv)."""
    k, v = _expand(c, kr, lp, cfg)
    q = jnp.concatenate([qn, qr], axis=-1)
    L = q.shape[0]
    rounded = _prompt_score_dtype(cfg)
    with jax.named_scope("attn.prompt"):
        if seq_impl == "flash":
            return flash_prompt(
                q, k, v, scale=cfg.softmax_scale, score_dtype=rounded,
                length=length,
            )
        pos = jnp.arange(L)
        return _attend(
            q, k, v, pos, pos, jnp.ones((L,), bool), cfg.softmax_scale, rounded
        )


def _decode_attention(qn, qr, cc, ckr, li, lp, read_blk, pos, active,
                      cfg: Config, *, kernel: bool):
    """One decode query a slot in the ABSORBED form over layer ``li`` of the
    pools ``cc (layers, blocks, block, C)`` and ``ckr (layers, blocks, dr,
    block)`` as carried, the step's own token written already.  ``qn (S, H,
    dn)``, ``qr (S, H, dr)``; ``read_blk (S, wb)`` the table's blocks of the
    static window.  Returns ``(o (S, H, dv), rows_read (S,))``.  No key and
    no value by head is made."""
    from seldon_core_tpu.ops.mla_attention import (
        mla_decode_attention,
        mla_decode_attention_reference,
    )

    nb, bs, cl = cc.shape[1:]
    with jax.named_scope("attn.absorb"):
        ql = jnp.einsum("shd,chd->shc", qn, lp["wuk"])
    with jax.named_scope("attn.latent"):
        read = mla_decode_attention if kernel else mla_decode_attention_reference
        ol, rows = read(
            ql, qr, cc.reshape((-1, bs, cl)), ckr.reshape((-1,) + ckr.shape[2:]),
            read_blk + li * nb, pos, scale=cfg.softmax_scale, active=active,
            rope=cfg.decode_rope == "on",
            score_dtype=(
                jnp.bfloat16 if cfg.decode_score_dtype == "bfloat16"
                else jnp.float32
            ),
        )
    with jax.named_scope("attn.absorb"):
        return jnp.einsum("shc,chd->shd", ol, lp["wuv"]), rows


# ---------------------------------------------------------------------------
# the MLPs
# ---------------------------------------------------------------------------

def _route(h2, w_router, b_router, cfg: Config):
    """Sigmoid scores over ALL experts -> (idx (T, K) int32, weights (T, K)
    f32).  The top-K is of score PLUS the learned bias; the weights are the
    chosen scores alone, renormalised over the K and scaled by
    ``routed_scale``.  float32 throughout."""
    logits = jnp.dot(
        h2.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + b_router.astype(jnp.float32), cfg.experts_per_tok)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    w = cfg.routed_scale * vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w.astype(jnp.float32)


def _moe(h2, lp, cfg: Config, tok_mask, counters, *, decode: bool,
         stacks=None, li=None):
    """``h2 (T, E)`` -> (routed + shared (T, E) float32, counters).
    ``stacks`` are the expert weights of every expert layer and ``li`` this
    one's place among them (``moe.experts_grouped`` says why a kernel wants
    those and not ``lp``'s); a caller without them gets the dense
    products."""
    with jax.named_scope("moe.route"):
        idx, w = _route(h2, lp["w_router"], lp["b_router"], cfg)
    return moe.routed_experts(
        h2, lp, idx, w, cfg.held, tok_mask, counters, decode=decode,
        kernel=stacks is not None, stacks=stacks, li=li, shared="sum",
    )


def _mlp_dense(h2, lp):
    """The leading layers' SwiGLU, ``MLP_CHUNK`` rows at a pass: a 16,384
    rung's 18,432-wide product is 0.6 GB a matrix whole.  -> (T, E) f32."""
    def one(x):
        g = jnp.einsum("te,ef->tf", x, lp["w_gate"])
        u = jnp.einsum("te,ef->tf", x, lp["w_up"])
        return jnp.einsum(
            "tf,fe->te", jax.nn.silu(g) * u, lp["w_down"],
            preferred_element_type=jnp.float32,
        )

    with jax.named_scope("mlp.dense"):
        T = h2.shape[0]
        if T <= MLP_CHUNK or T % MLP_CHUNK:
            return one(h2)
        return lax.map(one, h2.reshape(T // MLP_CHUNK, MLP_CHUNK, -1)).reshape(T, -1)


def _after_attention(x, o, lp, cfg: Config, tok_mask, ctr, *, dense: bool,
                     decode: bool, stacks=None, li=None):
    """The rest of a layer behind its attention ``o (T, H, dv)``: the output
    projection and the layer's MLP (``dense``: the SwiGLU; else the expert
    layer, ``stacks`` and ``li`` as :func:`_moe` takes them), each added to
    the stream."""
    with jax.named_scope("attn.out"):
        x = add(x, jnp.einsum("thd,hde->te", o, lp["wo"]))
    h2 = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if dense:
        return add(x, _mlp_dense(h2, lp)), ctr
    ffn, ctr = _moe(h2, lp, cfg, tok_mask, ctr, decode=decode, stacks=stacks, li=li)
    return add(x, ffn), ctr


def _head(params, h, cfg: Config):
    return rms_head(h, params["ln_f"], params["head"], cfg.norm_eps)


def _scan_layers(params, cfg: Config, carry, layer_fn):
    """``layer_fn(carry, li, mi, lp, dense) -> carry`` over the leading dense
    layers and then the expert layers, a scan each: ``li`` the layer's place
    in the pool, ``mi`` its place in its own stack."""
    nd = cfg.n_dense_layers
    for dense, stack, first in (
        (True, params["dense_layers"], 0), (False, params["layers"], nd),
    ):
        n = stack["wqa"].shape[0]
        if n == 0:
            continue
        if n == 1:  # nothing to scan: every index static
            lp = jax.tree.map(lambda a: a[0], stack)
            carry = layer_fn(carry, first, 0, lp, dense)
            continue

        def body(carry, xs, dense=dense, first=first):
            return layer_fn(carry, first + xs[0], xs[0], xs[1], dense), None

        carry, _ = lax.scan(body, carry, (jnp.arange(n), stack))
    return carry


# ---------------------------------------------------------------------------
# full forward (scoring; the registry's ``apply``)
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """Full-sequence logits ``(B, L, V)``, one sequence after the other, in
    the expanded form."""

    def one(toks):
        L = toks.shape[0]
        pos = jnp.arange(L)
        ok = jnp.ones((L,), bool)

        def layer(x, li, mi, lp, dense):
            h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            qn, qr, c, kr = _latents(h, lp, cfg, pos)
            o = _attend_prompt(qn, qr, c, kr, lp, cfg, "dense")
            return _after_attention(
                x, o, lp, cfg, ok, None, dense=dense, decode=False
            )[0]

        x = _scan_layers(params, cfg, params["tok_emb"][toks], layer)
        return _head(params, x, cfg)[0]

    return lax.map(one, tokens.astype(jnp.int32))


def apply(params: dict, batch: jax.Array, cfg: Config) -> jax.Array:
    """Serving entry (``JAX_MODEL``): next-token distribution."""
    return jax.nn.softmax(forward(params, batch, cfg)[:, -1].astype(jnp.float32))


# ---------------------------------------------------------------------------
# the latent paged cache
# ---------------------------------------------------------------------------

def init_paged_cache(
    cfg: Config, n_slots: int, n_blocks: int, block_size: int,
    dtype=jnp.float32, kv_sharded: bool = False, kv_dtype: str | None = None,
) -> dict:
    """The uniform pool, one table, holding a token's LATENTS and nothing by
    head: ``c (layers, blocks, block_size, kv_lora_rank)``, a row a token,
    and ``kr (layers, blocks, qk_rope_dim, block_size)``, a block of rotary
    keys carried TRANSPOSED, its tokens along the lanes: 64 wide by tokens
    it is no whole 128-lane tile (the array would be padded to twice its
    bytes in HBM, and the decode read copies whole blocks as they lie:
    ``ops/mla_attention.py``).  ``kv_lora_rank + qk_rope_dim`` values a token
    a layer (576: 1,152 B in bfloat16), no padding.  ``counters`` are
    ``COUNTERS``, uint32, wrapping."""
    if kv_dtype is not None:
        raise TypeError(
            f"kimi_k2 has no int8 latent pool (kv_cache_dtype={kv_dtype!r}): a "
            "latent row is key and value at once and has no quantised form here"
        )
    if kv_sharded:
        raise TypeError(
            "kimi_k2 has no pool split over a mesh: its latents have no head "
            "axis to split by and its decode read is single-device"
        )
    return {
        **paged.bookkeeping(cfg.max_seq, n_slots, block_size, len(COUNTERS)),
        "c": jnp.zeros(
            (cfg.n_layers, n_blocks, block_size, cfg.kv_lora_rank), dtype
        ),
        "kr": jnp.zeros(
            (cfg.n_layers, n_blocks, cfg.qk_rope_dim, block_size), dtype
        ),
    }


def paged_kv_slot_bytes(
    cfg: Config, block_size: int, *, kv_dtype: str | None = None, dtype="float32"
) -> int:
    """HBM bytes one max_seq slot costs in the paged pool: the latent and
    the rotary key of every token on every layer."""
    del block_size, kv_dtype
    per_token = (cfg.kv_lora_rank + cfg.qk_rope_dim) * cfg.n_layers
    return paged.slot_bytes(cfg.max_seq, per_token, dtype)


def prefill_slot_paged(
    params: dict, tokens: jax.Array, length: jax.Array, slot: jax.Array,
    blocks_row: jax.Array, cache: dict, cfg: Config, *, mesh=None,
    seq_impl: str = "dense", lora=None, adapter_id=None,
    return_hidden: bool = False,
):
    """Prefill ONE request's prompt into the blocks reserved for ``slot``
    (the contract of ``llama.prefill_slot_paged``): the latents go to the
    pool, the attention runs in the expanded form.  ``seq_impl="flash"``
    through the tiled Pallas kernel; ``"dense"`` through chunked XLA."""
    del mesh, adapter_id
    paged.no_lora("kimi_k2", lora)
    bs = cache["c"].shape[2]
    lp_ = tokens.shape[1]
    pos = jnp.arange(lp_)
    real = pos < length
    phys = blocks_row[: lp_ // bs]
    x = params["tok_emb"][tokens[0]]  # (Lp, E)
    stacks = {k: params["layers"][k] for k in moe.EXPERT_KEYS}

    def layer(carry, li, mi, lp, dense):
        x, cc, ckr, ctr = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        qn, qr, c, kr = _latents(h, lp, cfg, pos)
        cc = paged.write_prompt(cc, li, phys, c, bs)
        ckr = _kr_write(ckr, li, phys, kr)
        # attend what the pool now holds: the latents as stored
        o = _attend_prompt(
            qn, qr, c.astype(cc.dtype), kr.astype(ckr.dtype), lp, cfg, seq_impl,
            length=length,
        )
        x, ctr = _after_attention(
            x, o, lp, cfg, real, ctr, dense=dense, decode=False,
            stacks=stacks, li=mi,
        )
        return x, cc, ckr, ctr

    ctr = paged.bump(cache.get("counters"), _P_TOKENS, length)
    ctr = paged.bump(ctr, _P_EXPANDED, lp_ * cfg.n_layers)
    x, cc, ckr, ctr = _scan_layers(
        params, cfg, (x, cache["c"], cache["kr"], ctr), layer
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - 1, {"c": cc, "kr": ckr}, ctr, slot,
        length, blocks_row, return_hidden, _head,
    )


def prefill_suffix_paged(
    params: dict, tokens: jax.Array, prefix_len: jax.Array, length: jax.Array,
    slot: jax.Array, blocks_row: jax.Array, suffix_blocks: jax.Array,
    cache: dict, cfg: Config, *, prefix_window: int, lora=None,
    adapter_id=None, return_hidden: bool = False, kv_sharded: bool = False,
):
    """Prefill the SUFFIX of a prompt whose first ``prefix_len`` tokens have
    their latents in the slot's table blocks already (prefix reuse, prompt
    chunks): the contract of ``llama.prefill_suffix_paged``.  The prefix's
    ``c`` and ``kr`` are read from the pool, up-projected with the suffix's
    own, and the suffix queries attend [prefix ++ suffix] in the expanded
    form, in XLA."""
    del adapter_id, kv_sharded
    paged.no_lora("kimi_k2", lora)
    bs = cache["c"].shape[2]
    ls = tokens.shape[1]
    pb = max(1, int(prefix_window) // bs)
    read_idx = blocks_row[:pb]
    qpos = prefix_len + jnp.arange(ls)
    kpos = jnp.concatenate([jnp.arange(pb * bs), qpos])
    kvalid = jnp.concatenate(
        [jnp.arange(pb * bs) < prefix_len, jnp.ones((ls,), bool)]
    )
    real = qpos < length
    x = params["tok_emb"][tokens[0]]
    stacks = {k: params["layers"][k] for k in moe.EXPERT_KEYS}

    def layer(carry, li, mi, lp, dense):
        x, cc, ckr, ctr = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        qn, qr, c, kr = _latents(h, lp, cfg, qpos)

        def behind(old, new):
            """[the prefix's blocks ``old`` (by token), flattened ++ the
            suffix's own rows, as they will be stored]."""
            old = old.reshape((pb * bs,) + new.shape[1:])
            return jnp.concatenate([old, new.astype(old.dtype)])

        k, v = _expand(
            behind(cc[li, read_idx], c),
            behind(_kr_by_token(ckr[li, read_idx]), kr), lp, cfg,
        )
        with jax.named_scope("attn.prompt"):
            o = _attend(
                jnp.concatenate([qn, qr], axis=-1), k, v, qpos, kpos, kvalid,
                cfg.softmax_scale, _prompt_score_dtype(cfg),
            )
        cc = paged.write_prompt(cc, li, suffix_blocks, c, bs)
        ckr = _kr_write(ckr, li, suffix_blocks, kr)
        x, ctr = _after_attention(
            x, o, lp, cfg, real, ctr, dense=dense, decode=False,
            stacks=stacks, li=mi,
        )
        return x, cc, ckr, ctr

    ctr = paged.bump(cache.get("counters"), _P_TOKENS, length - prefix_len)
    ctr = paged.bump(ctr, _P_EXPANDED, (pb * bs + ls) * cfg.n_layers)
    x, cc, ckr, ctr = _scan_layers(
        params, cfg, (x, cache["c"], cache["kr"], ctr), layer
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - prefix_len - 1, {"c": cc, "kr": ckr},
        ctr, slot, length, blocks_row, return_hidden, _head,
    )


def decode_slots_paged(
    params: dict, tokens: jax.Array, cache: dict, active: jax.Array,
    cfg: Config, *, window: int | None = None, kernel: bool = False,
    lora=None, adapter_ids=None, kv_sharded: bool = False,
):
    """One decode step for every slot against the latent paged cache (the
    contract of ``llama.decode_slots_paged``), in the ABSORBED form: no key
    and no value by head is made.  ``window`` (static) bounds the table's
    columns read; ``kernel`` (static) reads through the Pallas kernel
    (``ops/mla_attention.py``), each slot's live blocks alone."""
    del adapter_ids, kv_sharded
    paged.no_lora("kimi_k2", lora)
    pos = cache["pos"]
    S = tokens.shape[0]
    bs = cache["c"].shape[2]
    write_blk, write_off, read_blk = paged.decode_frame(
        cache, active, bs, window, cfg.max_seq
    )
    x = params["tok_emb"][tokens]  # (S, E)
    stacks = {k: params["layers"][k] for k in moe.EXPERT_KEYS}

    def layer(carry, li, mi, lp, dense):
        x, cc, ckr, ctr = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        qn, qr, c, kr = _latents(h[:, None], lp, cfg, pos[:, None])
        cc = cc.at[li, write_blk, write_off].set(c[:, 0].astype(cc.dtype))
        ckr = _kr_write(ckr, li, write_blk, kr[:, 0], write_off)
        o, rows = _decode_attention(
            qn[:, 0], qr[:, 0], cc, ckr, li, lp, read_blk, pos, active, cfg,
            kernel=kernel,
        )
        ctr = paged.bump(ctr, _ROWS_READ, jnp.sum(rows))
        x, ctr = _after_attention(
            x, o, lp, cfg, active, ctr, dense=dense, decode=True,
            stacks=stacks, li=mi,
        )
        return x, cc, ckr, ctr

    ctr = paged.bump(cache.get("counters"), _STEPS, 1)
    ctr = paged.bump(ctr, _ROWS_LIVE, cfg.n_layers * jnp.sum(jnp.where(active, pos + 1, 0)))
    x, cc, ckr, ctr = _scan_layers(
        params, cfg, (x, cache["c"], cache["kr"], ctr), layer
    )
    out = dict(cache)
    out.update(c=cc, kr=ckr, pos=jnp.where(active, pos + 1, pos))
    if ctr is not None:
        out["counters"] = ctr
    return _head(params, x, cfg)[0], out
