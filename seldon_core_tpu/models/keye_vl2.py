"""Keye-VL-2.0 decoder (``model_type: KeyeVL2``, Keye-VL-2.0-30B-A3B) for
generative serving: the third family under the contract
``executor/generation.py::GenerativeModel`` reads.  The language model only:
the vision tower is out of scope.  ``mrope_section [16, 24, 24]`` splits the
64 rotary frequencies among three position components; a text token's three
components are equal, so on text M-RoPE IS one-dimensional RoPE at the
token's position, and text positions are what is served.

One layer, ``x (T, hidden)`` (sequential pre-norm block, no bias anywhere)::

    h   = RMSNorm(x; g1, eps)
    q   = RoPE(RMSNorm_head(h Wq))   (T, H, D)     k = RoPE(RMSNorm_head(h Wk))  (T, KV, D)
    v   = h Wv                       (T, KV, D)    rotate-half over all D dims, theta
    qI  = RoPE(h WqI)                (T, HI, DI)   kI = RoPE(LayerNorm(h WkI))   (T, DI)  one index key a token
    wI  = h Ww                       (T, HI)
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])              for s <= t      (float32)
    S_t = the min(topk, t + 1) keys s <= t with the largest I[t, s]                 (ties: lower s first)
    o[t, a] = sum_{s in S_t} softmax_{s in S_t}(q[t, a] . k[s, a // G] / sqrt(D)) v[s, a // G]
                                                                  all H heads share S_t
    x   = x + o Wo
    h2  = RMSNorm(x; g2)
    p   = softmax(h2 Wr) over all experts, float32;  E_t = top-k of p;  w_e = p_e / sum_{e' in E_t} p_e'
    x   = x + sum_{e in E_t, e held here} w_e * Wd_e(silu(Wg_e h2) * Wu_e h2)       no shared expert
    logits = RMSNorm(x_L; gf) W_head                                                 untied

A positive factor on ``I`` (DeepSeek's ``n_heads^-1/2 * head_dim^-1/2``) does
not change ``S_t`` and is left out.  Assumed (the configuration's file lists
the same, each with its reason): (a) QK-norm per head; (b) the indexer reads
the normed hidden ``h``; (c) LayerNorm (weight and bias) on the index key and
rotate-half RoPE over all index dims at the layer's theta; (d)
``q_chunk_size`` / ``kv_chunk_size`` are an implementation's scoring tiles,
``topk`` counts tokens; (e) the Hadamard rotation and FP8 of DeepSeek's
indexer are an implementation's and are not served.  The index scores, the
top-``topk``, the router's softmax and top-k run in float32 (a near-tie
flipped by bfloat16 swaps a key or an expert, which is not rounding noise).

The paged pool is uniform and holds a THIRD per-token array beside K and V:
the index keys ``ik (layers, blocks, DI, block)`` under the same table (a
block transposed, its tokens along the lanes: ``init_paged_cache``; only
``paged.write_transposed``, ``paged.by_token`` (here ``_ik_write``,
``_ik_by_token``) and ``_select_rows`` know which way round a block lies),
so prefix reuse shares a block's index keys with its K/V.
Prefill, suffix prefill and decode write a token's index key where they
write its K/V.

Decode, a layer: with ``kernel`` the selection is one Pallas kernel, a slot
a grid step (``ops/sparse_attention.py::select_decode_topk``): the slot's
LIVE blocks of index keys copied from the pool by table entry, scored in
VMEM, the ``topk``-th score found by bisection, the selected positions
packed to the front, ascending; nothing but the positions goes to HBM.
Without it, the XLA lines the kernel is held to: the whole static window's
keys gathered, scored, masked past ``pos`` and sorted (``lax.top_k``).
Either way the slot then attends those rows of the pool alone, addressed
through the table (``sparse_decode_attention``: an XLA gather of the rows;
Mosaic takes no copy of one row of a tiled pool).  A static window of
``topk`` or fewer goes the dense way (with ``kernel`` through
``ops/paged_attention.py``).  Prefill
(``seq_impl="flash"``): a rung of ``topk`` or fewer is causal attention
(every key is selected); a longer one takes its queries ``QUERY_CHUNK`` at a
time — ``select_topk_mask`` makes a chunk's selection (scores in tiles that
never leave VMEM, the exact ``topk``-th score of a row by bisection, ties to
the lower positions) as one int8 a pair, and ``masked_flash_attention`` runs
the tiled attention under it: eight query heads of a kv head a step, over the
key tiles that start at or before the strip's last query alone, its running
max and denominator whole vregs and a score selected once (PERF.md §6, PR 44
and PR 46: what a tile does and why).  The other way — gathering a query's
``topk`` rows — would move ``topk`` x 2 KB x 2 for every query and layer
(200 GB a 24k prompt) and was not built.

Expert products are ``models/moe.py``'s under a softmax route, chosen by its
one rule of static shapes (``moe.experts_plan``): grouped in a prompt; in a
decode step the kernel that streams only the experts some token chose (8
slots x top-8 touch a third of 128), and the dense products for a caller
that hands ``_moe`` no stack.  ``experts_held`` means what it means there.
``COUNTERS`` keeps that module's names and adds the selection's five.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models import moe, paged
from seldon_core_tpu.models.common import annotate_params
from seldon_core_tpu.models.layers import add, layernorm, rms_head, rope
# benchmark/reference/kinds/keye_vl2_decoder.py reads ``_rmsnorm`` here
from seldon_core_tpu.models.layers import rmsnorm as _rmsnorm
from seldon_core_tpu.models.layers import sample_tokens  # noqa: F401  (contract)
# a block of index keys lies transposed in the pool
from seldon_core_tpu.models.paged import by_token as _ik_by_token
from seldon_core_tpu.models.paged import write_transposed as _ik_write
from seldon_core_tpu.ops.sparse_attention import (
    index_scores,
    masked_flash_attention,
    select_decode_topk,
    select_decode_topk_reference,
    select_topk_mask,
    sparse_decode_attention,
)

# query rows one pass of the XLA attention scores at once
ATTN_Q_CHUNK = 128
# queries whose selection (one int8 a key) is alive at once in a prompt
QUERY_CHUNK = 8192

COUNTERS = moe.COUNTERS + (
    "dsa.keys_scored",            # decode: index keys scored, layers, slots and steps summed
    "dsa.keys_selected",          # decode: keys attended, likewise
    "dsa.prefill_keys_scored",    # prefill: (query, key) pairs scored, in units of 1,024, layers summed
    "dsa.prefill_keys_selected",  # prefill: pairs attended, likewise
    "dsa.key_blocks_read",        # decode: pool blocks of index keys read, as the selection itself counts them, likewise
)
_STEPS, _P_TOKENS = moe.STEPS, moe.PREFILL_TOKENS
_SCORED, _SELECTED, _P_SCORED, _P_SELECTED, _BLOCKS_READ = range(
    len(moe.COUNTERS), len(moe.COUNTERS) + 5
)
# every per-token array of the paged pool, under the one table
POOL_ARRAYS = ("k", "v", "ik")


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 151936
    hidden: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    ffn: int = 768  # ONE expert's width (moe_intermediate_size)
    n_experts: int = 128  # the router's width: always the whole model's
    experts_per_tok: int = 8
    experts_held: str = ""  # "first:count"; empty holds all n_experts
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    max_seq: int = 32768
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    index_dtype: str = "float32"  # "bfloat16" is a control, never served
    select: str = "on"  # "off" attends every key: a control, never served

    def __post_init__(self):
        moe.held_range(self.experts_held, self.n_experts)  # or refused
        if self.n_heads % self.n_kv_heads or self.head_dim % 2 or self.index_dim % 2:
            raise ValueError(
                "n_heads must group over n_kv_heads; head_dim, index_dim even"
            )
        if self.select not in ("on", "off") or self.index_topk < 1:
            raise ValueError("select is 'on' or 'off'; index_topk at least 1")

    @property
    def held(self) -> tuple[int, int]:
        """(first, count) of the routed experts this share holds."""
        return moe.held_range(self.experts_held, self.n_experts)

    @property
    def selects(self) -> bool:
        return self.select == "on"

    @classmethod
    def tiny(cls, max_seq: int = 64, **kw) -> "Config":
        """Test-scale config: same code paths, toy sizes."""
        base = dict(
            vocab_size=256, hidden=64, n_layers=2, n_heads=8, n_kv_heads=2,
            head_dim=8, ffn=32, n_experts=16, experts_per_tok=4,
            index_heads=4, index_dim=8, index_topk=8, max_seq=max_seq,
            rope_theta=10000.0,
        )
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> dict:
    """Random weights IN ``dtype``, one layer (an expert leaf: one expert of
    one layer) at a time, so that the float32 temporary is never larger than
    that; expert ``e`` of layer ``l`` has the same values in every share
    that holds it."""
    c = cfg
    first, count = c.held
    keys = jax.random.split(rng, 13)
    layer_ids = jnp.arange(c.n_layers)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)

    def stacked(key, shape, fan_in):
        return lax.map(
            lambda l: normal(jax.random.fold_in(key, l), shape, fan_in), layer_ids
        )

    def experts(key, shape, fan_in):
        ids = first + jnp.arange(count)

        def layer(l):
            lk = jax.random.fold_in(key, l)
            return lax.map(
                lambda e: normal(jax.random.fold_in(lk, e), shape, fan_in), ids
            )

        return lax.map(layer, layer_ids)

    def rows(key, n, width, fan_in):
        """An (n, width) matrix in slabs of at most 8,192 rows."""
        slab = max(s for s in range(1, min(n, 8192) + 1) if n % s == 0)
        return lax.map(
            lambda i: normal(jax.random.fold_in(key, i), (slab, width), fan_in),
            jnp.arange(n // slab),
        ).reshape(n, width)

    e, f, h, kv, d = c.hidden, c.ffn, c.n_heads, c.n_kv_heads, c.head_dim
    hi, di = c.index_heads, c.index_dim
    ones = lambda *shape: jnp.ones((c.n_layers,) + shape, dtype)  # noqa: E731
    return {
        "tok_emb": rows(keys[0], c.vocab_size, e, e),
        "layers": {
            "ln1": ones(e), "ln2": ones(e),
            "q_norm": ones(d), "k_norm": ones(d),
            "wq": stacked(keys[1], (e, h, d), e),
            "wk": stacked(keys[2], (e, kv, d), e),
            "wv": stacked(keys[3], (e, kv, d), e),
            "wo": stacked(keys[4], (h, d, e), h * d),
            "wqi": stacked(keys[5], (e, hi, di), e),
            "wki": stacked(keys[6], (e, di), e),
            "wwi": stacked(keys[7], (e, hi), e),
            "ki_norm_w": ones(di),
            "ki_norm_b": jnp.zeros((c.n_layers, di), dtype),
            "w_router": stacked(keys[8], (e, c.n_experts), e),
            "we_gate": experts(keys[9], (e, f), e),
            "we_up": experts(keys[10], (e, f), e),
            "we_down": experts(keys[11], (f, e), f),
        },
        "ln_f": jnp.ones((e,), dtype),
        # (vocab, hidden) as the embedding is, read transposed
        "head": rows(keys[12], c.vocab_size, e, e),
    }


_AXIS_RULES = [
    (r"layers/wq$", ("layers", "embed", "heads", "head_dim")),
    (r"layers/w[kv]$", ("layers", "embed", "kv_heads", "head_dim")),
    (r"layers/wo", ("layers", "heads", "head_dim", "embed")),
    (r"layers/wqi", ("layers", "embed", None, None)),
    (r"layers/w(ki|wi|_router)", ("layers", "embed", None)),
    (r"layers/we_(gate|up)", ("layers", None, "embed", "mlp")),
    (r"layers/we_down", ("layers", None, "mlp", "embed")),
    (r"layers/ln[12]", ("layers", "embed")),
    (r"layers/(q_norm|k_norm|ki_norm_[wb])", ("layers", None)),
    (r"tok_emb|head", ("vocab", "embed")),
    (r"ln_f", ("embed",)),
]


def param_logical_axes(params):
    return annotate_params(params, _AXIS_RULES)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _qkv(h, lp, cfg: Config, positions):
    """Projections of ``h (..., L, E)``: per-head RMSNorm on q and k, then
    rotate-half RoPE over all ``head_dim``."""
    q = jnp.einsum("...le,ehd->...lhd", h, lp["wq"])
    k = jnp.einsum("...le,ehd->...lhd", h, lp["wk"])
    v = jnp.einsum("...le,ehd->...lhd", h, lp["wv"])
    q = rope(_rmsnorm(q, lp["q_norm"], cfg.norm_eps), positions, cfg.rope_theta)
    k = rope(_rmsnorm(k, lp["k_norm"], cfg.norm_eps), positions, cfg.rope_theta)
    return q, k, v


def _index(h, lp, cfg: Config, positions):
    """The indexer's projections of ``h (..., L, E)``: queries ``(..., L,
    HI, DI)``, head weights ``(..., L, HI)`` float32, the one key a token
    ``(..., L, DI)``."""
    with jax.named_scope("attn.index"):
        qi = jnp.einsum("...le,ejd->...ljd", h, lp["wqi"])
        ki = layernorm(
            jnp.einsum("...le,ed->...ld", h, lp["wki"]), lp["ki_norm_w"],
            cfg.norm_eps,
        ) + lp["ki_norm_b"]
        wi = jnp.einsum(
            "...le,ej->...lj", h.astype(jnp.float32),
            lp["wwi"].astype(jnp.float32), precision=lax.Precision.HIGHEST,
        )
        qi = rope(qi, positions, cfg.rope_theta)
        ki = rope(ki[..., None, :], positions, cfg.rope_theta)[..., 0, :]
        return qi, wi, ki


def _score_dtype(cfg: Config):
    return jnp.bfloat16 if cfg.index_dtype == "bfloat16" else jnp.float32


def _attend(q, k, v, index, qpos, kpos, kvalid, cfg: Config):
    """Attention of one sequence in plain XLA, ``ATTN_Q_CHUNK`` queries at
    a pass.  q: (Lq, H, D); k, v: (Lk, KV, D), the keys in the order of
    their positions.  ``index = (qI, wI, kI)`` engages the selection (each
    query gathers and attends the ``index_topk`` seen keys it scores
    highest); ``None`` attends every seen key."""
    lq, nh, d = q.shape
    lk, kvh = k.shape[:2]
    g = nh // kvh
    scale = 1.0 / math.sqrt(d)
    cq = lq if lq <= ATTN_Q_CHUNK or lq % ATTN_Q_CHUNK else ATTN_Q_CHUNK
    if index is not None and lk <= cfg.index_topk:
        index = None  # every seen key is selected

    def one(args):
        qc, pc, ic = args
        qg = qc.reshape(cq, kvh, g, d)
        seen = (kpos[None, :] <= pc[:, None]) & kvalid[None, :]
        ks, vs, rows = k, v, "skd"
        if ic is not None:
            with jax.named_scope("attn.select"):
                scores = index_scores(ic[0], ic[1], index[2], _score_dtype(cfg))
                scores = jnp.where(seen, scores, -jnp.inf)
                # best first, the lower position first among equals
                vals, idx = lax.top_k(scores, cfg.index_topk)
                seen = vals > -jnp.inf  # (cq, topk): fewer seen than topk
                ks, vs, rows = k[idx], v[idx], "qskd"
        with jax.named_scope("attn.sparse"):
            s = jnp.einsum(
                f"qkgd,{rows}->kgqs", qg, ks, preferred_element_type=jnp.float32
            ) * scale
            s = jnp.where(seen[None, None], s, jnp.finfo(jnp.float32).min)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(f"kgqs,{rows}->qkgd", p.astype(vs.dtype), vs)
        return o.reshape(cq, nh, d)

    n = lq // cq
    chunks = (
        q.reshape(n, cq, nh, d), qpos.reshape(n, cq),
        None if index is None else (
            index[0].reshape((n, cq) + index[0].shape[1:]),
            index[1].reshape(n, cq, -1),
        ),
    )
    return lax.map(one, chunks).reshape(lq, nh, d)


def _attend_flash(q, k, v, index, cfg: Config):
    """A prompt's own attention through the Pallas kernels: causal where
    every key is selected, else the selection of ``QUERY_CHUNK`` queries at
    a time as a mask and the tiled attention under it.  q: (L, H, D); k, v:
    (L, KV, D) at positions 0..L-1."""
    from seldon_core_tpu.ops.flash_attention import flash_attention

    L = q.shape[0]
    qt, kt, vt = (a.transpose(1, 0, 2) for a in (q, k, v))
    if index is None or L <= cfg.index_topk:
        blk = min(512, L)
        with jax.named_scope("attn.sparse"):
            out = flash_attention(
                qt[None], kt[None], vt[None], causal=True, block_q=blk,
                block_k=blk,
            )[0]
        return out.transpose(1, 0, 2)
    qi, wi, ki = index
    qc = L
    if L > QUERY_CHUNK:  # a rung is a power of two or three times one
        qc = QUERY_CHUNK if L % QUERY_CHUNK == 0 else QUERY_CHUNK // 2
    outs = []
    for q0 in range(0, L, qc):
        lk = q0 + qc
        with jax.named_scope("attn.select"):
            mask = select_topk_mask(
                qi[q0:lk], wi[q0:lk], ki[:lk], topk=cfg.index_topk,
                q_offset=q0, score_dtype=_score_dtype(cfg),
            )
        with jax.named_scope("attn.sparse"):
            outs.append(masked_flash_attention(
                qt[:, q0:lk], kt[:, :lk], vt[:, :lk], mask, q_offset=q0
            ))
    return jnp.concatenate(outs, axis=1).transpose(1, 0, 2)


def _attend_prompt(q, k, v, index, cfg: Config, seq_impl: str):
    """A whole prompt's own attention at positions ``0 .. L - 1``, by the
    Pallas kernels (``"flash"``) or in chunked XLA."""
    if seq_impl == "flash":
        return _attend_flash(q, k, v, index, cfg)
    pos = jnp.arange(q.shape[0])
    return _attend(q, k, v, index, pos, pos, jnp.ones(pos.shape, bool), cfg)


def _select_rows(qi, wi, cik, li, read_blk, pos, active, cfg: Config, *,
                 kernel: bool):
    """A decode step's selection on layer ``li``: ``(rows (S, index_topk)
    int32, blocks_read (S,) int32)`` — the pool rows (``ck.reshape(-1,
    kvd)``'s) of the exact top ``index_topk`` seen keys of each slot, and
    the blocks of index keys read to find them.  ``cik`` the index keys'
    pool as carried.  ``kernel``: one Pallas kernel over each slot's LIVE
    blocks, rows by position; else the XLA lines over the whole static
    window, best first."""
    nb, di, bs = cik.shape[1:]
    with jax.named_scope("attn.select"):
        select = select_decode_topk if kernel else select_decode_topk_reference
        return select(
            qi[:, 0].astype(cik.dtype), wi[:, 0], cik.reshape((-1, di, bs)),
            read_blk + li * nb, jnp.where(active, pos, -1),
            topk=cfg.index_topk, score_dtype=_score_dtype(cfg),
        )


def _decode_read(q, ck, cv, li, read_blk, pos, active, n_sel, rows, *,
                 kernel: bool):
    """One decode query a slot over layer ``li`` of the pools ``ck``, ``cv``
    ``(layers, blocks, block, kvd)``: the first ``n_sel`` of the selected
    pool ``rows`` alone; with ``rows`` None every seen key, through the paged
    kernel with ``kernel``.  ``q (S, 1, H, D)`` -> ``(S, H, D)``."""
    S = q.shape[0]
    nb, bs, kvd = ck.shape[1:]
    flat = (ck.shape[0] * nb * bs, kvd)
    if rows is None and not kernel:
        rows = (
            ((read_blk + li * nb) * bs)[:, :, None] + jnp.arange(bs)
        ).reshape(S, -1)
    with jax.named_scope("attn.sparse"):
        if rows is not None:
            return sparse_decode_attention(
                q[:, 0], ck.reshape(flat), cv.reshape(flat), rows, n_sel
            )
        from seldon_core_tpu.ops import paged_decode_attention

        return paged_decode_attention(
            q, ck.reshape((-1, bs, kvd)), cv.reshape((-1, bs, kvd)),
            read_blk + li * nb, pos, active=active,
        )[:, 0]


def _decode_attention(q, qi, wi, ck, cv, cik, li, read_blk, pos, active, n_sel,
                      cfg: Config, *, sparse: bool, kernel: bool):
    """One decode query a slot over layer ``li`` of the pools ``ck``, ``cv``,
    ``cik (layers, blocks, block, ...)`` — every pool BY TOKEN — the step's
    own token written already.  ``q (S, 1, H, D)``, ``qi (S, 1, HI, DI)``,
    ``wi (S, 1, HI)``; ``read_blk (S, wb)`` the table's blocks of the static
    window.  ``sparse``: ``_select_rows`` then ``_decode_read`` of those
    rows; else every seen key.  Returns ``(S, H, D)``.  The two parts as
    ``decode_slots_paged`` composes them, for a caller that holds its index
    keys by token (the benchmark's reference kind): the served step calls
    the parts on the pool as it carries it."""
    rows = None
    if sparse:
        rows, _ = _select_rows(
            qi, wi, _ik_by_token(cik), li, read_blk, pos, active, cfg, kernel=kernel
        )
    return _decode_read(
        q, ck, cv, li, read_blk, pos, active, n_sel, rows, kernel=kernel
    )


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _route(h2, w_router, cfg: Config):
    """Softmax over ALL experts -> (idx (T, K) int32, weights (T, K) f32
    renormalised over the K chosen), in float32 throughout."""
    logits = jnp.dot(
        h2.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    vals, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.experts_per_tok)
    w = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w.astype(jnp.float32)


def _moe(h2, lp, cfg: Config, tok_mask, counters, *, decode: bool,
         stacks=None, li=None):
    """``h2 (T, E)`` -> (the held experts' part (T, E) float32, counters).
    ``stacks`` are the expert weights of every layer and ``li`` this layer
    (``moe.experts_grouped`` says why a kernel wants those and not
    ``lp``'s); a caller without them gets the dense products where the
    touched-only kernel would have run.  A prompt's grouped product runs
    over this layer alone, every expert of it held, in long passes."""
    with jax.named_scope("moe.route"):
        idx, w = _route(h2, lp["w_router"], cfg)
    return moe.routed_experts(
        h2, lp, idx, w, cfg.held, tok_mask, counters, decode=decode,
        kernel=stacks is not None, stacks=stacks, li=li, group_alone=True,
        group_chunk=moe.GROUP_CHUNK_WHOLE,
    )


def _after_attention(x, o, lp, cfg: Config, tok_mask, ctr, *, decode: bool,
                     stacks=None, li=None):
    """The rest of a layer behind its attention ``o (T, H, D)``: the output
    projection and the expert layer (``stacks``, ``li``: :func:`_moe`), each
    added to the stream."""
    x = add(x, jnp.einsum("thd,hde->te", o, lp["wo"]))
    h2 = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
    ffn, ctr = _moe(h2, lp, cfg, tok_mask, ctr, decode=decode, stacks=stacks, li=li)
    return add(x, ffn), ctr


def _head(params, h, cfg: Config):
    return rms_head(h, params["ln_f"], params["head"], cfg.norm_eps)


def _scan_layers(params, carry, layer_fn):
    """``layer_fn(carry, li, lp) -> carry`` over the layers, in one scan."""

    def body(carry, xs):
        return layer_fn(carry, *xs), None

    n = params["layers"]["wq"].shape[0]
    carry, _ = lax.scan(body, carry, (jnp.arange(n), params["layers"]))
    return carry


def _pairs(n):
    """sum_{t < n} (t + 1): the (query, key) pairs a causal prompt sees."""
    return n * (n + 1) // 2


def _selected_pairs(n, topk: int):
    """sum_{t < n} min(topk, t + 1)."""
    return jnp.where(n <= topk, _pairs(n), _pairs(topk) + (n - topk) * topk)


def _count_prompt(ctr, cfg: Config, lo, hi):
    """The prompt counters for queries at positions ``[lo, hi)``: pairs in
    units of 1,024 (a long prompt's pairs over six layers pass 2**32)."""
    if ctr is None:
        return None
    k = cfg.index_topk if cfg.selects else cfg.max_seq
    scored = (_pairs(hi) - _pairs(lo)) >> 10
    chosen = (_selected_pairs(hi, k) - _selected_pairs(lo, k)) >> 10
    ctr = paged.bump(ctr, _P_TOKENS, hi - lo)
    ctr = paged.bump(ctr, _P_SCORED, scored * cfg.n_layers)
    return paged.bump(ctr, _P_SELECTED, chosen * cfg.n_layers)


# ---------------------------------------------------------------------------
# full forward (scoring; the registry's ``apply``)
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """Full-sequence logits ``(B, L, V)``, one sequence after the other."""

    def one(toks):
        L = toks.shape[0]
        pos = jnp.arange(L)
        ok = jnp.ones((L,), bool)

        def layer(x, li, lp):
            h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = _qkv(h, lp, cfg, pos)
            index = _index(h, lp, cfg, pos) if cfg.selects else None
            o = _attend(q, k, v, index, pos, pos, ok, cfg)
            return _after_attention(x, o, lp, cfg, ok, None, decode=False)[0]

        x = _scan_layers(params, params["tok_emb"][toks], layer)
        return _head(params, x, cfg)[0]

    return lax.map(one, tokens.astype(jnp.int32))


def apply(params: dict, batch: jax.Array, cfg: Config) -> jax.Array:
    """Serving entry (``JAX_MODEL``): next-token distribution."""
    return jax.nn.softmax(forward(params, batch, cfg)[:, -1].astype(jnp.float32))


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------

def init_paged_cache(
    cfg: Config, n_slots: int, n_blocks: int, block_size: int,
    dtype=jnp.float32, kv_sharded: bool = False, kv_dtype: str | None = None,
) -> dict:
    """The uniform pool of ``models/llama.py`` with a third per-token array:
    ``k`` and ``v`` ``(layers, blocks, block_size, kv_heads * head_dim)``, a
    row holding its kv heads side by side, and the index keys ``ik (layers,
    blocks, index_dim, block_size)``, all under one table.  A block of index
    keys is carried TRANSPOSED, its tokens along the lanes: 64 wide by
    tokens it is no whole tile (XLA keeps it in HBM this way round whatever
    the shape says), and the decode step's selection copies whole blocks
    from the pool as they lie (``ops/sparse_attention.py::
    select_decode_topk``).  ``counters`` are ``COUNTERS``, uint32, wrapping."""
    if kv_dtype is not None:
        raise TypeError(
            f"keye_vl2 has no int8 KV pool (kv_cache_dtype={kv_dtype!r}): the "
            "index keys ride the pool beside K/V and have no quantised form"
        )
    if kv_sharded:
        raise TypeError(
            "keye_vl2 has no pool split over a mesh: its index keys have one "
            "head and its decode read is single-device"
        )
    rows = (cfg.n_layers, n_blocks, block_size)
    return {
        **paged.bookkeeping(cfg.max_seq, n_slots, block_size, len(COUNTERS)),
        "k": jnp.zeros(rows + (cfg.n_kv_heads * cfg.head_dim,), dtype),
        "v": jnp.zeros(rows + (cfg.n_kv_heads * cfg.head_dim,), dtype),
        "ik": jnp.zeros(
            (cfg.n_layers, n_blocks, cfg.index_dim, block_size), dtype
        ),
    }


def paged_kv_slot_bytes(
    cfg: Config, block_size: int, *, kv_dtype: str | None = None, dtype="float32"
) -> int:
    """HBM bytes one max_seq slot costs in the paged pool: K, V and the
    index key of every token on every layer."""
    del block_size, kv_dtype
    per_token = (2 * cfg.n_kv_heads * cfg.head_dim + cfg.index_dim) * cfg.n_layers
    return paged.slot_bytes(cfg.max_seq, per_token, dtype)


def prefill_slot_paged(
    params: dict, tokens: jax.Array, length: jax.Array, slot: jax.Array,
    blocks_row: jax.Array, cache: dict, cfg: Config, *, mesh=None,
    seq_impl: str = "dense", lora=None, adapter_id=None,
    return_hidden: bool = False,
):
    """Prefill ONE request's prompt into the blocks reserved for ``slot``
    (the contract of ``llama.prefill_slot_paged``).  ``seq_impl="flash"``
    runs the selection and the attention through the Pallas kernels;
    ``"dense"`` through chunked XLA."""
    del mesh, adapter_id
    paged.no_lora("keye_vl2", lora)
    bs = cache["k"].shape[2]
    lp_ = tokens.shape[1]
    pos = jnp.arange(lp_)
    real = pos < length
    phys = blocks_row[: lp_ // bs]
    x = params["tok_emb"][tokens[0]]  # (Lp, E)

    def layer(carry, li, lp):
        x, ck, cv, cik, ctr = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, cfg, pos)
        qi, wi, ki = _index(h, lp, cfg, pos)
        ck = paged.write_prompt(ck, li, phys, k, bs)
        cv = paged.write_prompt(cv, li, phys, v, bs)
        cik = _ik_write(cik, li, phys, ki)
        index = (qi, wi, ki.astype(cik.dtype)) if cfg.selects else None
        o = _attend_prompt(q, k, v, index, cfg, seq_impl)
        x, ctr = _after_attention(x, o, lp, cfg, real, ctr, decode=False)
        return x, ck, cv, cik, ctr

    ctr = _count_prompt(cache.get("counters"), cfg, 0, length)
    x, ck, cv, cik, ctr = _scan_layers(
        params, (x, cache["k"], cache["v"], cache["ik"], ctr), layer
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - 1, {"k": ck, "v": cv, "ik": cik}, ctr,
        slot, length, blocks_row, return_hidden, _head,
    )


def prefill_suffix_paged(
    params: dict, tokens: jax.Array, prefix_len: jax.Array, length: jax.Array,
    slot: jax.Array, blocks_row: jax.Array, suffix_blocks: jax.Array,
    cache: dict, cfg: Config, *, prefix_window: int, lora=None,
    adapter_id=None, return_hidden: bool = False, kv_sharded: bool = False,
):
    """Prefill the SUFFIX of a prompt whose first ``prefix_len`` tokens have
    K/V and index keys in the slot's table blocks already (prefix reuse,
    prompt chunks): the contract of ``llama.prefill_suffix_paged``.  Suffix
    queries score and attend over [the prefix read from the pool ++ the
    suffix], in XLA."""
    del adapter_id, kv_sharded
    paged.no_lora("keye_vl2", lora)
    bs = cache["k"].shape[2]
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

    def layer(carry, li, lp):
        x, ck, cv, cik, ctr = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, cfg, qpos)
        qi, wi, ki = _index(h, lp, cfg, qpos)

        def behind(old, new):
            """[the prefix's blocks ``old`` (by token), flattened ++ the
            suffix's own rows, as they will be stored]."""
            old = old.reshape((pb * bs,) + new.shape[1:])
            return jnp.concatenate([old, new.astype(old.dtype)]).astype(new.dtype)

        index = None
        if cfg.selects:
            index = (qi, wi, behind(_ik_by_token(cik[li, read_idx]), ki))
        o = _attend(
            q, behind(ck[li, read_idx], k), behind(cv[li, read_idx], v), index,
            qpos, kpos, kvalid, cfg,
        )
        ck = paged.write_prompt(ck, li, suffix_blocks, k, bs)
        cv = paged.write_prompt(cv, li, suffix_blocks, v, bs)
        cik = _ik_write(cik, li, suffix_blocks, ki)
        x, ctr = _after_attention(x, o, lp, cfg, real, ctr, decode=False)
        return x, ck, cv, cik, ctr

    ctr = _count_prompt(cache.get("counters"), cfg, prefix_len, length)
    x, ck, cv, cik, ctr = _scan_layers(
        params, (x, cache["k"], cache["v"], cache["ik"], ctr), layer
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - prefix_len - 1,
        {"k": ck, "v": cv, "ik": cik}, ctr, slot, length, blocks_row,
        return_hidden, _head,
    )


def decode_slots_paged(
    params: dict, tokens: jax.Array, cache: dict, active: jax.Array,
    cfg: Config, *, window: int | None = None, kernel: bool = False,
    lora=None, adapter_ids=None, kv_sharded: bool = False,
):
    """One decode step for every slot against the paged cache (the contract
    of ``llama.decode_slots_paged``).  ``window`` (static) bounds the rows
    scored; past ``index_topk`` of them a slot attends the rows it selects
    (gathered in XLA).  ``kernel`` (static) makes that selection in the
    Pallas kernel over each slot's live blocks
    (``ops/sparse_attention.py::select_decode_topk``), and takes the dense
    way, a window of ``index_topk`` or fewer, through the paged
    decode-attention kernel (``ops/paged_attention.py``)."""
    del adapter_ids, kv_sharded
    paged.no_lora("keye_vl2", lora)
    pos = cache["pos"]
    S = tokens.shape[0]
    bs = cache["k"].shape[2]
    write_blk, write_off, read_blk = paged.decode_frame(
        cache, active, bs, window, cfg.max_seq
    )
    W = read_blk.shape[1] * bs
    kvd = cfg.n_kv_heads * cfg.head_dim
    topk = cfg.index_topk
    sparse = cfg.selects and W > topk
    n_seen = jnp.where(active, jnp.minimum(pos + 1, W), 0)
    n_sel = jnp.minimum(n_seen, topk) if sparse else n_seen
    x = params["tok_emb"][tokens]  # (S, E)
    stacks = {k: params["layers"][k] for k in moe.EXPERT_KEYS}

    def layer(carry, li, lp):
        x, ck, cv, cik, ctr = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(h[:, None], lp, cfg, pos[:, None])
        qi, wi, ki = _index(h[:, None], lp, cfg, pos[:, None])
        ck = ck.at[li, write_blk, write_off].set(k.reshape(S, kvd).astype(ck.dtype))
        cv = cv.at[li, write_blk, write_off].set(v.reshape(S, kvd).astype(cv.dtype))
        cik = _ik_write(cik, li, write_blk, ki[:, 0], write_off)
        rows = None
        if sparse:
            rows, read = _select_rows(
                qi, wi, cik, li, read_blk, pos, active, cfg, kernel=kernel
            )
            ctr = paged.bump(ctr, _BLOCKS_READ, jnp.sum(read))
        o = _decode_read(
            q, ck, cv, li, read_blk, pos, active, n_sel, rows, kernel=kernel
        )
        x, ctr = _after_attention(
            x, o, lp, cfg, active, ctr, decode=True, stacks=stacks, li=li
        )
        return x, ck, cv, cik, ctr

    ctr = paged.bump(cache.get("counters"), _STEPS, 1)
    ctr = paged.bump(ctr, _SCORED, jnp.sum(n_seen) * cfg.n_layers)
    ctr = paged.bump(ctr, _SELECTED, jnp.sum(n_sel) * cfg.n_layers)
    x, ck, cv, cik, ctr = _scan_layers(
        params, (x, cache["k"], cache["v"], cache["ik"], ctr), layer
    )
    out = dict(cache)
    out.update(k=ck, v=cv, ik=cik, pos=jnp.where(active, pos + 1, pos))
    if ctr is not None:
        out["counters"] = ctr
    return _head(params, x, cfg)[0], out
