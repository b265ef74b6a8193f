"""Cohere2-MoE decoder (``model_type: cohere2_moe``, Command A+) for
generative serving: the second family under the contract
``executor/generation.py::GenerativeModel`` reads.

One layer, with ``h = LayerNorm(x)`` (mean subtracted, no bias) feeding both
sub-blocks (the parallel block: one norm, no second one)::

    x' = x + Attn_l(h) + MoE(h)
    Attn_l   l % layer_pattern != layer_pattern - 1  ("sliding_attention"):
                 q, k rotated by interleaved-pair RoPE (rope_gptj); key j is
                 visible to query i iff  i - sliding_window < j <= i
             else ("full_attention"): no position encoding; j <= i
             grouped-query attention, softmax(q k^T / sqrt(head_dim)) v, Wo
    MoE      s = sigmoid(h Wr) over ALL n_experts, in float32; T = top-k of s
             w_e = s_e / sum_{e' in T} s_e'            (over all k chosen)
             routed = sum_{e in T, e held here} w_e * Wd_e(silu(Wg_e h) * Wu_e h)
             shared = mean_j Wd'_j(silu(Wg'_j h) * Wu'_j h)
             MoE(h) = routed + shared
    logits = logit_scale * LayerNorm_f(x_L) E^T        (tied embedding)

``experts_held = "first:count"`` is one chip's share of an expert-parallel
deployment: the layer routes over all ``n_experts``, normalises over all
``experts_per_tok`` chosen, and computes only the part its own experts give;
what the absent experts would add is left out (no code stands in for the
other chips) and that partial result goes on to the next layer.  No token is
dropped: there is no capacity limit.  Only the held experts' weights exist
in ``params``; expert ``e`` of layer ``l`` has the same values whichever
share holds it (its key is folded from ``(l, e)``), so the shares of one
layer add up to the uncut layer (``tests/test_cohere2_moe.py``).

The held experts' products are ``models/moe.py``'s, chosen by its one rule
of static shapes (``moe.experts_plan``) behind this family's sigmoid route.

``init_params``, ``models/convert.py`` and a checkpoint carry the attention
projections by head (``wq (layers, E, H, D)``, ``wo (layers, H, D, E)``): the
canonical tree.  The products read them PACKED (:func:`pack_params`: heads
folded, the contracted axis last), the layout an engine makes once at build
and hands its programs; an entry function given the canonical tree packs it
inside its program.

The paged pool is uniform: every layer keeps every token's K/V, and a
sliding layer READS only the blocks that hold its window (the window saves
reads, not memory).  Device counters of the routing ride the cache
(``cache["counters"]``) and are fetched with a decode block's tokens.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models import moe, paged
from seldon_core_tpu.models.common import annotate_params
from seldon_core_tpu.models.layers import flash_prompt, layernorm, rope_pairs
from seldon_core_tpu.models.layers import sample_tokens  # noqa: F401  (contract)

# query rows one pass of the XLA attention scores at once
ATTN_Q_CHUNK = 128
# the XLA decode read gathers the window of this many slots at once, where
# the gathered K and V of all slots together would pass DECODE_GATHER_BYTES
DECODE_SLOT_CHUNK = 8
DECODE_GATHER_BYTES = 256 << 20

COUNTERS = moe.COUNTERS  # the contract reads ``family_mod.COUNTERS``


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 262144
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn: int = 4096  # ONE expert's width, routed and shared alike
    n_experts: int = 128  # the router's width: always the whole model's
    experts_per_tok: int = 8
    n_shared_experts: int = 4
    experts_held: str = ""  # "first:count"; empty holds all n_experts
    sliding_window: int = 4096
    layer_pattern: int = 4  # every layer_pattern-th layer is full, position-free
    max_seq: int = 8192
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    router_dtype: str = "float32"  # "bfloat16" is the control, never served

    def __post_init__(self):
        if self.n_layers % self.layer_pattern:
            raise ValueError(
                f"n_layers {self.n_layers} is not whole periods of "
                f"layer_pattern {self.layer_pattern}"
            )
        moe.held_range(self.experts_held, self.n_experts)  # or refused
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("n_heads must group over n_kv_heads; head_dim even")

    @property
    def held(self) -> tuple[int, int]:
        """(first, count) of the routed experts this share holds."""
        return moe.held_range(self.experts_held, self.n_experts)

    @classmethod
    def tiny(cls, max_seq: int = 64, **kw) -> "Config":
        """Test-scale config: same code paths, toy sizes."""
        base = dict(
            vocab_size=256, hidden=64, n_layers=4, n_heads=8, n_kv_heads=2,
            head_dim=8, ffn=32, n_experts=16, experts_per_tok=4,
            n_shared_experts=2, sliding_window=8, layer_pattern=4,
            max_seq=max_seq, rope_theta=10000.0,
        )
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> dict:
    """Random weights IN ``dtype``: every leaf is made one layer (an expert
    leaf: one expert of one layer) at a time and cast before the next, so
    the float32 temporary is never larger than that — a share sized for one
    chip in bfloat16 is never alive in float32.  Expert ``e`` of layer ``l``
    draws from a key folded from ``(l, e)``: the same values in every share
    that holds it."""
    c = cfg
    first, count = c.held
    keys = jax.random.split(rng, 12)
    layer_ids = jnp.arange(c.n_layers)

    def stacked(key, shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)

        def one(l):
            k = jax.random.fold_in(key, l)
            return (jax.random.normal(k, shape) * scale).astype(dtype)

        return lax.map(one, layer_ids)

    def experts(key, shape, fan_in, ids):
        scale = 1.0 / math.sqrt(fan_in)

        def layer(l):
            lk = jax.random.fold_in(key, l)

            def one(e):
                k = jax.random.fold_in(lk, e)
                return (jax.random.normal(k, shape) * scale).astype(dtype)

            return lax.map(one, ids)

        return lax.map(layer, layer_ids)

    e, f, h, kv, d = c.hidden, c.ffn, c.n_heads, c.n_kv_heads, c.head_dim
    held_ids = first + jnp.arange(count)
    shared_ids = jnp.arange(c.n_shared_experts)
    # the embedding in slabs of rows, for the same reason
    slab = 4096 if c.vocab_size % 4096 == 0 else c.vocab_size
    emb = lax.map(
        lambda i: (
            jax.random.normal(jax.random.fold_in(keys[0], i), (slab, e))
            / math.sqrt(e)
        ).astype(dtype),
        jnp.arange(c.vocab_size // slab),
    ).reshape(c.vocab_size, e)
    return {
        "tok_emb": emb,
        "layers": {
            "ln": jnp.ones((c.n_layers, e), dtype),
            "wq": stacked(keys[1], (e, h, d), e),
            "wk": stacked(keys[2], (e, kv, d), e),
            "wv": stacked(keys[3], (e, kv, d), e),
            "wo": stacked(keys[4], (h, d, e), h * d),
            "w_router": stacked(keys[5], (e, c.n_experts), e),
            "we_gate": experts(keys[6], (e, f), e, held_ids),
            "we_up": experts(keys[7], (e, f), e, held_ids),
            "we_down": experts(keys[8], (f, e), f, held_ids),
            "ws_gate": experts(keys[9], (e, f), e, shared_ids),
            "ws_up": experts(keys[10], (e, f), e, shared_ids),
            "ws_down": experts(keys[11], (f, e), f, shared_ids),
        },
        "ln_f": jnp.ones((e,), dtype),
    }


_AXIS_RULES = [
    (r"layers/w_router", ("layers", "embed", None)),
    (r"layers/w[es]_(gate|up)", ("layers", None, "embed", "mlp")),
    (r"layers/w[es]_down", ("layers", None, "mlp", "embed")),
    (r"layers/ln", ("layers", "embed")),
    (r"tok_emb", ("vocab", "embed")),
    (r"ln_f", ("embed",)),
]


# the attention projections by head, as ``init_params`` makes them, and as
# ``pack_params`` carries them: ``heads`` stays the axis a mesh splits
_CANONICAL_AXES = [
    (r"layers/wq$", ("layers", "embed", "heads", "head_dim")),
    (r"layers/w[kv]$", ("layers", "embed", "kv_heads", "head_dim")),
    (r"layers/wo$", ("layers", "heads", "head_dim", "embed")),
]
_PACKED_AXES = [
    (r"layers/w[qo]$", ("layers", "heads", "embed")),
    (r"layers/w[kv]$", ("layers", "kv_heads", "embed")),
]


def param_logical_axes(params):
    """Logical axes of the canonical or the packed tree (a leaf's rank says
    which, as it does to :func:`pack_params`)."""
    packed = params["layers"]["wq"].ndim == 3
    return annotate_params(
        params, (_PACKED_AXES if packed else _CANONICAL_AXES) + _AXIS_RULES
    )


# ---------------------------------------------------------------------------
# the serving layout of the attention projections
# ---------------------------------------------------------------------------

# the leaves ``pack_params`` lays out, by path (``/stats/summary`` lists them
# with their shapes as ``params_packed``)
PACKED = ("layers/wq", "layers/wk", "layers/wv", "layers/wo")


def pack_params(params: dict) -> dict:
    """The tree with ``layers/wq``, ``wk``, ``wv`` and ``wo`` as the products
    read them: ``(layers, heads * head_dim, E)``, the heads folded into one
    axis and the contracted axis LAST; every other leaf as it is, no value
    changed.  (A decode block that is handed ``wq`` as ``(layers, E, H, D)``
    copies it whole into another tiling before its loop and a layer of it
    again in every step: docs/PERFORMANCE.md, "A weight is carried as its
    product reads it".)  A tree already packed (``wq`` of rank 3) is returned
    as it is, so every entry function calls this first: the canonical tree is
    packed inside the program, a packed one costs nothing.  A leaf it does
    not change is the same object in both trees."""
    if params["layers"]["wq"].ndim == 3:
        return params
    layers = dict(params["layers"])
    for name in ("wq", "wk", "wv"):  # (layers, E, heads, D) -> (layers, heads * D, E)
        w = layers[name]
        layers[name] = w.reshape(w.shape[:2] + (-1,)).swapaxes(1, 2)
    wo = layers["wo"]  # (layers, H, D, E): a reshape
    layers["wo"] = wo.reshape(wo.shape[0], -1, wo.shape[-1])
    return {**params, "layers": layers}


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

# the most rows of an in projection whose split into heads is pinned to its
# result: where the chip's readings cross (PERF.md section 6, PR 56: the
# prompt rungs to 1,024 are quicker pinned, those from 2,048 quicker free)
_PIN_ROWS = 1024


def _project_in(h, w, cfg: Config):
    """``h (..., L, E)`` through one layer's packed ``wq`` / ``wk`` / ``wv``
    ``(heads * D, E)`` -> ``(..., L, heads, D)``."""
    y = jnp.einsum("...le,fe->...lf", h, w)
    if y.size // y.shape[-1] <= _PIN_ROWS:
        # a decode or verify step's rows, a short prompt's: the split into
        # heads (and RoPE's into pairs) stays on the result.  Left free, XLA
        # moves it onto the weight, and then cuts the layer out of the stack
        # and re-tiles it (134 MB of ``wq`` a layer, in every step of a
        # decode block).  Over more rows the result it re-lays out instead
        # costs more than that one cut
        y = lax.optimization_barrier(y)
    return y.reshape(y.shape[:-1] + (-1, cfg.head_dim))


def _project_out(o, wo):
    """``o (..., L, H, D)`` through one layer's packed ``wo (H * D, E)`` ->
    ``(..., L, E)``."""
    return jnp.einsum("...lf,fe->...le", o.reshape(o.shape[:-2] + (-1,)), wo)


def _qkv(h, lp, cfg: Config, positions, full: bool):
    """Projections of ``h (..., L, E)``; RoPE on a sliding layer only."""
    q, k, v = (_project_in(h, lp[n], cfg) for n in ("wq", "wk", "wv"))
    if not full:
        q = rope_pairs(q, positions, cfg.rope_theta)
        k = rope_pairs(k, positions, cfg.rope_theta)
    return q, k, v


def _visible(qpos, kpos, window):
    """(.., Lq, Lk) mask: key at ``kpos`` visible to query at ``qpos``."""
    seen = kpos[..., None, :] <= qpos[..., :, None]
    if window is not None:
        seen = seen & (kpos[..., None, :] > qpos[..., :, None] - window)
    return seen


def _attend(q, k, v, qpos, kpos, window, kvalid=None):
    """Grouped-query attention of one sequence in plain XLA, the queries in
    chunks of ``ATTN_Q_CHUNK`` so that the scores of 128 heads over
    thousands of keys are never alive at once.  q: (Lq, H, D); k, v:
    (Lk, KV, D); positions int32; scores and softmax in float32."""
    lq, nh, d = q.shape
    lk, kvh = k.shape[:2]
    g = nh // kvh
    scale = 1.0 / math.sqrt(d)
    cq = lq if lq <= ATTN_Q_CHUNK or lq % ATTN_Q_CHUNK else ATTN_Q_CHUNK
    # under a window a chunk of queries sees at most window + cq - 1 keys
    # in a row, and only those are scored — where the keys are a prompt's
    # own, in the order of their positions with no gap (behind a prefix
    # read by blocks, ``kvalid``, rows past the prefix lie in between)
    span = lk
    if window is not None and kvalid is None:
        span = min(lk, window + cq - 1)
    if kvalid is None:
        kvalid = jnp.ones((lk,), bool)

    def one(args):
        qc, pc, last = args  # (cq, H, D), (cq,), index of the last key seen
        k0 = jnp.clip(last + 1 - span, 0, lk - span)
        kc, vc = (lax.dynamic_slice_in_dim(a, k0, span) for a in (k, v))
        kp, ok = (lax.dynamic_slice_in_dim(a, k0, span) for a in (kpos, kvalid))
        qg = qc.reshape(cq, kvh, g, d)
        s = jnp.einsum(
            "qkgd,skd->kgqs", qg, kc, preferred_element_type=jnp.float32
        ) * scale
        seen = _visible(pc, kp, window) & ok[None, :]
        s = jnp.where(seen[None, None], s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p.astype(vc.dtype), vc)
        return o.reshape(cq, nh, d)

    # the queries are the LAST lq of the keys (a prompt's own, or a suffix
    # behind its prefix): chunk c's last query is key lk - lq + (c + 1) cq - 1
    last = lk - lq + (jnp.arange(lq // cq) + 1) * cq - 1
    out = lax.map(
        one, (q.reshape(lq // cq, cq, nh, d), qpos.reshape(-1, cq), last)
    )
    return out.reshape(lq, nh, d)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _route(h2, w_router, cfg: Config):
    """Scores over ALL experts -> (idx (T, K) int32, weights (T, K) f32
    normalised over all K chosen).  The matmul, the sigmoid, the top-k and
    the normalisation run in float32: a near-tie at the k-th place flipped
    by bfloat16 rounding swaps an expert, which is not rounding noise."""
    rdt = jnp.bfloat16 if cfg.router_dtype == "bfloat16" else jnp.float32
    logits = jnp.dot(
        h2.astype(rdt), w_router.astype(rdt),
        precision=lax.Precision.HIGHEST, preferred_element_type=rdt,
    )
    vals, idx = lax.top_k(jax.nn.sigmoid(logits), cfg.experts_per_tok)
    w = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w.astype(jnp.float32)


def _moe(h2, lp, cfg: Config, tok_mask, counters, *, decode: bool, stacks, li,
         sharded: bool = False):
    """``h2 (T, E)`` -> (routed + shared (T, E) float32, counters).  ``lp``
    is this layer's weights, ``stacks`` every layer's and ``li`` the layer
    (``moe.experts_grouped`` says why it wants those: both kernels run over
    the carried stack); ``sharded`` (static) says the stacks lie over a
    mesh."""
    with jax.named_scope("moe.route"):
        idx, w = _route(h2, lp["w_router"], cfg)
    return moe.routed_experts(
        h2, lp, idx, w, cfg.held, tok_mask, counters, decode=decode,
        kernel=not sharded, stacks=stacks, li=li, shared="mean",
    )


def _head(params, h, cfg: Config):
    with jax.named_scope("head"):
        h = layernorm(h, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("...e,ve->...v", h, params["tok_emb"])
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        return logits, h


def _scan_layers(params, cfg: Config, carry, layer_fn):
    """One ``lax.scan`` over periods of ``layer_pattern`` layers; inside a
    period the layers are unrolled, so each layer's attention kind is
    static.  ``layer_fn(carry, li, full, lp) -> carry``."""
    p = cfg.layer_pattern
    n_periods = cfg.n_layers // p
    if n_periods == 1:  # nothing to scan: every index static
        for j in range(p):
            lp = jax.tree.map(lambda a: a[j], params["layers"])
            carry = layer_fn(carry, j, j == p - 1, lp)
        return carry
    xs = jax.tree.map(
        lambda a: a.reshape((n_periods, p) + a.shape[1:]), params["layers"]
    )

    def body(carry, inputs):
        pi, lps = inputs
        for j in range(p):
            lp = jax.tree.map(lambda a: a[j], lps)
            carry = layer_fn(carry, pi * p + j, j == p - 1, lp)
        return carry, None

    carry, _ = lax.scan(body, carry, (jnp.arange(n_periods), xs))
    return carry


def _residual(x, attn, ffn):
    return (x.astype(jnp.float32) + attn.astype(jnp.float32) + ffn).astype(x.dtype)


# ---------------------------------------------------------------------------
# full forward (scoring; the registry's ``apply``)
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """Full-sequence logits ``(B, L, V)``, one sequence after the other."""
    params = pack_params(params)

    def one(toks):
        L = toks.shape[0]
        pos = jnp.arange(L)
        mask = jnp.ones((L,), bool)

        def layer(x, li, full, lp):
            h = layernorm(x, lp["ln"], cfg.norm_eps)
            q, k, v = _qkv(h, lp, cfg, pos, full)
            o = _attend(q, k, v, pos, pos, None if full else cfg.sliding_window)
            attn = _project_out(o, lp["wo"])
            ffn, _ = _moe(h, lp, cfg, mask, None, decode=False, stacks=params["layers"], li=li)
            return _residual(x, attn, ffn)

        x = _scan_layers(params, cfg, params["tok_emb"][toks], layer)
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
    dtype=jnp.float32, kv_sharded: bool = False,
) -> dict:
    """The uniform pool of ``models/llama.py``: every layer, sliding ones
    too, keeps every token (a pool sized by layer type is PERF.md §7's).
    A row holds its kv heads side by side, ``(layers, blocks, block_size,
    kv_heads * head_dim)``: the layout the paged kernel reads a block in, so
    the pool is never re-tiled on the way to it (``kv_sharded``: this
    family has no pool split by head).
    ``counters`` are the routing counters (``COUNTERS``), uint32, wrapping."""
    del kv_sharded
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads * cfg.head_dim)
    return {
        **paged.bookkeeping(cfg.max_seq, n_slots, block_size, len(COUNTERS)),
        "k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
    }


def paged_kv_slot_bytes(
    cfg: Config, block_size: int, *, kv_dtype: str | None = None, dtype="float32"
) -> int:
    """HBM bytes one max_seq slot costs in the paged pool."""
    del block_size, kv_dtype
    per_token = 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers
    return paged.slot_bytes(cfg.max_seq, per_token, dtype)


def window_blocks(cfg: Config, block_size: int, queries: int = 1) -> int:
    """Blocks that cover a sliding layer's window for ``queries`` positions
    in a row: ``sliding_window + queries - 1`` tokens starting anywhere in
    a block."""
    span = cfg.sliding_window + queries - 1
    return -(-(span - 1) // block_size) + 1


def window_read(table, pos, cfg: Config, block_size: int, queries: int = 1):
    """Which blocks a sliding layer reads for slots at ``pos``: the
    ``window_blocks`` table entries from the block that holds
    ``pos - sliding_window + 1`` on.  -> (physical ids (S, nb), the position
    of each row read (S, nb * block_size))."""
    mb = table.shape[1]
    nb = window_blocks(cfg, block_size, queries)
    start = jnp.maximum(pos - cfg.sliding_window + 1, 0) // block_size
    logical = start[:, None] + jnp.arange(nb)[None, :]  # (S, nb)
    phys = jnp.take_along_axis(table, jnp.minimum(logical, mb - 1), axis=1)
    kpos = (
        logical[:, :, None] * block_size + jnp.arange(block_size)
    ).reshape(table.shape[0], nb * block_size)
    return phys, kpos


def prefill_slot_paged(
    params: dict, tokens: jax.Array, length: jax.Array, slot: jax.Array,
    blocks_row: jax.Array, cache: dict, cfg: Config, *, mesh=None,
    seq_impl: str = "dense", lora=None, adapter_id=None,
    return_hidden: bool = False,
):
    """Prefill ONE request's prompt into the blocks reserved for ``slot``
    (the contract of ``llama.prefill_slot_paged``).  ``seq_impl="flash"``
    runs the prompt's attention through the Pallas tiled kernel with the
    window inside it; ``"dense"`` through chunked XLA attention."""
    del adapter_id
    paged.no_lora("cohere2_moe", lora)
    params = pack_params(params)
    bs = cache["k"].shape[2]
    lp_ = tokens.shape[1]
    pos = jnp.arange(lp_)
    real = pos < length
    phys = blocks_row[: lp_ // bs]
    x = params["tok_emb"][tokens[0]]  # (Lp, E)

    def layer(carry, li, full, lp):
        x, ck, cv, ctr = carry
        window = None if full else cfg.sliding_window
        h = layernorm(x, lp["ln"], cfg.norm_eps)
        with jax.named_scope("attn.full" if full else "attn.window"):
            q, k, v = _qkv(h, lp, cfg, pos, full)
            ck = paged.write_prompt(ck, li, phys, k, bs)
            cv = paged.write_prompt(cv, li, phys, v, bs)
            if seq_impl == "flash":
                o = flash_prompt(q, k, v, window=window, length=length)
            else:
                o = _attend(q, k, v, pos, pos, window)
            attn = _project_out(o, lp["wo"])
        ffn, ctr = _moe(
            h, lp, cfg, real, ctr, decode=False, stacks=params["layers"], li=li,
            sharded=mesh is not None,
        )
        return _residual(x, attn, ffn), ck, cv, ctr

    ctr = paged.bump(cache.get("counters"), moe.PREFILL_TOKENS, length)
    x, new_k, new_v, ctr = _scan_layers(
        params, cfg, (x, cache["k"], cache["v"], ctr), layer
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - 1, {"k": new_k, "v": new_v}, ctr, slot,
        length, blocks_row, return_hidden, _head,
    )


def prefill_suffix_paged(
    params: dict, tokens: jax.Array, prefix_len: jax.Array, length: jax.Array,
    slot: jax.Array, blocks_row: jax.Array, suffix_blocks: jax.Array,
    cache: dict, cfg: Config, *, prefix_window: int, lora=None,
    adapter_id=None, return_hidden: bool = False, kv_sharded: bool = False,
):
    """Prefill the SUFFIX of a prompt whose first ``prefix_len`` tokens have
    K/V in the slot's table blocks already (prefix reuse, prompt chunks):
    the contract of ``llama.prefill_suffix_paged``.  Suffix queries attend
    over [the prefix read from the pool ++ the suffix]; a sliding layer
    masks what lies before its window."""
    del adapter_id
    paged.no_lora("cohere2_moe", lora)
    params = pack_params(params)
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

    def layer(carry, li, full, lp):
        x, ck, cv, ctr = carry
        window = None if full else cfg.sliding_window
        h = layernorm(x, lp["ln"], cfg.norm_eps)
        with jax.named_scope("attn.full" if full else "attn.window"):
            q, k, v = _qkv(h, lp, cfg, qpos, full)
            kp = ck[li, read_idx].reshape((pb * bs,) + k.shape[1:])  # (P, KV, D)
            vp = cv[li, read_idx].reshape((pb * bs,) + v.shape[1:])
            o = _attend(
                q, jnp.concatenate([kp.astype(k.dtype), k]),
                jnp.concatenate([vp.astype(v.dtype), v]),
                qpos, kpos, window, kvalid,
            )
            attn = _project_out(o, lp["wo"])
            ck = paged.write_prompt(ck, li, suffix_blocks, k, bs)
            cv = paged.write_prompt(cv, li, suffix_blocks, v, bs)
        ffn, ctr = _moe(
            h, lp, cfg, real, ctr, decode=False, stacks=params["layers"], li=li,
            sharded=kv_sharded,
        )
        return _residual(x, attn, ffn), ck, cv, ctr

    ctr = paged.bump(cache.get("counters"), moe.PREFILL_TOKENS, length - prefix_len)
    x, new_k, new_v, ctr = _scan_layers(
        params, cfg, (x, cache["k"], cache["v"], ctr), layer
    )
    return paged.finish_prefill(
        params, cfg, cache, x, length - prefix_len - 1, {"k": new_k, "v": new_v},
        ctr, slot, length, blocks_row, return_hidden, _head,
    )


def decode_slots_paged(
    params: dict, tokens: jax.Array, cache: dict, active: jax.Array,
    cfg: Config, *, window: int | None = None, kernel: bool = False,
    lora=None, adapter_ids=None, kv_sharded: bool = False,
):
    """One decode step for every slot against the paged cache (the contract
    of ``llama.decode_slots_paged``).  ``window`` (static) bounds the rows a
    full layer reads; a sliding layer reads the blocks of its own window.
    ``kernel`` (static) reads through the Pallas paged decode-attention
    kernel (``ops/paged_attention.py``), the window inside it, instead of
    gathering the rows in XLA."""
    logits, cache = _decode_paged_multi(
        params, tokens[:, None], cache, active, active[:, None], cfg,
        window=window, kernel=kernel, lora=lora, adapter_ids=adapter_ids,
        kv_sharded=kv_sharded,
    )
    cache["pos"] = jnp.where(active, cache["pos"] + 1, cache["pos"])
    return logits[:, 0], cache


def decode_slots_spec_paged(
    params: dict, qtokens: jax.Array, cache: dict, active: jax.Array,
    qvalid: jax.Array, cfg: Config, *, window: int | None = None,
    kernel: bool = False, lora=None, adapter_ids=None,
    return_hidden: bool = False, kv_sharded: bool = False,
):
    """Speculative verify pass over ``L = 1 + draft`` positions a slot (the
    contract of ``llama.decode_slots_spec_paged``)."""
    return _decode_paged_multi(
        params, qtokens, cache, active, qvalid, cfg, window=window,
        kernel=kernel, lora=lora, adapter_ids=adapter_ids,
        return_hidden=return_hidden, kv_sharded=kv_sharded,
    )


def _decode_paged_multi(
    params, qtokens, cache, active, qvalid, cfg: Config, *, window,
    kernel: bool = False, lora=None, adapter_ids=None,
    return_hidden: bool = False, kv_sharded: bool = False,
    window_read_off: bool = False,
):
    """L queries a slot at positions ``pos .. pos + L - 1``.

    A full layer reads ``table[:, :window // bs]``, the slot's first blocks,
    under ``row <= position``.  A sliding layer reads the
    :func:`window_blocks` blocks from the one that holds
    ``pos - sliding_window + 1`` on (:func:`window_read`) under the same
    test and ``row > position - sliding_window`` — fewer rows than the full
    read once ``window`` has outgrown them; until then it reads what the
    full layer reads, under its own mask.  ``window_read_off`` (tests) keeps
    the full read on every layer.  ``kv_sharded`` says the deployment lies
    over a tensor-parallel mesh, its expert stacks too."""
    del adapter_ids
    paged.no_lora("cohere2_moe", lora)
    params = pack_params(params)
    pos, table = cache["pos"], cache["table"]
    S, L = qtokens.shape
    bs = cache["k"].shape[2]
    mb = table.shape[1]
    W = cfg.max_seq if window is None else min(window, cfg.max_seq)
    wb = max(1, W // bs)
    positions = pos[:, None] + jnp.arange(L)[None, :]  # (S, L)
    full_idx = table[:, :wb]
    full_kpos = jnp.broadcast_to(jnp.arange(wb * bs)[None, :], (S, wb * bs))
    if window_read_off or window_blocks(cfg, bs, L) >= wb:
        win_idx, win_kpos = full_idx, full_kpos
    else:
        win_idx, win_kpos = window_read(table, pos, cfg, bs, L)
    win_first = win_kpos[:, 0]  # position of the first row a sliding layer reads
    # inactive slots and draft positions past the reservation write to the
    # sink block 0 (models/llama.py::_decode_paged_multi has the reasons)
    write_blk = jnp.where(
        qvalid,
        jnp.take_along_axis(table, jnp.minimum(positions // bs, mb - 1), axis=1),
        0,
    )
    write_off = positions % bs
    kvh, d = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kvh
    scale = 1.0 / math.sqrt(d)
    tok_mask = jnp.broadcast_to(active[:, None], (S, L)).reshape(S * L)
    x = params["tok_emb"][qtokens]  # (S, L, E)

    def layer(carry, li, full, lp):
        x, ck, cv, ctr = carry
        h = layernorm(x, lp["ln"], cfg.norm_eps)
        with jax.named_scope("attn.full" if full else "attn.window"):
            q, k, v = _qkv(h, lp, cfg, positions, full)
            ck = ck.at[li, write_blk, write_off].set(
                k.reshape(S, L, kvh * d).astype(ck.dtype)
            )
            cv = cv.at[li, write_blk, write_off].set(
                v.reshape(S, L, kvh * d).astype(cv.dtype)
            )
            window = None if full else cfg.sliding_window
            idx, kpos = (full_idx, full_kpos) if full else (win_idx, win_kpos)

            def read(args):
                """A few slots' rows gathered from the carried pool by
                (layer, block) and attended: the gathered window of all
                slots at once is a gigabyte at 8k."""
                qc, ic, kc, pc = args
                n = qc.shape[0]
                kw = ck[li, ic].reshape(n, -1, kvh, d)
                vw = cv[li, ic].reshape(n, -1, kvh, d)
                s = jnp.einsum(
                    "bqkgd,bskd->bkgqs", qc, kw,
                    preferred_element_type=jnp.float32,
                ) * scale
                seen = _visible(pc, kc, window)  # (n, L, rows)
                s = jnp.where(
                    seen[:, None, None], s, jnp.finfo(jnp.float32).min
                )
                p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vw.dtype), vw)

            if kernel:
                from seldon_core_tpu.ops import paged_decode_attention

                # the kernel is handed the WHOLE pool, layers flattened
                # into blocks, and this layer's blocks by offset: a layer
                # cut out of the pool would be a copy of it (XLA fuses no
                # slice into a kernel's operand)
                nb = ck.shape[1]
                flat = (cfg.n_layers * nb, bs, kvh * d)
                o = paged_decode_attention(
                    q, ck.reshape(flat), cv.reshape(flat), idx + li * nb, pos,
                    first=None if full else win_first, window=window,
                    active=active,
                )
            else:
                big = 2 * idx.size * bs * kvh * d * ck.dtype.itemsize
                sc = S
                if big > DECODE_GATHER_BYTES and S % DECODE_SLOT_CHUNK == 0:
                    sc = DECODE_SLOT_CHUNK
                chunked = jax.tree.map(
                    lambda a: a.reshape((S // sc, sc) + a.shape[1:]),
                    (q.reshape(S, L, kvh, g, d), idx, kpos, positions),
                )
                o = lax.map(read, chunked)
            attn = _project_out(o.reshape(S, L, cfg.n_heads, d), lp["wo"])
        ffn, ctr = _moe(
            h.reshape(S * L, -1), lp, cfg, tok_mask, ctr, decode=True,
            stacks=params["layers"], li=li, sharded=kv_sharded,
        )
        return _residual(x, attn, ffn.reshape(x.shape)), ck, cv, ctr

    ctr = paged.bump(cache.get("counters"), moe.STEPS, 1)
    x, new_k, new_v, ctr = _scan_layers(
        params, cfg, (x, cache["k"], cache["v"], ctr), layer
    )
    out = dict(cache)
    out["k"], out["v"] = new_k, new_v
    if ctr is not None:
        out["counters"] = ctr
    logits, h = _head(params, x, cfg)
    if return_hidden:
        return logits, out, h
    return logits, out
