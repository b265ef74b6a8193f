"""Llama-family decoder for generative serving.

TPU-first design choices (vs. a torch port):

* params are a plain pytree with **stacked layer weights** — one ``lax.scan``
  over the layer axis instead of Python-unrolled blocks, so compile time is
  O(1) in depth and XLA pipelines the layer loop;
* RoPE + GQA + SwiGLU as in Llama-2/3; head/mlp axes carry logical-sharding
  names so tensor parallelism comes from annotations alone;
* KV cache is a static-shape ``(layers, B, max_seq, kv_heads, head_dim)``
  pair updated with ``dynamic_update_slice`` — no dynamic shapes anywhere, so
  decode steps never recompile;
* long-context prefill can route attention through ring / Ulysses sequence
  parallelism (:mod:`seldon_core_tpu.parallel.ring`) over the ``sp`` mesh
  axis.

The reference has no generative serving at all (its tensors are 2-D
batch×features, reference: engine/.../predictors/AverageCombinerUnit.java:47-49);
this family is the capability the TPU build adds for the Llama configs in
BASELINE.json.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from seldon_core_tpu.models.common import annotate_params
from seldon_core_tpu.models.layers import rmsnorm as _rmsnorm
from seldon_core_tpu.models.layers import rope as _rope
from seldon_core_tpu.models.layers import sample_tokens  # noqa: F401  (contract)
from seldon_core_tpu.parallel.ring import ring_self_attention


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 32000
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    @classmethod
    def llama3_8b(cls) -> "Config":
        return cls(
            vocab_size=128256, hidden=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, ffn=14336, max_seq=8192,
        )

    @classmethod
    def llama3_1b(cls, max_seq: int = 2048) -> "Config":
        """Llama-3.2-1B shape (vocab truncated to keep the embedding from
        dominating the 1.2B total): the bench-scale real model."""
        return cls(
            vocab_size=32000, hidden=2048, n_layers=16, n_heads=32,
            n_kv_heads=8, ffn=8192, max_seq=max_seq,
        )

    @classmethod
    def tiny(cls, max_seq: int = 128) -> "Config":
        """Test-scale config: same code paths, toy sizes."""
        return cls(
            vocab_size=256, hidden=64, n_layers=2, n_heads=4,
            n_kv_heads=2, ffn=128, max_seq=max_seq, rope_theta=10000.0,
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> dict:
    c = cfg
    k = jax.random.split(rng, 9)
    s = 1.0 / math.sqrt(c.hidden)

    def norm(key, *shape):
        return (jax.random.normal(key, shape) * s).astype(dtype)

    nl = c.n_layers
    return {
        "tok_emb": norm(k[0], c.vocab_size, c.hidden),
        "layers": {
            "wq": norm(k[1], nl, c.hidden, c.n_heads, c.head_dim),
            "wk": norm(k[2], nl, c.hidden, c.n_kv_heads, c.head_dim),
            "wv": norm(k[3], nl, c.hidden, c.n_kv_heads, c.head_dim),
            "wo": norm(k[4], nl, c.n_heads, c.head_dim, c.hidden),
            "w_gate": norm(k[5], nl, c.hidden, c.ffn),
            "w_up": norm(k[6], nl, c.hidden, c.ffn),
            "w_down": norm(k[7], nl, c.ffn, c.hidden),
            "ln_att": jnp.ones((nl, c.hidden), dtype),
            "ln_mlp": jnp.ones((nl, c.hidden), dtype),
        },
        "ln_f": jnp.ones((c.hidden,), dtype),
        "head": norm(k[8], c.hidden, c.vocab_size),
    }


_AXIS_RULES = [
    (r"layers/wq", ("layers", "embed", "heads", "head_dim")),
    (r"layers/w[kv]$", ("layers", "embed", "kv_heads", "head_dim")),
    (r"layers/wo", ("layers", "heads", "head_dim", "embed")),
    (r"layers/w_(gate|up)", ("layers", "embed", "mlp")),
    (r"layers/w_down", ("layers", "mlp", "embed")),
    (r"layers/ln_(att|mlp)", ("layers", "embed")),
    (r"tok_emb", ("vocab", "embed")),
    (r"head$", ("embed", "vocab")),
    (r"ln_f", ("embed",)),
]


def param_logical_axes(params):
    return annotate_params(params, _AXIS_RULES)


# ---------------------------------------------------------------------------
# batched multi-LoRA adapters (docs/MULTITENANT.md)
# ---------------------------------------------------------------------------
#
# S-LoRA/Punica-style serving: ONE stacked adapter pool in HBM,
# ``(n_layers, n_adapters, ...)`` per low-rank factor, and a per-batch-row
# ``adapter_id`` gather inside the SAME fused prefill/decode programs that
# serve the base model — N fine-tune variants of one base ride one compiled
# step with no per-adapter programs and no weight swapping.  Adapter row 0
# is the reserved NULL adapter (all-zero factors): a null-adapter slot's
# delta is exactly 0.0, so its outputs are bit-identical to a lora-off
# build (the pinned-equal matrix in tests/test_lora.py holds this).

LORA_ATTN_TARGETS = ("wq", "wk", "wv", "wo")
LORA_MLP_TARGETS = ("w_gate", "w_up", "w_down")


def _lora_shapes(cfg: Config, rank: int) -> dict:
    """Per-target (a, b) factor shapes WITHOUT the leading
    ``(n_layers, n_adapters)`` stack axes: ``delta = (x @ a) @ b`` matches
    the base projection's contraction exactly."""
    e, h, kv, d, f = (
        cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn,
    )
    return {
        "wq": ((e, rank), (rank, h, d)),
        "wk": ((e, rank), (rank, kv, d)),
        "wv": ((e, rank), (rank, kv, d)),
        "wo": ((h, d, rank), (rank, e)),
        "w_gate": ((e, rank), (rank, f)),
        "w_up": ((e, rank), (rank, f)),
        "w_down": ((f, rank), (rank, e)),
    }


def init_lora_params(
    cfg: Config,
    n_adapters: int,
    rank: int,
    targets: tuple = LORA_ATTN_TARGETS,
    dtype=jnp.float32,
) -> dict:
    """Zero-initialized stacked adapter pool: ``{target: {"a": (L, A, in..,
    r), "b": (L, A, r, out..)}}``.  Layers lead so the pool rides the layer
    ``lax.scan`` as xs alongside ``params["layers"]``; adapter row 0 stays
    all-zero forever (the null adapter)."""
    shapes = _lora_shapes(cfg, int(rank))
    nl, na = cfg.n_layers, int(n_adapters)
    out = {}
    for t in targets:
        sa, sb = shapes[t]
        out[t] = {
            "a": jnp.zeros((nl, na) + sa, dtype),
            "b": jnp.zeros((nl, na) + sb, dtype),
        }
    return out


def lora_adapter_factors(
    rng: jax.Array,
    cfg: Config,
    rank: int,
    targets: tuple = LORA_ATTN_TARGETS,
    scale: float = 0.05,
    dtype=jnp.float32,
) -> dict:
    """ONE adapter's random factors ``{target: {"a": (L, in.., r), "b":
    (L, r, out..)}}`` — the synthetic stand-in for a trained LoRA delta
    (tests, bench, and the graph-declared adapter registry).  ``b`` is
    non-zero (unlike training init) so distinct adapters provably produce
    distinct generations."""
    shapes = _lora_shapes(cfg, int(rank))
    keys = jax.random.split(rng, 2 * len(targets))
    out = {}
    for i, t in enumerate(targets):
        sa, sb = shapes[t]
        fan_in = 1
        for s in sa[:-1]:
            fan_in *= s
        out[t] = {
            "a": (
                jax.random.normal(keys[2 * i], (cfg.n_layers,) + sa)
                / math.sqrt(fan_in)
            ).astype(dtype),
            "b": (
                jax.random.normal(keys[2 * i + 1], (cfg.n_layers,) + sb)
                * scale
            ).astype(dtype),
        }
    return out


def lora_pool_bytes(cfg: Config, n_adapters: int, rank: int,
                    targets: tuple = LORA_ATTN_TARGETS,
                    dtype="float32") -> int:
    """HBM bytes the stacked adapter pool costs — the ``adapter_pool``
    class in the memory manager's ledger (executor/memory.py)."""
    import numpy as _np

    itemsize = 2 if str(dtype) in ("bfloat16", "bf16") else _np.dtype(
        dtype
    ).itemsize
    total = 0
    for t in targets:
        sa, sb = _lora_shapes(cfg, int(rank))[t]
        n = 1
        for s in sa:
            n *= s
        m = 1
        for s in sb:
            m *= s
        total += (n + m) * cfg.n_layers * int(n_adapters) * itemsize
    return total


def _lora_delta(h, la, aid):
    """Per-row low-rank delta: ``h (B, L, in..)`` through adapter
    ``aid[b]``'s factors gathered from ONE layer's pool slice ``la =
    {"a": (A, in.., r), "b": (A, r, out..)}``.  The gather is per batch
    row — a mixed-adapter batch pays two small einsums, never a
    per-adapter program."""
    a = la["a"][aid]  # (B, in.., r)
    b = la["b"][aid]  # (B, r, out..)
    if a.ndim == 4:  # o-proj input (B, H, D, r)
        xa = jnp.einsum("blhd,bhdr->blr", h, a)
    else:
        xa = jnp.einsum("ble,ber->blr", h, a)
    if b.ndim == 4:  # attention out head-shaped (B, r, H|KV, D)
        return jnp.einsum("blr,brhd->blhd", xa, b)
    return jnp.einsum("blr,brf->blf", xa, b)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _gqa_repeat(kv, n_heads):
    """(B, L, Hkv, D) -> (B, L, H, D) by repeating each kv head."""
    reps = n_heads // kv.shape[2]
    return jnp.repeat(kv, reps, axis=2)


def _dense_causal_attention(q, k, v):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    ql, kl = q.shape[1], k.shape[1]
    mask = jnp.arange(ql)[:, None] + (kl - ql) >= jnp.arange(kl)[None, :]
    s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _layer(x, lp, cfg: Config, positions, attn_fn, kv_hook=None, lora=None,
           aid=None):
    """``kv_hook(k, v) -> (k_attn, v_attn, stored)`` lets a quantized KV
    pool attend the DEQUANTIZED values it will actually cache (fake-quant
    consistency: a reused prefix then reads byte-identical K/V to what the
    cold prefill attended, keeping prefix reuse bit-exact under int8).

    ``lora`` is ONE layer's adapter-pool slice (``{target: {"a": (A, ..),
    "b": (A, ..)}}``) and ``aid (B,)`` the per-row adapter ids — the
    batched multi-LoRA gather (docs/MULTITENANT.md); ``None`` compiles the
    plain base-model layer."""
    q, k, v = _qkv(x, lp, cfg, positions, lora, aid)
    if kv_hook is None:
        ka, va, stored = k, v, (k, v)
    else:
        ka, va, stored = kv_hook(k, v)
    with jax.named_scope("attn.prompt"):
        o = attn_fn(
            q, _gqa_repeat(ka, cfg.n_heads), _gqa_repeat(va, cfg.n_heads)
        )
    x = x + _attn_out(o, lp, lora, aid)
    h = _rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
    x = x + _mlp_block(h, lp, lora, aid)
    return x, stored


# The named scopes of a layer (``attn.qkv``, ``attn.prompt`` in a prompt's
# own attention and ``attn.paged`` where the pool is read, ``attn.out``,
# ``mlp.gate_up``, ``mlp.down``, ``head``) are metadata on the operations of
# the prefill, suffix and decode programs alike: a profiler trace names a
# kernel and a weight stream by them, and no program's work changes.


def _qkv(x, lp, cfg: Config, positions, lora=None, aid=None):
    """Attention norm, the three projections (with optional per-row LoRA
    deltas) and the rotation of ``q`` and ``k``."""
    with jax.named_scope("attn.qkv"):
        h = _rmsnorm(x, lp["ln_att"], cfg.norm_eps)
        q = jnp.einsum("ble,ehd->blhd", h, lp["wq"])
        k = jnp.einsum("ble,ehd->blhd", h, lp["wk"])
        v = jnp.einsum("ble,ehd->blhd", h, lp["wv"])
        if lora is not None:
            if "wq" in lora:
                q = q + _lora_delta(h, lora["wq"], aid)
            if "wk" in lora:
                k = k + _lora_delta(h, lora["wk"], aid)
            if "wv" in lora:
                v = v + _lora_delta(h, lora["wv"], aid)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_out(o, lp, lora=None, aid=None):
    with jax.named_scope("attn.out"):
        proj = jnp.einsum("blhd,hde->ble", o, lp["wo"])
        if lora is not None and "wo" in lora:
            proj = proj + _lora_delta(o, lora["wo"], aid)
    return proj


def _mlp_block(h, lp, lora=None, aid=None):
    """SwiGLU MLP with optional per-row LoRA deltas on gate/up/down."""
    with jax.named_scope("mlp.gate_up"):
        gate = h @ lp["w_gate"]
        up = h @ lp["w_up"]
        if lora is not None:
            if "w_gate" in lora:
                gate = gate + _lora_delta(h, lora["w_gate"], aid)
            if "w_up" in lora:
                up = up + _lora_delta(h, lora["w_up"], aid)
        act = jax.nn.silu(gate) * up
    with jax.named_scope("mlp.down"):
        down = act @ lp["w_down"]
        if lora is not None and "w_down" in lora:
            down = down + _lora_delta(act, lora["w_down"], aid)
    return down


def _head(params, x, cfg: Config):
    """Final norm and the vocabulary projection -> ``(logits, hidden)``."""
    with jax.named_scope("head"):
        h = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        return h @ params["head"], h


# ---------------------------------------------------------------------------
# forward / prefill / decode
# ---------------------------------------------------------------------------

def forward(
    params: dict,
    tokens: jax.Array,
    cfg: Config,
    *,
    mesh: Mesh | None = None,
    seq_impl: str = "dense",
) -> jax.Array:
    """Full-sequence logits ``(B, L, V)`` (scoring / perplexity serving).

    ``seq_impl`` in {"dense", "ring", "ulysses"}: with a mesh whose ``sp`` > 1
    the attention runs sequence-parallel over ICI.
    """
    attn_fn = _select_attn(mesh, seq_impl)
    x = params["tok_emb"][tokens]
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)

    def body(x, lp):
        x, _ = _layer(x, lp, cfg, positions, attn_fn)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _head(params, x, cfg)[0]


def init_cache(cfg: Config, batch: int, dtype=jnp.float32) -> dict:
    shape = (cfg.n_layers, batch, cfg.max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), "pos": jnp.zeros((), jnp.int32)}


CACHE_LOGICAL_AXES = {"k": ("layers", "batch", None, "kv_heads", "head_dim"),
                      "v": ("layers", "batch", None, "kv_heads", "head_dim"),
                      "pos": None}


def _select_attn(mesh: Mesh | None, seq_impl: str):
    if seq_impl == "flash":
        # Pallas tiled attention (ops/flash_attention.py): O(S*D) memory
        # instead of materializing (B,H,S,S) scores — the long-context
        # single-host path; ring/ulysses cover the multi-chip sp axis
        from seldon_core_tpu.ops import flash_causal_attention_blhd

        return flash_causal_attention_blhd
    if seq_impl == "dense" or mesh is None:
        return _dense_causal_attention

    def attn_fn(q, k, v):
        return ring_self_attention(mesh, q, k, v, causal=True, impl=seq_impl)

    return attn_fn


def prefill(
    params: dict,
    tokens: jax.Array,
    cfg: Config,
    cache: dict,
    *,
    mesh: Mesh | None = None,
    seq_impl: str = "dense",
) -> tuple[jax.Array, dict]:
    """Run the prompt through the model, filling the KV cache.

    Returns ``(last_logits (B, V), cache)``.  ``tokens`` may be shorter than
    ``max_seq``; the cache records the true length in ``pos``.  Long prompts
    can route attention through ring/Ulysses sequence parallelism over the
    mesh's ``sp`` axis (``seq_impl`` in {"dense", "ring", "ulysses"}).
    """
    x, (ks, vs) = _prefill_core(params, tokens, cfg, _select_attn(mesh, seq_impl))
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0)),
        "pos": jnp.asarray(tokens.shape[1], jnp.int32),
    }
    return _head(params, x[:, -1], cfg)[0], cache


def _prefill_core(params, tokens, cfg: Config, attn_fn, kv_hook=None,
                  lora=None, aid=None):
    """Embed + layer scan shared by :func:`prefill`, :func:`embed_pooled` and the paged prefills.
    Returns ``(hidden (B, L, E), stored)`` where ``stored`` is
    ``(ks, vs) (layers, B, L, kv, hd)`` for float pools, or the kv_hook's
    per-layer pytree (quantized blocks + scales) when one is given.
    ``lora``/``aid``: the stacked adapter pool (layers-first) + per-row
    adapter ids — the pool rides the scan xs next to the layer weights."""
    x = params["tok_emb"][tokens]
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)

    if lora is None:
        def body(x, lp):
            x, stored = _layer(x, lp, cfg, positions, attn_fn, kv_hook)
            return x, stored

        x, stored = jax.lax.scan(body, x, params["layers"])
    else:
        def body(x, inputs):
            lp, ll = inputs
            x, stored = _layer(
                x, lp, cfg, positions, attn_fn, kv_hook, lora=ll, aid=aid
            )
            return x, stored

        x, stored = jax.lax.scan(body, x, (params["layers"], lora))
    return x, stored


def decode_step(params: dict, token: jax.Array, cache: dict, cfg: Config) -> tuple[jax.Array, dict]:
    """One generation step: ``token (B,) int32`` -> ``(logits (B, V), cache)``.

    The single-sequence special case of :func:`decode_slots`: every batch row
    shares one position (``cache["pos"]`` scalar), all rows active.
    """
    B = token.shape[0]
    slot_cache = {
        "k": cache["k"],
        "v": cache["v"],
        "pos": jnp.full((B,), cache["pos"], jnp.int32),
    }
    logits, slot_cache = decode_slots(
        params, token, slot_cache, jnp.ones((B,), bool), cfg
    )
    return logits, {"k": slot_cache["k"], "v": slot_cache["v"], "pos": cache["pos"] + 1}


# ---------------------------------------------------------------------------
# slot-based primitives for continuous-batching serving
# ---------------------------------------------------------------------------
#
# A *slot* is one row of a persistent multi-sequence KV cache.  The serving
# scheduler (executor/generation.py) admits a request by prefilling its
# prompt into a free slot while decode steps keep running for every other
# slot — continuous batching with zero dynamic shapes: one compiled decode
# program serves every step of every mix of requests.

def embed_pooled(
    params: dict,
    tokens: jax.Array,
    length: jax.Array,
    cfg: Config,
    *,
    mesh: Mesh | None = None,
    seq_impl: str = "dense",
) -> jax.Array:
    """Mean-pooled final hidden state of one prompt: the embeddings path.

    ``tokens`` is ``(1, Lpad)`` right-padded to a bucket length; ``length``
    is the true prompt length (traced — one compiled program per bucket,
    exactly like :func:`prefill_slot_paged`).  Pure forward: no KV cache is
    written and no slot is consumed, so the scheduler can batch these
    alongside decode without spending pool blocks.  Returns the final-norm
    hidden states averaged over the real (unpadded) rows, ``(E,) float32``
    — padding rows are masked out of the mean so the vector is invariant
    to the bucket the prompt landed in.
    """
    x, _ = _prefill_core(params, tokens, cfg, _select_attn(mesh, seq_impl))
    h = _rmsnorm(x[0], params["ln_f"], cfg.norm_eps).astype(jnp.float32)
    mask = (jnp.arange(h.shape[0]) < length).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    return (h * mask[:, None]).sum(axis=0) / denom


# ---------------------------------------------------------------------------
# paged KV cache (block pool + per-slot block tables)
# ---------------------------------------------------------------------------
#
# The static slot cache above pre-allocates ``n_slots x max_seq`` rows, so
# HBM is billed for the WORST-CASE length of every slot: at max_seq 8192 a
# 16-slot 1.1B cache is 8.6 GB even when every request is 200 tokens.  The
# paged layout allocates from a pool of fixed-size blocks:
#
#   k/v: (layers, n_blocks, block_size, kv_heads * head_dim) on one device,
#        (layers, n_blocks, block_size, kv_heads, head_dim) with the kv heads
#        split over a mesh — the same row-major bytes (init_paged_cache)
#   table: (n_slots, max_seq // block_size) int32  — physical block ids
#
# A slot's logical position p lives in physical row
# ``(table[slot, p // bs], p % bs)``.  Blocks are RESERVED AT ADMISSION for
# ``prompt + max_new_tokens`` (both known up front in serving), so there is
# no mid-flight OOM and no preemption machinery — the TPU-friendly version
# of vLLM's paged attention: shapes stay static, one compiled program per
# (bucket, window), the allocator is a host-side free list.  Slot count now
# scales with the POOL (HBM budget), not with n_slots x max_seq.

def init_paged_cache(
    cfg: Config,
    n_slots: int,
    n_blocks: int,
    block_size: int,
    dtype=jnp.float32,
    kv_dtype: str | None = None,
    kv_sharded: bool = False,
) -> dict:
    """``kv_dtype="int8"`` stores K/V blocks as int8 with one ``dtype``
    scale per (position, kv-head) — ``k_scale``/``v_scale`` of shape
    ``(layers, n_blocks, block_size, kv_heads)`` — roughly doubling the
    sequences a fixed HBM pool holds (docs/PERFORMANCE.md).  Attention
    reads dequantize in place; writes quantize per row, so incremental
    decode appends never rescale neighbouring rows.

    A row of the pool holds its heads side by side, ``(..., block_size,
    kv_heads * head_dim)``: the shape the paged decode kernel copies blocks
    of, so the carried pool is its operand as it is (a pool with a head
    axis is re-tiled whole on the way to a kernel, PERF.md §6).
    ``kv_sharded``: the pool is to be split over a mesh by kv head, and
    keeps the head axis to split, ``(..., block_size, kv_heads,
    head_dim)``.  The programs read either (same bytes, same order); what
    leaves the device is always the five-dimensional frame."""
    if cfg.max_seq % block_size:
        raise ValueError(
            f"max_seq {cfg.max_seq} must be a multiple of block_size {block_size}"
        )
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    mb = cfg.max_seq // block_size
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    rows = shape if kv_sharded else shape[:3] + (shape[3] * shape[4],)
    cache = {
        "k": jnp.zeros(rows, jnp.int8 if kv_dtype == "int8" else dtype),
        "v": jnp.zeros(rows, jnp.int8 if kv_dtype == "int8" else dtype),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "table": jnp.zeros((n_slots, mb), jnp.int32),
    }
    if kv_dtype == "int8":
        cache["k_scale"] = jnp.zeros(shape[:4], dtype)
        cache["v_scale"] = jnp.zeros(shape[:4], dtype)
    return cache


def paged_kv_slot_bytes(
    cfg: Config, block_size: int, *, kv_dtype: str | None = None, dtype="float32"
) -> int:
    """HBM bytes one max_seq slot costs in the paged pool — the geometry
    behind ``kv_slots_per_chip`` accounting.  ``dtype`` is the pool's
    float dtype (scales use it too); int8 pools bill 1 byte per element
    plus one scale per (position, kv-head)."""
    import numpy as _np

    itemsize = 2 if str(dtype) in ("bfloat16", "bf16") else _np.dtype(dtype).itemsize
    if kv_dtype == "int8":
        per_head = cfg.head_dim * 1 + itemsize  # int8 rows + one scale
    else:
        per_head = cfg.head_dim * itemsize
    per_token = 2 * cfg.n_kv_heads * per_head * cfg.n_layers  # K and V
    return cfg.max_seq * per_token


def _quant_kv(x, scale_dtype):
    """``x (..., head_dim)`` float -> ``(int8 (..., head_dim), scale (...))``.
    Symmetric per-(position, head) absmax scaling: the max-magnitude
    element maps to exactly ±127, so quantization is deterministic and a
    stored block re-exports bit-identically."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(scale_dtype)


def _dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]).astype(
        dtype
    )


def _pool_read(pool, li, read_idx, kv_sharded=False):
    """The blocks ``read_idx`` of layer ``li`` (a traced scalar) of the
    carried ``(layers, n_blocks, ...)`` pool, chosen by where the pool lives
    (PERF.md §6, PR 25; same elements in the same order either way).

    A pool on one device: ONE gather addressed by (layer, block).  Cutting
    the layer out first makes XLA materialise the layer's whole pool every
    layer of every step — it does not fuse a dynamic-slice into a gather's
    operand — and that copy cost more than the step's own reads.

    ``kv_sharded`` (static): the kv-head axis is split over a mesh.  A
    device then holds a few heads of every block in 2-row tiles, which the
    gather reads at under half the rate; the layer, a fraction the size, is
    cut out first, and XLA re-tiles it into fast memory on the way."""
    if kv_sharded:
        return jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)[read_idx]
    return pool[li, read_idx]


def _pool_rows(pool, rows):
    """``rows (..., kv_heads, head_dim)`` in the K/V pool's own row shape
    and dtype (heads side by side, or a head axis: init_paged_cache)."""
    return rows.reshape(rows.shape[:-2] + pool.shape[3:]).astype(pool.dtype)


def _fake_quant_hook(scale_dtype):
    """kv_hook for :func:`_layer` under an int8 pool: attention sees the
    dequantized values, the scan collects ``(qk, sk, qv, sv)`` to store."""

    def hook(k, v):
        qk, sk = _quant_kv(k, scale_dtype)
        qv, sv = _quant_kv(v, scale_dtype)
        return (
            _dequant_kv(qk, sk, k.dtype),
            _dequant_kv(qv, sv, v.dtype),
            (qk, sk, qv, sv),
        )

    return hook


def prefill_slot_paged(
    params: dict,
    tokens: jax.Array,
    length: jax.Array,
    slot: jax.Array,
    blocks_row: jax.Array,
    cache: dict,
    cfg: Config,
    *,
    mesh: Mesh | None = None,
    seq_impl: str = "dense",
    lora: dict | None = None,
    adapter_id: jax.Array | None = None,
    return_hidden: bool = False,
) -> tuple[jax.Array, dict] | tuple[jax.Array, dict, jax.Array]:
    """Prefill ONE request's prompt into the blocks reserved for ``slot``.

    ``tokens`` is ``(1, Lpad)`` right-padded to a bucket that is a multiple
    of the block size; ``blocks_row`` is the slot's full ``(max_blocks,)``
    table row (reserved physical ids, zero-padded).  Pad rows land in
    reserved blocks and are masked by decode's validity test, exactly like
    the static-slot variant.  ``lora``/``adapter_id`` select the request's
    adapter from the stacked pool (docs/MULTITENANT.md); adapter 0 (or no
    pool) is the base model."""
    bs = cache["k"].shape[2]
    lp = tokens.shape[1]
    quant = "k_scale" in cache
    hook = _fake_quant_hook(cache["k_scale"].dtype) if quant else None
    aid = (
        None if lora is None
        else jnp.asarray(adapter_id, jnp.int32).reshape(1)
    )
    x, stored = _prefill_core(
        params, tokens, cfg, _select_attn(mesh, seq_impl), kv_hook=hook,
        lora=lora, aid=aid,
    )
    # (layers, 1, Lp, kv, hd) -> (layers, Lb, bs, kv, hd) scattered to the
    # slot's first Lb physical blocks
    lb = lp // bs
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    phys = blocks_row[:lb]
    cache = dict(cache)
    if quant:
        qk, sk, qv, sv = stored
        cache["k"] = cache["k"].at[:, phys].set(
            _pool_rows(cache["k"], qk[:, 0].reshape(cfg.n_layers, lb, bs, kvh, hd))
        )
        cache["v"] = cache["v"].at[:, phys].set(
            _pool_rows(cache["v"], qv[:, 0].reshape(cfg.n_layers, lb, bs, kvh, hd))
        )
        cache["k_scale"] = cache["k_scale"].at[:, phys].set(
            sk[:, 0].reshape(cfg.n_layers, lb, bs, kvh)
        )
        cache["v_scale"] = cache["v_scale"].at[:, phys].set(
            sv[:, 0].reshape(cfg.n_layers, lb, bs, kvh)
        )
    else:
        ks, vs = stored
        ksb = ks[:, 0].reshape(cfg.n_layers, lb, bs, kvh, hd)
        vsb = vs[:, 0].reshape(cfg.n_layers, lb, bs, kvh, hd)
        cache["k"] = cache["k"].at[:, phys].set(_pool_rows(cache["k"], ksb))
        cache["v"] = cache["v"].at[:, phys].set(_pool_rows(cache["v"], vsb))
    cache["pos"] = cache["pos"].at[slot].set(length)
    cache["table"] = cache["table"].at[slot].set(blocks_row)
    h = jax.lax.dynamic_index_in_dim(x[0], length - 1, axis=0, keepdims=False)
    logits, h = _head(params, h, cfg)
    if return_hidden:
        # post-ln_f hidden at the sampled position — the Medusa heads'
        # input (executor/generation.py stashes it per slot)
        return logits, cache, h
    return logits, cache


def prefill_suffix_paged(
    params: dict,
    tokens: jax.Array,
    prefix_len: jax.Array,
    length: jax.Array,
    slot: jax.Array,
    blocks_row: jax.Array,
    suffix_blocks: jax.Array,
    cache: dict,
    cfg: Config,
    *,
    prefix_window: int,
    lora: dict | None = None,
    adapter_id: jax.Array | None = None,
    return_hidden: bool = False,
    kv_sharded: bool = False,
) -> tuple[jax.Array, dict] | tuple[jax.Array, dict, jax.Array]:
    """Prefill only the SUFFIX of a prompt whose first ``prefix_len``
    tokens already have K/V in the slot's table blocks (KV prefix reuse,
    cache/prefix.py).

    ``tokens`` is ``(1, Ls)`` — the suffix right-padded to a bucket that is
    a multiple of the block size; ``prefix_len`` is the reused length (a
    multiple of the block size, traced); ``length`` the TOTAL true prompt
    length; ``blocks_row`` the slot's full table row whose first
    ``prefix_len // bs`` entries are the shared prefix blocks;
    ``suffix_blocks`` ``(Ls // bs,)`` the physical blocks the suffix K/V
    scatters into.  ``prefix_window`` (STATIC; one compiled program per
    (suffix bucket, window)) bounds how many prefix rows attention reads —
    the smallest block-multiple covering ``prefix_len``.  ``kv_sharded``
    (STATIC) as in :func:`decode_slots_paged`.

    Numerics: suffix queries attend over [gathered prefix K/V ++ suffix
    K/V] with the same einsum/mask/softmax shapes as the full-prefill
    attention, and K/V at a position depends causally only on tokens at or
    before it — so generation from a reused prefix is bit-identical to a
    cold prefill (pinned-equal test in tests/test_cache.py).
    """
    bs = cache["k"].shape[2]
    ls = tokens.shape[1]
    pw = int(prefix_window)
    pb = max(1, pw // bs)
    lb = ls // bs
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    quant = "k_scale" in cache
    x = params["tok_emb"][tokens]  # (1, Ls, E)
    positions = prefix_len + jnp.arange(ls)[None, :]  # (1, Ls) global positions
    read_idx = blocks_row[:pb]  # (pb,) physical prefix blocks
    # mask: prefix col j visible iff j < prefix_len; suffix col j iff j <= i
    prefix_valid = jnp.arange(pb * bs)[None, :] < prefix_len  # (1, P)
    causal = jnp.arange(ls)[:, None] >= jnp.arange(ls)[None, :]  # (Ls, Ls)
    mask = jnp.concatenate(
        [jnp.broadcast_to(prefix_valid, (ls, pb * bs)), causal], axis=1
    )  # (Ls, P + Ls)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    hook = _fake_quant_hook(cache["k_scale"].dtype) if quant else None
    aid = (
        None if lora is None
        else jnp.asarray(adapter_id, jnp.int32).reshape(1)
    )

    def body(carry, inputs):
        x, ck, cv, cks, cvs = carry
        li, lp = inputs[0], inputs[1]
        ll = inputs[2] if lora is not None else None
        q, k, v = _qkv(x, lp, cfg, positions, ll, aid)
        if quant:
            # attend the dequantized suffix K/V (fake-quant: exactly what
            # the pool will hold) and collect the quantized form to store
            k, v, (qk, sk, qv, sv) = hook(k, v)

        def read(pool):
            return _pool_read(pool, li, read_idx, kv_sharded)

        with jax.named_scope("attn.paged"):
            kp = read(ck).reshape(pb, bs, kvh, hd)
            vp = read(cv).reshape(pb, bs, kvh, hd)
            if quant:
                kp = _dequant_kv(kp, read(cks), k.dtype)
                vp = _dequant_kv(vp, read(cvs), v.dtype)
            kp = kp.reshape(1, pb * bs, kvh, hd).astype(k.dtype)
            vp = vp.reshape(1, pb * bs, kvh, hd).astype(v.dtype)
            k_all = jnp.concatenate([kp, k], axis=1)  # (1, P+Ls, kv, hd)
            v_all = jnp.concatenate([vp, v], axis=1)
            kf = _gqa_repeat(k_all, cfg.n_heads)
            vf = _gqa_repeat(v_all, cfg.n_heads)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) * scale
            s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
        x = x + _attn_out(o, lp, ll, aid)
        h = _rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
        mlp = _mlp_block(h, lp, ll, aid)
        if quant:
            ck = ck.at[li, suffix_blocks].set(
                _pool_rows(ck, qk[0].reshape(lb, bs, kvh, hd)))
            cv = cv.at[li, suffix_blocks].set(
                _pool_rows(cv, qv[0].reshape(lb, bs, kvh, hd)))
            cks = cks.at[li, suffix_blocks].set(sk[0].reshape(lb, bs, kvh))
            cvs = cvs.at[li, suffix_blocks].set(sv[0].reshape(lb, bs, kvh))
        else:
            ksb = k[0].reshape(lb, bs, kvh, hd)
            vsb = v[0].reshape(lb, bs, kvh, hd)
            ck = ck.at[li, suffix_blocks].set(_pool_rows(ck, ksb))
            cv = cv.at[li, suffix_blocks].set(_pool_rows(cv, vsb))
        return (x + mlp, ck, cv, cks, cvs), None

    zero = jnp.zeros((), jnp.int8)  # scan carries need SOME leaf when not quant
    xs = (jnp.arange(cfg.n_layers), params["layers"])
    if lora is not None:
        xs = xs + (lora,)
    (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
        body,
        (
            x,
            cache["k"],
            cache["v"],
            cache["k_scale"] if quant else zero,
            cache["v_scale"] if quant else zero,
        ),
        xs,
    )
    cache = dict(cache)
    cache.update(
        k=new_k,
        v=new_v,
        pos=cache["pos"].at[slot].set(length),
        table=cache["table"].at[slot].set(blocks_row),
    )
    if quant:
        cache["k_scale"] = new_ks
        cache["v_scale"] = new_vs
    h = jax.lax.dynamic_index_in_dim(
        x[0], length - prefix_len - 1, axis=0, keepdims=False
    )
    logits, h = _head(params, h, cfg)
    if return_hidden:
        return logits, cache, h
    return logits, cache


def decode_slots_paged(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    active: jax.Array,
    cfg: Config,
    *,
    window: int | None = None,
    kernel: bool = False,
    lora: dict | None = None,
    adapter_ids: jax.Array | None = None,
    kv_sharded: bool = False,
) -> tuple[jax.Array, dict]:
    """One decode step for every slot against the paged cache.

    Identical contract to :func:`decode_slots`; attention reads gather the
    first ``window // block_size`` table entries per slot straight from the
    carried pool by (layer, block) (:func:`_pool_read`), so the bytes moved
    are the window's, as in the static window read — the pool layout
    changes where rows LIVE, not how many are read.  ``kernel`` (static)
    routes the attention read through the fused Pallas paged
    decode-attention kernel (``ops/paged_attention.py``) instead of the
    XLA gather path.  ``kv_sharded`` (static) says the pool's kv-head axis
    is split over a mesh, which picks the other read of :func:`_pool_read`.
    ``lora``/``adapter_ids (S,)`` gather each slot's adapter delta inside
    the same fused step — mixed-adapter batches ride ONE program
    (docs/MULTITENANT.md)."""
    logits, cache = _decode_paged_multi(
        params, tokens[:, None], cache, active, active[:, None], cfg,
        window=window, kernel=kernel, lora=lora, adapter_ids=adapter_ids,
        kv_sharded=kv_sharded,
    )
    cache["pos"] = jnp.where(active, cache["pos"] + 1, cache["pos"])
    return logits[:, 0], cache


def decode_slots_spec_paged(
    params: dict,
    qtokens: jax.Array,
    cache: dict,
    active: jax.Array,
    qvalid: jax.Array,
    cfg: Config,
    *,
    window: int | None = None,
    kernel: bool = False,
    lora: dict | None = None,
    adapter_ids: jax.Array | None = None,
    return_hidden: bool = False,
    kv_sharded: bool = False,
) -> tuple[jax.Array, dict] | tuple[jax.Array, dict, jax.Array]:
    """Speculative verify pass: score ``L = 1 + draft`` query positions per
    slot in ONE model call (docs/PERFORMANCE.md).

    ``qtokens (S, L)`` is the current token followed by the drafted ones;
    query ``j`` runs at position ``pos + j`` and its K/V is written there
    (exactly the bytes the sequential path would write if the draft is
    accepted).  ``qvalid (S, L)`` gates the cache writes — draft positions
    beyond the slot's remaining-token budget (whose blocks may not be
    reserved) are routed to the sink block.  ``cache["pos"]`` is NOT
    advanced: the caller moves it by however many tokens were accepted —
    rejected positions stay above ``pos``, invisible to every later read
    and overwritten by the next pass before they can be accepted.

    Returns ``(logits (S, L, V), cache)`` — plus the post-``ln_f`` hidden
    states ``(S, L, E)`` when ``return_hidden`` (STATIC) is set, so the
    Medusa-heads proposer can draft from the verified hidden without a
    second forward.
    """
    return _decode_paged_multi(
        params, qtokens, cache, active, qvalid, cfg, window=window,
        kernel=kernel, lora=lora, adapter_ids=adapter_ids,
        return_hidden=return_hidden, kv_sharded=kv_sharded,
    )


def _decode_paged_multi(
    params, qtokens, cache, active, qvalid, cfg: Config, *, window,
    kernel: bool = False, lora: dict | None = None,
    adapter_ids: jax.Array | None = None, return_hidden: bool = False,
    kv_sharded: bool = False,
):
    """Shared L-query decode body: ``L=1`` is the classic decode step,
    ``L>1`` the fused speculative verify.  The per-row contraction shapes
    are identical in both, so a verify pass's first position is bit-equal
    to the single-token step it replaces.

    ``kernel`` (static — folded into the serving program cache keys) swaps
    the attention read side for the Pallas paged decode-attention kernel:
    block-table gather, int8 dequant, and the softmax/PV contraction fuse
    into one VMEM-resident pass over the pool blocks instead of
    materializing the gathered window in HBM (docs/PERFORMANCE.md §7).
    The K/V *write* side (scatter of this step's rows) is unchanged."""
    pos = cache["pos"]  # (S,)
    table = cache["table"]  # (S, MB)
    S, L = qtokens.shape
    bs = cache["k"].shape[2]
    mb = table.shape[1]
    quant = "k_scale" in cache
    W = cfg.max_seq if window is None else min(window, cfg.max_seq)
    wb = max(1, W // bs)
    W = wb * bs
    read_idx = table[:, :wb]  # (S, wb) physical blocks attention reads
    x = params["tok_emb"][qtokens]  # (S, L, E)
    offs = jnp.arange(L)[None, :]
    positions = pos[:, None] + offs  # (S, L)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    # row r visible to query j iff r <= pos + j (draft positions see the
    # draft K/V written before them — causal speculation)
    valid = jnp.arange(W)[None, None, :] <= positions[:, :, None]  # (S, L, W)
    # Per-query write target: physical block + in-block offset.  INACTIVE
    # slots still flow through the scatter (fixed shapes), but their table
    # rows may reference blocks already reclaimed and handed to another
    # request — their writes are routed to physical block 0, which the
    # allocator reserves as a garbage sink and never hands out; the same
    # routing guards draft positions past the slot's block reservation.
    write_blk = jnp.where(
        qvalid,
        jnp.take_along_axis(
            table, jnp.minimum(positions // bs, mb - 1), axis=1
        ),
        0,
    )  # (S, L)
    write_off = positions % bs
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    sdt = cache["k_scale"].dtype if quant else None
    zero = jnp.zeros((), jnp.int8)
    aid = (
        None if lora is None
        else jnp.asarray(adapter_ids, jnp.int32).reshape(S)
    )

    def body(carry, inputs):
        x, ck, cv, cks, cvs = carry
        li, lp = inputs[0], inputs[1]
        ll = inputs[2] if lora is not None else None
        q, k, v = _qkv(x, lp, cfg, positions, ll, aid)
        if quant:
            qk, sk = _quant_kv(k, sdt)
            qv, sv = _quant_kv(v, sdt)
            ck = ck.at[li, write_blk, write_off].set(_pool_rows(ck, qk))
            cv = cv.at[li, write_blk, write_off].set(_pool_rows(cv, qv))
            cks = cks.at[li, write_blk, write_off].set(sk)
            cvs = cvs.at[li, write_blk, write_off].set(sv)
        else:
            ck = ck.at[li, write_blk, write_off].set(_pool_rows(ck, k))
            cv = cv.at[li, write_blk, write_off].set(_pool_rows(cv, v))
        with jax.named_scope("attn.paged"):
            if kernel:
                # fused Pallas read side: table gather + (dequant +) attention
                # in one VMEM pass over the blocks a live slot holds.  The
                # kernel is handed the WHOLE carried pool, layers flattened
                # into blocks (a reshape of leading dimensions: no copy), and
                # this layer's blocks by offset: a layer cut out of the pool
                # would be a copy of it (XLA fuses no slice into a kernel's
                # operand, PERF.md §6)
                from seldon_core_tpu.ops import paged_decode_attention

                def whole(pool):
                    return pool.reshape((-1,) + pool.shape[2:])

                o = paged_decode_attention(
                    q, whole(ck), whole(cv), read_idx + li * ck.shape[1], pos,
                    k_scale=whole(cks) if quant else None,
                    v_scale=whole(cvs) if quant else None,
                    active=active,
                )
            else:
                # gather each slot's visible blocks:
                # (S, wb, bs, kv, hd) -> (S, W, ..)
                def read(pool):
                    return _pool_read(pool, li, read_idx, kv_sharded)

                kw = read(ck).reshape(S, wb, bs, kv, hd)
                vw = read(cv).reshape(S, wb, bs, kv, hd)
                if quant:
                    kw = _dequant_kv(kw, read(cks), q.dtype)
                    vw = _dequant_kv(vw, read(cvs), q.dtype)
                kw = kw.reshape(S, W, kv, hd)
                vw = vw.reshape(S, W, kv, hd)
                # grouped-query attention against the *un-repeated* cache:
                # repeating kv to n_heads here would multiply cache reads by the
                # group size every decode step, defeating GQA's bandwidth savings
                groups = cfg.n_heads // cfg.n_kv_heads
                qg = q.reshape(S, L, cfg.n_kv_heads, groups, cfg.head_dim)
                s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kw) * scale
                s = jnp.where(
                    valid[:, None, None, :, :], s, jnp.finfo(s.dtype).min
                )
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bkgqs,bskd->bqkgd", p, vw)
                o = o.reshape(S, L, cfg.n_heads, cfg.head_dim)
        x = x + _attn_out(o, lp, ll, aid)
        h = _rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
        mlp = _mlp_block(h, lp, ll, aid)
        return (x + mlp, ck, cv, cks, cvs), None

    xs_in = (jnp.arange(cfg.n_layers), params["layers"])
    if lora is not None:
        xs_in = xs_in + (lora,)
    (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
        body,
        (
            x,
            cache["k"],
            cache["v"],
            cache["k_scale"] if quant else zero,
            cache["v_scale"] if quant else zero,
        ),
        xs_in,
    )
    out = dict(cache)
    out["k"] = new_k
    out["v"] = new_v
    if quant:
        out["k_scale"] = new_ks
        out["v_scale"] = new_vs
    logits, x = _head(params, x, cfg)
    if return_hidden:
        return logits, out, x
    return logits, out


# ---------------------------------------------------------------------------
# learned speculation (docs/PERFORMANCE.md §6): Medusa-style decode heads
# and layer-truncated self-draft weights.  Both are DRAFT sources only —
# the fused verify/accept pass scores their proposals against the real
# model, so neither can change emitted tokens, only the acceptance rate.
# ---------------------------------------------------------------------------


def init_medusa_heads(
    rng: jax.Array,
    cfg: Config,
    n_heads: int,
    base_head: jax.Array | None = None,
    dtype=jnp.float32,
) -> dict:
    """``n_heads`` Medusa-style draft heads: head ``j`` predicts the token
    ``j + 1`` positions past the one the input hidden state emitted.

    Each head is the standard Medusa residual block over the post-``ln_f``
    hidden ``h``: ``logits_j = (h + silu(h @ w1[j])) @ head[j]``.  With
    ``base_head`` (the base model's ``lm_head``) the output projections
    start as copies of it and ``w1`` near zero — untrained heads then draft
    "repeat the next-token argmax", a harmless self-draft for the pinned
    bit-identity tests.  Real (trained) heads load by path through
    ``executor/checkpoint.py`` instead (``spec_heads_path``)."""
    n_heads = int(n_heads)
    e, v = cfg.hidden, cfg.vocab_size
    k1, k2 = jax.random.split(jax.random.PRNGKey(0) if rng is None else rng)
    w1 = 0.01 * jax.random.normal(k1, (n_heads, e, e), dtype=jnp.float32)
    if base_head is not None:
        head = jnp.broadcast_to(
            jnp.asarray(base_head, jnp.float32)[None], (n_heads, e, v)
        )
    else:
        head = 0.02 * jax.random.normal(k2, (n_heads, e, v), dtype=jnp.float32)
    return {"w1": w1.astype(dtype), "head": jnp.asarray(head, dtype)}


def apply_medusa_heads(heads: dict, h: jax.Array) -> jax.Array:
    """Head logits ``(S, K, V)`` from per-slot hidden states ``h (S, E)``.
    Pure jnp with static shapes: runs INSIDE the fused decode program, so
    heads drafting costs zero extra host syncs."""
    w1 = heads["w1"]
    hx = h.astype(w1.dtype)
    hk = hx[:, None, :] + jax.nn.silu(jnp.einsum("se,kef->skf", hx, w1))
    return jnp.einsum("ske,kev->skv", hk, heads["head"])


def medusa_head_bytes(cfg: Config, n_heads: int, dtype=jnp.float32) -> int:
    """HBM bytes ``n_heads`` resident Medusa heads cost (MemoryManager
    accounting, docs/MULTITENANT.md)."""
    itemsize = jnp.dtype(dtype).itemsize
    e, v = cfg.hidden, cfg.vocab_size
    return int(n_heads) * (e * e + e * v) * itemsize


def truncate_params(params: dict, n_layers: int) -> dict:
    """LayerSkip-style self-draft weights: the target's OWN first
    ``n_layers`` transformer blocks with its embedding, final norm, and
    lm_head — a co-resident draft model at ``n_layers / cfg.n_layers`` of
    the per-token cost with no second checkpoint.  The stacked layer
    leaves are sliced (new device arrays); everything else is shared by
    reference."""
    n = int(n_layers)
    layers = {k: v[:n] for k, v in params["layers"].items()}
    return {**params, "layers": layers}


def decode_slots(
    params: dict,
    tokens: jax.Array,
    cache: dict,
    active: jax.Array,
    cfg: Config,
    *,
    window: int | None = None,
) -> tuple[jax.Array, dict]:
    """One decode step for EVERY slot: ``tokens (S,)`` -> ``(logits (S, V),
    cache)``; only ``active`` slots advance their position.

    Inactive slots still flow through the math (their outputs are ignored and
    their cache writes land at a frozen position that the next prefill
    overwrites) — the cost of a fixed shape is far below a recompile.

    ``window`` (static) bounds the cache rows attention READS to
    ``[0, window)``.  The caller guarantees every live position (including
    this step's write) is below it.  Attention reads are the decode
    bandwidth bill once contexts are long — at max_seq 2048 with 8 slots,
    full-width reads cost more than the entire 1.1B-param weight stream —
    so serving picks a power-of-two ceiling over the live positions and
    compiles one program per ceiling instead of always paying max_seq
    (measured 2.7x decode throughput at short contexts).
    """
    pos = cache["pos"]  # (S,)
    S = tokens.shape[0]
    W = cfg.max_seq if window is None else min(window, cfg.max_seq)
    x = params["tok_emb"][tokens][:, None]  # (S, 1, E)
    positions = pos[:, None]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    valid = jnp.arange(W)[None, :] <= pos[:, None]  # (S, W)
    slot_idx = jnp.arange(S)
    kv, hd = cfg.n_kv_heads, cfg.head_dim

    # The cache rides the scan CARRY, not xs/ys: as scan inputs/outputs XLA
    # materializes a fresh full-size copy of every layer's slab per step
    # (~1 GB/step at 8 slots x 2048 ctx), which dwarfs the actual row
    # writes.  Carried buffers alias in place, so each step's memory bill is
    # the windowed read + one row write per slot — measured 2.5x decode
    # throughput on the 1.1B config.
    def body(carry, inputs):
        x, ck, cv = carry
        li, lp = inputs
        h = _rmsnorm(x, lp["ln_att"], cfg.norm_eps)
        q = jnp.einsum("ble,ehd->blhd", h, lp["wq"])
        k = jnp.einsum("ble,ehd->blhd", h, lp["wk"])
        v = jnp.einsum("ble,ehd->blhd", h, lp["wv"])
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        # per-slot scatter: each slot writes its own position (one shared
        # scalar would force all slots to the same length)
        ck = ck.at[li, slot_idx, pos].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[li, slot_idx, pos].set(v[:, 0].astype(cv.dtype))
        # windowed read of THIS layer's rows [0, W)
        kw = jax.lax.dynamic_slice(ck, (li, 0, 0, 0, 0), (1, S, W, kv, hd))[0]
        vw = jax.lax.dynamic_slice(cv, (li, 0, 0, 0, 0), (1, S, W, kv, hd))[0]
        # grouped-query attention against the *un-repeated* cache: repeating
        # kv to n_heads here would multiply cache reads by the group size
        # every decode step, defeating GQA's bandwidth savings
        groups = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(S, 1, cfg.n_kv_heads, groups, cfg.head_dim)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kw) * scale
        s = jnp.where(valid[:, None, None, None, :], s, jnp.finfo(s.dtype).min)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, vw)
        o = o.reshape(S, 1, cfg.n_heads, cfg.head_dim)
        x = x + jnp.einsum("blhd,hde->ble", o, lp["wo"])
        h = _rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
        mlp = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        return (x + mlp, ck, cv), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body,
        (x, cache["k"], cache["v"]),
        (jnp.arange(cfg.n_layers), params["layers"]),
    )
    cache = {
        "k": new_k,
        "v": new_v,
        "pos": jnp.where(active, pos + 1, pos),
    }
    return _head(params, x[:, 0], cfg)[0], cache


def generate(
    params: dict,
    tokens: jax.Array,
    cfg: Config,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Greedy (or sampled) generation: ``tokens (B, L)`` -> ``(B, max_new)``.

    The whole loop is one ``lax.scan`` over compiled decode steps.
    """
    cache = init_cache(cfg, tokens.shape[0])
    logits, cache = prefill(params, tokens, cfg, cache)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def pick(logits, key):
        if temperature > 0.0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def body(carry, key):
        logits, cache = carry
        tok = pick(logits, key).astype(jnp.int32)
        logits, cache = decode_step(params, tok, cache, cfg)
        return (logits, cache), tok

    keys = jax.random.split(rng, max_new_tokens)
    (_, _), toks = jax.lax.scan(body, (logits, cache), keys)
    return toks.T  # (B, max_new)


def apply(params: dict, batch: jax.Array, cfg: Config) -> jax.Array:
    """Serving entry: next-token distribution for a token batch ``(B, L)``."""
    logits = forward(params, batch.astype(jnp.int32), cfg)
    return jax.nn.softmax(logits[:, -1])


def make_train_step(
    cfg: Config,
    optimizer: Any = None,
    *,
    mesh: Any = None,
    seq_impl: str = "dense",
):
    """Causal-LM training/fine-tuning step (cross-entropy over shifted
    tokens).  The reference's only 'learning' is bandit feedback counters
    (examples/routers/epsilon_greedy/EpsilonGreedy.py:42-60); here online
    fine-tuning is a first-class sharded step — also what the multi-chip
    dry-run compiles.  ``mesh``/``seq_impl`` select sequence-parallel
    attention (ring/ulysses) for the forward pass.
    """
    import optax

    if optimizer is None:
        optimizer = optax.adamw(1e-4)

    def loss_fn(params, tokens):
        logits = forward(params, tokens, cfg, mesh=mesh, seq_impl=seq_impl)
        targets = tokens[:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return optimizer, train_step
