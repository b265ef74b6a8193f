"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference never splits one model invocation across processes (SURVEY.md
§2.7); long-context generative serving forces it: a sequence sharded over the
``sp`` mesh axis must attend across shards.  Two standard strategies, both
expressed with XLA collectives so they compile into the step function:

* **Ring attention** (`ring_attention`): each shard holds a KV block and
  rotates it around the ring with ``ppermute`` while accumulating a
  numerically-stable online softmax (flash-attention style).  ICI traffic is
  overlapped with compute by XLA latency hiding; memory per chip is O(L/n).
* **Ulysses all-to-all** (`ulysses_attention`): ``all_to_all`` re-shards
  sequence->heads, runs dense local attention, and re-shards back.  Cheaper
  for moderate L when heads % sp == 0.

Both are plain functions over per-shard blocks, used inside ``shard_map``
(see :func:`ring_self_attention` for the wrapper used by models/tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _block_attend(q, k, v, q_offset, k_offset, causal, scale):
    """Dense attention of a local Q block against one KV block with global
    position masking.  q: (B, Lq, H, D); k/v: (B, Lk, H, D)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # (B,H,Lq)
    # guard fully-masked rows (all -inf): contribute nothing
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)  # noqa: E741
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m_safe, l, jnp.isfinite(m)


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True):
    """Online-softmax attention over a KV ring.  Call inside shard_map.

    Per-shard shapes: q/k/v ``(B, L_local, H, D)``; the global sequence is the
    concatenation over the ``axis_name`` ring in index order.  Returns the
    local output block ``(B, L_local, H, D)``.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    l_local = q.shape[1]
    q_offset = idx * l_local

    # accumulators for the online softmax across ring steps
    acc = jnp.zeros(q.shape, jnp.float32)  # numerator
    bhq = (q.shape[0], q.shape[2], q.shape[1])
    m_run = jnp.full(bhq, -jnp.inf)
    l_run = jnp.zeros(bhq)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, i):
        acc, m_run, l_run, k_blk, v_blk = carry
        kv_idx = (idx - i) % n  # whose block we hold after i rotations
        o, m_blk, l_blk, any_valid = _block_attend(
            q, k_blk, v_blk, q_offset, kv_idx * l_local, causal, scale
        )
        m_new = jnp.maximum(m_run, jnp.where(any_valid, m_blk, -jnp.inf))
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        c_old = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_new_safe), 0.0)
        c_blk = jnp.where(any_valid, jnp.exp(m_blk - m_new_safe), 0.0)
        acc = acc * c_old.transpose(0, 2, 1)[..., None] + (
            o.astype(jnp.float32) * c_blk.transpose(0, 2, 1)[..., None]
        )
        l_run = l_run * c_old + l_blk * c_blk
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (acc, m_new, l_run, k_blk, v_blk), None

    # scan (not fori_loop) so the ring is reverse-differentiable — the
    # sequence-parallel fine-tuning step backprops through it
    (acc, m_run, l_run, _, _), _ = jax.lax.scan(
        body, (acc, m_run, l_run, k, v), jnp.arange(n)
    )
    denom = jnp.where(l_run > 0, l_run, 1.0).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True):
    """All-to-all sequence parallelism: re-shard seq->heads, attend locally,
    re-shard back.  Requires n_heads % sp_size == 0.  Call inside shard_map
    with per-shard (B, L_local, H, D) blocks."""
    n = jax.lax.psum(1, axis_name)
    # (B, L/n, H, D) -> (B, L, H/n, D): gather sequence, scatter heads
    q = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    o, _, l, _ = _block_attend(q, k, v, 0, 0, causal, scale)  # noqa: E741
    o = o / jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    del n
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ring_self_attention(
    mesh: Mesh,
    q,
    k,
    v,
    *,
    causal: bool = True,
    impl: str = "ring",
    seq_axis: str = "sp",
):
    """shard_map wrapper: global (B, L, H, D) arrays sequence-sharded over
    ``seq_axis``; returns the global attention output with the same sharding."""
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    # batch stays dp-sharded through the ring; heads are gathered (ring+tp
    # jointly would need head-sharded specs — future kernel work)
    spec = P(("dp", "fsdp"), seq_axis, None, None)
    wrapped = jax.shard_map(
        functools.partial(fn, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return wrapped(q, k, v)
