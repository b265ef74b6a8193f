"""The frame of a paged cache, as every family but ``llama`` builds it (that
one keeps its own pool code: int8 scales, the mesh's layout): the
bookkeeping arrays beside a family's pools, the writes of a prompt's rows
into its blocks, a per-token array carried with its blocks TRANSPOSED, a
decode step's read of a layer of a K/V pool, the tail of a prefill program, a slot's bytes, the on-device counters' add and
the refusal of what no such family serves.  The pools themselves — their
names, shapes and what reads them — are the family's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def bookkeeping(max_seq: int, n_slots: int, block_size: int, n_counters: int) -> dict:
    """What every paged cache holds beside its pools: each slot's position,
    its row of the block table, and the family's ``COUNTERS`` (uint32,
    wrapping)."""
    if max_seq % block_size:
        raise ValueError(
            f"max_seq {max_seq} must be a multiple of block_size {block_size}"
        )
    return {
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "table": jnp.zeros((n_slots, max_seq // block_size), jnp.int32),
        "counters": jnp.zeros((n_counters,), jnp.uint32),
    }


def slot_bytes(max_seq: int, values_per_token: int, dtype) -> int:
    """HBM bytes the pool's rows of one ``max_seq`` slot cost, at a family's
    ``values_per_token`` (all layers, all its per-token arrays)."""
    itemsize = 2 if str(dtype) in ("bfloat16", "bf16") else np.dtype(dtype).itemsize
    return max_seq * values_per_token * itemsize


def no_lora(family: str, lora) -> None:
    if lora is not None:
        raise TypeError(f"{family} has no LoRA path")


def bump(counters, index: int, by):
    if counters is None:
        return None
    return counters.at[index].add(jnp.asarray(by, jnp.uint32))


def write_prompt(pool, li, phys, rows, bs):
    """Scatter ``rows (L, ...)`` of layer ``li`` into the blocks ``phys``."""
    lb = rows.shape[0] // bs
    return pool.at[li, phys].set(rows.reshape(lb, bs, -1).astype(pool.dtype))


def write_transposed(pool, li, blk, rows, off=None):
    """Write a token's narrow rows (``keye_vl2``'s index keys, ``kimi_k2``'s
    rotary keys: 64 wide) into layer ``li`` of their pool AS IT IS CARRIED,
    ``(layers, blocks, D, block)``: a block transposed, its tokens along the
    lanes (the family's ``init_paged_cache`` says why).  Whole blocks ``blk``
    from ``rows (len(blk) * block, D)``, a prompt's; or with ``off`` one
    token a block, ``rows (S, D)`` at ``blk[s], off[s]``: a decode step's."""
    rows = rows.astype(pool.dtype)
    if off is not None:
        return pool.at[li, blk, :, off].set(rows)
    blocks = rows.reshape(-1, pool.shape[3], rows.shape[-1])
    return pool.at[li, blk].set(by_token(blocks))


def by_token(blocks):
    """Blocks as such a pool carries them, ``(..., D, block)``, seen by
    token, ``(..., block, D)`` — and back: its own inverse.  With
    :func:`write_transposed` and the kernel that reads the blocks as they
    lie, all that knows which way round a block lies."""
    return jnp.swapaxes(blocks, -1, -2)


def decode_frame(cache, active, bs: int, window, max_seq: int):
    """Where a decode step writes and what it reads, one token a slot:
    ``(write_blk (S,), write_off (S,), read_blk (S, wb))`` — the block and
    the row in it of each slot's position (an inactive slot writes to the
    sink block 0: ``models/llama.py::_decode_paged_multi`` has the reasons),
    and the table's columns that cover the static ``window``."""
    pos, table = cache["pos"], cache["table"]
    S, mb = table.shape
    W = max_seq if window is None else min(window, max_seq)
    write_blk = jnp.where(
        active, table[jnp.arange(S), jnp.minimum(pos // bs, mb - 1)], 0
    )
    return write_blk, pos % bs, table[:, : max(1, W // bs)]


def attend_paged(q, ck, cv, li, read_blk, pos, active, *, kernel: bool):
    """One decode query a slot over layer ``li`` (traced or not) of the pools
    ``ck``, ``cv (layers, blocks, block, kv_heads * head_dim)`` as carried,
    the step's own row written already, under ``attn.paged``.  ``q (S, H,
    D)`` -> the same.  ``kernel`` (static) reads through the Pallas paged
    kernel, each slot's live blocks alone; else the gathered window."""
    from seldon_core_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    nb, bs, row = ck.shape[1:]
    with jax.named_scope("attn.paged"):
        if kernel:
            # the whole carried pool, layers flattened into blocks, this
            # layer's by offset (models/llama.py::_decode_paged_multi says why)
            return paged_decode_attention(
                q[:, None], ck.reshape(-1, bs, row), cv.reshape(-1, bs, row),
                read_blk + li * nb, pos, active=active,
            )[:, 0]
        kv = row // q.shape[-1]
        layer = [
            lax.dynamic_index_in_dim(c, li, keepdims=False).reshape(nb, bs, kv, -1)
            for c in (ck, cv)
        ]
        return paged_decode_attention_reference(q[:, None], *layer, read_blk, pos)[:, 0]


def finish_prefill(params, cfg, cache, x, at, pools: dict, ctr, slot, length,
                   blocks_row, return_hidden, head):
    """The tail of a prefill program: the written ``pools`` (by the cache's
    names) and the counters back into the cache, the slot's position and
    table row set, and ``head(params, h, cfg)`` on the row ``at`` of ``x``,
    the last real token's.  -> ``(logits, cache[, hidden])``."""
    cache = dict(cache)
    cache.update(
        pools,
        pos=cache["pos"].at[slot].set(length),
        table=cache["table"].at[slot].set(blocks_row),
    )
    if ctr is not None:
        cache["counters"] = ctr
    h = lax.dynamic_index_in_dim(x, at, axis=0, keepdims=False)
    logits, h = head(params, h, cfg)
    if return_hidden:
        return logits, cache, h
    return logits, cache
