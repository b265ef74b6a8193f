"""The routed experts of a decode step as a Pallas TPU kernel that streams
only the experts some token chose.

``models/moe.py::experts_dense`` runs every held expert over every
token: a waste wherever some held expert has no token — a chip holds 128 and
8 tokens x top-8 touch a third, or holds 16 and 30 tokens touch 12 — since
the step is bound by reading expert weights, and what it reads of the others
is multiplied by a combine weight of exactly 0.  This kernel computes the
same sum with those terms left out, and reads a byte as fast as the dense
products where every held expert is touched::

    out[t] = sum over the touched experts x of
             cw[t, x] * (silu(h2[t] @ gate_x) * (h2[t] @ up_x)) @ down_x

One grid step is one touched expert (``F`` in tiles where an expert's three
matrices would not fit fast memory twice): its ``gate`` and ``up (E, F)``
and ``down (F, E)`` come into VMEM by the (scalar-prefetched) list of
touched ids, the next expert's in flight meanwhile; the products run for all
``T`` rows with float32 accumulation, row ``t`` is scaled by ``cw[t, x]`` —
0 for a row that did not choose ``x``, as in ``experts_dense`` — and added
into a float32 ``(T, E)`` accumulator that stays resident.  The grid's
static bound is the most experts ``T`` tokens can touch; a step past the
number touched names the block the step before it named, so the pipeline
copies nothing for it, and its products are skipped.  (With nothing touched
the first expert of the list is still copied once: the pipeline's first
block travels before the body can say no.)

Operands are bfloat16 as served (float32 in the tests: computed at HIGHEST
precision), every accumulation and the combine are float32, and the product
behind ``down`` stays float32 where ``experts_dense`` rounds it to the
operand dtype: nowhere lower precision than the dense formulation.

The stacks are those of EVERY layer, ``(layers * held, ...)``, and the layer
an offset into the list: a layer cut out of the stack first would be a copy
of its experts on every call (XLA fuses no slice into a kernel's operand).
The kernel is single-device: it is not offered expert stacks sharded over a
mesh, as the paged decode kernel is not offered a sharded pool.  Compiled by
Mosaic on every backend but the CPU, where it runs in Pallas interpret mode
(``tests/test_touched_experts.py`` pins it to ``experts_dense``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from seldon_core_tpu.ops.paged_attention import mxu_operands

# one grid step's three weight blocks may take this much of VMEM (they are
# double-buffered): a whole expert of 3 x 3.1 MB at Keye-VL-2.0's widths,
# F in tiles of 512 at Command A+'s
STEP_BYTES = 12 << 20
_N, _BASE = range(2)  # the second scalar operand: experts touched, li * held


def f_tile(hidden: int, ffn: int, itemsize: int) -> int:
    """Columns of ``F`` one grid step takes: all of them where an expert's
    three matrices fit :data:`STEP_BYTES`, else the largest multiple of 128
    that divides ``F`` and does."""
    if 3 * hidden * ffn * itemsize <= STEP_BYTES:
        return ffn
    fits = [
        t for t in range(128, ffn, 128)
        if ffn % t == 0 and 3 * hidden * t * itemsize <= STEP_BYTES
    ]
    if not fits:
        raise ValueError(
            f"no tile of ffn {ffn} at hidden {hidden} fits {STEP_BYTES} bytes"
        )
    return fits[-1]


def _kernel(ids_ref, meta_ref, h_ref, cw_ref, gate_ref, up_ref, down_ref, o_ref):
    g, f = pl.program_id(0), pl.program_id(1)

    @pl.when((g == 0) & (f == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < meta_ref[_N])
    def _expert():
        cdt, prec = mxu_operands(gate_ref.dtype)

        def dot(a, b):
            return jax.lax.dot_general(
                a, b.astype(cdt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )

        h = h_ref[...].astype(cdt)
        act = jax.nn.silu(dot(h, gate_ref[...])) * dot(h, up_ref[...])
        d = dot(act.astype(cdt), down_ref[...])  # (T, E) float32
        # column ids[g] of the combine weights, picked without a dynamic
        # lane slice: (T, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, cw_ref.shape, 1) == ids_ref[g]
        w = jnp.sum(jnp.where(col, cw_ref[...], 0.0), axis=1, keepdims=True)
        o_ref[...] += d * w


def touched_expert_products(
    h2: jax.Array,
    cw: jax.Array,
    ids: jax.Array,
    n: jax.Array,
    we_gate: jax.Array,
    we_up: jax.Array,
    we_down: jax.Array,
    *,
    base: jax.Array | int = 0,
    tile: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``h2 (T, E)`` through the experts ``ids[:n]`` of one layer -> ``(T,
    E)`` float32, the sum ``experts_dense`` forms over the experts with a
    nonzero column of ``cw``.

    ``cw (T, X)`` float32: row ``t``'s combine weight on each of the layer's
    ``X`` held experts, 0 where not chosen.  ``ids (G,)`` int32: the touched
    experts first, each once, the tail filled with the last of them; ``n``
    how many (0: zeros come back).  ``G`` is the grid's static bound.
    ``we_gate``, ``we_up (N, E, F)`` and ``we_down (N, F, E)`` are the
    stacks of every layer flattened over (layer, expert), and ``base`` the
    layer's offset ``li * X`` into them.  ``tile`` (static) columns of ``F``
    a grid step takes (default: :func:`f_tile`).

    ``interpret`` defaults to True on the CPU backend only; every other
    backend compiles the kernel, and nothing falls back in its name.
    """
    T, E = h2.shape
    X = cw.shape[1]
    G = ids.shape[0]
    F = we_gate.shape[2]
    itemsize = we_gate.dtype.itemsize
    tf = f_tile(E, F, itemsize) if tile is None else int(tile)
    if F % tf:
        raise ValueError(f"tile {tf} does not divide ffn {F}")
    nf = F // tf
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # whole sublane tiles of rows, a bfloat16 operand's 16 too
    Tp = -(-T // 16) * 16
    h2 = jnp.pad(h2, ((0, Tp - T), (0, 0)))
    cw = jnp.pad(cw.astype(jnp.float32), ((0, Tp - T), (0, 0)))
    meta = jnp.stack([
        jnp.asarray(n, jnp.int32), jnp.asarray(base, jnp.int32)
    ])

    def expert(g, ids_ref, meta_ref):
        return meta_ref[_BASE] + ids_ref[g]

    def col(g, f, meta_ref):
        # a step past the touched names the last block of the last of them
        return jnp.where(g < meta_ref[_N], f, nf - 1)

    def whole(g, f, ids_ref, meta_ref):
        return (0, 0)

    def up_block(g, f, ids_ref, meta_ref):
        return (expert(g, ids_ref, meta_ref), 0, col(g, f, meta_ref))

    def down_block(g, f, ids_ref, meta_ref):
        return (expert(g, ids_ref, meta_ref), col(g, f, meta_ref), 0)

    step = 3 * E * tf * itemsize
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, nf),
            in_specs=[
                pl.BlockSpec((Tp, E), whole),
                pl.BlockSpec((Tp, X), whole),
                pl.BlockSpec((None, E, tf), up_block),
                pl.BlockSpec((None, E, tf), up_block),
                pl.BlockSpec((None, tf, E), down_block),
            ],
            out_specs=pl.BlockSpec((Tp, E), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, E), jnp.float32),
        # the accumulator is carried from one expert to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * step + (16 << 20),
        ),
        interpret=interpret,
    )(jnp.asarray(ids, jnp.int32), meta, h2, cw, we_gate, we_up, we_down)
    return out[:T]


def touched_list(touched: jax.Array, bound: int):
    """``touched (X,)`` bool -> (``ids (bound,)`` int32: the touched ids in
    ascending order, the tail filled with the last of them — 0 where none
    is —, and how many there are).  ``bound`` is at least that many."""
    x = touched.shape[0]
    rank = jnp.cumsum(touched) - 1  # a touched id's place in the list
    place = (rank[None, :] == jnp.arange(bound)[:, None]) & touched[None, :]
    arange = jnp.arange(x, dtype=jnp.int32)
    ids = jnp.sum(jnp.where(place, arange[None, :], 0), axis=1)
    n = jnp.sum(touched).astype(jnp.int32)
    last = jnp.max(jnp.where(touched, arange, 0))
    return jnp.where(jnp.arange(bound) < n, ids, last).astype(jnp.int32), n


__all__ = ["touched_expert_products", "touched_list", "f_tile"]
