"""A prompt's selective state-space recurrence as a Pallas TPU kernel.

The state-space mixer of ``models/jamba.py`` carries, a layer, a float32 state
``S (d_state, d_inner)`` through the tokens::

    S_t = exp(D_t[None, :] * A) * S_{t-1} + (D_t * c_t)[None, :] * B_t[:, None]
    y_t = sum_n S_t[n, :] * C_t[n] + Dskip * c_t

``T`` dependent updates: written as XLA it is ``T`` tiny sequential steps or a
``(T, d_state, d_inner)`` float32 array in HBM (1,024 tokens at 16 x 5,120:
335 MB a layer).  Here the channels are tiled over the grid (``tile`` lanes a
tile), the tokens stream through in chunks along a sequential grid axis, and a
tile's ``(d_state, tile)`` state lives on the chip from the first chunk to the
last: in the vector registers inside a chunk (``16 x 512`` float32 is 8 of
them), in the revisited output block between chunks.  ``D_t`` and ``c`` are
read once a token, ``y`` is written once, the state once a prompt.  Everything
inside is float32.

Layout.  The state index lies along the SUBLANES and the channels along the
lanes: what varies by channel alone (``D_t``, ``c_t``, ``Dskip``) is a row
spread down the sublanes, what varies by state index alone (``B_t``, ``C_t``)
a column spread across the lanes, and ``y_t`` is a sum down the sublanes.  The
other way round a state of 16 would fill an eighth of every register.  ``B``
and ``C`` reach the kernel as ``(T / 16, d_state, 16)``: sixteen tokens'
columns side by side, so a group of sixteen tokens is one dynamic index on
the leading axis and every token's column a static lane of it.

The real length (scalar prefetch) stops the recurrence inside a padded rung:
from ``length`` on ``D_t`` counts as 0 (the state stays; ``y`` is finite and
belongs to padding), and a chunk wholly past it is not computed at all.

The MXU does none of this: the vector unit bounds the kernel (per token and
128 channels: the exponent's argument, the decay, the input's outer product,
their sum, the output's product, two registers each, and a sum down sixteen
sublanes), not HBM.  :func:`selective_scan_reference` is the same recurrence
as a ``lax.scan`` over tokens: what the CPU, the tests and ``seq_impl:
dense`` run, and :func:`selective_step` one token's update for every slot of
a decode step.

The kernel is compiled by Mosaic on every backend but the CPU, where it runs
in Pallas interpret mode so the equivalence tests pin it to the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 16  # tokens unrolled together: one bfloat16 tile of rows
CHUNK = 256  # tokens a grid step streams through
TILE = 512  # channels (lanes) a grid step holds the state of


def _advance(s, decay, drive, product_dtype):
    """``decay * s + drive`` in float32; ``product_dtype`` (a negative
    control, never served) rounds each product to that type first."""
    if product_dtype is None:
        return decay * s + drive

    def rounded(x):
        return x.astype(product_dtype).astype(jnp.float32)

    return rounded(rounded(decay) * s) + rounded(drive)


def _scan_kernel(
    len_ref,  # (1,) int32 scalar prefetch: the prompt's real length
    c_ref,  # (chunk, tile) the convolution's output, activations' dtype
    dt_ref,  # (chunk, tile) float32 D_t
    b_ref,  # (chunk / GROUP, N, GROUP) float32: B by token, columns
    cc_ref,  # (chunk / GROUP, N, GROUP) float32: C
    a_ref,  # (N, tile) float32 A = -exp(A_log), state index by channel
    d_ref,  # (1, tile) float32 Dskip
    y_ref,  # (chunk, tile) out
    s_ref,  # (N, tile) float32 out: the state, resident across chunks
    y_scr,  # (chunk, tile) float32
    *,
    chunk,
    product_dtype,
):
    ci = pl.program_id(1)
    length = len_ref[0]
    n, tile = a_ref.shape

    @pl.when(ci == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(ci * chunk >= length)
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci * chunk < length)
    def _live():
        a = a_ref[...]

        def group(g, s):
            at = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
            c16 = c_ref[at, :].astype(jnp.float32)
            row = lax.broadcasted_iota(jnp.int32, (GROUP, tile), 0)
            real = ci * chunk + g * GROUP + row < length
            dt16 = jnp.where(real, dt_ref[at, :], 0.0)
            dtc16 = dt16 * c16
            b16 = b_ref[g]  # (N, GROUP)
            c16n = cc_ref[g]
            for i in range(GROUP):
                dt = jnp.broadcast_to(dt16[i:i + 1, :], (n, tile))
                dtc = jnp.broadcast_to(dtc16[i:i + 1, :], (n, tile))
                bt = jnp.broadcast_to(b16[:, i:i + 1], (n, tile))
                ct = jnp.broadcast_to(c16n[:, i:i + 1], (n, tile))
                s = _advance(s, jnp.exp(dt * a), dtc * bt, product_dtype)
                y_scr[pl.ds(g * GROUP + i, 1), :] = jnp.sum(
                    s * ct, axis=0, keepdims=True
                )
            return s

        s_ref[...] = lax.fori_loop(0, chunk // GROUP, group, s_ref[...])
        y_ref[...] = (
            y_scr[...] + d_ref[...] * c_ref[...].astype(jnp.float32)
        ).astype(y_ref.dtype)


def _fit(size: int, preferred: int, unit: int) -> int:
    """The largest multiple of ``unit`` that divides ``size`` and is at most
    ``preferred``; ``size`` itself where there is none."""
    fits = [
        n for n in range(unit, min(preferred, size) + 1, unit) if size % n == 0
    ]
    return max(fits) if fits else size


def selective_scan(
    c: jax.Array,
    dt: jax.Array,
    b: jax.Array,
    cc: jax.Array,
    a: jax.Array,
    d_skip: jax.Array,
    length,
    *,
    chunk: int = CHUNK,
    tile: int = TILE,
    interpret: bool | None = None,
    product_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """The recurrence over one prompt.  ``c (T, Di)`` the convolution's
    output (any float dtype: ``y`` comes back in it), ``dt (T, Di)`` float32
    ``D_t``, ``b`` and ``cc (T, N)`` float32 ``B`` and ``C``, ``a (N, Di)``
    float32 ``A`` with the state index leading, ``d_skip (Di,)``, ``length``
    the real tokens of the ``T`` (a traced scalar).  Returns ``(y (T, Di), S
    (N, Di) float32)``: ``S`` as of token ``length - 1``; rows of ``y`` from
    ``length`` on belong to padding and are finite.  ``T`` is padded here to
    whole chunks.  ``product_dtype`` (static; a negative control, never
    served) rounds the recurrence's products to that type."""
    T, di = c.shape
    n = a.shape[0]
    chunk = min(chunk, -(-T // GROUP) * GROUP)
    chunk -= chunk % GROUP
    tp = -(-T // chunk) * chunk
    tile = _fit(di, tile, 128)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    def rows(x):
        return jnp.pad(x, ((0, tp - T), (0, 0)))

    def columns(x):
        """(T, N) -> (T / GROUP, N, GROUP): a group's columns side by side."""
        x = rows(x.astype(jnp.float32))
        return x.reshape(tp // GROUP, GROUP, n).transpose(0, 2, 1)

    kernel = functools.partial(_scan_kernel, chunk=chunk, product_dtype=product_dtype)
    by_token = pl.BlockSpec((chunk, tile), lambda d, t, ln: (t, d))
    by_group = pl.BlockSpec((chunk // GROUP, n, GROUP), lambda d, t, ln: (t, 0, 0))
    by_channel = lambda rows_: pl.BlockSpec((rows_, tile), lambda d, t, ln: (0, d))  # noqa: E731
    y, s = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(di // tile, tp // chunk),
            in_specs=[by_token, by_token, by_group, by_group,
                      by_channel(n), by_channel(1)],
            out_specs=[by_token, by_channel(n)],
            scratch_shapes=[pltpu.VMEM((chunk, tile), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((tp, di), c.dtype),
            jax.ShapeDtypeStruct((n, di), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ssm.scan",
    )(
        jnp.asarray(length, jnp.int32).reshape(1), rows(c),
        rows(dt.astype(jnp.float32)), columns(b), columns(cc),
        a.astype(jnp.float32), d_skip.astype(jnp.float32).reshape(1, di),
    )
    return y[:T], s


def _update_kernel(
    li_ref,  # (1,) int32 scalar prefetch: the layer
    s_ref,  # (1, G, N, tile) float32: G slots' states of that layer
    c_ref,  # (G, tile) the convolution's output
    dt_ref,  # (G, tile) float32 D_t (0 for a slot that stands still)
    b_ref,  # (1, N, G) float32: B by slot, columns
    cc_ref,  # (1, N, G) float32: C
    a_ref,  # (N, tile) float32
    d_ref,  # (1, tile) float32 Dskip
    o_ref,  # (1, G, N, tile) out: the same block of the same array
    y_ref,  # (G, tile) float32 out
    *,
    product_dtype,
):
    del li_ref
    n, tile = a_ref.shape
    G = c_ref.shape[0]
    a = a_ref[...]
    cg = c_ref[...].astype(jnp.float32)
    dtg = dt_ref[...]
    dtcg = dtg * cg
    bg, ccg = b_ref[0], cc_ref[0]
    for i in range(G):
        dt = jnp.broadcast_to(dtg[i:i + 1, :], (n, tile))
        dtc = jnp.broadcast_to(dtcg[i:i + 1, :], (n, tile))
        bt = jnp.broadcast_to(bg[:, i:i + 1], (n, tile))
        ct = jnp.broadcast_to(ccg[:, i:i + 1], (n, tile))
        s = _advance(
            s_ref[0, i].astype(jnp.float32), jnp.exp(dt * a), dtc * bt, product_dtype
        )
        o_ref[0, i] = s.astype(o_ref.dtype)
        y_ref[pl.ds(i, 1), :] = jnp.sum(s * ct, axis=0, keepdims=True)
    y_ref[...] = y_ref[...] + d_ref[...] * cg


def update_group(n_slots: int) -> int | None:
    """Slots one grid step of :func:`selective_update` takes: sixteen where
    they divide the slots, all of them where they are few; None where
    neither is a block the compiler takes."""
    if n_slots % GROUP == 0:
        return GROUP
    if n_slots % 8 == 0 and n_slots <= 64:
        return 8
    return n_slots if n_slots <= 32 else None


def selective_update(
    states: jax.Array,
    layer,
    c: jax.Array,
    dt: jax.Array,
    b: jax.Array,
    cc: jax.Array,
    a: jax.Array,
    d_skip: jax.Array,
    *,
    tile: int = 1024,
    interpret: bool | None = None,
    product_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """One token's update of every slot's state of one layer, IN PLACE in
    the carried array: ``states (layers, S, N, Di)`` (the whole array: the
    call aliases it to its first result and touches ``layer``'s blocks
    alone), ``layer`` a traced scalar, ``c`` and ``dt (S, Di)``, ``b`` and
    ``cc (S, N)``, ``a (N, Di)``.  Returns ``(states, y (S, Di) float32)``:
    :func:`selective_step` of ``states[layer]``, each state read once and
    written once (the XLA lines read it twice: once for ``y``'s sum, once
    for the update in place)."""
    L, S, n, di = states.shape
    G = update_group(S)
    if G is None:
        raise ValueError(f"{S} slots are no whole groups of {GROUP}")
    tile = _fit(di, tile, 128)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    def columns(x):
        return x.astype(jnp.float32).reshape(S // G, G, n).transpose(0, 2, 1)

    state = pl.BlockSpec((1, G, n, tile), lambda g, d, li: (li[0], g, 0, d))
    by_slot = pl.BlockSpec((G, tile), lambda g, d, li: (g, d))
    by_group = pl.BlockSpec((1, n, G), lambda g, d, li: (g, 0, 0))
    by_channel = lambda rows_: pl.BlockSpec((rows_, tile), lambda g, d, li: (0, d))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_update_kernel, product_dtype=product_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // G, di // tile),
            in_specs=[state, by_slot, by_slot, by_group, by_group,
                      by_channel(n), by_channel(1)],
            out_specs=[state, by_slot],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, states.dtype),
            jax.ShapeDtypeStruct((S, di), jnp.float32),
        ],
        # the states are the call's second operand (the layer is the first)
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="ssm.update",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), states, c,
        dt.astype(jnp.float32), columns(b), columns(cc),
        a.astype(jnp.float32), d_skip.astype(jnp.float32).reshape(1, di),
    )


def selective_step(s, c, dt, b, cc, a, d_skip, *, product_dtype=None):
    """One token's update of every row's state, the XLA lines: ``s (..., N,
    Di)`` float32, ``c`` and ``dt (..., Di)``, ``b`` and ``cc (..., N)``, ``a
    (N, Di)``.  Returns ``(y (..., Di) float32, S)``.  A decode step's update
    of all slots, and the body of :func:`selective_scan_reference`."""
    c = c.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt[..., None, :] * a)
    drive = (dt * c)[..., None, :] * b.astype(jnp.float32)[..., :, None]
    s = _advance(s, decay, drive, product_dtype)
    y = jnp.sum(s * cc.astype(jnp.float32)[..., :, None], axis=-2)
    return y + d_skip.astype(jnp.float32) * c, s


def selective_scan_reference(c, dt, b, cc, a, d_skip, length, *, s0=None,
                             product_dtype=None):
    """:func:`selective_scan` as a ``lax.scan`` over the tokens (same
    arguments, same results; ``s0`` a state to start from, zeros unset)."""
    T, di = c.shape
    n = a.shape[0]
    a = a.astype(jnp.float32)

    def step(s, xs):
        ct, dtt, bt, cct, t = xs
        dtt = jnp.where(t < length, dtt.astype(jnp.float32), 0.0)
        y, s = selective_step(s, ct, dtt, bt, cct, a, d_skip,
                              product_dtype=product_dtype)
        return s, y.astype(c.dtype)

    s0 = jnp.zeros((n, di), jnp.float32) if s0 is None else s0
    s, y = lax.scan(step, s0, (c, dt, b, cc, jnp.arange(T)))
    return y, s
