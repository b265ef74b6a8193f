"""The routed expert layer of ``cohere2_moe``, ``keye_vl2``, ``kimi_k2`` and
``zaya``: which product runs a share's held experts for a call, the three
products, and what the on-device counters then say.  A family brings its
router (its ``_route``: sigmoid, softmax, sigmoid plus a bias; ``zaya``'s MLP
whose top-1 may be an index no share holds, a token that skips the layer)
and asks :func:`routed_experts`; it decides nothing else.

The held experts' products have three formulations, chosen from static
shapes in one place (:func:`experts_plan`).  A prefill (thousands of tokens)
sorts its (token, expert) pairs by expert and runs grouped products
(``lax.ragged_dot``) over the held pairs alone, in chunks whose count
follows the pairs actually held.  A decode step (a few tokens) is bound by
reading expert weights, and a Pallas kernel streams the experts its tokens
chose and no other (``ops/touched_experts.py``): a third of the 128 that 8
slots x top-8 hold, 12 of the 16 that 30 live slots share.  Every held
expert over every token, densely, is what is left where the kernel cannot be
handed the stacks (a mesh, a caller without them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# tokens in one call above which the held experts' products are grouped
GROUPED_FROM = 256
# rows of (token, expert) pairs one grouped pass takes
GROUP_CHUNK = 4096
# and where a share holds every expert of a layer (Keye-VL-2.0's): a pass
# reads all of them, so passes are few and long
GROUP_CHUNK_WHOLE = 32768

# the names a family's ``COUNTERS`` start with, in this order: the programs
# index them by position, ``/stats/summary`` reads them by name
COUNTERS = (
    "moe.pairs_routed",          # decode: (token, choice) pairs, layers summed: live tokens x experts_per_tok,
                                 # a choice that is no expert (``zaya``'s no-op) among them
    "moe.pairs_held",            # decode: of those, pairs whose expert is held here
    "moe.experts_touched",       # decode: held experts with >= 1 token, summed over layers and steps
    "moe.max_tokens_on_expert",  # decode: the busiest held expert's tokens, summed over layers and steps
    "moe.steps",                 # decode steps counted
    "moe.prefill_pairs_routed",  # prefill: pairs chosen (real tokens only)
    "moe.prefill_pairs_held",
    "moe.prefill_tokens",
    "moe.experts_read",          # decode: held experts whose weights the step streamed (touched ones under the
                                 # touched-only kernel, every held one densely), summed over layers and steps
)
# what a family's program bumps itself, once a step and once a prompt
STEPS, PREFILL_TOKENS = COUNTERS.index("moe.steps"), COUNTERS.index("moe.prefill_tokens")
_EXPERTS_READ = COUNTERS.index("moe.experts_read")
# a layer's routed expert weights, in ``lp`` and in the stacks
EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def held_range(experts_held: str, n_experts: int) -> tuple[int, int]:
    """``(first, count)`` of a ``Config.experts_held`` of ``"first:count"``
    (empty: all ``n_experts``), one chip's share of an expert-parallel
    deployment: the family routes over ALL its experts and normalises over
    all the chosen; only the held experts' part is computed, and what the
    absent ones would add is left out.  No token is dropped."""
    first, count = 0, n_experts
    if experts_held:
        first, _, count = str(experts_held).partition(":")
        first, count = int(first), int(count)
    if first < 0 or count < 1 or first + count > n_experts:
        raise ValueError(
            f"experts_held {experts_held!r} is not a range of the "
            f"{n_experts} experts"
        )
    return first, count


def experts_plan(n_tokens: int, *, kernel: bool = True) -> str:
    """Which formulation runs the held experts' products for a call of
    ``n_tokens`` tokens, from static shapes alone: ``"grouped"`` for a
    prompt (:data:`GROUPED_FROM` tokens or more); else ``"touched"`` — the
    kernel that streams only the experts some token chose — wherever the
    kernel can be handed the stacks (``kernel``: every layer's are at hand,
    and on one device; it is not offered stacks sharded over a mesh), and
    ``"dense"`` where it cannot.  The kernel reads a byte as fast as the
    dense products and never more of them (PERF.md §6, PR 47: level where a
    call touches every expert it holds, ahead by what it skips elsewhere)."""
    if n_tokens >= GROUPED_FROM:
        return "grouped"
    return "touched" if kernel else "dense"


def _tokens_on_experts(local, held, count: int):
    """(count,): the tokens on each held expert."""
    return jnp.sum(
        (local[..., None] == jnp.arange(count)) & held[..., None], axis=(0, 1)
    )


def _combine_weights(local, held, w, count: int):
    """(T, X) float32: a token's weight on each held expert, 0 where not
    chosen."""
    onehot = local[..., None] == jnp.arange(count)  # (T, K, X)
    return jnp.sum(
        jnp.where(onehot & held[..., None], w[..., None], 0.0), axis=1
    )


def experts_dense(h2, lp, local, held, w):
    """Every held expert over every token: the call reads each held
    expert's weights once whichever tokens chose it.  -> (T, E) f32.  What
    a mesh-sharded expert stack runs, and a caller with no stack at hand."""
    cw = _combine_weights(local, held, w, lp["we_gate"].shape[0])
    g = jnp.einsum("te,xef->xtf", h2, lp["we_gate"])
    u = jnp.einsum("te,xef->xtf", h2, lp["we_up"])
    d = jnp.einsum("xtf,xfe->xte", jax.nn.silu(g) * u, lp["we_down"])
    return jnp.einsum("xte,tx->te", d.astype(jnp.float32), cw)


def experts_touched(h2, stacks, li, local, held, w):
    """The held experts that at least one token chose, and no other (a
    call of under :data:`GROUPED_FROM` tokens): what
    :func:`experts_dense` sums, less the terms whose weight is 0, through
    the kernel that streams an expert by the list of those touched
    (``ops/touched_experts.py``).  ``stacks`` and ``li`` as
    :func:`experts_grouped` takes them, and for its reason.  -> (T, E) f32."""
    from seldon_core_tpu.ops.touched_experts import touched_expert_products, touched_list

    T, K = local.shape
    n_layers, count = stacks["we_gate"].shape[:2]
    ids, n = touched_list(
        _tokens_on_experts(local, held, count) > 0, min(count, T * K)
    )
    flat = [
        stacks[k].reshape((n_layers * count,) + stacks[k].shape[2:])
        for k in EXPERT_KEYS
    ]
    return touched_expert_products(
        h2, _combine_weights(local, held, w, count), ids, n, *flat,
        base=li * count,
    )


def experts_grouped(h2, stacks, li, local, held, w, chunk: int):
    """The held (token, expert) pairs alone, sorted by expert, through
    grouped products (prefill).  Pairs are taken ``chunk`` rows at a
    pass and the passes follow the pairs actually held, so no routing is
    dropped and none is paid for that is not there.  -> (T, E) f32.

    ``stacks`` are the expert weights of EVERY layer, ``(layers, held, ..)``,
    and ``li`` this layer: the grouped product runs over all ``layers *
    held`` groups with the other layers' groups empty.  A layer cut out of
    the stack first is a copy of its 16 experts (half a gigabyte a matrix)
    on every call — the grouped product is a kernel, and XLA fuses no slice
    into a kernel's operand."""
    T, K = local.shape
    n_layers, count = stacks["we_gate"].shape[:2]
    M = T * K
    R = min(chunk, M)
    key = jnp.where(held, local, count).reshape(M)  # pairs not held sort last
    order = jnp.argsort(key, stable=True)
    tok = (order // K).astype(jnp.int32)  # token of each sorted pair
    w_sorted = w.reshape(M)[order]
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n_held = ends[-1]
    flat = {
        k: stacks[k].reshape((n_layers * count,) + stacks[k].shape[2:])
        for k in EXPERT_KEYS
    }

    def body(i, out):
        r0 = i * R
        rows = r0 + jnp.arange(R)
        live = rows < n_held
        t = tok[jnp.minimum(rows, M - 1)]
        xg = h2[t]  # (R, E)
        gs = jnp.clip(ends - r0, 0, R) - jnp.clip(starts - r0, 0, R)
        gs = lax.dynamic_update_slice(
            jnp.zeros((n_layers * count,), jnp.int32), gs, (li * count,)
        )
        g = lax.ragged_dot(xg, flat["we_gate"], gs)
        u = lax.ragged_dot(xg, flat["we_up"], gs)
        d = lax.ragged_dot(jax.nn.silu(g) * u, flat["we_down"], gs)
        wr = w_sorted[jnp.minimum(rows, M - 1)]
        # rows past the pairs held belong to no group: whatever the grouped
        # product left there is replaced, not scaled
        y = jnp.where(live[:, None], d.astype(jnp.float32) * wr[:, None], 0.0)
        return out.at[t].add(y)

    out = jnp.zeros((T, h2.shape[1]), jnp.float32)
    return lax.fori_loop(0, (n_held + R - 1) // R, body, out)


def _count_routing(counters, local, held, tok_mask, per_tok: int, count: int,
                   decode: bool, plan: str):
    """``counters`` with one expert layer's routing added (``COUNTERS``'
    first four and the experts ``plan`` read in a decode step, the prefill
    pair in a prompt).  Routed counts every real token's ``per_tok``
    choices, held the pairs whose expert this share holds: at top-1 with a
    choice that is no expert the two part by the tokens that skipped."""
    if counters is None:
        return None
    n_tok = jnp.sum(tok_mask).astype(jnp.uint32)
    n_held = jnp.sum(held).astype(jnp.uint32)
    n_routed = n_tok * jnp.uint32(per_tok)
    if decode:
        per = _tokens_on_experts(local, held, count)
        touched = jnp.sum(per > 0).astype(jnp.uint32)
        read = touched if plan == "touched" else jnp.uint32(count)
        add = jnp.zeros_like(counters).at[jnp.arange(4)].add(jnp.stack([
            n_routed, n_held, touched, jnp.max(per).astype(jnp.uint32),
        ])).at[_EXPERTS_READ].add(read)
    else:
        add = jnp.zeros_like(counters).at[jnp.arange(5, 7)].add(
            jnp.stack([n_routed, n_held])
        )
    return counters + add


def routed_experts(h2, lp, idx, w, held_range, tok_mask, counters, *,
                   decode: bool, kernel: bool, stacks=None, li=None,
                   group_alone: bool = False, group_chunk: int | None = None,
                   shared: str | None = None):
    """The expert layer behind a family's router: ``h2 (T, E)``, the chosen
    experts ``idx (T, K)`` over ALL the model's and their weights ``w (T,
    K)`` float32 -> ``(the held experts' part (T, E) float32, counters)``.
    ``held_range = (first, count)`` is the share's; ``tok_mask (T,)`` the
    real tokens; ``counters`` a family's, ``COUNTERS`` leading, or None.

    Asks :func:`experts_plan` and runs what it says under ``moe.experts``.
    ``lp`` is this layer's weights; ``stacks`` every layer's
    :data:`EXPERT_KEYS` and ``li`` the layer's place in them, which is what
    a kernel wants (:func:`experts_grouped` says why); ``kernel`` (static)
    says the touched-only kernel may be handed them: they are at hand, and
    on one device.  The grouped product runs over ``stacks`` too — over this
    layer's experts as a stack of one where there are none, or with
    ``group_alone`` (Keye-VL-2.0: cutting 128 experts out of the carried
    stack is a copy of 1.2 GB, 3 ms, against a prompt's hundreds, and the
    product then runs over 128 groups, not 128 x layers) — ``group_chunk``
    rows a pass (:data:`GROUP_CHUNK` unless said).  ``shared`` adds the
    ``"sum"`` or the ``"mean"`` of the experts every token takes
    (``lp["ws_*"]``) under ``moe.shared``: here, between the products and
    the count, where each family's program has had them."""
    first, count = held_range
    plan = experts_plan(h2.shape[0], kernel=kernel)
    with jax.named_scope("moe.route"):
        local = idx - first
        held = (local >= 0) & (local < count) & tok_mask[:, None]
    with jax.named_scope("moe.experts"):
        if plan == "grouped":
            if stacks is None or group_alone:
                stacks, li = {k: lp[k][None] for k in EXPERT_KEYS}, 0
            out = experts_grouped(
                h2, stacks, li, local, held, w,
                GROUP_CHUNK if group_chunk is None else group_chunk,
            )
        elif plan == "touched":
            out = experts_touched(h2, stacks, li, local, held, w)
        else:
            out = experts_dense(h2, lp, local, held, w)
    if shared is not None:
        with jax.named_scope("moe.shared"):
            g = jnp.einsum("te,jef->jtf", h2, lp["ws_gate"])
            u = jnp.einsum("te,jef->jtf", h2, lp["ws_up"])
            every = jnp.einsum(
                "jtf,jfe->te", jax.nn.silu(g) * u, lp["ws_down"],
                preferred_element_type=jnp.float32,
            )
            if shared == "mean":
                every = every / lp["ws_gate"].shape[0]
    counters = _count_routing(
        counters, local, held, tok_mask, idx.shape[1], count, decode, plan
    )
    return (out if shared is None else out + every), counters
