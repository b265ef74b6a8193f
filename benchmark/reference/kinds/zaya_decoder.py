"""Reference kind ``zaya_decoder``: the served weights of ``models/zaya.py``
remade from the seed (in the served dtype, by the program's own init with the
same key), and the engine's probe tokens held, teacher-forced, against the
plain forward pass of ``../zaya_decoder.py``.

The harness's probes (64 in, 32 out) stop at 96 tokens where the cell's
requests run to 3,584, and the served tokens are a blunt witness of this
model: its attention is peaked (a key temperature of 4 to 8 on cosine
scores) and its router top-1, so a bfloat16 rounding of a score or of a
probability that a float32 pass does not make moves which key or which
expert a token takes, and twenty blocks on the two passes name different
best tokens at most positions of 262,272 near-tied logits.  The probes are
therefore held by the MEAN of the served tokens' deficits under the
reference's best logit and by the share of positions where they agree (a
widest deficit over 128 positions is one heavy-tailed draw: PERF.md section 7),
with limits a wrong program misses by far.  So beside them ``mechanism``
runs, here in the child, what the timed path runs at the timed sizes: a
prompt of the configuration's
``reference.state_probe_tokens`` REAL tokens from the seed in its padded rung
(2,000 in 2,048), through the served program's own layer functions on every
block (``models/zaya.py``: ``_cca_prompt_parts``, ``_attend_prompt`` with the
kernel the graph names, ``_moe`` under the grouped products, ``_merge``, in
the served dtype), then ``reference.state_probe_steps`` decode steps of ONE
slot from the tails that prompt left (``_cca_step_parts``, the block's K and V
written into a pool of this block's own and read through ``paged.attend_paged``
with the kernel wherever the engine would choose it, ``_moe`` under the
touched-only kernel), and holds each part of each block to the reference's
equation GIVEN THE PROGRAM'S OWN INPUTS to that part.  The router is held by
what is continuous, the experts by the choice the program made:

* ``projection_rel_err_max``: ``q``, ``k`` (as attended) and ``v`` (as
  shifted) of the prompt's real rows and of the steps against the reference's
  convolutions, mean, norms, temperature, rotary and shift over the same ``h``
  as ONE sequence with no padding and no tails (a tail taken at the rung's
  end, zeroed or stale reads about 1 on the first step), and the depth state
  ``z`` against ``h Wd + bd + gam * z_before`` of the same ``h`` and the same
  ``z_before``; the worst row's error over the rows' mean norm;
* ``attention_rel_err_max``: the prompt's attention on its last 256 real rows
  against the dense masked softmax of the program's own ``q``, ``k``, ``v``;
* ``decode_read_rel_err_max``: the steps' attention against the same over the
  prompt's rows and the steps' as the pool holds them;
* ``router_prob_abs_err_max``: the 17 probabilities against the reference's
  from the same ``z``;
* ``choice_deficit_max``: how far under the reference's best ``p + bal`` the
  served choice lies, 0 where they choose alike: a differing choice is
  allowed only under ``choice_deficit_limit``; ``choice_differs_share_max``:
  the largest share of a block's token-layers whose choice differs;
* ``expert_rel_err_max``: the sublayer's ``f`` against the reference's loop
  over the experts UNDER THE PROGRAM'S CHOICE, weighted by the reference's
  probability of it;
* ``entry_*``: THE ENGINE'S ENTRY FUNCTIONS held to that composition
  (:func:`entry`, :func:`linked`).  ``zaya.prefill_slot_paged`` and
  ``zaya.decode_slots_paged``, jitted as ``executor/generation.py`` jits them
  (the whole layer scan, the cache donated, the steps a scan of
  ``decode_block`` a dispatch), run the same sequence on a cache of the
  graph's own 48 slots and 769 blocks whose table deals the blocks to the
  slots at random: the slot is given a former request's prompt and then,
  between its neighbours' prompts, the 2,000 real tokens in the 2,048 rung;
  most slots are live with prompts of other lengths and tokens of their
  own, some hold a prompt and sit inactive, some were never given one.  The
  slot's K and V rows of every block read back through its table row and its
  tails after the prompt and after the steps are held to the composition's, both in the
  served dtype — AS FAR AS TWO RUNS IN THAT DTYPE CAN BE HELD TO EACH OTHER.
  Scores with a deviation of 4 to 8 (the seeded key temperature) carry a
  bfloat16 rounding from block to block at about twice a block: the two
  runs' rows are bit for bit alike at block 0 (the median row's error is
  0), differ by 0.3 % at block 1, 1 % at block 2, 2-3 % at block 3 and
  wholly by block 12, on every seed (PERF.md section 6); a top-1 choice that
  a rounding flips adds a token's whole expert output to that.  One scan
  body serves every block (a block's number enters by the index of its
  weights, its pool rows and its tails alone), so the link holds the early
  blocks and prints the last:
  ``entry_first_block_rel_err_max`` the WORST row of block 0 (K and V of the
  prompt's rows and of the steps', its tails after the prompt and after the
  steps), which no router and no attention precedes: a tail taken at the
  rung's end, lost, stale or another slot's, a wrong block or row of the
  pool, lower precision in the CCA chain;
  ``entry_early_blocks_median_rel_err_max`` the largest MEDIAN row of blocks
  1 and 2 (``EARLY_BLOCKS``), which a whole-program fault (a dropped ``z``
  carry, another block's weights or tails, lower precision) moves and a
  flipped token does not; ``entry_handoff_rel_err_max`` the worst, in
  blocks 0 and 1, of what the prompt's program hands the first step's (the
  tails it left, the first step's K and V);
  ``entry_bookkeeping_faults_max`` how many values of the inactive slots'
  tails and positions moved, and how many live slots' positions are not the
  steps on: none.  ``entry_last_block_median_rel_err`` (0.8) is printed.

``judges/token_logits_and_choice.py`` holds both sets of numbers to the
configuration's limits.  Graphs that are never served (``state_probe.py
--graph-param``) are the controls the limits must refuse:
``control_weights: "int8"`` runs the composition on the same weights rounded
to int8 a column; ``control_entry: "int8"`` runs the ENTRY functions on them
(rounded in place: the chip does not hold them twice) beside the
composition as served, ``control_entry: "tail_lost"`` zeroes the slot's
tails between the prompt's program and the first step's,
``control_entry: "z_dropped"`` traces the entry functions with a router that
hears nothing of the block before;
``control_read`` plants a wrong read in the composition —
``"block_off_by_one"`` (the paged read's first table entry names the block
before), ``"pos_minus_1"`` (the paged read stops a row short),
``"prompt_v_rolled"`` (the prompt's kernel is handed V a row late).

WHAT ``mechanism`` IS NOT.  The composition is a jit of this child's own over
the family's layer functions, one slot and one block at a time; the entry
part is the family's entry functions under this child's ``jax.jit``, one
slot's rows of them judged, in the early blocks.  Neither is the engine
PROCESS — the scheduler's table, its admission and its ``decode_k`` program
with sampling in it — which the window times, and neither holds a fault that
only a block past the third would show; what holds those in every run is the
probes' pair of limits, which a wrong program misses and a healing fault
does not (PERF.md section 7)."""

from __future__ import annotations

JUDGE = "token_logits_and_choice"  # unless the configuration names another

FIELDS = ("vocab_size", "hidden", "n_layers", "n_heads", "n_kv_heads", "head_dim",
          "cca_time0", "cca_time1", "partial_rotary_factor", "rope_theta",
          "n_experts", "experts_per_tok", "moe_intermediate_size",
          "router_hidden_size", "tie_word_embeddings", "max_seq", "norm_eps")
JUDGED_ROWS = 256  # the prompt's last real rows whose attention is judged


def reference_kw(cfg) -> dict:
    return dict(rotary_dim=cfg.rotary_dim, theta=cfg.rope_theta, eps=cfg.norm_eps)


def stated(graph: dict):
    """The program's ``Config`` of a graph's parameters."""
    from seldon_core_tpu.models import zaya

    return zaya.Config(**{k: graph[k] for k in FIELDS if k in graph})


def model(graph: dict, seed: int):
    """(cfg, the served tree, the reference's keyword arguments) for a
    configuration's graph."""
    import jax

    from seldon_core_tpu.models import zaya

    import frame

    cfg = stated(graph)
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    frame.lap("backend")
    params = jax.jit(lambda key: zaya.init_params(key, cfg, dtype))(
        jax.random.PRNGKey(seed)
    )
    jax.block_until_ready(params)
    frame.lap("weights")
    return cfg, params, reference_kw(cfg)


def deficits(ref_logits, tokens) -> tuple[list[float], int]:
    """How far each served token lies under the reference's top logit at its
    position, and at how many positions it IS the top."""
    out, agree = [], 0
    for row, t in zip(ref_logits, tokens):
        out.append(float(row.max() - row[t]))
        agree += int(row.argmax() == t)
    return out, agree


def row_err(found, ref):
    """The worst row's ``|found - ref|`` over the rows' root-mean-square
    norm of ``ref``, in float32: a row of zeros (a token that chose the
    no-op) divides nothing."""
    import jax.numpy as jnp

    found, ref = jnp.asarray(found, jnp.float32), jnp.asarray(ref, jnp.float32)
    found, ref = found.reshape(found.shape[0], -1), ref.reshape(ref.shape[0], -1)
    worst = jnp.max(jnp.sum((found - ref) ** 2, axis=1))
    return jnp.sqrt(worst / jnp.mean(jnp.sum(ref**2, axis=1)))


def int8_columns(w):
    """``w`` rounded to int8 with one scale a column (its last axis), back
    in its own dtype: the control's weights."""
    import jax.numpy as jnp

    f = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
    return (jnp.round(f / jnp.where(scale > 0, scale, 1.0)) * scale).astype(w.dtype)


# faults planted in the composition's reads (``control_read``) and in the
# entry functions' run (``control_entry``): graphs that are never served
READ_PLANTS = ("block_off_by_one", "pos_minus_1", "prompt_v_rolled")
ENTRY_PLANTS = ("int8", "tail_lost", "z_dropped")
EARLY_BLOCKS = 3  # two bfloat16 runs of one sequence are held to each other over this many


def _one_slot(tails: dict) -> dict:
    """The composition's tails without their slot axis."""
    return {n: t[0] if t.ndim == 2 else t[:, 0] for n, t in tails.items()}


def entry(cfg, graph: dict, params: dict, seed: int, tokens, L: int, K: int,
          kernel: bool) -> dict:
    """What the ENGINE's entry functions leave of the mechanism's sequence:
    ``zaya.prefill_slot_paged`` and ``zaya.decode_slots_paged`` jitted as
    ``executor/generation.py`` jits them (the cache donated, ``seq_impl`` and
    ``kernel`` the graph's, the steps a scan of ``decode_block`` a dispatch),
    on a cache of the graph's own ``n_slots``, ``kv_blocks`` and
    ``kv_block_size`` whose table deals every block but the sink to the slots
    at random.  Slot ``J`` is given a former request's prompt, then — between
    its neighbours' — the ``L`` real tokens in their padded rung; every
    eighth slot is never given a prompt, every eighth holds a prompt and sits
    inactive, the others are live with prompts of ``rung / 2 .. rung`` real
    tokens; then ``K`` steps, ``J``'s tokens the sequence's and the others'
    random.  -> ``J``'s rows of ``k`` and ``v (layers, L + K, W)`` read back
    through its table row, its tails after its prompt and after the steps, and
    ``bookkeeping_faults``: how many values of the inactive slots' tails and
    positions moved, and how many live slots' positions are not ``K`` on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import zaya as zm

    import frame

    plant = graph.get("control_entry")
    if plant not in (None,) + ENTRY_PLANTS:
        raise ValueError(f"control_entry {plant!r}: one of {ENTRY_PLANTS} or nothing")
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    seq_impl = graph.get("seq_impl", "dense")
    S, bs, nb = int(graph["n_slots"]), int(graph["kv_block_size"]), int(graph["kv_blocks"])
    rung, mb = -(-L // bs) * bs, cfg.max_seq // bs
    per = min(mb, (nb - 1) // S)  # blocks a slot is dealt
    if per * bs < L + K:
        raise ValueError(f"{per} blocks of {bs} a slot do not hold {L} + {K} tokens")
    rng = np.random.default_rng([seed, 0xE27])
    table = np.zeros((S, mb), np.int32)
    table[:, :per] = rng.permutation(np.arange(1, nb))[: S * per].reshape(S, per)
    J = S // 2 + 1
    idle = [s for s in range(S) if s % 8 == 0 and s != J]
    held = [s for s in range(S) if s % 8 == 3 and s != J]
    still = np.asarray(idle + held, np.int64)
    lengths = rng.integers(rung // 2 + 1, rung + 1, size=S)
    prompts = rng.integers(1, cfg.vocab_size, size=(S, rung))
    prompts[J, :L], lengths[J] = tokens[:L], L

    if plant == "int8":  # in place: a chip does not hold the weights twice
        params = jax.jit(
            lambda p: jax.tree.map(lambda w: int8_columns(w) if w.ndim >= 2 else w, p),
            donate_argnums=0,
        )(params)
    prefill = jax.jit(
        lambda params, toks, length, slot, row, cache: zm.prefill_slot_paged(
            params, toks, length, slot, row, cache, cfg, seq_impl=seq_impl
        ),
        donate_argnums=5,
    )

    def steps(params, toks, active, cache):
        def body(cache, t):
            logits, cache = zm.decode_slots_paged(
                params, t, cache, active, cfg, window=None, kernel=kernel
            )
            return cache, jnp.argmax(logits[J])  # the head stays in the program

        cache, best = jax.lax.scan(body, cache, toks)
        return best, cache

    decode = jax.jit(steps, donate_argnums=3)

    def given(cache, slot, toks, length):
        row = np.where(np.arange(rung) < length, toks, 0)[None]  # padded as the engine pads
        return prefill(
            params, jnp.asarray(row, jnp.int32), jnp.int32(length), jnp.int32(slot),
            jnp.asarray(table[slot]), cache,
        )

    whole = zm._router_parts
    if plant == "z_dropped":  # traced into both programs: no block hears the one before
        zm._router_parts = lambda h2, z, lp, cfg: whole(h2, jnp.zeros_like(z), lp, cfg)
    try:
        cache = zm.init_paged_cache(cfg, S, nb, bs, dtype)
        # a former request's prompt in J's slot, on J's blocks
        _, cache = given(cache, J, prompts[J - 1], int(lengths[J - 1]))
        for s in range(S):
            if s not in idle:
                _, cache = given(cache, s, prompts[s], int(lengths[s]))
        mine = lambda c: {  # noqa: E731
            "tail_u": c["tail_u"][:, :, J], "tail_c": c["tail_c"][:, :, J],
            "tail_v": c["tail_v"][:, J],
        }
        others = lambda c: [c["pos"][still]] + [  # noqa: E731
            jnp.take(c[n], still, axis=c[n].ndim - 2) for n in zm.SLOT_ARRAYS
        ]
        tails_prompt, before = mine(cache), others(cache)
        if plant == "tail_lost":  # between the prompt's program and the first step's
            cache = {
                n: a.at[..., J, :].set(0) if n in zm.SLOT_ARRAYS else a for n, a in cache.items()
            }
        active = np.ones((S,), bool)
        active[still] = False
        toks = rng.integers(1, cfg.vocab_size, size=(K, S))
        toks[:, J] = tokens[L:]
        blk = int(graph.get("decode_block", K))
        blk = blk if K % blk == 0 else K
        for a in range(0, K, blk):
            _, cache = decode(
                params, jnp.asarray(toks[a:a + blk], jnp.int32), jnp.asarray(active), cache
            )
        need = jnp.asarray(table[J, : -(-(L + K) // bs)])
        rows = lambda pool: pool[:, need].reshape(pool.shape[0], -1, pool.shape[-1])[:, : L + K]  # noqa: E731
        moved = sum(jnp.sum(a != b) for a, b in zip(before, others(cache)))
        late = jnp.sum(jnp.where(active, cache["pos"] != jnp.asarray(lengths + K), False))
        out = {
            "k": rows(cache["k"]), "v": rows(cache["v"]), "tails_prompt": tails_prompt,
            "tails_end": mine(cache), "bookkeeping_faults": moved + late, "slots": S,
        }
        jax.block_until_ready(out)
        return out
    finally:
        zm._router_parts = whole


def linked(found: dict, kept: dict, L: int) -> dict:
    """The entry functions' rows held to the composition's, both in the
    served dtype, as far as two runs in that dtype can be held to each other
    (the module's docstring): ``first_block`` the worst row of block 0;
    ``early_median`` the largest median row of the early blocks behind it;
    ``handoff`` the worst of what the prompt's program hands the first
    step's, in block 0 and the next; ``last_block_median`` how far the chain
    has gone by the last block, which is printed and not judged."""
    import jax.numpy as jnp

    def err(a, b):
        """Each row's ``|a - b|`` over the rows' root-mean-square norm of
        ``b``: ``(..., rows, C) -> (..., rows)``."""
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        norm = jnp.mean(jnp.sum(b * b, axis=-1), axis=-1, keepdims=True)
        return jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1) / norm)

    def tails(when):
        """``(layers,)``: a block's worst tail."""
        return jnp.max(jnp.stack([
            err(found[when][n].reshape(a.shape[0], 1, -1), a.reshape(a.shape[0], 1, -1))[:, 0]
            for n, a in kept[when].items()
        ]), axis=0)

    k, v = err(found["k"], kept["k"]), err(found["v"], kept["v"])  # (layers, L + K)
    after, end = tails("tails_prompt"), tails("tails_end")
    median = jnp.max(jnp.stack([
        jnp.median(part, axis=-1) for a in (k, v) for part in (a[:, :L], a[:, L:])
    ]), axis=0)
    handoff = jnp.max(jnp.stack([after, k[:, L], v[:, L]]), axis=0)
    return {
        "first_block": jnp.max(jnp.stack([k[0].max(), v[0].max(), after[0], end[0]])),
        "early_median": jnp.max(median[1:EARLY_BLOCKS]),
        "handoff": jnp.max(handoff[: EARLY_BLOCKS - 1]),
        "last_block_median": median[-1],
        "bookkeeping_faults": found["bookkeeping_faults"],
    }


def mechanism(cfg, graph: dict, params: dict, seed: int, n_tokens: int,
              n_steps: int) -> dict:
    """Every block at the timed sizes, part by part on the program's own
    inputs (the module's docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import paged
    from seldon_core_tpu.models import zaya as zm

    import frame
    import zaya_decoder as ref

    L, K = int(n_tokens), int(n_steps)
    B = min(JUDGED_ROWS, L)
    seq_impl = graph.get("seq_impl", "dense")
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    bs = int(graph.get("kv_block_size", 16))
    rung = -(-L // bs) * bs  # the ladder's rungs are whole blocks
    n_blocks = -(-(L + K) // bs)
    kw = reference_kw(stated(graph))
    tokens = np.random.default_rng([seed, 0x5EED5]).integers(
        1, cfg.vocab_size, size=L + K
    )
    # the rung's padding rows hold token 0, as the engine pads a prompt
    padded = np.concatenate([tokens[:L], np.zeros(rung - L, np.int64), tokens[L:]])
    # the engine's own choice of the decode read: the graph's word, else the
    # kernel wherever the backend compiles it
    kernel = graph.get("decode_kernel")
    if kernel is None:
        kernel = jax.default_backend() != "cpu"
    control = graph.get("control_weights")
    if control not in (None, "int8"):
        raise ValueError(f"control_weights {control!r}: int8 or nothing")
    plant = graph.get("control_read")
    if plant not in (None,) + READ_PLANTS:
        raise ValueError(f"control_read {plant!r}: one of {READ_PLANTS} or nothing")
    real = jnp.arange(rung) < L
    # one slot whose table names blocks 1.. in order (block 0 is the sink)
    table = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None]
    every = jnp.ones((1,), bool)
    # the planted read of a wrong block: the table's first entry names the
    # block before its own (here the sink)
    read_table = table.at[0, 0].add(-1) if plant == "block_off_by_one" else table

    @jax.jit
    def served(x, z, lp):
        """One block over the prompt's rung and then the steps: ``x (rung +
        K, E)``, ``z (rung + K, R)`` float32.  -> (the next block's ``x`` and
        ``z``, the parts)."""
        if control:
            lp = {k: int8_columns(v) if v.ndim >= 2 else v for k, v in lp.items()}
        stacks = {k: lp[k][None] for k in zm.moe.EXPERT_KEYS}
        h = zm.rmsnorm(x, lp["ln_a"], cfg.norm_eps)
        p = zm._cca_prompt_parts(h[:rung], lp, cfg, jnp.int32(L))
        k, v = p["k"].astype(dtype), p["v"].astype(dtype)
        seen = jnp.roll(v, 1, axis=0) if plant == "prompt_v_rolled" else v
        o = zm._attend_prompt(p["q"], k, seen.reshape(k.shape), seq_impl)
        # the block's own pool, written as the prompt program writes it
        pool = jnp.zeros((1, n_blocks + 1, bs, k.shape[1] * k.shape[2]), dtype)
        ck = paged.write_prompt(pool, 0, table[0, : rung // bs], k, bs)
        cv = paged.write_prompt(pool, 0, table[0, : rung // bs], v, bs)

        def step(carry, ht):
            ck, cv, tails, pos = carry
            s = zm._cca_step_parts(ht[None], lp, cfg, **tails, pos=pos, active=every)
            blk, off = table[0, pos // bs], pos % bs
            ck = ck.at[0, blk, off].set(s["k"].reshape(1, -1).astype(dtype))
            cv = cv.at[0, blk, off].set(s["v"].astype(dtype))
            o = paged.attend_paged(
                s["q"], ck, cv, 0, read_table,
                pos - 1 if plant == "pos_minus_1" else pos, every, kernel=kernel,
            )
            tails = {n: s[n] for n in zm.SLOT_ARRAYS}
            keep = {n: s[n][0] for n in ("q", "k", "v")}
            return (ck, cv, tails, pos + 1), dict(keep, o=o[0])

        tails = {
            "tail_u": p["tail_u"][:, None].astype(dtype),
            "tail_c": p["tail_c"][:, None].astype(dtype),
            "tail_v": p["tail_v"][None].astype(dtype),
        }
        tails_prompt = tails
        (ck, cv, tails, _), d = jax.lax.scan(
            step, (ck, cv, tails, jnp.full((1,), L, jnp.int32)), h[rung:]
        )
        o_all = jnp.concatenate([o, d["o"].astype(o.dtype)])
        x = zm._merge(x, zm._cca_out(o_all, lp), lp["res_a"])
        h2 = zm.rmsnorm(x, lp["ln_m"], cfg.norm_eps)
        f_p, r_p, _ = zm._moe(
            h2[:rung], z[:rung], lp, cfg, real, None, decode=False, stacks=stacks, li=0
        )
        f_d, r_d, _ = zm._moe(
            h2[rung:], z[rung:], lp, cfg, jnp.ones((K,), bool), None, decode=True,
            stacks=stacks, li=0,
        )
        f = jnp.concatenate([f_p, f_d])
        router = {n: jnp.concatenate([r_p[n], r_d[n]]) for n in ("z", "p", "e", "w")}
        parts = dict(h=h, prompt=p, o=o, steps=d, h2=h2, z_before=z, f=f, **router)
        # what the engine's entry functions leave of this block, as stored
        kept = {
            "k": jnp.concatenate([k[:L].reshape(L, -1), d["k"].reshape(K, -1).astype(dtype)]),
            "v": jnp.concatenate([v[:L], d["v"].astype(dtype)]),
            "tails_prompt": _one_slot(tails_prompt), "tails_end": _one_slot(tails),
        }
        return zm._merge(x, f, lp["res_m"]), router["z"], parts, kept

    rows = np.concatenate([np.arange(L), rung + np.arange(K)])  # the real ones

    @jax.jit
    def judged(lp, parts):
        """One block's parts held to the reference, the sequence as the
        reference sees it: ``L + K`` real rows, no padding, no tails."""
        f = ref.f32
        p, d = parts["prompt"], parts["steps"]
        q, k, v = ref.cca_qkv(
            f(parts["h"][rows]), lp, rotary_dim=kw["rotary_dim"], theta=kw["theta"]
        )
        v = v.reshape(v.shape[0], -1)
        h2 = f(parts["h2"][rows])
        z = h2 @ f(lp["r_down"]) + f(lp["r_down_b"]) + f(lp["r_gam"]) * parts["z_before"][rows]
        # the attention of the program's own q, k, v, as the pool holds them
        sq = f(jnp.concatenate([p["q"][:L], d["q"]]))
        sk = f(jnp.concatenate([p["k"][:L].astype(dtype), d["k"].astype(dtype)]))
        sv = f(jnp.concatenate([p["v"][:L].astype(dtype), d["v"].astype(dtype)]))
        sv = sv.reshape(sk.shape)
        o_p = ref.attend_rows(sq[L - B:L], sk[:L], sv[:L], L - B)
        o_d = ref.attend_rows(sq[L:], sk, sv, L)
        # the router from the same z; the experts under the program's choice
        p_ref = ref.router_probs(parts["z"][rows], lp, kw["eps"])
        e, e_ref = parts["e"][rows], ref.choose(p_ref, lp)[0]
        w_ref = jnp.take_along_axis(p_ref, e[:, None], axis=-1)[:, 0]
        f_ref = ref.experts(h2, e, w_ref, lp)
        return {
            "projection": jnp.stack([
                row_err(p["q"][:L], q[:L]), row_err(p["k"][:L], k[:L]),
                row_err(p["v"][:L], v[:L]), row_err(d["q"], q[L:]),
                row_err(d["k"], k[L:]), row_err(d["v"], v[L:]),
                row_err(parts["z"][rows], z),
            ]),
            "attention": row_err(parts["o"][L - B:L], o_p),
            "decode_read": row_err(d["o"], o_d),
            "router_prob": jnp.max(jnp.abs(parts["p"][rows] - p_ref)),
            "choice_deficit": jnp.max(ref.choice_deficit(p_ref, e, lp)),
            "choice_differs": jnp.mean(e != e_ref),
            "expert": row_err(parts["f"][rows], f_ref),
            "skipped": jnp.mean(e == cfg.n_experts),
            "top_share": jnp.max(jnp.mean(jax.nn.one_hot(e, cfg.n_experts + 1), axis=0)),
        }

    x = params["tok_emb"][jnp.asarray(padded, jnp.int32)].astype(dtype)
    z = jnp.zeros((rung + K, cfg.router_hidden_size), jnp.float32)
    by_layer, kept = [], []
    for lp in ref.layers_of(params):
        x, z, parts, mine = served(x, z, lp)
        kept.append(mine)
        with jax.default_matmul_precision("highest"):
            got = judged(lp, parts)
        by_layer.append({k: float(jnp.max(v)) for k, v in got.items()})
    del parts
    kept = jax.tree.map(lambda *a: jnp.stack(a), *kept)
    frame.lap("mechanism")
    found = entry(cfg, graph, params, seed, tokens, L, K, kernel)
    link = {k: float(v) for k, v in linked(found, kept, L).items()}
    frame.lap("entry")
    out = {
        "state_probe_tokens": L, "state_probe_steps": K, "state_probe_rung": rung,
        "blocks_judged": len(by_layer), "decode_read": "kernel" if kernel else "gather",
        "choice_differs_share": sum(r["choice_differs"] for r in by_layer) / len(by_layer),
        "skipped_share": sum(r["skipped"] for r in by_layer) / len(by_layer),
        "entry_slots": found["slots"],
        "entry_first_block_rel_err_max": link["first_block"],
        "entry_early_blocks_median_rel_err_max": link["early_median"],
        "entry_handoff_rel_err_max": link["handoff"],
        "entry_bookkeeping_faults_max": link["bookkeeping_faults"],
        "entry_last_block_median_rel_err": link["last_block_median"],
        "choice_top_share": max(r["top_share"] for r in by_layer),
    }
    for part, name in (
        ("projection", "projection_rel_err"), ("attention", "attention_rel_err"),
        ("decode_read", "decode_read_rel_err"), ("router_prob", "router_prob_abs_err"),
        ("choice_deficit", "choice_deficit"), ("choice_differs", "choice_differs_share"),
        ("expert", "expert_rel_err"),
    ):
        out[f"{name}_max"] = max(r[part] for r in by_layer)
        out[f"{name}_max_by_layer"] = [r[part] for r in by_layer]
    return out


def check(config: dict, graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import numpy as np

    import frame
    import zaya_decoder as ref

    del chips  # the stage lies on one device
    frame.lap("import")
    cfg, params, kw = model(graph, seed)
    found, agree, n = [], 0, 0
    for pr in probes["probes"]:
        prompt, toks = pr["prompt"], pr["tokens"]
        # only the rows that are judged leave the last block
        rows = list(range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        lg = np.asarray(ref.logits(params, prompt + toks[:-1], rows=rows, **kw))
        d, a = deficits(lg, toks)
        found += d
        agree += a
        n += len(toks)
    frame.lap("forward")
    top = sorted(found)
    out = {
        "kind": "zaya_decoder", "positions": n,
        "argmax_agree_share": agree / n,
        "logit_deficit_mean": sum(found) / n,
        "logit_deficit_p50": top[n // 2],
        "logit_deficit_max": top[-1],
        "logit_deficit_p99": top[min(n - 1, int(0.99 * n))],
    }
    limits = config["reference"]
    if limits.get("state_probe_tokens"):
        out.update(mechanism(
            cfg, graph, params, seed, int(limits["state_probe_tokens"]),
            int(limits.get("state_probe_steps", 64)),
        ))
    return out
