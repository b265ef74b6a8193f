"""Reference kind ``keye_vl2_decoder``: the served weights of
``models/keye_vl2.py`` remade from the seed (in the served dtype, by the
program's own init with the same key), and the engine's probe tokens held,
teacher-forced, against the plain forward pass of ``../keye_vl2_decoder.py``
given the same ``experts_held`` and ``index_topk``.  What it finds is given
for all positions and apart for those at which every key is attended
(``_dense``: a context of ``index_topk`` or fewer) and those at which the
model selects (``_selecting``).

The harness's own probes end under ``index_topk``, so beside them
``mechanism`` runs, here in the child, a prompt of the configuration's
``reference.selection_probe_tokens`` through the served program's own layer
functions (``models/keye_vl2.py``: ``_qkv``, ``_index``, ``_attend_prompt``,
``_decode_attention``, ``_after_attention``, in the served dtype and through
the kernels the graph names) and holds each part of a layer to the
reference's equation GIVEN THE PROGRAM'S OWN INPUTS to that part: the
projections against float32 ones of the same hidden state, the keys
selected against the reference's explicit sort of float32 scores of the
same index queries and keys, the attention and the decode read against the
reference's attention over the reference's set of the same q, k, v.  Given
the same inputs these are well conditioned (a float32 score of stored
operands is exact to 1e-7; one key swapped is counted as one), where the
logits at the end of six layers are not: with random weights a rounding of
the hidden state swaps a few per cent of the keys at the ``topk``-th place
and moves the attention's output by a quarter of itself
(``chain=True`` counts that, layer by layer).
``judges/token_logits_by_context.py`` holds each set of numbers to its own
limits."""

from __future__ import annotations

JUDGE = "token_logits_by_context"  # unless the configuration names another

FIELDS = ("vocab_size", "hidden", "n_layers", "n_heads", "n_kv_heads",
          "head_dim", "ffn", "n_experts", "experts_per_tok", "experts_held",
          "index_heads", "index_dim", "index_topk", "max_seq", "rope_theta",
          "norm_eps")
JUDGED_ROWS = 256  # query rows judged at once: one block of the reference's
DECODE_SLOTS = 8  # the prompt's last positions, each taken as a decode step


def model(graph: dict, seed: int, chips: int):
    """(cfg, head weights, a function that yields the layers' weights one by
    one, the reference's keyword arguments) for a configuration's graph."""
    import jax

    from seldon_core_tpu.models import keye_vl2

    import frame
    import keye_vl2_decoder as ref

    cfg = keye_vl2.Config(**{k: graph[k] for k in FIELDS if k in graph})
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    devices = frame.layer_devices(cfg.n_layers, chips)
    frame.lap("backend")
    params = frame.init_spread(
        lambda key: keye_vl2.init_params(key, cfg, dtype),
        jax.random.PRNGKey(seed), devices,
    )
    frame.lap("weights")

    def layers():
        for local in frame.local_stacks(params["layers"], len(devices)):
            yield from ref.layers_of(local)

    head = jax.tree.map(
        lambda a: jax.device_put(a, devices[0]),
        {k: params[k] for k in ("tok_emb", "head", "ln_f")},
    )
    kw = dict(
        theta=cfg.rope_theta, eps=cfg.norm_eps, topk=cfg.index_topk,
        top_k=cfg.experts_per_tok, held=cfg.held,
    )
    return cfg, head, layers, kw


def deficits(ref_logits, tokens) -> tuple[list[float], int]:
    """How far each served token lies under the reference's top logit at its
    position, and at how many positions it IS the top."""
    out, agree = [], 0
    for row, t in zip(ref_logits, tokens):
        out.append(float(row.max() - row[t]))
        agree += int(row.argmax() == t)
    return out, agree


def summary(found: list[float], agree: int, suffix: str = "") -> dict:
    n = len(found)
    if not n:
        return {"positions" + suffix: 0}
    top = sorted(found)
    return {
        "positions" + suffix: n,
        "argmax_agree_share" + suffix: agree / n,
        "logit_deficit_max" + suffix: top[-1],
        "logit_deficit_p99" + suffix: top[min(n - 1, int(0.99 * n))],
    }


def rel_err(found, ref, axes):
    """|found - ref| / |ref| over ``axes``, in float32."""
    import jax.numpy as jnp

    found, ref = jnp.asarray(found, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.sqrt(jnp.sum((found - ref) ** 2, axes) / jnp.sum(ref**2, axes))


def mechanism(cfg, graph: dict, head: dict, layers, seed: int, n_tokens: int,
              *, chain: bool = False, float32: bool = False) -> dict:
    """The selection at work, part by part on the program's own inputs (the
    module's docstring).  ``chain`` also runs the reference's own six layers
    on its own float32 hidden state and counts how far the two have parted;
    ``float32`` serves in float32 at the highest matmul precision (each
    layer's weights raised as it is used): a diagnosis's, never a run's."""
    import contextlib
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import keye_vl2 as kv
    from seldon_core_tpu.ops import sparse_attention as sa

    import frame
    import keye_vl2_decoder as ref

    L, B, topk = int(n_tokens), JUDGED_ROWS, cfg.index_topk
    seq_impl = graph.get("seq_impl", "dense")
    dtype = jnp.float32 if float32 else frame.served_dtype(graph.get("dtype", "float32"))
    bs = int(graph.get("kv_block_size", 16))
    if L % max(bs, 512 if seq_impl == "flash" else 1) or L < topk + 2 * B:
        raise ValueError(f"selection_probe_tokens {L} must be whole tiles past topk")
    blocks = (topk, L - B)  # the first rows that select, and the last
    tokens = np.random.default_rng([seed, 0x5E1EC7]).integers(1, cfg.vocab_size, size=L)
    pos = jnp.arange(L)
    spos = jnp.arange(L - DECODE_SLOTS, L)
    sparse = cfg.selects and L > topk
    score_dtype = kv._score_dtype(cfg)
    mech = dict(theta=cfg.rope_theta, eps=cfg.norm_eps)

    @jax.jit
    def served(x, lp):
        h = kv._rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = kv._qkv(h, lp, cfg, pos)
        qi, wi, ki = kv._index(h, lp, cfg, pos)
        ki = ki.astype(dtype)  # as the pool holds it
        index = (qi, wi, ki) if cfg.selects else None
        o = kv._attend_prompt(q, k, v, index, cfg, seq_impl)
        # the last positions once more, each as a decode step reads them
        kvd = cfg.n_kv_heads * cfg.head_dim
        n_seen = spos + 1
        od = kv._decode_attention(
            q[spos][:, None], qi[spos][:, None], wi[spos][:, None],
            k.reshape(1, L // bs, bs, kvd), v.reshape(1, L // bs, bs, kvd),
            ki.reshape(1, L // bs, bs, -1), 0,
            jnp.broadcast_to(jnp.arange(L // bs), (DECODE_SLOTS, L // bs)),
            spos, jnp.ones((DECODE_SLOTS,), bool),
            jnp.minimum(n_seen, topk) if sparse else n_seen, cfg,
            sparse=sparse, kernel=bool(graph.get("decode_kernel")),
        )
        ok = jnp.ones((L,), bool)
        y = kv._after_attention(x, o, lp, cfg, ok, None, decode=False)[0]
        return y, dict(q=q, k=k, v=v, qi=qi, wi=wi, ki=ki, o=o, od=od)

    @functools.partial(jax.jit, static_argnames=("a",))
    def served_set(qi, wi, ki, a):
        """(B, L) bool: the keys the program's queries ``a .. a + B`` attend,
        by the kernel its prompt attention calls (that attention's own
        calls, a chunk at a time, are held through its output)."""
        seen = pos[None, :] <= a + jnp.arange(B)[:, None]
        if not sparse:
            return seen
        if seq_impl != "flash":
            return sa.select_topk_mask_reference(
                qi[a:a + B], wi[a:a + B], ki, topk=topk, q_offset=a,
                score_dtype=score_dtype,
            ) != 0
        lk = min(L, -(-(a + B) // 512) * 512)
        mask = sa.select_topk_mask(
            qi[a:a + B], wi[a:a + B], ki[:lk], topk=topk, q_offset=a,
            score_dtype=score_dtype,
        )
        return jnp.pad(mask, ((0, 0), (0, L - lk))) != 0

    @functools.partial(jax.jit, static_argnames=("a",))
    def judged(x, lp, p, mine, a):
        """One block of queries held to the reference on the same inputs."""
        f = ref.f32
        want = ref.selected(f(p["qi"][a:a + B]), f(p["wi"][a:a + B]), f(p["ki"]), a, topk)
        o = ref.attend(f(p["q"][a:a + B]), f(p["k"]), f(p["v"]), want)
        h = ref.rmsnorm(f(x), f(lp["ln1"]), cfg.norm_eps)
        plain = dict(zip(("q", "k", "v", "qi", "ki", "wi"), ref._project(h, lp, **mech)))
        return {
            # keys of one set that the other lacks (the larger of the two
            # counts: sets of one size differ by as many either way)
            "swaps": jnp.maximum(
                jnp.sum(want & ~mine, axis=1), jnp.sum(mine & ~want, axis=1)
            ),
            "attention": rel_err(p["o"][a:a + B], o, (1, 2)),
            "decode": rel_err(p["od"], o[-DECODE_SLOTS:], (1, 2)),
            "projection": jnp.stack([
                rel_err(p[n][a:a + B], plain[n][a:a + B], None) for n in plain
            ]),
        }

    @functools.partial(jax.jit, static_argnames=("a",))
    def parted(x_ref, lp, mine, a):
        """Keys of the reference's OWN set (its own float32 hidden state)
        that the program's queries ``a .. a + B`` do not attend."""
        h = ref.rmsnorm(x_ref, ref.f32(lp["ln1"]), cfg.norm_eps)
        _, _, _, qi, ki, wi = ref._project(h, lp, **mech)
        want = ref.selected(qi[a:a + B], wi[a:a + B], ki, a, topk)
        return jnp.sum(want & ~mine, axis=1)

    def last_logits(x):
        h = ref.rmsnorm(ref.f32(x[L - B:]), ref.f32(head["ln_f"]), cfg.norm_eps)
        return np.asarray(h @ ref.f32(head["head"]).T)

    x = head["tok_emb"][jnp.asarray(tokens, jnp.int32)].astype(dtype)
    x_ref = ref.f32(head["tok_emb"][jnp.asarray(tokens, jnp.int32)])
    by_layer = []
    exact = jax.default_matmul_precision("highest")
    with exact if float32 else contextlib.nullcontext():
        for lp in layers():
            dev = next(iter(lp["wq"].devices()))
            x = jax.device_put(x, dev)
            mine_lp = jax.tree.map(lambda a: a.astype(dtype), lp) if float32 else lp
            y, parts = served(x, mine_lp)
            row = {"swaps": [], "attention": [], "projection": [], "parted": []}
            for a in blocks:
                mine = served_set(parts["qi"], parts["wi"], parts["ki"], a)
                with jax.default_matmul_precision("highest"):
                    got = judged(x, lp, parts, mine, a)
                    if chain:
                        x_ref = jax.device_put(x_ref, dev)
                        row["parted"] += np.asarray(parted(x_ref, lp, mine, a)).tolist()
                row["swaps"] += np.asarray(got["swaps"]).tolist()
                row["attention"] += np.asarray(got["attention"]).tolist()
                row["projection"].append(float(jnp.max(got["projection"])))
            row["decode"] = np.asarray(got["decode"]).tolist()  # of the last block
            by_layer.append(row)
            if chain:
                with jax.default_matmul_precision("highest"):
                    x_ref = ref.layer(
                        x_ref, lp, topk=topk, top_k=cfg.experts_per_tok,
                        held=cfg.held, **mech,
                    )
            x = y
    frame.lap("mechanism")
    out = {
        "selection_probe_tokens": L,
        "selection_rows_judged": len(by_layer) * len(blocks) * B,
        "selection_swaps_max": max(max(r["swaps"]) for r in by_layer),
        "selection_swaps_mean": float(np.mean([r["swaps"] for r in by_layer])),
        "projection_rel_err_max": max(max(r["projection"]) for r in by_layer),
        "attention_rel_err_max": max(max(r["attention"]) for r in by_layer),
        "decode_read_rel_err_max": max(max(r["decode"]) for r in by_layer),
        "selection_swaps_max_by_layer": [max(r["swaps"]) for r in by_layer],
        "attention_rel_err_max_by_layer": [max(r["attention"]) for r in by_layer],
    }
    if chain:
        mine, plain = last_logits(x), last_logits(jax.device_put(x_ref, x.devices().pop()))
        found, agree = deficits(plain, mine.argmax(-1))
        out.update(
            chain_keys_parted_mean_by_layer=[float(np.mean(r["parted"])) for r in by_layer],
            chain_keys_parted_max_by_layer=[max(r["parted"]) for r in by_layer],
            **summary(found, agree, "_chain"),
        )
    return out


def check(config: dict, graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import numpy as np

    import frame
    import keye_vl2_decoder as ref

    frame.lap("import")
    cfg, head, layers, kw = model(graph, seed, chips)
    # (deficits, agreements) of the positions that attend everything and of
    # those that select: the row at sequence index i has a context of i + 1
    sets = {"_dense": ([], 0), "_selecting": ([], 0)}
    for pr in probes["probes"]:
        prompt, toks = pr["prompt"], pr["tokens"]
        # only the rows that are judged leave the last layer: a prompt of
        # thousands of tokens times the vocabulary is gigabytes
        rows = list(range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        lg = np.asarray(ref.logits(
            head, prompt + toks[:-1], layers=layers(), rows=rows, **kw
        ))
        for i, row, t in zip(rows, lg, toks):
            name = "_selecting" if i + 1 > cfg.index_topk else "_dense"
            d, a = deficits([row], [t])
            sets[name] = (sets[name][0] + d, sets[name][1] + a)
    frame.lap("forward")
    both = sets["_dense"][0] + sets["_selecting"][0]
    out = {"kind": "keye_vl2_decoder",
           **summary(both, sets["_dense"][1] + sets["_selecting"][1])}
    for name, (found, agree) in sets.items():
        out.update(summary(found, agree, name))
    n_tokens = config["reference"].get("selection_probe_tokens")
    if n_tokens:
        out.update(mechanism(cfg, graph, head, layers, seed, int(n_tokens)))
    return out
