"""Reference kind ``kimi_k2_decoder``: the served weights of
``models/kimi_k2.py`` remade from the seed (in the served dtype, by the
program's own init with the same key), and the engine's probe tokens held,
teacher-forced, against the plain forward pass of ``../kimi_k2_decoder.py``
(the EXPANDED form only) given the same ``experts_held``.

The harness's own probes end at 96 tokens, where YaRN's ramp, the decode
read's tile loop and the table's later blocks are idle, so beside them
``mechanism`` runs, here in the child, a prompt of the configuration's
``reference.parts_probe_tokens`` from the seed through the served program's
own layer functions on every layer (``models/kimi_k2.py``: ``_latents``,
``_attend_prompt``, ``_decode_attention``, ``_after_attention``, in the
served dtype and through the kernels the graph names) and holds each part of
a layer to the reference's equation GIVEN THE PROGRAM'S OWN INPUTS to that
part: the rotary and latent projections against float32 ones of the same
hidden state (``projection_rel_err_max``), the prompt's attention on the last
rows against the reference's expanded attention of the same stored ``qn``,
``qr``, ``c``, ``kr`` (``attention_rel_err_max``), and the last positions
read as a decode step reads them — absorbed, through the read the graph
names, over a pool of the prompt's rows — against the same expanded rows
(``decode_read_rel_err_max``).  ``judges/token_logits_and_parts.py`` holds
both sets of numbers to the configuration's limits.

WHAT ``mechanism`` IS NOT.  It is a unit check of the layer functions and
kernels, composed here: a jit of this child's own, one sequence, 8 "slots"
over a pool whose table is ``arange``, one layer at a time, on the graph of
the configuration's file AS COMMITTED.  It is not the engine's compiled
``prefill:b<rung>`` and ``decode_k`` programs at 32 slots through the
scheduler's table, which the window times; what holds THOSE in every run is
the probes' pair of limits alone, which a lower precision of the scores
passes (the configuration's ``reference.why`` has the readings).  An engine
run under ``run.py --graph-param`` is judged here on the committed graph, so
a control reaches ``mechanism`` through ``parts_probe.py`` only.  PERF.md §7
names what would close this: probes of the traffic's own lengths in the
harness, a ``benchmark`` PR's."""

from __future__ import annotations

JUDGE = "token_logits_and_parts"  # unless the configuration names another

FIELDS = ("vocab_size", "hidden", "n_layers", "n_dense_layers", "n_heads",
          "q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
          "v_head_dim", "ffn_dense", "ffn", "n_experts", "experts_per_tok",
          "n_shared_experts", "routed_scale", "experts_held", "max_seq",
          "rope_theta", "rope_factor", "rope_original_max", "rope_beta_fast",
          "rope_beta_slow", "rope_mscale", "rope_mscale_all_dim", "norm_eps",
          "decode_rope", "softmax_mscale", "decode_score_dtype",
          "prompt_score_dtype")
JUDGED_ROWS = 256  # query rows judged at once: one block of the reference's
DECODE_SLOTS = 8  # the prompt's last positions, each taken as a decode step


def rope_group(cfg) -> dict:
    """The config's ``rope_scaling`` group as the reference takes it."""
    return {
        "factor": cfg.rope_factor,
        "original_max_position_embeddings": cfg.rope_original_max,
        "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
        "mscale": cfg.rope_mscale, "mscale_all_dim": cfg.rope_mscale_all_dim,
    }


def reference_kw(cfg) -> dict:
    return dict(
        rope=rope_group(cfg), theta=cfg.rope_theta, eps=cfg.norm_eps,
        top_k=cfg.experts_per_tok, held=cfg.held, scale=cfg.routed_scale,
    )


def stated(graph: dict):
    """The program's ``Config`` of a graph's parameters."""
    from seldon_core_tpu.models import kimi_k2

    return kimi_k2.Config(**{k: graph[k] for k in FIELDS if k in graph})


def model(graph: dict, seed: int):
    """(cfg, the served tree, the reference's keyword arguments) for a
    configuration's graph.  The controls (``decode_rope`` and the like) are
    the served program's alone: the reference has no such switch."""
    import jax

    from seldon_core_tpu.models import kimi_k2

    import frame

    cfg = stated(graph)
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    frame.lap("backend")
    params = jax.jit(lambda key: kimi_k2.init_params(key, cfg, dtype))(
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


def rel_err(found, ref, axes):
    """|found - ref| / |ref| over ``axes``, in float32."""
    import jax.numpy as jnp

    found, ref = jnp.asarray(found, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.sqrt(jnp.sum((found - ref) ** 2, axes) / jnp.sum(ref**2, axes))


def mechanism(cfg, graph: dict, params: dict, seed: int, n_tokens: int) -> dict:
    """The layer functions past the probes' 96 tokens, part by part on the
    program's own inputs (the module's docstring).  ``cfg`` is what the
    served functions run under (a control's switches, a wrong ramp); the
    reference takes YaRN's numbers and the norms' from ``graph`` as given."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import kimi_k2 as kk

    import frame
    import kimi_k2_decoder as ref

    L, B = int(n_tokens), JUDGED_ROWS
    seq_impl = graph.get("seq_impl", "dense")
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    bs = int(graph.get("kv_block_size", 16))
    if L % max(bs, 512 if seq_impl == "flash" else 1) or L < 2 * B:
        raise ValueError(f"parts_probe_tokens {L} must be whole tiles and blocks")
    blocks = (L // 2 // B * B, L - B)  # the rows judged: a block midway, and the last
    tokens = np.random.default_rng([seed, 0x1A7E27]).integers(1, cfg.vocab_size, size=L)
    pos = jnp.arange(L)
    spos = jnp.arange(L - DECODE_SLOTS, L)
    # the reference's own frequencies and scale, from the graph's group
    said = stated(graph)
    freqs, m = ref.yarn(rope_group(said), said.qk_rope_dim, float(said.rope_theta))
    sigma = float((said.qk_nope_dim + said.qk_rope_dim) ** -0.5 * m * m)
    kernel = bool(graph.get("decode_kernel"))

    @functools.partial(jax.jit, static_argnames=("dense",))
    def served(x, lp, dense):
        h = kk._rmsnorm(x, lp["ln1"], cfg.norm_eps)
        qn, qr, c, kr = kk._latents(h, lp, cfg, pos)
        c, kr = c.astype(dtype), kr.astype(dtype)  # as the pool holds them
        o = kk._attend_prompt(qn, qr, c, kr, lp, cfg, seq_impl)
        # the last positions once more, each as a decode step reads them:
        # absorbed, over a pool of the prompt's own rows
        od, _ = kk._decode_attention(
            qn[spos], qr[spos], c.reshape(1, L // bs, bs, -1),
            kk._kr_by_token(kr.reshape(1, L // bs, bs, -1)), 0, lp,
            jnp.broadcast_to(jnp.arange(L // bs), (DECODE_SLOTS, L // bs)),
            spos, jnp.ones((DECODE_SLOTS,), bool), cfg, kernel=kernel,
        )
        ok = jnp.ones((L,), bool)
        y = kk._after_attention(x, o, lp, cfg, ok, None, dense=dense, decode=False)[0]
        return y, dict(qn=qn, qr=qr, c=c, kr=kr, o=o, od=od)

    @functools.partial(jax.jit, static_argnames=("a",))
    def judged(x, lp, p, a):
        """One block of rows held to the reference on the same inputs."""
        f = ref.f32
        h = ref.rmsnorm(f(x), f(lp["ln1"]), said.norm_eps)
        plain = dict(zip(("qn", "qr", "c", "kr"), ref.project(h, lp, freqs, said.norm_eps)))
        k, v = ref.expand(f(p["c"]), f(p["kr"]), lp["wuk"], lp["wuv"])
        q = jnp.concatenate([f(p["qn"][a:a + B]), f(p["qr"][a:a + B])], axis=-1)
        o = ref.attend_rows(q, k, v, a, sigma)
        return {
            "attention": rel_err(p["o"][a:a + B], o, (1, 2)),
            "decode": rel_err(p["od"], o[-DECODE_SLOTS:], (1, 2)),
            "projection": jnp.stack([
                rel_err(p[n][a:a + B], plain[n][a:a + B], None) for n in plain
            ]),
        }

    x = params["tok_emb"][jnp.asarray(tokens, jnp.int32)].astype(dtype)
    by_layer = []
    for lp in ref.layers_of(params):
        y, parts = served(x, lp, dense="w_gate" in lp)
        row = {"attention": [], "projection": []}
        for a in blocks:
            with jax.default_matmul_precision("highest"):
                got = judged(x, lp, parts, a)
            row["attention"] += np.asarray(got["attention"]).tolist()
            row["projection"].append(float(jnp.max(got["projection"])))
        row["decode"] = np.asarray(got["decode"]).tolist()  # of the last block
        by_layer.append(row)
        x = y
    frame.lap("mechanism")
    return {
        "parts_probe_tokens": L,
        "parts_rows_judged": len(by_layer) * len(blocks) * B,
        "projection_rel_err_max": max(max(r["projection"]) for r in by_layer),
        "attention_rel_err_max": max(max(r["attention"]) for r in by_layer),
        "decode_read_rel_err_max": max(max(r["decode"]) for r in by_layer),
        "attention_rel_err_max_by_layer": [max(r["attention"]) for r in by_layer],
        "decode_read_rel_err_max_by_layer": [max(r["decode"]) for r in by_layer],
    }


def check(config: dict, graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import numpy as np

    import frame
    import kimi_k2_decoder as ref

    del chips  # one chip's share: the tree lies on one device
    frame.lap("import")
    cfg, params, kw = model(graph, seed)
    found, agree, n = [], 0, 0
    for pr in probes["probes"]:
        prompt, toks = pr["prompt"], pr["tokens"]
        # only the rows that are judged leave the last layer
        rows = list(range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        lg = np.asarray(ref.logits(params, prompt + toks[:-1], rows=rows, **kw))
        d, a = deficits(lg, toks)
        found += d
        agree += a
        n += len(toks)
    frame.lap("forward")
    top = sorted(found)
    out = {
        "kind": "kimi_k2_decoder", "positions": n,
        "argmax_agree_share": agree / n,
        "logit_deficit_max": top[-1],
        "logit_deficit_p99": top[min(n - 1, int(0.99 * n))],
    }
    n_tokens = config["reference"].get("parts_probe_tokens")
    if n_tokens:
        out.update(mechanism(cfg, graph, params, seed, int(n_tokens)))
    return out
