"""Reference kind ``jamba_decoder``: the served weights of ``models/jamba.py``
remade from the seed (in the served dtype, by the program's own init with the
same key), and the engine's probe tokens held, teacher-forced, against the
plain forward pass of ``../jamba_decoder.py``.

The harness's probes (64 in, 32 out) do reach this family's mechanism — a
64-token prompt in a 256 rung is three quarters padding, and 32 steps carry
the state — but stop at 96 tokens where the cell's requests run to 2,176.  So
beside them ``mechanism`` runs, here in the child, what the timed path runs at
the timed sizes: a prompt of the configuration's
``reference.state_probe_tokens`` REAL tokens from the seed in its padded rung
(1,000 in 1,024), through the served program's own layer functions on every
layer (``models/jamba.py``: ``_ssm_prompt_parts`` with the kernel the graph
names, ``_ssm_step_parts`` with the in-place update kernel wherever the
engine would choose it, ``_after_mixer``, in the served dtype), then
``reference.state_probe_steps`` decode steps of one slot from the state and
tail the prompt left, and holds each part of each state-space layer to the
reference's equation GIVEN THE PROGRAM'S OWN INPUTS to that part:

* ``projection_rel_err_max``: ``B``, ``C`` and ``D_t`` of the prompt's last
  256 real rows and of the steps against float32 ones of the same ``c``, and
  the steps' ``c`` against the reference's convolution of the same ``u``
  behind the prompt's last three real inputs (a missing inner norm or bias
  reads about 1; so does a tail taken at the rung's end);
* ``scan_rel_err_max``: the prompt's ``y`` on the last 256 real rows against
  the reference's recurrence of the same ``c``, ``D_t``, ``B``, ``C``;
* ``state_rel_err_max``: ``S`` after the prompt against that recurrence's
  (its padding rows must have moved nothing), and after the steps against
  the reference's continued from ITS OWN state over the served steps'
  ``c``, ``D_t``, ``B``, ``C`` (so a state kept in a lower precision shows);
* ``decode_rel_err_max``: the steps' ``y`` against that continuation's.

``judges/token_logits_and_state.py`` holds both sets of numbers to the
configuration's limits.

WHAT ``mechanism`` IS NOT.  It is a unit check of the layer functions and the
kernel, composed here: a jit of this child's own, one sequence, one slot, one
layer at a time, on the graph of the configuration's file AS COMMITTED.  It
is not the engine's compiled ``prefill:b<rung>`` and ``decode_k`` programs at
128 slots through the scheduler's table, which the window times; what holds
THOSE in every run is the probes' pair of limits.  An engine run under
``run.py --graph-param`` is judged here on the committed graph, so a control
reaches ``mechanism`` through ``state_probe.py`` only."""

from __future__ import annotations

JUDGE = "token_logits_and_state"  # unless the configuration names another

FIELDS = ("vocab_size", "hidden", "n_layers", "n_heads", "n_kv_heads", "ffn",
          "attn_layer_period", "attn_layer_offset", "mamba_d_state",
          "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
          "mamba_proj_bias", "max_seq", "norm_eps", "ssm_state_dtype",
          "ssm_product_dtype", "ssm_padding", "conv_tail_at", "dt_bias")
JUDGED_ROWS = 256  # the prompt's last real rows, judged


def reference_kw(cfg) -> dict:
    return dict(
        period=cfg.attn_layer_period, offset=cfg.attn_layer_offset, eps=cfg.norm_eps
    )


def stated(graph: dict):
    """The program's ``Config`` of a graph's parameters."""
    from seldon_core_tpu.models import jamba

    return jamba.Config(**{k: graph[k] for k in FIELDS if k in graph})


def model(graph: dict, seed: int):
    """(cfg, the served tree, the reference's keyword arguments) for a
    configuration's graph.  The controls (``ssm_state_dtype`` and the like)
    are the served program's alone: the reference has no such switch."""
    import jax

    from seldon_core_tpu.models import jamba

    import frame

    cfg = stated(graph)
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    frame.lap("backend")
    params = jax.jit(lambda key: jamba.init_params(key, cfg, dtype))(
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


def rel_err(found, ref, axes=None):
    """|found - ref| / |ref| over ``axes``, in float32."""
    import jax.numpy as jnp

    found, ref = jnp.asarray(found, jnp.float32), jnp.asarray(ref, jnp.float32)
    return jnp.sqrt(jnp.sum((found - ref) ** 2, axes) / jnp.sum(ref**2, axes))


def mechanism(cfg, graph: dict, params: dict, seed: int, n_tokens: int,
              n_steps: int) -> dict:
    """The state-space layers at the timed sizes, part by part on the
    program's own inputs (the module's docstring).  ``cfg`` is what the
    served functions run under (a control's switches); the reference takes
    the norms' epsilon and the pattern from ``graph`` as given."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import jamba as jm

    import frame
    import jamba_decoder as ref

    L, K = int(n_tokens), int(n_steps)
    B = min(JUDGED_ROWS, L)
    seq_impl = graph.get("seq_impl", "dense")
    dtype = frame.served_dtype(graph.get("dtype", "float32"))
    bs = int(graph.get("kv_block_size", 16))
    rung = -(-L // bs) * bs  # the ladder's rungs are whole blocks
    said = stated(graph)
    tokens = np.random.default_rng([seed, 0x5EED5]).integers(
        1, cfg.vocab_size, size=L + K
    )
    # the rung's padding rows hold token 0, as the engine pads a prompt
    padded = np.concatenate([tokens[:L], np.zeros(rung - L, np.int64), tokens[L:]])
    n_conv = cfg.mamba_d_conv - 1
    # the engine's own choice of the decode step's kernels: the graph's
    # word, else wherever the backend compiles them
    kernel = graph.get("decode_kernel")
    if kernel is None:
        kernel = jax.default_backend() != "cpu"
    in_place = {"layer": jnp.int32(0)} if kernel else {}

    @jax.jit
    def served_ssm(x, lp):
        """One state-space layer over the prompt's rung and then the steps:
        ``x (rung + K, E)``.  -> (the next layer's ``x``, the parts)."""
        h = jm._rmsnorm(x, lp["ln1"], cfg.norm_eps)
        p = jm._ssm_prompt_parts(h[:rung], lp, cfg, jnp.int32(L), seq_impl)

        def step(carry, ht):
            s, tail = carry
            q = jm._ssm_step_parts(
                ht[None], lp, cfg, s, tail, jnp.ones((1,), bool), **in_place
            )
            keep = {k: q[k][0] for k in ("u", "z", "c", "dt", "b", "cc", "y")}
            return (q["s"], q["tail"]), keep

        s0 = p["s"].astype(cfg.state_dtype)[None]  # as the slot holds it
        if in_place:
            s0 = s0[None]  # an array of one layer, updated in place
        (s_end, _), d = jax.lax.scan(
            step, (s0, p["tail"].astype(dtype)[:, None]), h[rung:]
        )
        y = jnp.concatenate([p["y"], d["y"].astype(p["y"].dtype)])
        z = jnp.concatenate([p["z"], d["z"]])
        out = jm._after_mixer(x, jm._ssm_out(y, z, lp), lp, cfg)
        return out, dict(prompt=p, steps=d, s_end=s_end.reshape(p["s"].shape))

    @jax.jit
    def served_attn(x, lp):
        """An attention layer over the real rows, causal, in XLA: not
        judged here, it hands the next layer its inputs."""
        real = jnp.concatenate([x[:L], x[rung:]])
        q, k, v = jm._qkv(jm._rmsnorm(real, lp["ln1"], cfg.norm_eps), lp)
        o = jm._attend_prompt(q, k, v, "dense")
        out = jm._after_mixer(real, jm._attn_out(o, lp), lp, cfg)
        return jnp.concatenate([out[:L], x[L:rung], out[L:]])

    @jax.jit
    def judged(lp, parts):
        """One state-space layer's parts held to the reference."""
        f = ref.f32
        p, d = parts["prompt"], parts["steps"]
        rows = slice(L - B, L)
        _, b, cc, dt = ref.project(f(p["c"][rows]), lp, said.norm_eps)
        y, s = ref.recurrence(
            f(p["c"][:L]), p["dt"][:L], p["b"][:L], p["cc"][:L], lp
        )
        # the steps: the convolution of the same u behind the prompt's last
        # real inputs, the projections of the same c, the recurrence
        # continued from the reference's own state
        before = jnp.concatenate(
            [jnp.zeros((n_conv, p["u"].shape[1]), jnp.float32), f(p["u"][:L])]
        )[-n_conv:]
        c_d = ref.conv(f(d["u"]), lp, before)
        _, b_d, cc_d, dt_d = ref.project(f(d["c"]), lp, said.norm_eps)
        y_d, s_d = ref.recurrence(f(d["c"]), d["dt"], d["b"], d["cc"], lp, s)
        return {
            "projection": jnp.stack([
                rel_err(p["b"][rows], b), rel_err(p["cc"][rows], cc),
                rel_err(p["dt"][rows], dt), rel_err(d["c"], c_d),
                rel_err(d["b"], b_d), rel_err(d["cc"], cc_d), rel_err(d["dt"], dt_d),
            ]),
            "scan": rel_err(p["y"][rows], y[rows], 1),
            "state": jnp.stack([
                rel_err(p["s"], s.T), rel_err(parts["s_end"], s_d.T),
            ]),
            "decode": rel_err(d["y"], y_d, 1),
        }

    x = params["tok_emb"][jnp.asarray(padded, jnp.int32)].astype(dtype)
    by_layer = []
    for lp in ref.layers_of(params, said.attn_layer_period, said.attn_layer_offset):
        if "wq" in lp:
            x = served_attn(x, lp)
            continue
        x, parts = served_ssm(x, lp)
        with jax.default_matmul_precision("highest"):
            got = judged(lp, parts)
        by_layer.append({k: float(jnp.max(v)) for k, v in got.items()})
        by_layer[-1]["state_after_prompt"] = float(got["state"][0])
    frame.lap("mechanism")
    out = {
        "state_probe_tokens": L, "state_probe_steps": K, "state_probe_rung": rung,
        "state_layers_judged": len(by_layer),
    }
    for part in ("projection", "scan", "state", "decode"):
        out[f"{part}_rel_err_max"] = max(r[part] for r in by_layer)
        out[f"{part}_rel_err_max_by_layer"] = [r[part] for r in by_layer]
    out["state_after_prompt_rel_err_max"] = max(r["state_after_prompt"] for r in by_layer)
    return out


def check(config: dict, graph: dict, seed: int, chips: int, probes: dict) -> dict:
    import numpy as np

    import frame
    import jamba_decoder as ref

    del chips  # the whole model lies on one device
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
        "kind": "jamba_decoder", "positions": n,
        "argmax_agree_share": agree / n,
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
