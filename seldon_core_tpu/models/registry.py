"""Model-family registry: name -> compiled, mesh-sharded graph unit.

A SeldonDeployment graph node can say ``implementation: JAX_MODEL`` with
parameters ``{"family": "resnet", "preset": "tiny"}`` and the engine builds
the corresponding :class:`JaxModelComponent` — the TPU-native replacement for
pointing a node's Endpoint at a model-microservice pod.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from seldon_core_tpu.executor import BucketSpec, CompiledModel, JaxModelComponent
from seldon_core_tpu.models import (
    bert, cnn, cohere2_moe, jamba, keye_vl2, kimi_k2, llama, mlp, resnet, zaya,
)


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    config_cls: type
    init_params: Callable
    apply: Callable  # apply(params, batch, cfg)
    param_logical_axes: Callable
    presets: dict[str, Callable[[], Any]]
    example_input: Callable[[Any, int], np.ndarray]  # (cfg, batch) -> array
    # init_params(rng, cfg, dtype) makes the weights IN the served dtype: a
    # family whose share of a deployment fills a chip in bfloat16 cannot be
    # made in float32 first and cast
    init_in_dtype: bool = False


def _f32(shape):
    return np.zeros(shape, np.float32)


_FAMILIES: dict[str, Family] = {
    "mlp": Family(
        "mlp", mlp.Config, mlp.init_params, mlp.apply, mlp.param_logical_axes,
        presets={"default": mlp.Config, "tiny": lambda: mlp.Config(in_features=16, hidden=32, n_classes=3)},
        example_input=lambda c, b: _f32((b, c.in_features)),
    ),
    "cnn": Family(
        "cnn", cnn.Config, cnn.init_params, cnn.apply, cnn.param_logical_axes,
        presets={"default": cnn.Config, "tiny": lambda: cnn.Config(image_size=8, hidden=32)},
        example_input=lambda c, b: _f32((b, c.image_size * c.image_size * c.channels)),
    ),
    "resnet": Family(
        "resnet", resnet.Config, resnet.init_params, resnet.apply, resnet.param_logical_axes,
        presets={
            "resnet50": resnet.Config,
            "tiny": lambda: resnet.Config(stage_sizes=(1, 1), width=8, n_classes=10, image_size=32),
        },
        example_input=lambda c, b: _f32((b, c.image_size, c.image_size, c.channels)),
    ),
    "bert": Family(
        "bert", bert.Config, bert.init_params, bert.apply, bert.param_logical_axes,
        presets={
            "base": bert.Config,
            "tiny": lambda: bert.Config(vocab_size=128, hidden=32, n_layers=2, n_heads=2, ffn=64, max_len=64),
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
    ),
    "llama": Family(
        "llama", llama.Config,
        lambda rng, cfg: llama.init_params(rng, cfg),
        llama.apply, llama.param_logical_axes,
        presets={
            "llama3-8b": llama.Config.llama3_8b,
            "llama3-1b": llama.Config.llama3_1b,
            "tiny": llama.Config.tiny,
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
    ),
    "cohere2_moe": Family(
        "cohere2_moe", cohere2_moe.Config, cohere2_moe.init_params,
        cohere2_moe.apply, cohere2_moe.param_logical_axes,
        presets={
            "command-a-plus": cohere2_moe.Config,
            "tiny": cohere2_moe.Config.tiny,
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
        init_in_dtype=True,
    ),
    "keye_vl2": Family(
        "keye_vl2", keye_vl2.Config, keye_vl2.init_params,
        keye_vl2.apply, keye_vl2.param_logical_axes,
        presets={
            "keye-vl-2-30b-a3b": keye_vl2.Config,
            "tiny": keye_vl2.Config.tiny,
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
        init_in_dtype=True,
    ),
    "kimi_k2": Family(
        "kimi_k2", kimi_k2.Config, kimi_k2.init_params,
        kimi_k2.apply, kimi_k2.param_logical_axes,
        presets={
            "kimi-k2-6": kimi_k2.Config,
            "tiny": kimi_k2.Config.tiny,
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
        init_in_dtype=True,
    ),
    "jamba": Family(
        "jamba", jamba.Config, jamba.init_params,
        jamba.apply, jamba.param_logical_axes,
        presets={
            "jamba2-3b": jamba.Config,
            "tiny": jamba.Config.tiny,
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
        init_in_dtype=True,
    ),
    "zaya": Family(
        "zaya", zaya.Config, zaya.init_params,
        zaya.apply, zaya.param_logical_axes,
        presets={
            "zaya1-8b": zaya.Config,
            "tiny": zaya.Config.tiny,
        },
        example_input=lambda c, b: np.ones((b, 16), np.int32),
        init_in_dtype=True,
    ),
}


def get_family(name: str) -> Family:
    try:
        return _FAMILIES[name]
    except KeyError:
        raise KeyError(f"unknown model family {name!r}; have {sorted(_FAMILIES)}") from None


def resolve_config(family: str, preset: str | None = None, **overrides) -> Any:
    fam = get_family(family)
    if preset is not None:
        cfg = fam.presets[preset]()
    else:
        cfg = fam.config_cls()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def _resolve_params(
    fam: Family, cfg: Any, params: Any, checkpoint: str | None, rng: int,
    mesh: Mesh | None = None, rules: Any = None, dtype: Any = None,
    pack: Callable | None = None,
):
    """Explicit params > checkpoint load > fresh init; the compiled wrapper
    casts/shards them at construction.  A fresh init runs under jit — one
    program, kept by the compile cache, instead of a compile per tensor —
    and for a mesh with the family's own shardings as the output, so each
    device generates its shard: the whole float32 tree never sits on the
    default device first, where a model sized for the mesh does not fit.
    (The values are the eager, unsharded init's: threefry is
    partitionable.)  A family that makes its weights in the served dtype
    (``init_in_dtype``) is given ``dtype``, and its key as an argument: one
    compiled init program for every seed.  ``pack`` (a generative family's
    ``pack_params``) lays out what is made HERE, inside the init's own
    program or on the loaded tree before anyone else names it: the
    canonical leaves are never on the device beside their packed ones."""
    if params is not None:
        return params
    if pack is None:
        pack = lambda tree: tree  # noqa: E731
    if checkpoint is not None:
        from seldon_core_tpu.executor.checkpoint import load_params

        return pack(load_params(checkpoint))

    if fam.init_in_dtype:
        args = (jax.random.PRNGKey(rng),)
        as_dtype = jnp.float32 if dtype is None else dtype

        def init(key):
            return pack(fam.init_params(key, cfg, as_dtype))
    else:
        args = ()

        def init():
            return pack(fam.init_params(jax.random.PRNGKey(rng), cfg))

    shardings = None
    if mesh is not None:
        from seldon_core_tpu.parallel.sharding import (
            DEFAULT_RULES,
            param_shardings,
        )

        shapes = jax.eval_shape(init, *args)
        shardings = param_shardings(
            shapes, mesh, fam.param_logical_axes(shapes), rules or DEFAULT_RULES
        )
    return jax.jit(init, out_shardings=shardings)(*args)


def build_compiled(
    family: str,
    *,
    preset: str | None = None,
    cfg: Any = None,
    mesh: Mesh | None = None,
    rules: Any = None,
    rng: int = 0,
    dtype: Any = None,
    buckets: BucketSpec = BucketSpec(),
    params: Any = None,
    checkpoint: str | None = None,
    **overrides,
) -> CompiledModel:
    fam = get_family(family)
    if cfg is None:
        cfg = resolve_config(family, preset, **overrides)
    elif overrides:
        # an explicit cfg leaves nothing for overrides to apply to; silently
        # dropping them would hide typo'd graph parameters
        raise TypeError(
            f"unknown JAX_MODEL parameters {sorted(overrides)} for family "
            f"{family!r} (config fields: "
            f"{sorted(f.name for f in dataclasses.fields(fam.config_cls))})"
        )
    params = _resolve_params(fam, cfg, params, checkpoint, rng, mesh, rules)
    apply_fn = lambda p, x: fam.apply(p, x, cfg)  # noqa: E731
    extra = {} if rules is None else {"rules": rules}
    return CompiledModel(
        apply_fn,
        params,
        mesh=mesh,
        param_axes=fam.param_logical_axes(params) if mesh is not None else None,
        buckets=buckets,
        dtype=dtype,
        name=f"{family}:{preset or 'default'}",
        **extra,
    )


def build_component(
    family: str,
    *,
    preset: str | None = None,
    cfg: Any = None,
    class_names: list[str] | None = None,
    batching: bool = True,
    max_batch: int = 64,
    max_delay_ms: float = 2.0,
    max_queue: int | None = None,
    input_dtype: str | None = None,
    seq: int | None = None,
    **kwargs,
) -> JaxModelComponent:
    if cfg is None:
        # resolve here (not inside build_compiled) so the warmup example can
        # be derived from the same config
        overrides = {
            k: kwargs.pop(k)
            for k in list(kwargs)
            if k in {f.name for f in dataclasses.fields(get_family(family).config_cls)}
        }
        cfg = resolve_config(family, preset, **overrides)
    # leftover kwargs must be real build_compiled options; anything unknown
    # (e.g. a typo'd config field) fails loudly in build_compiled
    model = build_compiled(family, preset=preset, cfg=cfg, **kwargs)
    warmup = example_input(family, cfg, 1)
    if seq is not None:
        # token models: the example's sequence length is a placeholder, and
        # a program is compiled per length — warm the one requests arrive at
        if warmup.ndim != 2 or warmup.dtype != np.int32:
            raise TypeError(
                f"seq applies to token models; family {family!r} takes "
                f"{warmup.dtype} inputs of shape (batch, {warmup.shape[1:]})"
            )
        warmup = np.ones((1, int(seq)), np.int32)
    if input_dtype is not None:
        # serve a non-default wire dtype (e.g. uint8 images, normalized on
        # device): warmup must compile the buckets for THAT dtype, or the
        # first real request eats the compile
        warmup = warmup.astype(np.dtype(input_dtype))
    return JaxModelComponent(
        model,
        class_names=class_names,
        batching=batching,
        max_batch=max_batch,
        max_delay_ms=max_delay_ms,
        max_queue=max_queue,
        warmup_example=warmup,
    )


def example_input(family: str, cfg: Any, batch: int = 1) -> np.ndarray:
    return get_family(family).example_input(cfg, batch)


# The generative contract: what ``executor/generation.py::GenerativeModel``
# reads of a family module.  A family builds from three neutral modules and
# imports no other family (``tests/test_models.py`` holds that):
# ``models/layers.py`` (norms, rotary embeddings, the residual add, a prompt's
# plain attention, the RMSNorm head, ``sample_tokens``), ``models/paged.py``
# (the frame of a paged cache: the bookkeeping arrays, a prompt's writes, a
# decode step's read of a K/V pool, ``finish_prefill``, a slot's bytes, the counters' add, ``no_lora``) and ``models/moe.py`` (the
# routed expert layer: ``routed_experts`` behind the family's own router, its
# ``COUNTERS`` leading the family's); docs/GENERATIVE.md lists what a new
# family touches.
#   required: ``init_params``, ``param_logical_axes`` (under a mesh),
#     ``init_paged_cache``, ``prefill_slot_paged``, ``decode_slots_paged``,
#     ``sample_tokens`` (a ``top_k`` argument where top-k sampling is asked
#     for; a ``kernel`` argument of ``decode_slots_paged`` where the paged
#     decode kernel is);
#   probed for (``hasattr``/``getattr``), each the feature named:
#     ``prefill_suffix_paged`` (prefix reuse, chunked prefill),
#     ``decode_slots_spec_paged`` (speculative decode; ``apply_medusa_heads``
#     + ``init_medusa_heads`` for learned heads), ``embed_pooled``
#     (embeddings), ``init_lora_params`` with ``LORA_*_TARGETS`` and
#     ``lora_adapter_factors`` (adapters), ``truncate_params`` (a layer-
#     truncated draft), ``paged_kv_slot_bytes`` (the KV ledger's own size of
#     a slot), ``COUNTERS`` (names of the on-device counters a step returns),
#     ``pack_params`` with ``PACKED`` (the family's serving layout:
#     ``pack_params(params)`` returns the tree with the leaves its products
#     read, named by path in ``PACKED``, re-laid out as they read them, and a
#     tree already packed as it is; every entry function of the family takes
#     either tree, ``init_params`` and a checkpoint keep the canonical one,
#     ``param_logical_axes`` names the axes of both; a tree made here is
#     packed inside its own init, one handed in when the model is built, and
#     ``/stats/summary`` lists the ``PACKED`` leaves with the shapes the
#     programs are handed as ``params_packed``),
#     ``POOL_ARRAYS`` (the names of ALL the per-token arrays its paged pool
#     holds under the one table, ``("k", "v")`` where it names none: counted
#     with the pool, and what moves K/V out of the pool — handoff, suspend,
#     the host-DRAM tier — refuses a family whose list is not ``k`` and ``v``),
#     ``SLOT_ARRAYS`` (the names of the arrays of its cache that hold state
#     PER SLOT and not by token — a recurrent or state-space state, or the
#     tail a short causal convolution needs of the tokens before, which no
#     block of the pool holds; ``()`` where it names none.  Counted: with a
#     slot's bytes through the family's ``paged_kv_slot_bytes``, and as
#     ``slot_state`` in the memory ledger and the pool's snapshot.  Refused,
#     by name: whatever moves or shares a slot's cache outside the programs
#     — handoff export and import, suspend and preemption, the host-DRAM and
#     peer prefix tiers (``_kv_alone``) — since a slot moved without its state
#     would decode on garbage; the family's ``init_paged_cache`` refuses a
#     mesh, and it brings no ``prefill_suffix_paged`` and no
#     ``decode_slots_spec_paged``: a shared prefix would need the state at the
#     prefix's end and a rejected draft a rewind of it).
# A feature asked of a family without its function is refused at build;
# prefix reuse, chunked prefill and adapters are turned off with a warning.
GENERATIVE_FAMILIES: dict[str, Any] = {
    "llama": llama, "cohere2_moe": cohere2_moe, "keye_vl2": keye_vl2,
    "kimi_k2": kimi_k2, "jamba": jamba, "zaya": zaya,
}


def build_generative_component(
    family: str = "llama",
    *,
    preset: str | None = None,
    cfg: Any = None,
    n_slots: int = 4,
    mesh: Mesh | None = None,
    rng: int = 0,
    dtype: Any = None,
    checkpoint: str | None = None,
    params: Any = None,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    eos_id: int | None = None,
    seq_impl: str = "dense",
    decode_block: int = 16,
    kv_block_size: int = 16,
    kv_blocks: int | None = None,
    queue_max: int | None = None,
    kv_prefix_reuse: bool | None = None,
    prefix_dram_gb: float | None = None,
    top_k: int = 0,
    overlap: bool | None = None,
    spec_draft: int | None = None,
    spec_ngram: int | None = None,
    spec_hist: int = 64,
    spec_method: str | None = None,
    spec_heads: int | None = None,
    spec_heads_path: str | None = None,
    spec_draft_model: str | None = None,
    kv_cache_dtype: str | None = None,
    prefill_chunk: int | None = None,
    decode_kernel: bool | None = None,
    lora_rank: int | None = None,
    lora_slots: int | None = None,
    lora_targets: str | None = None,
    lora_adapters: Any = None,
    adapter: str | None = None,
    pack_class: str | None = None,
    pack_slo_ms: float | None = None,
    conf_signal: bool | None = None,
    embed: bool | None = None,
    **overrides,
):
    """Build a continuous-batching generative graph unit (JAX_GENERATIVE).

    ``kv_block_size`` / ``kv_blocks`` size the paged KV pool (defaults:
    16-token blocks, pool big enough for every slot at full max_seq).
    ``prefix_dram_gb`` (with ``kv_prefix_reuse``) byte-bounds the
    host-DRAM prefix tier: index evictions demote into host memory and
    promote back with one fused scatter (docs/CACHING.md "Tiered prefix
    store"; env fallback ``SCT_PREFIX_DRAM_GB``).
    ``spec_draft``/``spec_ngram``/``spec_hist`` turn on fused
    self-speculative decoding; ``spec_method`` picks the proposer
    (``ngram``/``heads``/``draft``) with ``spec_heads``/``spec_heads_path``
    sizing/loading Medusa-style heads and ``spec_draft_model`` naming the
    co-resident draft geometry (docs/PERFORMANCE.md §6);
    ``kv_cache_dtype="int8"`` stores the paged
    pool quantized with per-(position, head) scales;
    ``prefill_chunk`` enables Sarathi-style chunked prefill interleaved
    with decode and ``decode_kernel`` the fused Pallas paged
    decode-attention kernel (docs/PERFORMANCE.md §7).
    ``lora_rank``/``lora_slots``/``lora_targets``/``lora_adapters`` turn
    on batched multi-LoRA serving (stacked adapter pool, per-slot gather
    fused into decode — docs/MULTITENANT.md); ``adapter`` sets the
    deployment-default adapter a request may override per call.
    ``pack_class`` (``interactive``/``batch``) and ``pack_slo_ms`` set
    this deployment's QoS class and queue-wait SLO band on a packed chip
    (docs/PACKING.md) — read when the engine registers co-resident
    deployments with the device arbiter.
    ``conf_signal`` compiles the cascade confidence signal (per-token
    top-2 logit margin) into the fused decode programs and ``embed`` warms
    the pooled-embedding programs for the /embeddings route
    (docs/GRAPHS.md); env fallbacks ``SCT_CASCADE_CONF_SIGNAL`` /
    ``SCT_EMBED``."""
    from seldon_core_tpu.executor.generation import (
        GenerativeComponent,
        GenerativeModel,
    )

    try:
        mod = GENERATIVE_FAMILIES[family]
    except KeyError:
        raise KeyError(
            f"family {family!r} has no generative contract; "
            f"have {sorted(GENERATIVE_FAMILIES)}"
        ) from None
    if seq_impl not in ("dense", "flash", "ring", "ulysses"):
        # eagerly: a typo would otherwise surface as an opaque KeyError
        # inside jit tracing at warmup
        raise TypeError(
            f"seq_impl must be one of dense/flash/ring/ulysses, got {seq_impl!r}"
        )
    fam = get_family(family)
    if cfg is None:
        cfg = resolve_config(family, preset, **overrides)
    elif overrides:
        raise TypeError(f"unknown generative parameters {sorted(overrides)}")
    params = _resolve_params(
        fam, cfg, params, checkpoint, rng, mesh, dtype=dtype,
        pack=getattr(mod, "pack_params", None),
    )
    model = GenerativeModel(
        cfg,
        params,
        family_mod=mod,
        n_slots=n_slots,
        mesh=mesh,
        param_axes=fam.param_logical_axes(params) if mesh is not None else None,
        dtype=dtype,
        seq_impl=seq_impl,
        name=f"{family}:{preset or 'default'}",
        decode_block=decode_block,
        kv_block_size=kv_block_size,
        kv_blocks=kv_blocks,
        prefix_reuse=kv_prefix_reuse,
        prefix_dram_gb=prefix_dram_gb,
        top_k=top_k,
        spec_draft=spec_draft,
        spec_ngram=spec_ngram,
        spec_hist=spec_hist,
        spec_method=spec_method,
        spec_heads=spec_heads,
        spec_heads_path=spec_heads_path,
        spec_draft_model=spec_draft_model,
        kv_cache_dtype=kv_cache_dtype,
        prefill_chunk=prefill_chunk,
        decode_kernel=decode_kernel,
        lora_rank=lora_rank,
        lora_slots=lora_slots,
        lora_targets=lora_targets,
        lora_adapters=lora_adapters,
        conf_signal=conf_signal,
        embed=embed,
    )
    return GenerativeComponent(
        model,
        max_new_tokens=max_new_tokens,
        temperature=temperature,
        eos_id=eos_id,
        queue_max=queue_max,
        overlap=overlap,
        adapter=adapter,
        pack_class=pack_class,
        pack_slo_ms=pack_slo_ms,
    )
