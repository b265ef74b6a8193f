"""Generative serving: slot-based continuous batching over compiled
prefill/decode programs.

The reference has no generative path at all (its tensors are 2-D
batch×features, reference: engine/.../predictors/AverageCombinerUnit.java:47-49);
this is the TPU-native capability the BASELINE Llama configs require.

Design (vLLM-style slots, XLA-flavored):

* a persistent paged KV cache holds ``n_slots`` independent sequences
  (a family's ``init_paged_cache``; the contract a family meets is listed
  at ``models/registry.py::GENERATIVE_FAMILIES``), each with its own
  position;
* **admission** prefills one request's prompt into a free slot — prompts are
  right-padded to a power-of-two bucket so there is one compiled prefill
  program per bucket, never per length;
* **decode** advances ALL active slots one token per device step with a
  single compiled program (static shapes, per-slot position masks) — new
  requests join between steps without stalling in-flight ones;
* sampling happens on device (``sample_tokens``, fused greedy/top-k): only
  ``(S,)`` token ids cross the host boundary per step, never ``(S, vocab)``
  logits;
* **overlapped pipeline** (docs/PERFORMANCE.md): the fused k-step decode
  program returns its final ``(tokens, active, remaining)`` carry as device
  arrays, so block N+1 dispatches straight from block N's on-device carry:
  *before* the host fetches block N's tokens where nothing could be
  admitted at N's end (a full house of fixed budgets, whoever waits) — the
  host consumes results while the chip is already computing the next block,
  and the per-block host round trip vanishes from the critical path.  Where
  something could, the decision is held while N runs (N+1 goes out as N is
  about to end if nobody came, else at the sync point or with N's tokens in
  hand), so a request that has a slot never waits for a block chained
  ahead of it.  Any host-side state change (admission, deadline reap,
  disconnect) marks the carry dirty and forces one synchronous dispatch
  rebuilt from host state.

``GenerationScheduler`` is the asyncio front: ``submit(prompt) ->
generated ids``; per-request ``max_new_tokens`` / ``temperature`` /
``eos_id``.  ``GenerativeComponent`` adapts it to the graph-unit contract so
an inference graph can contain a generative node (implementation
``JAX_GENERATIVE``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import threading
import time
from functools import partial
from typing import Any, AsyncIterator, Callable

import jax
import numpy as np

from seldon_core_tpu import qos
from seldon_core_tpu.graph.units import GraphUnitError, SeldonComponent
from seldon_core_tpu.obs import (
    RECORDER,
    STAGE_ADMIT_ROUND,
    STAGE_DEVICE_STEP,
    STAGE_FIRST_WRITE,
    STAGE_INGRESS,
    STAGE_SLOT_WAIT,
    STAGE_SYNC_POINT,
    STAGE_TTFT,
    TIMELINE,
)
from seldon_core_tpu.obs.device import DeviceLedger
from seldon_core_tpu.obs.metering import METER
from seldon_core_tpu.obs.stall import StallWatchdog
from seldon_core_tpu.obs.timeline import (
    EVENT_PREEMPT,
    EVENT_RESUME,
    EVENT_SUSPEND,
)
from seldon_core_tpu.ops.flash_attention import TILE_PLANS, admitted_tiles
from seldon_core_tpu.ops.paged_attention import blocks_per_step
from seldon_core_tpu.utils.tracectx import current_trace_id
from seldon_core_tpu.parallel.sharding import (
    DEFAULT_RULES,
    ShardingRules,
    shard_params,
)
from seldon_core_tpu.utils.metrics import DEFAULT as DEFAULT_METRICS

log = logging.getLogger(__name__)


# from this rung up the prefill ladder also holds the midpoint between a
# rung and its double: under it a program is bound by the weights it reads
# once and padding is nearly free; over it a rung's worst padding is
# thousands of tokens of compute-bound work, and a further program to warm
# is the cheaper of the two
HALF_RUNGS_FROM = 4096


def _prefill_buckets(max_seq: int, block: int = 16) -> tuple[int, ...]:
    """The lengths prompts are padded to, one compiled program each:
    doubling from the KV block size (16 at the least) and, from
    ``HALF_RUNGS_FROM`` up, the midpoint before each double (x1.5, rounded
    up to whole KV blocks); the last rung is ``max_seq``."""
    sizes = []
    b = max(16, block)
    while b < max_seq:
        sizes.append(b)
        if b >= HALF_RUNGS_FROM:
            mid = -(-(b + b // 2) // block) * block
            if mid < max_seq:
                sizes.append(mid)
        b *= 2
    sizes.append(max_seq)
    return tuple(sizes)


# placeholder history-seed row for non-speculative prefills: the jitted
# prefill takes the argument either way but never reads it with spec off
_NO_HIST = np.zeros(1, np.int32)


class OutOfKVBlocks(Exception):
    """The paged KV pool cannot reserve the blocks this request needs right
    now; the scheduler holds the request until completions free blocks."""


class GenerativeModel:
    """Compiled slot-cache generation engine for one decoder family.

    Cache buffers are donated to each step, so calls must never interleave;
    an internal lock serializes them (the scheduler already serializes its
    own calls, but warmup may overlap traffic that arrives before /ready).

    What ``family_mod`` must expose, and what is probed for (``pack_params``,
    the family's serving layout, among it), is listed at
    ``models/registry.py::GENERATIVE_FAMILIES``.  A tree the family's
    ``pack_params`` changes here is placed by the family's own
    ``param_logical_axes`` of the packed tree: a caller's ``param_axes``
    fit the tree it handed in, not the one the programs get.
    """

    def __init__(
        self,
        cfg: Any,
        params: Any,
        *,
        family_mod: Any = None,
        n_slots: int = 4,
        mesh: Any = None,
        rules: ShardingRules = DEFAULT_RULES,
        param_axes: Any = None,
        dtype: Any = None,
        seq_impl: str = "dense",
        name: str = "generative",
        decode_block: int = 16,
        driver: Any = None,
        kv_block_size: int = 16,
        kv_blocks: int | None = None,
        prefix_reuse: bool | None = None,
        prefix_dram_gb: float | None = None,
        top_k: int = 0,
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
        conf_signal: bool | None = None,
        embed: bool | None = None,
        memory: Any = None,
    ):
        if family_mod is None:
            from seldon_core_tpu.models import llama as family_mod
        if int(n_slots) < 1:
            # a zero-slot scheduler would park every request forever
            raise GraphUnitError(f"n_slots must be >= 1, got {n_slots}")
        kv_block_size = int(kv_block_size)
        if kv_block_size < 1 or kv_block_size & (kv_block_size - 1):
            raise GraphUnitError(
                f"kv_block_size must be a power of two, got {kv_block_size}"
            )
        if cfg.max_seq % kv_block_size:
            raise GraphUnitError(
                f"max_seq {cfg.max_seq} is not a multiple of kv_block_size "
                f"{kv_block_size}"
            )
        # Multi-host slice: every prefill/decode call is SPMD across the
        # hosts' processes, coordinated through the MultihostDriver (the
        # coordinator leads; engine workers execute the same steps via the
        # follower loop).  Token outputs get replicated so the coordinator
        # reads them locally.
        self._multihost = mesh is not None and any(
            d.process_index != jax.process_index() for d in mesh.devices.flat
        )
        self.driver = driver if self._multihost else None
        if self._multihost and self.driver is None:
            from seldon_core_tpu.executor.multihost import get_driver

            self.driver = get_driver()
            if self.driver is None:
                raise GraphUnitError(
                    f"generative model {name!r}: mesh spans processes but no "
                    "MultihostDriver exists (engine boot initializes it)"
                )
        self.family = family_mod
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.name = name
        self.mesh = mesh
        # decode steps per device dispatch (the scheduler's block size);
        # 1 disables the scan path entirely
        self.decode_block = max(1, int(decode_block))
        # --- device-side decode frontier (docs/PERFORMANCE.md) ---
        # self-speculative n-gram decoding: draft spec_draft tokens per
        # verify pass from a per-slot on-device history ring; greedy output
        # stays bit-identical to the plain path, accepted tokens cost ~one
        # device step for k tokens.  Opt-in: graph param or SCT_SPEC_DRAFT.
        if spec_draft is None:
            spec_draft = int(os.environ.get("SCT_SPEC_DRAFT", "0") or 0)
        if spec_ngram is None:
            spec_ngram = int(os.environ.get("SCT_SPEC_NGRAM", "3") or 3)
        self.spec_draft = max(0, int(spec_draft))
        self.spec_ngram = max(1, int(spec_ngram))
        self.spec_hist = max(8, int(spec_hist))
        if self.spec_draft and self.decode_block <= 1:
            # the draft/verify/accept loop lives inside the fused k-step
            # program; the single-token step has no verify pass to fuse
            # into.  Loud build-time error — silently dropping speculation
            # here used to ship deployments whose operators believed spec
            # was on while every token paid full price.
            raise GraphUnitError(
                f"generative model {name!r}: spec_draft={self.spec_draft} "
                f"requires decode_block > 1, got decode_block="
                f"{self.decode_block} — the draft/verify/accept loop fuses "
                "into the k-step decode program.  Raise decode_block "
                "(graph param or SCT_DECODE_BLOCK) or unset spec_draft "
                "(graph param or SCT_SPEC_DRAFT)."
            )
        # learned speculation (docs/PERFORMANCE.md §6): the draft source.
        #   ngram — PR 7 self-speculation from the per-slot history ring
        #   heads — Medusa-style multi-token heads over the post-ln_f
        #           hidden, drafted inside the same fused step
        #   draft — a co-resident layer-truncated (or preset) draft model
        #           with its own paged KV, greedily unrolled in-program
        # All three feed the SAME verify/accept pass, so greedy output is
        # bit-identical to spec-off regardless of method — only the
        # acceptance rate differs.
        if spec_method is None:
            spec_method = os.environ.get("SCT_SPEC_METHOD", "") or "ngram"
        spec_method = str(spec_method).lower()
        if spec_method not in ("ngram", "heads", "draft"):
            raise GraphUnitError(
                f"spec_method must be 'ngram', 'heads', or 'draft', got "
                f"{spec_method!r}"
            )
        self.spec_method = spec_method if self.spec_draft else None
        if spec_heads is None:
            spec_heads = int(os.environ.get("SCT_SPEC_HEADS", "0") or 0)
        if spec_heads_path is None:
            spec_heads_path = os.environ.get("SCT_SPEC_HEADS_PATH") or None
        if spec_draft_model is None:
            spec_draft_model = os.environ.get("SCT_SPEC_DRAFT_MODEL") or None
        self.spec_heads = 0
        self.spec_heads_path = None
        self._draft_geom: tuple | None = None
        if self.spec_draft:
            if not hasattr(family_mod, "decode_slots_spec_paged"):
                raise GraphUnitError(
                    f"generative family {family_mod.__name__} has no "
                    "decode_slots_spec_paged; speculative decoding needs the "
                    "fused verify step"
                )
            if self.spec_hist <= self.spec_ngram + self.spec_draft:
                raise GraphUnitError(
                    f"spec_hist {self.spec_hist} must exceed spec_ngram "
                    f"{self.spec_ngram} + spec_draft {self.spec_draft}"
                )
            if self.spec_method == "heads":
                self.spec_heads = max(self.spec_draft, int(spec_heads or 0))
                self.spec_heads_path = spec_heads_path
                if not hasattr(family_mod, "apply_medusa_heads"):
                    raise GraphUnitError(
                        f"generative family {family_mod.__name__} has no "
                        "apply_medusa_heads; spec_method='heads' needs the "
                        "Medusa head block"
                    )
            elif self.spec_method == "draft":
                self._draft_geom = self._parse_draft_model(
                    spec_draft_model, name
                )
        # tokens a slot can emit per fused decode step (verify width)
        self._tps = 1 + self.spec_draft
        # cascade confidence signal (docs/GRAPHS.md): per-step top-2 logit
        # margin computed INSIDE the fused decode programs and fetched WITH
        # the block's tokens, so escalation decisions cost zero extra host
        # syncs.  STATIC (a program-cache key via _program_config):
        # deployments with and without the signal never share a compiled
        # step.  Opt-in via the ``conf_signal`` graph parameter or
        # SCT_CASCADE_CONF_SIGNAL=1.
        if conf_signal is None:
            conf_signal = os.environ.get("SCT_CASCADE_CONF_SIGNAL", "0") == "1"
        self.conf_signal = bool(conf_signal)
        # embeddings path (docs/GRAPHS.md): mean-pooled final hidden states
        # via a pure forward — no KV write, no slot.  The flag only gates
        # warmup compilation of the per-bucket embed programs;
        # embed_dispatch works whenever the family provides embed_pooled.
        # Opt-in via the ``embed`` graph parameter or SCT_EMBED=1.
        if embed is None:
            embed = os.environ.get("SCT_EMBED", "0") == "1"
        self.embed_enabled = bool(embed) and hasattr(family_mod, "embed_pooled")
        # int8 paged-KV quantization: ~2x sequences per HBM byte; opt-in
        # via the kv_cache_dtype graph param or SCT_KV_DTYPE=int8
        if kv_cache_dtype is None:
            kv_cache_dtype = os.environ.get("SCT_KV_DTYPE") or None
        if kv_cache_dtype in ("", "auto", "bf16", "bfloat16", "float32", "fp32"):
            kv_cache_dtype = None  # pool float dtype — the default layout
        if kv_cache_dtype not in (None, "int8"):
            raise GraphUnitError(
                f"kv_cache_dtype must be 'int8' or unset, got {kv_cache_dtype!r}"
            )
        self.kv_dtype: str | None = kv_cache_dtype
        # chunked prefill (Sarathi-style, docs/PERFORMANCE.md §7): split an
        # admission's prompt into fixed-size chunks so the scheduler can
        # interleave one chunk per decode sync point — a long prompt then
        # bounds in-flight streams' inter-token latency by ONE chunk's
        # latency instead of the whole prefill.  Chunk boundaries land on
        # KV-block boundaries (rounded up); each chunk past the first runs
        # the suffix-prefill program over the slot's own already-written
        # blocks, so the written K/V — and the first sampled token — are
        # bit-identical to the monolithic prefill.  Opt-in per deployment
        # via the ``prefill_chunk`` graph parameter or SCT_PREFILL_CHUNK.
        if prefill_chunk is None:
            prefill_chunk = int(os.environ.get("SCT_PREFILL_CHUNK", "0") or 0)
        prefill_chunk = max(0, int(prefill_chunk))
        if prefill_chunk:
            prefill_chunk = min(
                -(-prefill_chunk // kv_block_size) * kv_block_size,
                cfg.max_seq,
            )
            if not hasattr(family_mod, "prefill_suffix_paged"):
                log.warning(
                    "generative model %r: family %s has no "
                    "prefill_suffix_paged; chunked prefill disabled",
                    name, family_mod,
                )
                prefill_chunk = 0
        self.prefill_chunk = prefill_chunk
        # Pallas paged decode-attention kernel (ops/paged_attention.py):
        # fuses block-table gather + int8 dequant + attention over the
        # paged pool inside the compiled decode step, and reads only the
        # blocks a live slot holds.  Single-device only — the kernel does
        # not partition over a mesh axis — compiled by Mosaic on the chip,
        # interpret-mode on CPU so tier-1 covers it.  Unset (the
        # ``decode_kernel`` graph parameter and SCT_DECODE_KERNEL both),
        # the program chooses: the kernel where the pool is on one device,
        # the family's decode takes ``kernel=`` and the backend compiles
        # Pallas; else the XLA gather.  Set, it is honoured, and asking
        # for the kernel where it cannot run is a build error: a
        # deployment whose operator believes the kernel is on must never
        # be quietly served by the XLA gather path.
        import inspect

        _dsp = getattr(family_mod, "decode_slots_paged", None)
        _takes_kernel = _dsp is not None and (
            "kernel" in inspect.signature(_dsp).parameters
        )
        if decode_kernel is None and os.environ.get("SCT_DECODE_KERNEL"):
            decode_kernel = os.environ["SCT_DECODE_KERNEL"] == "1"
        if decode_kernel is None:
            decode_kernel = (
                mesh is None and _takes_kernel
                and jax.default_backend() != "cpu"
            )
        decode_kernel = bool(decode_kernel)
        if decode_kernel:
            if not _takes_kernel:
                raise GraphUnitError(
                    f"generative model {name!r}: decode_kernel is set but "
                    f"family {family_mod.__name__} has no kernel decode "
                    "path.  Unset decode_kernel (graph param or "
                    "SCT_DECODE_KERNEL)."
                )
            if mesh is not None:
                raise GraphUnitError(
                    f"generative model {name!r}: decode_kernel is set with "
                    "a mesh, and the Pallas paged decode kernel is "
                    "single-device (it does not partition over a mesh "
                    "axis).  Unset decode_kernel (graph param or "
                    "SCT_DECODE_KERNEL) or drop the mesh."
                )
        self.decode_kernel = decode_kernel
        # what the decode read has to touch against what its window spans,
        # in pool blocks, summed over decode dispatches from what the host
        # holds (no device sync): Σ over active slots of the blocks up to
        # the position ceiling, and slots x window / block
        self.kv_blocks_live = 0
        self.kv_blocks_window = 0
        # batched multi-LoRA serving (docs/MULTITENANT.md): a stacked
        # (n_layers, lora_slots, ...) adapter pool in HBM, gathered per
        # generation slot INSIDE the fused prefill/decode programs —
        # serving N fine-tune variants of one base from one compiled step.
        # Row 0 is the reserved null adapter (all zeros): adapter-less
        # requests are bit-identical to a lora-off build.  (lora_rank,
        # lora_slots) are STATIC (program cache keys); which named adapter
        # occupies which row is host bookkeeping (executor/lora.py) so
        # registration/eviction never recompiles mid-traffic.
        if lora_rank is None:
            lora_rank = int(os.environ.get("SCT_LORA_RANK", "0") or 0)
        self.lora_rank = max(0, int(lora_rank))
        if lora_slots is None:
            lora_slots = int(os.environ.get("SCT_LORA_SLOTS", "8") or 8)
        if lora_targets is None:
            lora_targets = os.environ.get("SCT_LORA_TARGETS", "qkvo")
        if self.lora_rank and not hasattr(family_mod, "init_lora_params"):
            log.warning(
                "generative model %r: family %s has no init_lora_params; "
                "multi-LoRA serving disabled", name, family_mod,
            )
            self.lora_rank = 0
        self.lora_slots = max(2, int(lora_slots)) if self.lora_rank else 0
        if self.lora_rank:
            targets = tuple(family_mod.LORA_ATTN_TARGETS)
            lt = str(lora_targets or "qkvo").lower()
            if lt in ("qkvo+mlp", "all", "mlp"):
                targets = targets + tuple(family_mod.LORA_MLP_TARGETS)
            elif lt not in ("qkvo", ""):
                raise GraphUnitError(
                    f"lora_targets must be 'qkvo' or 'qkvo+mlp', got "
                    f"{lora_targets!r}"
                )
            self.lora_targets = targets
        else:
            self.lora_targets = ()

        if dtype is not None:
            import jax.numpy as jnp

            def _cast(p):
                dt = getattr(p, "dtype", None) or np.asarray(p).dtype
                return p.astype(dtype) if jnp.issubdtype(dt, jnp.floating) else p

            params = jax.tree.map(_cast, params)
        # the family's serving layout (``pack_params``, optional), before a
        # program is traced.  The registry packs what it makes itself inside
        # the weights' own init; a tree handed in canonical is packed here
        self._params_packed: dict = {}
        pack = getattr(family_mod, "pack_params", None)
        if pack is not None:
            packed = pack(params)
            if packed is not params and param_axes is not None:
                param_axes = family_mod.param_logical_axes(packed)
            params = packed
            for path in family_mod.PACKED:
                leaf = params
                for key in path.split("/"):
                    leaf = leaf[key]
                self._params_packed[path] = list(leaf.shape)
        if mesh is not None:
            if param_axes is not None:
                params = shard_params(params, mesh, param_axes, rules)
            else:
                from jax.sharding import NamedSharding, PartitionSpec as P

                params = jax.device_put(params, NamedSharding(mesh, P()))
        else:
            params = jax.device_put(params)
        self.params = params

        # stacked LoRA adapter pool: device tensors + host registry.  The
        # pool rides every prefill/decode dispatch as a plain (non-donated)
        # argument like the base params; factors are small (rank r), so it
        # replicates across a mesh rather than sharding.
        self.lora_pool = None
        self._lora = None
        self.lora_bytes = 0
        self._slot_aidx = np.zeros(self.n_slots, np.int32)
        self._slot_salt: dict[int, bytes] = {}
        if self.lora_rank:
            lt = family_mod.init_lora_params(
                cfg, self.lora_slots, self.lora_rank,
                targets=self.lora_targets,
                dtype=dtype if dtype is not None else np.float32,
            )
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                lt = jax.device_put(lt, NamedSharding(mesh, P()))
            else:
                lt = jax.device_put(lt)
            self._lora = lt
            self.lora_bytes = sum(
                int(x.nbytes) for x in jax.tree.leaves(lt)
            )
            from seldon_core_tpu.executor.lora import AdapterPool

            self.lora_pool = AdapterPool(
                self.lora_slots, self.lora_rank,
                writer=self._lora_write, name=name,
            )

        # paged KV pool: block 0 is the reserved garbage sink for inactive
        # slots' fixed-shape writes (models/llama.py decode_slots_paged);
        # default pool still admits every slot at full max_seq, an operator
        # shrinks it (or raises n_slots) to oversubscribe against typical
        # lengths instead of worst-case ones
        self.kv_block_size = kv_block_size
        self.max_blocks_per_slot = cfg.max_seq // kv_block_size
        if kv_blocks is None:
            kv_blocks = 1 + self.n_slots * self.max_blocks_per_slot
        self.kv_blocks = int(kv_blocks)
        min_blocks = 1 + self.max_blocks_per_slot
        if self.kv_blocks < min_blocks:
            raise GraphUnitError(
                f"kv_blocks {self.kv_blocks} cannot hold even one max_seq "
                f"request (+sink); need >= {min_blocks}"
            )
        self._free_blocks: list[int] = list(range(1, self.kv_blocks))
        self._slot_blocks: dict[int, list[int]] = {}
        # KV prefix reuse (cache/prefix.py; docs/CACHING.md): a radix index
        # over token-id prefixes -> ref-counted blocks in this pool, so
        # prompts sharing a prefix (system prompts, few-shot preambles)
        # prefill only their novel suffix.  Opt-in per deployment via the
        # ``kv_prefix_reuse`` graph parameter or SCT_CACHE_PREFIX=1; needs
        # the family to provide the suffix-prefill program.
        if prefix_reuse is None:
            prefix_reuse = os.environ.get("SCT_CACHE_PREFIX", "0") == "1"
        if prefix_reuse and not hasattr(family_mod, "prefill_suffix_paged"):
            log.warning(
                "generative model %r: family %s has no prefill_suffix_paged; "
                "KV prefix reuse disabled", name, family_mod,
            )
            prefix_reuse = False
        self.prefix_index = None
        # host-DRAM prefix tier (cache/tiers.py; docs/CACHING.md "Tiered
        # prefix store"): index evictions demote their blocks into a
        # byte-bounded host store instead of dropping them; a later radix
        # match promotes them back with one fused scatter.  Opt-in via the
        # ``prefix_dram_gb`` graph parameter or SCT_PREFIX_DRAM_GB.
        self.host_store = None
        if prefix_reuse:
            from seldon_core_tpu.cache.prefix import PrefixIndex

            self.prefix_index = PrefixIndex(kv_block_size)
            if prefix_dram_gb is None:
                prefix_dram_gb = float(
                    os.environ.get("SCT_PREFIX_DRAM_GB", "0") or 0
                )
            dram_bytes = int(float(prefix_dram_gb) * (1 << 30))
            if dram_bytes > 0 and self._multihost:
                # demotion needs a coordinator-side device fetch of the
                # victim blocks, which a multi-host slice cannot address
                # (same constraint as export_slot_kv)
                log.warning(
                    "generative model %r: host-DRAM prefix tier is not "
                    "supported on a multi-host slice; disabled", name,
                )
            elif dram_bytes > 0:
                from seldon_core_tpu.cache.tiers import HostPrefixStore

                self.host_store = HostPrefixStore(
                    kv_block_size, dram_bytes, on_bytes=self._note_dram_bytes
                )
        # peer-replica prefix tier bookkeeping: chain-level keys installed
        # from a peer pull that no admission has hit yet (the first hit is
        # credited to the peer tier, later ones to plain HBM), plus the
        # pull/serve counters for the per-tier telemetry
        self._peer_chains: set = set()
        self.peer_hits = 0  # admissions whose prefix came from a peer pull
        self.peer_installs = 0  # chain levels installed from peer pulls
        self.peer_serves = 0  # chains exported to pulling peers
        self.dram_hits = 0  # admissions that promoted >=1 level from DRAM
        # per-slot reuse bookkeeping: the prompt (for index insertion at
        # release) and how many leading blocks were matched (shared refs)
        self._slot_prompt: dict[int, np.ndarray] = {}
        self._slot_matched: dict[int, int] = {}
        # which tier satisfied the slot's prefix match (hbm/dram/peer/none)
        # + how many levels the admission promoted from DRAM — stamped
        # into the timeline admit event via reservation_snapshot
        self._slot_tier: dict[int, str] = {}
        self._slot_promoted: dict[int, int] = {}
        # full table row per reserved slot (shared-prefix blocks included):
        # the disagg KV export reads the slot's prompt blocks through it
        self._slot_row: dict[int, np.ndarray] = {}

        # the pool's per-token arrays, under the one table: ALL of them, as
        # the family names them (``family.POOL_ARRAYS``; ``k`` and ``v``
        # where it names none).  The pool's bytes, its reported dtype and its
        # placement read this list; what moves K/V out of the pool carries k
        # and v alone and refuses, by name, a family whose list is anything
        # else (:meth:`_kv_alone`)
        pool_names = self._pool_names = tuple(
            getattr(family_mod, "POOL_ARRAYS", ("k", "v"))
        )
        # the cache's per-SLOT arrays (``family.SLOT_ARRAYS``; none where it
        # names none): state that is not a row a token and lies in no block
        # of the pool.  Counted with a slot's bytes and as ``slot_state``;
        # whatever moves or shares a slot's cache refuses them by name too
        self._slot_names = tuple(getattr(family_mod, "SLOT_ARRAYS", ()))
        cache_dtype = dtype if dtype is not None else np.float32
        # a pool that is placed over a mesh keeps its kv-head axis to be
        # split by; on one device a row holds its heads side by side
        # (models/llama.py::init_paged_cache)
        pool_axes = {"kv_sharded": True} if mesh is not None else {}
        if self.kv_dtype:
            try:
                cache = family_mod.init_paged_cache(
                    cfg, self.n_slots, self.kv_blocks, kv_block_size,
                    dtype=cache_dtype, kv_dtype=self.kv_dtype, **pool_axes,
                )
            except TypeError:
                raise GraphUnitError(
                    f"generative family {family_mod.__name__} does not "
                    f"support kv_cache_dtype={self.kv_dtype!r}"
                ) from None
        else:
            cache = family_mod.init_paged_cache(
                cfg, self.n_slots, self.kv_blocks, kv_block_size,
                dtype=cache_dtype, **pool_axes,
            )
        if self.spec_draft:
            # per-slot history ring for the on-device n-gram proposer:
            # token at position p lives at hist[slot, p % H]
            import jax.numpy as jnp

            cache["hist"] = jnp.zeros(
                (self.n_slots, self.spec_hist), jnp.int32
            )
        # learned proposer state (docs/PERFORMANCE.md §6).  _spec_ps rides
        # every decode-k dispatch as a plain (non-donated) argument like
        # the base params: the Medusa head block for 'heads', the draft
        # model's weights for 'draft', None for 'ngram'.
        self._spec_ps = None
        self._draft_cfg = None
        self.spec_heads_bytes = 0
        self.draft_weight_bytes = 0
        self.draft_kv_bytes = 0
        if self.spec_method == "heads":
            import jax.numpy as jnp

            if self.spec_heads_path:
                # trained heads from an .npz checkpoint (executor/checkpoint)
                from seldon_core_tpu.executor.checkpoint import load_params

                heads = load_params(self.spec_heads_path)
                w1 = heads.get("w1") if isinstance(heads, dict) else None
                hd = heads.get("head") if isinstance(heads, dict) else None
                if (
                    w1 is None or hd is None
                    or np.shape(w1)[:1] != np.shape(hd)[:1]
                    or np.shape(w1)[0] < self.spec_draft
                    or np.shape(hd)[-1] != cfg.vocab_size
                ):
                    raise GraphUnitError(
                        f"generative model {name!r}: Medusa checkpoint "
                        f"{self.spec_heads_path!r} must hold w1 (K, E, E) + "
                        f"head (K, E, V) with K >= spec_draft="
                        f"{self.spec_draft} and V == {cfg.vocab_size}"
                    )
                self.spec_heads = int(np.shape(w1)[0])
                heads = {
                    "w1": jnp.asarray(w1, cache_dtype),
                    "head": jnp.asarray(hd, cache_dtype),
                }
            else:
                # synthesized from the base lm_head: untrained heads draft
                # "repeat the argmax" — harmless (verify still emits the
                # real tokens) and enough for the pinned-equal matrix
                heads = family_mod.init_medusa_heads(
                    jax.random.PRNGKey(0), cfg, self.spec_heads,
                    base_head=params["head"], dtype=cache_dtype,
                )
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                heads = jax.device_put(heads, NamedSharding(mesh, P()))
            else:
                heads = jax.device_put(heads)
            self._spec_ps = heads
            self.spec_heads_bytes = sum(
                int(x.nbytes) for x in jax.tree.leaves(heads)
            )
            # per-slot post-ln_f hidden of the LAST emitted token — the
            # heads' draft input, refreshed by every prefill/verify pass
            cache["hlast"] = jnp.zeros(
                (self.n_slots, cfg.hidden), cache_dtype
            )
        elif self.spec_method == "draft":
            import dataclasses

            import jax.numpy as jnp

            kind, geo = self._draft_geom
            if kind == "truncate":
                # the target's own first-N layers: sliced layer stacks are
                # fresh (billed) arrays, everything else shared by ref
                dcfg = dataclasses.replace(cfg, n_layers=int(geo))
                dparams = family_mod.truncate_params(params, int(geo))
                self.draft_weight_bytes = sum(
                    int(x.nbytes) for x in jax.tree.leaves(dparams["layers"])
                )
            else:
                from seldon_core_tpu.models.registry import resolve_config

                fam_name = family_mod.__name__.rsplit(".", 1)[-1]
                dcfg = resolve_config(fam_name, geo, max_seq=cfg.max_seq)
                if dcfg.vocab_size != cfg.vocab_size:
                    raise GraphUnitError(
                        f"generative model {name!r}: draft preset {geo!r} "
                        f"vocab {dcfg.vocab_size} != target vocab "
                        f"{cfg.vocab_size}; drafts would index a different "
                        "token space"
                    )
                dparams = family_mod.init_params(
                    jax.random.PRNGKey(0), dcfg,
                )
                if dtype is not None:
                    dparams = jax.tree.map(_cast, dparams)
                if mesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec as P

                    dparams = jax.device_put(
                        dparams, NamedSharding(mesh, P())
                    )
                else:
                    dparams = jax.device_put(dparams)
                self.draft_weight_bytes = sum(
                    int(x.nbytes) for x in jax.tree.leaves(dparams)
                )
            self._spec_ps = dparams
            self._draft_cfg = dcfg
            # draft paged KV: same pool geometry, STATIC per-slot block
            # ownership — slot i owns [1 + i*mb, 1 + (i+1)*mb), block 0 the
            # sink.  No allocator, no refcounts: zero leaked draft blocks
            # by construction, and drift after import/resume self-heals
            # (the verify pass re-syncs d_pos and the next draft step
            # rewrites the row).
            mbd = dcfg.max_seq // kv_block_size
            d_blocks = 1 + self.n_slots * mbd
            dkv = family_mod.init_paged_cache(
                dcfg, self.n_slots, d_blocks, kv_block_size,
                dtype=cache_dtype, **pool_axes,
            )
            cache["d_k"] = dkv["k"]
            cache["d_v"] = dkv["v"]
            cache["d_pos"] = dkv["pos"]
            cache["d_table"] = jnp.asarray(
                1 + np.arange(self.n_slots * mbd, dtype=np.int32).reshape(
                    self.n_slots, mbd
                )
            )
            self.draft_kv_bytes = int(dkv["k"].nbytes) + int(dkv["v"].nbytes)
        if mesh is not None:
            # KV heads ride the tp axis like the attention weights; blocks
            # and rows stay local (decode is latency-, not FLOP-bound)
            from jax.sharding import NamedSharding, PartitionSpec as P

            kv_sh = NamedSharding(mesh, P(None, None, None, "tp", None))
            rep = NamedSharding(mesh, P())
            placed = {
                **{n: jax.device_put(cache[n], kv_sh) for n in pool_names},
                "pos": jax.device_put(cache["pos"], rep),
                "table": jax.device_put(cache["table"], rep),
            }
            if "k_scale" in cache:
                sc_sh = NamedSharding(mesh, P(None, None, None, "tp"))
                placed["k_scale"] = jax.device_put(cache["k_scale"], sc_sh)
                placed["v_scale"] = jax.device_put(cache["v_scale"], sc_sh)
            if "hist" in cache:
                placed["hist"] = jax.device_put(cache["hist"], rep)
            if "hlast" in cache:
                placed["hlast"] = jax.device_put(cache["hlast"], rep)
            if "d_k" in cache:
                # draft KV shards like the target pool when its head count
                # divides the tp axis (always true for truncate — same
                # heads); odd preset geometries replicate
                tp = int(mesh.shape.get("tp", 1))
                d_sh = (
                    kv_sh
                    if self._draft_cfg.n_kv_heads % max(tp, 1) == 0
                    else rep
                )
                placed["d_k"] = jax.device_put(cache["d_k"], d_sh)
                placed["d_v"] = jax.device_put(cache["d_v"], d_sh)
                placed["d_pos"] = jax.device_put(cache["d_pos"], rep)
                placed["d_table"] = jax.device_put(cache["d_table"], rep)
            cache = placed
        else:
            # commit the cache to its device, as the mesh path's explicit
            # shardings do.  reset() and the KV import replace entries with
            # device_put(..., sharding) — committed arrays — and jit keys
            # its programs on committedness: a cache that starts out
            # uncommitted makes every warmed program compile a second time
            # on its first serving call, after /ready.
            cache = jax.device_put(
                cache, next(iter(jax.tree.leaves(self.params)[0].devices()))
            )
        self._cache = cache
        if self.host_store is not None:
            self._kv_alone("the host-DRAM prefix tier (prefix_dram_gb)")
        self.prefill_buckets = _prefill_buckets(cfg.max_seq, kv_block_size)

        fam = family_mod

        # fused on-device sampling: greedy or top-k, inside the compiled
        # step — the host never sees logits.  top_k is STATIC (one program
        # per value), validated here so a typo fails at build, not in jit.
        self.top_k = int(top_k or 0)
        if self.top_k:
            import inspect

            if "top_k" not in inspect.signature(fam.sample_tokens).parameters:
                raise GraphUnitError(
                    f"generative family {fam.__name__} does not support "
                    "on-device top-k sampling (sample_tokens lacks top_k)"
                )
            import functools

            _sample = functools.partial(fam.sample_tokens, top_k=self.top_k)
        else:
            _sample = fam.sample_tokens

        def _replicate(x):
            """Token outputs replicate across the slice so the coordinator
            can read the full result locally (no-op single-host)."""
            # topology is fixed per process and the program caches are
            # per-instance, so two configs differing in _multihost can
            # never share a compiled program
            # sct: program-key-ok fixed per-process topology
            if not self._multihost:
                return x
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))

        spec_d = self.spec_draft
        spec_n = self.spec_ngram
        spec_H = self.spec_hist
        # STATIC proposer selection (a _program_config member): the three
        # methods are different compiled programs, never shared
        spec_m = self.spec_method
        # _draft_cfg is fully determined by _draft_geom (a _program_config
        # member) plus the base model config — same geometry, same draft
        # sct: program-key-ok _draft_geom pins it
        dcfg = self._draft_cfg
        # static decode-attention implementation choice: the Pallas kernel
        # path when enabled, the XLA gather path otherwise (both ride the
        # program cache keys via _program_config)
        dec_kw = {"kernel": True} if self.decode_kernel else {}
        # the pool's kv-head axis rides tp (placement above); the model
        # picks its pool read by it (models/llama.py::_pool_read).  A
        # draft pool whose heads do not divide tp was placed replicated
        tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        pool_kw = {"kv_sharded": True} if tp > 1 else {}
        dec_kw.update(pool_kw)
        # cascade confidence: static branch — programs with the signal emit
        # one extra (rows, S) float32 output riding the existing fetch
        conf_on = self.conf_signal

        def _conf_margin(logits):
            """Top-2 logit margin per row: equal to the top-2 LOGPROB
            margin (softmax is shift-invariant), so thresholds written in
            logprob space apply directly.  Runs inside the compiled step —
            the host never sees logits."""
            import jax.numpy as jnp

            top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
            return top2[..., 0] - top2[..., 1]

        def _prefill(params, tokens, length, slot, blocks, temperature, seed,
                     hist_seed, aid, lora, cache):
            if spec_m == "heads":
                # stash the post-ln_f hidden at the sampled position: the
                # Medusa heads draft from it at the first decode block
                logits, cache, hid = fam.prefill_slot_paged(
                    params, tokens, length, slot, blocks, cache, cfg,
                    mesh=mesh, seq_impl=seq_impl, lora=lora, adapter_id=aid,
                    return_hidden=True,
                )
                cache["hlast"] = cache["hlast"].at[slot].set(
                    hid.astype(cache["hlast"].dtype)
                )
            else:
                logits, cache = fam.prefill_slot_paged(
                    params, tokens, length, slot, blocks, cache, cfg,
                    mesh=mesh, seq_impl=seq_impl, lora=lora, adapter_id=aid,
                )
            key = jax.random.PRNGKey(seed)
            tok = _sample(logits[None], temperature[None], key)[0]
            if spec_d:
                # seed the proposer ring: prompt tail (host-computed) plus
                # the first sampled token at its position's row
                row = hist_seed.at[length % spec_H].set(tok)
                cache["hist"] = cache["hist"].at[slot].set(row)
            return _replicate(tok), cache

        def _decode(window):
            def fn(params, tokens, active, temperature, seed, aid, lora, cache):
                logits, cache = fam.decode_slots_paged(
                    params, tokens, cache, active, cfg, window=window,
                    lora=lora, adapter_ids=aid, **dec_kw,
                )
                key = jax.random.PRNGKey(seed)
                toks = _sample(logits, temperature, key)
                if conf_on:
                    return (
                        _replicate(toks),
                        _replicate(_conf_margin(logits)),
                        cache,
                    )
                return _replicate(toks), cache

            return fn

        def _decode_k(k, window):
            """k decode steps in ONE device dispatch (lax.scan), with
            per-slot eos/budget early exit ON DEVICE.  One host round trip
            and one dispatch overhead per k tokens instead of per token.

            Returns the per-step ``(k, S)`` tokens/active-mask AND the final
            ``(tokens, active, remaining)`` carry as device arrays: the
            overlapped pipeline feeds the carry straight into the next
            block's dispatch so steady-state decode never waits on a host
            round trip (the carry args are donated — each block consumes
            its predecessor's buffers in place)."""
            from jax import lax
            import jax.numpy as jnp

            def fn(params, tokens, active, temperature, seed, eos, remaining,
                   aid, lora, spec_ps, cache):
                del spec_ps  # uniform decode-k signature; ngram/off use None
                base_key = jax.random.PRNGKey(seed)

                def body(carry, i):
                    tokens, active, remaining, cache = carry
                    # NOTE: no all-inactive early-exit cond here.  A
                    # lax.cond whose false branch returns the carry verbatim
                    # cannot alias the cache buffers of both branches, so
                    # XLA inserts a full cache copy EVERY step — hundreds of
                    # MB of pure overhead per token that dwarfs the FLOPs
                    # the cond occasionally skips (decode is bandwidth-bound;
                    # inactive slots' math is already masked).
                    logits, cache = fam.decode_slots_paged(
                        params, tokens, cache, active, cfg, window=window,
                        lora=lora, adapter_ids=aid, **dec_kw,
                    )
                    key = jax.random.fold_in(base_key, i)
                    toks = _sample(logits, temperature, key)
                    toks = jnp.where(active, toks, tokens)
                    remaining = jnp.where(active, remaining - 1, remaining)
                    done = (toks == eos) | (remaining <= 0)
                    active2 = active & ~done
                    ys = (
                        (toks, active, _conf_margin(logits))
                        if conf_on
                        else (toks, active)
                    )
                    return (toks, active2, remaining, cache), ys

                (tokens, active, remaining, cache), ys = lax.scan(
                    body, (tokens, active, remaining, cache), jnp.arange(k)
                )
                return tuple(_replicate(y) for y in ys) + (
                    _replicate(tokens),
                    _replicate(active),
                    _replicate(remaining),
                    cache,
                )

            return fn

        def _decode_k_spec(k, window):
            """k fused SPECULATIVE verify passes in one device dispatch
            (docs/PERFORMANCE.md): each pass drafts ``spec_draft`` tokens
            — from the slot's on-device history ring (``ngram``), from the
            Medusa head block over the last verified hidden (``heads``), or
            by greedily unrolling the co-resident draft model over its own
            paged KV (``draft``) — scores current + drafts in one batched
            model call, accepts the longest agreeing prefix, and emits
            1..(1+draft) tokens — so accepted tokens cost ~one device step
            apiece-divided-by-acceptance.  Same contract as
            :func:`_decode_k` with ``k * (1 + draft)`` result rows: the
            second output is the per-row EMITTED mask (exactly the role
            the was-active mask plays in the plain block), and the
            ``(tokens, active, remaining)`` carry stays device-resident
            for the overlapped pipeline.  The proposer feeds ONLY the
            draft lanes — row 0 of a pass is bit-identical to the
            non-speculative program's output, so greedy output never
            depends on the method (only the acceptance rate does)."""
            from jax import lax
            import jax.numpy as jnp

            from seldon_core_tpu.executor.speculative import (
                propose_heads,
                propose_ngram,
            )

            L = 1 + spec_d

            def fn(params, tokens, active, temperature, seed, eos, remaining,
                   aid, lora, spec_ps, cache):
                base_key = jax.random.PRNGKey(seed)
                S = tokens.shape[0]
                offs = jnp.arange(L)[None, :]
                slot_col = jnp.arange(S)[:, None]

                def body(carry, i):
                    tokens, active, remaining, cache = carry
                    hist = cache["hist"]
                    pos = cache["pos"]
                    if spec_m == "heads":
                        head_logits = fam.apply_medusa_heads(
                            spec_ps, cache["hlast"]
                        )
                        drafts = propose_heads(head_logits, draft=spec_d)
                    elif spec_m == "draft":
                        # greedy unroll of the co-resident draft model over
                        # its own paged KV (block-granular view of the same
                        # donated cache dict).  Each step writes the row it
                        # consumed, so draft KV rows < d_pos always hold
                        # the TRUE sequence (accepted prefix) — and the
                        # post-verify d_pos re-sync below heals any drift
                        # from imports/resume by letting the next unroll
                        # rewrite from the synced row.
                        dc = {
                            "k": cache["d_k"], "v": cache["d_v"],
                            "pos": cache["d_pos"], "table": cache["d_table"],
                        }

                        def dbody(dcarry, _):
                            cur, dc = dcarry
                            dlogits, dc = fam.decode_slots_paged(
                                spec_ps, cur, dc, active, dcfg,
                                window=window,
                                **(dec_kw if dcfg.n_kv_heads % tp == 0 else {}),
                            )
                            nxt = jnp.argmax(dlogits, axis=-1).astype(
                                jnp.int32
                            )
                            return (nxt, dc), nxt

                        (_, dc), drafts_t = lax.scan(
                            dbody, (tokens, dc), None, length=spec_d
                        )
                        drafts = drafts_t.T
                        cache["d_k"], cache["d_v"] = dc["k"], dc["v"]
                        cache["d_pos"] = dc["pos"]
                    else:
                        drafts = propose_ngram(
                            hist, pos, tokens, n=spec_n, draft=spec_d
                        )
                    qtoks = jnp.concatenate([tokens[:, None], drafts], axis=1)
                    # writes past the slot's reserved blocks (drafts beyond
                    # the remaining budget) route to the sink block
                    qvalid = active[:, None] & (offs < remaining[:, None])
                    if spec_m == "heads":
                        logits, cache, hid = fam.decode_slots_spec_paged(
                            params, qtoks, cache, active, qvalid, cfg,
                            window=window, lora=lora, adapter_ids=aid,
                            return_hidden=True, **dec_kw,
                        )
                    else:
                        logits, cache = fam.decode_slots_spec_paged(
                            params, qtoks, cache, active, qvalid, cfg,
                            window=window, lora=lora, adapter_ids=aid,
                            **dec_kw,
                        )
                    key = jax.random.fold_in(base_key, i)
                    V = logits.shape[-1]
                    out = _sample(
                        logits.reshape(S * L, V),
                        jnp.repeat(temperature, L),
                        key,
                    ).reshape(S, L)
                    # accept the longest prefix where the draft agrees with
                    # what the model actually emits
                    agree = (drafts == out[:, :-1]).astype(jnp.int32)
                    n_acc = jnp.cumprod(agree, axis=1).sum(axis=1)
                    base = qvalid & (offs <= n_acc[:, None])
                    eos_here = base & (eos[:, None] >= 0) & (out == eos[:, None])
                    eos_before = (
                        jnp.cumsum(eos_here.astype(jnp.int32), axis=1)
                        - eos_here.astype(jnp.int32)
                    )
                    emitted = base & (eos_before == 0)
                    n_em = emitted.sum(axis=1)
                    last = jnp.maximum(n_em - 1, 0)
                    new_cur = jnp.take_along_axis(out, last[:, None], axis=1)[:, 0]
                    tokens = jnp.where(active, new_cur, tokens)
                    remaining = jnp.where(active, remaining - n_em, remaining)
                    active2 = active & ~eos_here.any(axis=1) & (remaining > 0)
                    # scatter emitted tokens into the history ring (their
                    # positions pos+1 .. pos+n_em) and advance pos
                    widx = (pos[:, None] + 1 + offs) % spec_H
                    old = jnp.take_along_axis(hist, widx, axis=1)
                    cache["hist"] = hist.at[slot_col, widx].set(
                        jnp.where(emitted, out, old)
                    )
                    cache["pos"] = jnp.where(active, pos + n_em, pos)
                    if spec_m == "heads":
                        # next pass drafts from the hidden of the LAST
                        # emitted token — the verify forward already
                        # computed it, so heads drafting stays free of
                        # extra model calls
                        new_h = jnp.take_along_axis(
                            hid, last[:, None, None], axis=1
                        )[:, 0]
                        cache["hlast"] = jnp.where(
                            active[:, None],
                            new_h.astype(cache["hlast"].dtype),
                            cache["hlast"],
                        )
                    elif spec_m == "draft":
                        # re-sync the draft clock to the accepted position:
                        # rows < pos already hold the true sequence, and
                        # the next unroll rewrites row pos with the new
                        # current token — self-healing after any import/
                        # resume drift
                        cache["d_pos"] = jnp.where(
                            active, cache["pos"], cache["d_pos"]
                        )
                    ys = (
                        (out.T, emitted.T, _conf_margin(logits).T)
                        if conf_on
                        else (out.T, emitted.T)
                    )
                    return (tokens, active2, remaining, cache), ys

                (tokens, active, remaining, cache), ys = lax.scan(
                    body, (tokens, active, remaining, cache), jnp.arange(k)
                )
                # (k, L, S) -> (k*L, S): chronological rows, same shape
                # contract the host delivery loop already speaks
                return tuple(_replicate(y.reshape(k * L, S)) for y in ys) + (
                    _replicate(tokens),
                    _replicate(active),
                    _replicate(remaining),
                    cache,
                )

            return fn

        def _prefill_suffix(pw):
            """Suffix-only prefill against a reused KV prefix (one compiled
            program per (suffix bucket, prefix window))."""

            def fn(params, tokens, prefix_len, length, slot, blocks_row,
                   suffix_blocks, temperature, seed, hist_seed, aid, lora,
                   cache):
                if spec_m == "heads":
                    logits, cache, hid = fam.prefill_suffix_paged(
                        params, tokens, prefix_len, length, slot, blocks_row,
                        suffix_blocks, cache, cfg, prefix_window=pw,
                        lora=lora, adapter_id=aid, return_hidden=True,
                        **pool_kw,
                    )
                    cache["hlast"] = cache["hlast"].at[slot].set(
                        hid.astype(cache["hlast"].dtype)
                    )
                else:
                    logits, cache = fam.prefill_suffix_paged(
                        params, tokens, prefix_len, length, slot, blocks_row,
                        suffix_blocks, cache, cfg, prefix_window=pw,
                        lora=lora, adapter_id=aid, **pool_kw,
                    )
                key = jax.random.PRNGKey(seed)
                tok = _sample(logits[None], temperature[None], key)[0]
                if spec_d:
                    row = hist_seed.at[length % spec_H].set(tok)
                    cache["hist"] = cache["hist"].at[slot].set(row)
                return _replicate(tok), cache

            return fn

        def _draft_prefill(spec_ps, tokens, length, slot, cache):
            """Draft-model prompt prefill (``spec_method='draft'``): write
            the prompt's K/V into the draft pool so block-one drafting
            sees real context instead of zeros.  Output-invisible — only
            ``d_*`` cache keys change, and the verify pass never reads
            them for emission — so a skipped/deferred run costs acceptance,
            never correctness.  One compiled program per prompt bucket."""
            dc = {
                "k": cache["d_k"], "v": cache["d_v"],
                "pos": cache["d_pos"], "table": cache["d_table"],
            }
            _, dc = fam.prefill_slot_paged(
                spec_ps, tokens, length, slot, dc["table"][slot], dc, dcfg,
                mesh=mesh, seq_impl=seq_impl,
            )
            cache["d_k"], cache["d_v"] = dc["k"], dc["v"]
            cache["d_pos"] = dc["pos"]
            cache["d_table"] = dc["table"]
            return cache

        def _embed(params, tokens, length):
            """Pooled-embedding forward (docs/GRAPHS.md): pure — no cache
            argument, nothing donated, no slot consumed.  One compiled
            program per prompt bucket, like prefill."""
            return _replicate(
                fam.embed_pooled(
                    params, tokens, length, cfg, mesh=mesh, seq_impl=seq_impl
                )
            )

        # cache buffers are donated: each step reuses the previous buffers
        # in place instead of holding two live copies of a multi-GB cache
        # (the lora pool arg is NOT donated — it persists across steps
        # like the base params)
        self._prefill = jax.jit(_prefill, donate_argnums=(10,))
        # draft-model prefill: built only for spec_method='draft'; batch-
        # class work a DeviceArbiter can defer (scheduler run loop)
        self._draft_prefill = (
            jax.jit(_draft_prefill, donate_argnums=(4,))
            if self.spec_method == "draft"
            else None
        )
        self._prefill_suffix_factory = _prefill_suffix
        self._prefill_suffix_jit: dict[tuple, Any] = {}
        self._decode_factory = _decode
        self._decode_jit: dict[tuple, Any] = {}  # (window, config) -> step
        self._decode_k_factory = _decode_k_spec if self.spec_draft else _decode_k
        self._decode_k_jit: dict[tuple, Any] = {}  # (k, window, config)
        # pooled-embedding program (POST /embeddings): jitted once, one
        # compile per prompt bucket via shape specialization; the seen-set
        # only drives compile telemetry
        self._embed_jit = jax.jit(_embed)
        self._embed_buckets_seen: set[int] = set()
        # static program configuration folded into every compiled-program
        # cache key: two deployments differing only in sampling/speculation/
        # quantization/chunking/kernel config must NEVER share a compiled
        # step (the audits in tests/test_spec.py + tests/test_chunked.py
        # hold this)
        self._program_config = (
            self.top_k, self.spec_draft, self.spec_ngram, self.spec_hist,
            self.spec_method, self.spec_heads, self._draft_geom,
            self.kv_dtype, self.prefill_chunk, self.decode_kernel,
            self.lora_rank, self.lora_slots, self.conf_signal,
        )
        # overlapped-pipeline state: the last dispatched block's final
        # (tokens, active, remaining) as DEVICE arrays, plus the host-side
        # (temperature, eos) the block ran with — a continue-dispatch feeds
        # these straight back into the next block without a host sync
        self._carry: tuple | None = None
        self._carry_aux: tuple | None = None
        self.overlapped = 0  # blocks dispatched from the on-device carry
        # deferred draft-model prefills (spec_method='draft' + arbiter):
        # batch-class payloads the scheduler drains at sync points instead
        # of running inline at admission
        self._pending_draft_prefill: list[dict] = []
        self.defer_draft_prefill = False
        self.draft_prefills = 0  # draft-pool prompt prefills dispatched
        # host-side per-slot position CEILING (>= true device position; the
        # device may stop early on eos).  Drives the attention-window bucket:
        # decode reads only cache rows [0, window) — the bandwidth bill once
        # contexts are long — so each block attends over the smallest
        # power-of-two covering the live positions (models/llama.py
        # decode_slots docstring has the numbers).
        self._pos_ceiling = np.zeros(self.n_slots, np.int64)
        if self.driver is not None:
            # symmetric SPMD step bodies for the follower loop; the k value
            # rides the payload so any block size stays in lockstep
            self._mh_prefill_key = self.driver.register_unique(
                f"gen:{name}:prefill", self._exec_prefill
            )
            self._mh_prefill_suffix_key = self.driver.register_unique(
                f"gen:{name}:prefill_suffix", self._exec_prefill_suffix
            )
            # draft-model prompt prefill is a driven step too: it writes
            # draft pool state on every process of the slice
            self._mh_draft_prefill_key = self.driver.register_unique(
                f"gen:{name}:draft_prefill", self._exec_draft_prefill
            )
            self._mh_decode_key = self.driver.register_unique(
                f"gen:{name}:decode", self._exec_decode
            )
            self._mh_decode_k_key = self.driver.register_unique(
                f"gen:{name}:decode_k", self._exec_decode_k
            )
            # overlap continue: payload carries only (k, window, seed) —
            # every process feeds its own locally-stored device carry
            self._mh_decode_cont_key = self.driver.register_unique(
                f"gen:{name}:decode_cont", self._exec_decode_cont
            )
            self._mh_embed_key = self.driver.register_unique(
                f"gen:{name}:embed", self._exec_embed
            )
            # reset writes the pos vector with a cross-process sharding —
            # a device_put every process must participate in, so it's a
            # driven step too (warmup calls it; a coordinator-only reset
            # wedges the slice)
            self._mh_reset_key = self.driver.register_unique(
                f"gen:{name}:reset", self._exec_reset
            )
            # disagg KV import writes blocks + pos/table on every process
            # of the slice (payload carries the raw ndarrays), so it is a
            # driven step like prefill/decode
            self._mh_import_key = self.driver.register_unique(
                f"gen:{name}:import", self._exec_import
            )
            # adapter-row installs write device state on every process of
            # the slice (payload carries the factor ndarrays), so they are
            # driven steps like prefill/decode
            self._mh_lora_key = self.driver.register_unique(
                f"gen:{name}:lora", self._exec_lora_load
            )

        # the family's own device counters (``cache["counters"]``, named by
        # ``family.COUNTERS``: uint32, cumulative, wrapping): a copy is
        # fetched WITH every decode block's tokens and the wrap-aware
        # difference summed here — no sync point of its own
        self._ctr_names = tuple(getattr(family_mod, "COUNTERS", ()))
        self._ctr_dev = None
        self._ctr_last = np.zeros(len(self._ctr_names), np.uint64)
        self._ctr_total = np.zeros(len(self._ctr_names), np.uint64)
        # observability
        self.steps = 0
        self.prefills = 0
        self.embeds = 0  # pooled-embedding forwards (docs/GRAPHS.md)
        # per-block confidence stash (cascade routing): the last fetched
        # block's (rows, S) top-2 logit margins, read by the scheduler's
        # delivery loop at the block's one sync — None when conf_signal
        # is off, so the fetch path stays sync-free either way
        self.last_conf_seq: np.ndarray | None = None
        self.prefills_reused = 0  # prefills that skipped a reused prefix
        self.prefill_chunks = 0  # chunked-prefill chunk dispatches
        # tokens prefilled, the rungs they were padded to, dispatches a rung
        self.prefill_rows: dict = {"real": 0, "padded": 0, "by_rung": {}}
        # of the whole prompts admitted: the tiled kernel's grid steps a head
        # in their rungs, and those a prompt's real length left to multiply
        self.prefill_tiles: dict = {"stepped": 0, "live": 0}
        self.imports = 0  # disagg KV handoffs imported into this pool
        # KV/HBM pool ledger (docs/OBSERVABILITY.md "generation forensics"):
        # high-water mark of blocks in use, and the byte classes the HBM
        # budget splits into — served on /stats/breakdown and as the
        # seldon_kv_* gauges so router/autoscaler pressure decisions are
        # debuggable after the fact
        self._blocks_high_water = 0
        self.param_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(self.params)
        )
        # program-cache telemetry: hits vs compiles across the dict-cached
        # program families (decode, decode_k, suffix-prefill), per-variant
        # compile seconds (warmup-attributed or measured at the first
        # serving call), and a bounded recent-compiles ring — a mid-traffic
        # recompile becomes a program.compile span instead of a mystery
        # latency spike
        self.program_hits = 0
        self.program_compiles = 0
        from collections import deque as _deque

        self._program_events: _deque = _deque(maxlen=64)
        self.warmup_program_seconds: dict[str, float] = {}
        self._in_warmup = False
        # static program-variant tag shared by warmup labels, profiler
        # TraceAnnotations, and compile telemetry (e.g. "[spec4,int8]")
        tag = []
        if self.spec_draft:
            # ngram (the PR 7 default) stays the bare "specN" tag; the
            # learned proposers name themselves + their geometry
            sfx = f"spec{self.spec_draft}"
            if self.spec_method == "heads":
                sfx += f"+heads{self.spec_heads}"
            elif self.spec_method == "draft":
                kind, geo = self._draft_geom
                sfx += f"+draft:{kind}{geo}" if kind == "truncate" \
                    else f"+draft:{geo}"
            tag.append(sfx)
        if self.kv_dtype:
            tag.append(self.kv_dtype)
        if self.prefill_chunk:
            tag.append(f"chunk{self.prefill_chunk}")
        if self.decode_kernel:
            tag.append("kernel")
        if self.lora_rank:
            tag.append(f"lora{self.lora_rank}")
        if self.conf_signal:
            tag.append("conf")
        self.variant_sfx = ("[" + ",".join(tag) + "]") if tag else ""
        # per-slot inter-token latency ledger (fed by the scheduler's
        # delivery loop): bounded ring for the /stats/breakdown percentiles
        # plus the seldon_itl_seconds histogram.  Each sample is one
        # (fetched block, slot) pair's delivery gap divided by the tokens it
        # carried — a prefill-induced decode stall inflates every live
        # slot's sample for that block, which is exactly what TTFT and
        # device-step histograms could not see.
        from collections import deque

        self._itl = deque(maxlen=4096)
        self._m_itl = DEFAULT_METRICS.itl.labels(name)
        # speculative-decoding ledger: tokens emitted vs (slot, verify-pass)
        # pairs — their ratio is accepted_tokens_per_step (> 1.0 means the
        # drafts are paying for themselves)
        self.spec_emitted_tokens = 0
        self.spec_verify_passes = 0
        # per-(bucket, program) compile attribution filled by warmup() and
        # served on GET /stats/warmup
        self.warmup_programs: list[str] = []
        # decode FLOPs ≈ 2·params per token (roofline's estimate) — feeds
        # the MFU gauge from measured step round trips
        self.flops_per_token = 2.0 * sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(self.params)
        )
        self._m_device_step = DEFAULT_METRICS.device_step.labels(name)
        self._m_mfu = DEFAULT_METRICS.mfu.labels(name)
        DEFAULT_METRICS.kv_slots_per_chip.labels(name).set(
            self.kv_slots_per_chip()
        )
        # RLock: warmup calls admit/step under the same lock
        self._lock = threading.RLock()
        # HBM memory manager (executor/memory.py): admission-time byte
        # reservation for this model's classes — with SCT_HBM_ENFORCE=1 an
        # over-committing SECOND deployment fails at build instead of
        # OOMing the chip mid-traffic (docs/MULTITENANT.md)
        if memory is None:
            from seldon_core_tpu.executor.memory import MEMORY as memory
        self.memory = memory
        self._mem_key = f"{name}:{id(self):x}"
        # host-DRAM byte classes (prefix_dram + suspend_dram): the host
        # ledger's reserve() REPLACES an owner's class dict, so both
        # classes re-reserve together through _note_host_bytes
        self._host_classes: dict[str, int] = {}
        kv_bytes = self._pool_bytes()
        scale_bytes = (
            int(self._cache["k_scale"].nbytes)
            + int(self._cache["v_scale"].nbytes)
            if "k_scale" in self._cache
            else 0
        )
        # held for the model's lifetime; release_memory() releases both
        # the HBM and host ledgers
        # sct: pairing-ok ownership transfer to release_memory()
        self.memory.reserve(
            self._mem_key,
            {
                "weights": self.param_bytes,
                "kv_pool": kv_bytes,
                "kv_scales": scale_bytes,
                **self._slot_state_class(),
                "adapter_pool": self.lora_bytes,
                # learned speculation (docs/MULTITENANT.md "draft-model
                # HBM accounting"): resident head block / draft weights /
                # the draft model's own paged KV pool
                "spec_heads": self.spec_heads_bytes,
                "draft_weights": self.draft_weight_bytes,
                "draft_kv": self.draft_kv_bytes,
            },
        )
        # graph-declared adapters ("name", "name:seed", comma-separated or
        # a list): registered at build so the deployment is ready to serve
        # them the moment readiness flips
        if self.lora_pool is not None and lora_adapters is None:
            lora_adapters = os.environ.get("SCT_LORA_ADAPTERS") or None
        if self.lora_pool is not None and lora_adapters:
            names = (
                [s for s in str(lora_adapters).split(",")]
                if isinstance(lora_adapters, str)
                else list(lora_adapters)
            )
            for ent in names:
                ent = str(ent).strip()
                if not ent:
                    continue
                nm, _, sd = ent.partition(":")
                self.register_adapter(
                    nm.strip(), seed=int(sd) if sd.strip() else None
                )
        # from here on, adapter registrations are dynamic: on a multi-host
        # slice they broadcast as driven steps instead of local writes
        self._built = True

    def _parse_draft_model(self, spec: str | None, name: str) -> tuple:
        """Resolve a ``spec_draft_model`` string into a STATIC geometry
        tuple (a ``_program_config`` member):

        - ``truncate:N`` — LayerSkip-style self-draft from the target's
          own first N layers (shared weights, no second checkpoint)
        - ``truncate:auto`` — N = max(1, n_layers // 8)
        - ``preset:NAME`` — a separate tiny preset of the same family
          (vocab must match the target's; max_seq is forced to it)
        """
        spec = str(spec or "truncate:auto").strip()
        kind, _, arg = spec.partition(":")
        kind = kind.lower()
        if kind == "truncate":
            arg = (arg or "auto").strip().lower()
            if not hasattr(self.family, "truncate_params"):
                raise GraphUnitError(
                    f"generative family {self.family.__name__} has no "
                    "truncate_params; spec_draft_model='truncate:...' needs "
                    "the layer-truncation helper"
                )
            if arg == "auto":
                n = max(1, int(self.cfg.n_layers) // 8)
            else:
                try:
                    n = int(arg)
                except ValueError:
                    raise GraphUnitError(
                        f"generative model {name!r}: bad truncate layer "
                        f"count in spec_draft_model={spec!r}"
                    ) from None
            if not 1 <= n < int(self.cfg.n_layers):
                raise GraphUnitError(
                    f"generative model {name!r}: truncate:{n} must keep "
                    f"1 <= N < n_layers ({self.cfg.n_layers})"
                )
            return ("truncate", n)
        if kind == "preset" and arg.strip():
            return ("preset", arg.strip())
        raise GraphUnitError(
            f"generative model {name!r}: spec_draft_model must be "
            f"'truncate:N', 'truncate:auto', or 'preset:NAME', got {spec!r}"
        )

    def note_itl(self, seconds: float) -> None:
        """One inter-token-latency sample (scheduler delivery loop)."""
        self._itl.append(float(seconds))
        self._m_itl.observe(seconds)

    def _itl_pct(self, q: float) -> float | None:
        if not self._itl:
            return None
        return float(np.percentile(np.asarray(self._itl), q))

    def _note_compile(self, label: str, seconds: float) -> None:
        """Program-cache telemetry for one fresh compile: the bounded
        recent-compiles ring, per-variant seconds, the prometheus counter,
        and — OUTSIDE warmup, where a compile means readiness lied about
        coverage — a ``program.compile`` root span so the latency spike it
        caused is attributable from /stats/spans."""
        seconds = round(seconds, 3)
        self._program_events.append(
            {
                "label": label,
                "seconds": seconds,
                "ts": time.time(),
                "warmup": self._in_warmup,
            }
        )
        self.warmup_program_seconds.setdefault(label, seconds)
        DEFAULT_METRICS.program_compiles.labels(self.name).inc()
        if not self._in_warmup:
            from seldon_core_tpu.utils.tracectx import make_trace_id

            RECORDER.record_span(
                "program.compile",
                trace_id=make_trace_id(),
                parent_id=None,
                start=time.time() - seconds,
                duration_s=seconds,
                service=self.name,
                attrs={"variant": label, "model": self.name},
            )
            log.warning(
                "generative model %r: mid-traffic program compile %s "
                "(%.3fs) — warmup did not cover this variant",
                self.name, label, seconds,
            )

    def _record_step(self, step_s: float) -> None:
        """Flight-recorder + metrics for one decode dispatch (runs on the
        scheduler's worker thread; all sinks are thread-safe).  ``step_s``
        is the host's wait for the block, which the QoS estimate reads."""
        RECORDER.record_stage(STAGE_DEVICE_STEP, step_s)
        self._m_device_step.observe(step_s)
        from seldon_core_tpu.obs import record_host_sync

        record_host_sync(self.name)  # sampled tokens materialized on host

    def record_mfu(self, tokens_emitted: int, busy_s: float) -> None:
        """The ``mfu`` gauge over the seconds a decode block OCCUPIED the
        device (the scheduler's device ledger, obs/device.py), not the
        host's wait for it."""
        if tokens_emitted and busy_s > 0:
            from seldon_core_tpu.executor.batcher import _chip_peak

            peak = _chip_peak()
            if peak:
                self._m_mfu.set(
                    tokens_emitted * self.flops_per_token / busy_s / peak
                )

    # ------------------------------------------------- multi-LoRA adapters

    def register_adapter(
        self,
        name: str,
        *,
        seed: int | None = None,
        factors: Any = None,
        scale: float = 0.05,
    ) -> int:
        """Install adapter ``name`` into the stacked pool and return its
        row (docs/MULTITENANT.md).  ``factors`` is the family's per-adapter
        pytree (``lora_adapter_factors`` layout); without one, synthetic
        factors are generated from ``seed`` (default: a stable hash of the
        name, so every replica builds the SAME stand-in deltas).  LRU
        eviction under pressure and :class:`AdapterPoolFull` when every
        row is pinned by in-flight slots."""
        if self.lora_pool is None:
            raise GraphUnitError(
                f"generative model {self.name!r} was built without "
                "multi-LoRA serving (set lora_rank / SCT_LORA_RANK)"
            )
        if factors is None:
            if seed is None:
                import zlib

                seed = zlib.crc32(str(name).encode())
            factors = self.family.lora_adapter_factors(
                jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), self.cfg,
                self.lora_rank, targets=self.lora_targets, scale=scale,
                dtype=self._lora[self.lora_targets[0]]["a"].dtype,
            )
        return self.lora_pool.register(name, factors)

    def _lora_write(self, idx: int, factors: Any) -> None:
        """AdapterPool's device writer: install one adapter's factors into
        pool row ``idx`` on every process of the slice.  Build-time
        registration (graph-declared adapters) runs symmetrically on every
        process from the same spec, so it writes locally; only DYNAMIC
        registrations after build are coordinator-led driven steps."""
        payload = {"idx": int(idx)}
        for t in self.lora_targets:
            payload[f"a:{t}"] = np.asarray(factors[t]["a"])
            payload[f"b:{t}"] = np.asarray(factors[t]["b"])
        if self.driver is not None and getattr(self, "_built", False):
            self.driver.lead(self._mh_lora_key, payload)
        else:
            self._exec_lora_load(payload)

    def _exec_lora_load(self, payload: dict) -> None:
        """Symmetric adapter-row install (runs on every slice process).
        The pool tensors are NOT donated by the step programs, so the
        functional ``.at[].set`` here never races a dispatched block — the
        in-flight block keeps reading the old buffers, the next dispatch
        picks up the new ones."""
        idx = int(payload["idx"])
        with self._lock:
            lt = {}
            for t, fac in self._lora.items():
                a = fac["a"].at[:, idx].set(
                    np.asarray(payload[f"a:{t}"]).astype(fac["a"].dtype)
                )
                b = fac["b"].at[:, idx].set(
                    np.asarray(payload[f"b:{t}"]).astype(fac["b"].dtype)
                )
                if self.mesh is not None:
                    a = jax.device_put(a, fac["a"].sharding)
                    b = jax.device_put(b, fac["b"].sharding)
                lt[t] = {"a": a, "b": b}
            self._lora = lt

    def _aid_vec(self, payload: dict):
        """Per-slot adapter-id vector for a decode dispatch (None with
        LoRA off — the compiled programs then take an empty pytree)."""
        if self._lora is None:
            return None
        aid = payload.get("aid")
        if aid is None:
            return np.zeros(self.n_slots, np.int32)
        return np.asarray(aid, np.int32)

    def _aid_scalar(self, payload: dict):
        if self._lora is None:
            return None
        return np.int32(payload.get("aid", 0))

    def note_adapter_tokens(self, adapter: str, n: int) -> None:
        """Per-adapter served-token ledger (scheduler delivery loop).
        Keyed by NAME, not slot: a request that completed inside the
        delivered block has already released its slot binding."""
        if self.lora_pool is None or not adapter:
            return
        if self.lora_pool.note_tokens_name(adapter, n):
            # cardinality guard: past SCT_METER_ADAPTER_LABELS distinct
            # adapters the label value rolls up into `other` (the pool's
            # own per-name ledger stays exact)
            DEFAULT_METRICS.lora_tokens.labels(
                self.name, DEFAULT_METRICS.adapter_label(adapter)
            ).inc(int(n))

    def slot_adapter(self, slot: int) -> str | None:
        """Resident adapter name bound to ``slot`` (None = base model)."""
        if self.lora_pool is None:
            return None
        return self.lora_pool.name_of(int(self._slot_aidx[int(slot)]))

    def adapters_snapshot(self) -> dict | None:
        """Adapter-pool ledger for ``GET /stats/breakdown`` — also
        refreshes the ``seldon_lora_*`` gauges."""
        if self.lora_pool is None:
            return None
        snap = self.lora_pool.snapshot()
        snap["bytes"] = self.lora_bytes
        m = DEFAULT_METRICS
        m.lora_resident.labels(self.name).set(snap["resident"])
        m.lora_evictions.labels(self.name).set(snap["evictions"])
        m.lora_bytes.labels(self.name).set(self.lora_bytes)
        return snap

    def release_memory(self) -> None:
        """Drop this model's HBM **and host-DRAM** ledger reservations
        (component close).  The host release is unconditional: suspend
        records (docs/PACKING.md) ledger host bytes even on deployments
        with no prefix tier, and a torn-down deployment's DRAM budget
        must return to the pool either way."""
        self.memory.release(self._mem_key)
        from seldon_core_tpu.executor.memory import host_memory

        self._host_classes.clear()
        host_memory().release(self._mem_key)

    def _note_host_bytes(self, cls: str, nbytes: int) -> None:
        """Merge one host-DRAM byte class (``prefix_dram`` /
        ``suspend_dram``) into this model's HOST-ledger reservation.
        ``reserve()`` REPLACES an owner's class dict, so every class this
        model ledgers re-reserves together — a suspend-store update must
        never wipe the prefix tier's bytes, or vice versa."""
        from seldon_core_tpu.executor.memory import host_memory

        self._host_classes[str(cls)] = int(nbytes)
        # reserve() replaces this owner's class dict (idempotent merge);
        # release_memory() drops the whole key
        # sct: pairing-ok ownership transfer to release_memory()
        host_memory().reserve(self._mem_key, dict(self._host_classes))

    def _note_dram_bytes(self, nbytes: int) -> None:
        """HostPrefixStore byte callback: ledger the DRAM tier's live
        bytes in the HOST memory manager (never the HBM one) and refresh
        the gauge.  Runs only at demote/promote/evict time — admission
        sync points, never the decode hot path."""
        self._note_host_bytes("prefix_dram", int(nbytes))
        DEFAULT_METRICS.prefix_tier_bytes.labels(self.name, "dram").set(
            int(nbytes)
        )

    def note_suspend_bytes(self, nbytes: int) -> None:
        """SuspendStore byte callback (docs/PACKING.md): preempted
        whole-slot records park in host DRAM under ``suspend_dram`` —
        same admission-sync-point-only cadence as the prefix tier."""
        self._note_host_bytes("suspend_dram", int(nbytes))

    # ------------------------------------------------------------------ ops

    def fit_bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise GraphUnitError(
            f"prompt length {n} exceeds max_seq {self.cfg.max_seq}"
        )

    def _count_prefill(self, payload: dict, *, reused: bool = False) -> None:
        """Prefill accounting that stays honest under chunking: a chunked
        admission counts ONE logical prefill (on its final chunk) plus one
        ``prefill_chunks`` tick per chunk dispatched; ``prefills_reused``
        only counts admissions whose reservation matched a shared prefix —
        never the suffix-program calls chunking itself issues.
        ``prefill_rows`` counts every dispatch but warm-up's: the tokens it
        prefilled and the rung it ran in, host integers already in hand."""
        if not self._in_warmup:
            rung = int(payload["padded"].shape[1])
            rows = self.prefill_rows
            rows["real"] += int(payload["length"]) - int(
                payload.get("prefix_len", 0)
            )
            rows["padded"] += rung
            key = str(rung)  # a JSON object's keys
            rows["by_rung"][key] = rows["by_rung"].get(key, 0) + 1
        ch = payload.get("chunk")
        if ch is None:
            self.prefills += 1
            if reused:
                self.prefills_reused += 1
            return
        self.prefill_chunks += 1
        if ch.get("last"):
            self.prefills += 1
            if ch.get("reused"):
                self.prefills_reused += 1

    def _exec_prefill(self, payload: dict):
        """Symmetric prefill body (runs on every slice process).  The
        TraceAnnotation names the dispatch after its program-cache variant
        label so a /profile/start capture lines up with span names and
        /stats/warmup entries."""
        label = f"prefill:b{int(payload['padded'].shape[1])}{self.variant_sfx}"
        with self._lock:
            with jax.profiler.TraceAnnotation(label):
                tok, self._cache = self._prefill(
                    self.params,
                    payload["padded"],
                    np.int32(payload["length"]),
                    np.int32(payload["slot"]),
                    np.asarray(payload["blocks"], np.int32),
                    np.float32(payload["temperature"]),
                    np.int32(payload["seed"]),
                    np.asarray(
                        payload.get("hist_seed", _NO_HIST), np.int32
                    ),
                    self._aid_scalar(payload),
                    self._lora,
                    self._cache,
                )
            self._count_prefill(payload)
            if not self._in_warmup:
                stepped, live = admitted_tiles(
                    int(payload["padded"].shape[1]), int(payload["length"])
                )
                self.prefill_tiles["stepped"] += stepped
                self.prefill_tiles["live"] += live
        return tok

    def reserve_blocks(self, slot: int, total_tokens: int) -> np.ndarray:
        """Reserve the physical blocks ``slot`` needs for a request whose
        prompt+generation will reach ``total_tokens``; returns the slot's
        zero-padded table row.  Raises :class:`OutOfKVBlocks` when the pool
        cannot cover it right now (the scheduler queues the request)."""
        row, _ = self.reserve_for_prompt(slot, None, total_tokens)
        return row

    def reserve_for_prompt(
        self,
        slot: int,
        prompt: "np.ndarray | None",
        total_tokens: int,
        adapter: str | None = None,
    ) -> tuple[np.ndarray, int]:
        """Prompt-aware reservation: with prefix reuse enabled, the longest
        chain of full prompt blocks already in the index is REFERENCED
        (shared, immutable) instead of allocated, and only the remainder
        comes from the free pool.  Returns ``(table row, prefix_len)`` —
        ``prefix_len`` tokens of prefill are skipped by the caller.

        ``adapter`` binds the slot to a resident LoRA adapter for the
        request's lifetime (refcounted; released with the slot) AND salts
        the prefix-index keys: LoRA on the attention projections changes
        K/V, so adapter-A blocks must never serve adapter-B — or the base
        model (docs/MULTITENANT.md)."""
        from seldon_core_tpu.cache.prefix import adapter_salt

        aidx = 0
        if adapter:
            if self.lora_pool is None:
                raise GraphUnitError(
                    f"request names adapter {adapter!r} but model "
                    f"{self.name!r} was built without multi-LoRA serving"
                )
            from seldon_core_tpu.executor.lora import UnknownAdapter

            try:
                aidx = self.lora_pool.acquire(adapter)
            except UnknownAdapter as e:
                raise GraphUnitError(str(e)) from None
        salt = adapter_salt(adapter)
        total = min(int(total_tokens), self.cfg.max_seq)
        need = -(-total // self.kv_block_size)
        self.release_slot(slot)  # a stale reservation on this slot is dead
        matched: list[int] = []
        if self.prefix_index is not None and prompt is not None:
            # never reuse the WHOLE prompt: the suffix program needs at
            # least one real token to produce the first sampled logits
            max_reuse = (int(prompt.size) - 1) // self.kv_block_size
            if max_reuse > 0:
                matched = self.prefix_index.match(
                    prompt, min(max_reuse, need), salt=salt
                )
        # DRAM tier lookup: demoted chain levels that EXTEND the HBM match
        # can be promoted back for the price of one fused scatter — they
        # come out of the free pool like owned blocks (and re-enter the
        # index when the slot releases), so the free-pool requirement is
        # unchanged whether or not the promotion happens
        promoted: list[tuple] = []
        if self.host_store is not None and prompt is not None:
            max_reuse = (int(prompt.size) - 1) // self.kv_block_size
            stop = min(max_reuse, need)
            if stop > len(matched):
                promoted = self.host_store.match(
                    prompt, len(matched) + 1, stop, salt=salt
                )
        own_need = need - len(matched)
        if len(self._free_blocks) < own_need and self.prefix_index is not None:
            # reclaim unreferenced index blocks before failing admission
            # (demoting their KV into the host store when the tier is on)
            self._demote_and_free(own_need - len(self._free_blocks))
        if len(self._free_blocks) < own_need:
            if matched:
                self.prefix_index.release(prompt, len(matched), salt=salt)
            if aidx:
                self.lora_pool.release_ref(aidx)
            raise OutOfKVBlocks(
                f"need {own_need} KV blocks, {len(self._free_blocks)} free"
            )
        got = self._free_blocks[-own_need:] if own_need else []
        if own_need:
            del self._free_blocks[-own_need:]
        n_promoted = 0
        if promoted:
            # scatter the demoted levels into the LEADING owned blocks —
            # they hold complete prompt KV, so release_slot's normal
            # insertion absorbs them back into the index at completion
            try:
                self._exec_promote(
                    self._promote_payload(got[: len(promoted)], promoted)
                )
                n_promoted = len(promoted)
                self.host_store.drop([e[0] for e in promoted])
                self.dram_hits += 1
            except Exception:
                # a failed promotion costs only the shortcut: the blocks
                # stay slot-owned and the suffix prefill covers them
                log.warning(
                    "generative model %r: DRAM prefix promotion failed; "
                    "falling back to plain prefill", self.name, exc_info=True,
                )
                n_promoted = 0
        used = (self.kv_blocks - 1) - len(self._free_blocks)
        if used > self._blocks_high_water:
            self._blocks_high_water = used
        self._slot_blocks[slot] = got
        self._slot_aidx[int(slot)] = aidx
        if salt:
            self._slot_salt[int(slot)] = salt
        if self.prefix_index is not None and prompt is not None:
            self._slot_prompt[slot] = np.asarray(prompt, np.int32).copy()
            self._slot_matched[slot] = len(matched)
            self._slot_promoted[slot] = n_promoted
            self._slot_tier[slot] = self._match_tier(
                prompt, len(matched), n_promoted, salt
            )
        row = np.zeros(self.max_blocks_per_slot, np.int32)
        row[: len(matched)] = matched
        row[len(matched):need] = got
        self._slot_row[slot] = row.copy()
        reused = len(matched) + n_promoted
        if reused:
            DEFAULT_METRICS.prefix_tokens_reused.labels(self.name).inc(
                reused * self.kv_block_size
            )
        return row, reused * self.kv_block_size

    def release_slot(self, slot: int) -> None:
        """Return ``slot``'s owned blocks to the pool and drop its shared-
        prefix refs (idempotent).  With prefix reuse on, the completed
        prompt's FULL blocks are absorbed into the index (zero-ref,
        LRU-evictable) instead of freed, so the next shared-prefix prompt
        finds them."""
        slot = int(slot)
        matched = self._slot_matched.pop(slot, 0)
        prompt = self._slot_prompt.pop(slot, None)
        blocks = self._slot_blocks.pop(slot, None)
        salt = self._slot_salt.pop(slot, b"")
        self._slot_tier.pop(slot, None)
        self._slot_promoted.pop(slot, None)
        aidx = int(self._slot_aidx[slot])
        if aidx:
            self._slot_aidx[slot] = 0
            if self.lora_pool is not None:
                self.lora_pool.release_ref(aidx)
        self._slot_row.pop(slot, None)
        if matched and prompt is not None and self.prefix_index is not None:
            self.prefix_index.release(prompt, matched, salt=salt)
        if blocks:
            if self.prefix_index is not None and prompt is not None:
                # owned blocks are table positions [matched, need); the
                # first (full_prompt_blocks - matched) of them hold ONLY
                # complete prompt K/V -> shareable (under the slot's
                # adapter salt — adapter-tagged chains never cross)
                full = int(prompt.size) // self.kv_block_size
                insertable = blocks[: max(0, full - matched)]
                if insertable:
                    rejected = self.prefix_index.insert(
                        prompt, insertable, matched, salt=salt
                    )
                    absorbed = set(insertable) - set(rejected)
                    blocks = [b for b in blocks if b not in absorbed]
            self._free_blocks.extend(blocks)
        if self.prefix_index is not None:
            DEFAULT_METRICS.prefix_blocks.labels(self.name).set(
                len(self.prefix_index)
            )

    @property
    def free_block_count(self) -> int:
        return len(self._free_blocks)

    def _pool_bytes(self) -> int:
        """HBM bytes of the pool's per-token arrays, as the family names
        them (scales are counted apart)."""
        return sum(int(self._cache[key].nbytes) for key in self._pool_names)

    def _slot_state_bytes(self) -> int:
        """HBM bytes of the cache's per-slot arrays, as the family names
        them: 0 for a family whose cache is all rows a token."""
        return sum(int(self._cache[key].nbytes) for key in self._slot_names)

    def _slot_state_class(self) -> dict:
        """The ledger's ``slot_state`` class, for a family that has any."""
        return {"slot_state": self._slot_state_bytes()} if self._slot_names else {}

    def _kv_alone(self, what: str) -> None:
        """Refuse ``what`` for a family whose cache is not exactly K and V
        under the table: the frames and stores outside the programs carry
        ``k`` and ``v`` (and an int8 pool's scales) alone, and a slot moved
        without its further arrays — the pool's, or state it keeps per slot
        — or as K and V it does not have, would decode on garbage."""
        if self._slot_names:
            raise TypeError(
                f"generative family {self.family.__name__.rsplit('.', 1)[-1]} "
                f"keeps {', '.join(self._slot_names)} per slot beside its "
                f"paged pool ({', '.join(self._pool_names)}): state that no "
                f"block holds; {what} carries k and v alone and is refused"
            )
        if self._pool_names != ("k", "v"):
            other = [n for n in self._pool_names if n not in ("k", "v")]
            how = "beside K/V" if "k" in self._pool_names else "and no K/V by head"
            raise TypeError(
                f"generative family {self.family.__name__.rsplit('.', 1)[-1]} "
                f"keeps {', '.join(other)} {how} in its paged "
                f"pool; {what} carries k and v alone and is refused"
            )

    # -------------------------------------------------- disagg KV handoff

    def export_slot_kv(self, slot: int, prompt_len: int) -> tuple:
        """Fetch the K/V of ``slot``'s prompt blocks to host for a disagg
        handoff (docs/DISAGGREGATION.md): ``(layers, ceil(L/bs), bs,
        kv_heads, head_dim)`` each.  An int8 pool returns a 4-tuple
        ``(k, v, k_scale, v_scale)`` — the QUANTIZED representation plus
        its scales travel verbatim so the import is bit-exact with no
        re-quantization.  The slot's reservation pins the blocks — shared
        prefix blocks included — so nothing here can be reclaimed or
        overwritten until the owner releases the slot, which it only does
        after the handoff succeeds or is abandoned."""
        if self._multihost:
            raise GraphUnitError(
                "disagg KV export is not supported from a multi-host slice "
                "(the coordinator cannot address every shard); run the "
                "prefill pool single-host or serve unified"
            )
        slot = int(slot)
        row = self._slot_row.get(slot)
        if row is None:
            raise GraphUnitError(f"slot {slot} holds no reservation to export")
        nb = -(-int(prompt_len) // self.kv_block_size)
        phys = np.asarray(row[:nb], np.int32)
        # once per migrated slot, off the per-token path (DISAGG.md)
        k, v, ks, vs = self._fetch_blocks(phys)
        return (k, v, ks, vs) if self.kv_dtype else (k, v)

    def _fetch_blocks(self, phys: np.ndarray) -> tuple:
        """The pool's blocks ``phys`` on the host, ``(k, v, k_scale,
        v_scale)`` (the scales None on a float pool), in the shape every
        frame and store outside the programs holds: ``(layers, n,
        block_size, kv_heads, head_dim)``, whatever row shape the pool is
        carried in (same row-major bytes).  ONE batched fetch."""
        self._kv_alone("a KV export (handoff, suspend, prefix demotion, peer pull)")
        names = ("k", "v") + (("k_scale", "v_scale") if self.kv_dtype else ())
        with self._lock:
            # sct: host-sync-ok handoff export / tier demotion / peer pull
            got = jax.device_get([self._cache[n][:, phys] for n in names])
        frame = (self.cfg.n_kv_heads, self.cfg.head_dim)
        k, v = (np.asarray(a).reshape(a.shape[:3] + frame) for a in got[:2])
        ks, vs = (np.asarray(a) for a in got[2:]) if self.kv_dtype else (None, None)
        return k, v, ks, vs

    def export_spec_state(self, slot: int) -> dict | None:
        """Proposer state for a handoff/suspend frame (codec v5): the
        method tag plus, for ``heads``, the slot's Medusa hidden — the one
        piece an importer cannot recompute without a forward pass.  The
        ``draft`` method ships no tensor: the importer re-prefills the
        draft pool from the carried token history and ``d_pos``
        self-heals at the first verify pass.  ``None`` for ngram/off —
        the history ring already travels as the frame's prompt."""
        if not self.spec_method or self.spec_method == "ngram":
            return None
        state: dict = {"method": self.spec_method}
        if self.spec_method == "heads":
            with self._lock:
                # once per migrated slot, off the per-token path
                state["hlast"] = np.asarray(  # sct: host-sync-ok handoff export
                    jax.device_get(self._cache["hlast"][int(slot)])
                )
        return state

    def draft_prefill_dispatch(self, slot: int, prompt: np.ndarray):
        """Prefill the co-resident draft model's paged KV for ``slot``
        (``spec_method='draft'``).  Batch-class work: with a DeviceArbiter
        attached the scheduler defers it to the next sync point
        (:meth:`drain_draft_prefills`) under the draft registrant, so
        interactive verify blocks never queue behind it.  Skipping or
        delaying it costs acceptance only — the verify pass never reads
        draft KV for emission, and ``d_pos`` re-syncs every pass."""
        if self._draft_prefill is None:
            return None
        prompt = np.asarray(prompt, np.int32).ravel()
        L = min(int(prompt.size), self.cfg.max_seq)
        if L < 1:
            return None
        bucket = self.fit_bucket(L)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = prompt[:L]
        payload = {"padded": padded, "length": L, "slot": int(slot)}
        if self.defer_draft_prefill and not self._in_warmup:
            self._pending_draft_prefill.append(payload)
            return None
        if self.driver is not None:
            return self.driver.lead(self._mh_draft_prefill_key, payload)
        return self._exec_draft_prefill(payload)

    def drain_draft_prefills(self) -> int:
        """Run the deferred draft-model prefills (scheduler sync points,
        under the arbiter's batch-class draft registrant)."""
        n = 0
        while self._pending_draft_prefill:
            payload = self._pending_draft_prefill.pop(0)
            if self.driver is not None:
                self.driver.lead(self._mh_draft_prefill_key, payload)
            else:
                self._exec_draft_prefill(payload)
            n += 1
        return n

    def _exec_draft_prefill(self, payload: dict):
        """Symmetric draft-prefill body (runs on every slice process).
        No token output and nothing fetched: a dispatch-only call, so the
        ≤1-host-sync-per-fused-block audit is untouched."""
        label = (
            f"draft_prefill:b{int(payload['padded'].shape[1])}"
            f"{self.variant_sfx}"
        )
        with self._lock:
            with jax.profiler.TraceAnnotation(label):
                self._cache = self._draft_prefill(
                    self._spec_ps,
                    payload["padded"],
                    np.int32(payload["length"]),
                    np.int32(payload["slot"]),
                    self._cache,
                )
            self.draft_prefills += 1
        return None

    def attach_imported(
        self,
        slot: int,
        prompt: np.ndarray,
        k: np.ndarray,
        v: np.ndarray,
        *,
        reserve_tokens: int = 0,
        k_scale: np.ndarray | None = None,
        v_scale: np.ndarray | None = None,
        first_token: int | None = None,
        adapter: str | None = None,
        spec_state: dict | None = None,
    ) -> None:
        """Install another engine's exported prompt KV into ``slot``:
        reserve blocks (longest-prefix reuse applies — blocks this pool
        already holds for the leading prompt blocks are referenced instead
        of rewritten; identical prefixes have bit-identical K/V so skipping
        the write preserves exactness), scatter the novel blocks, and set
        the slot's position/table.  After this the slot decodes exactly as
        if it had prefilled locally.  Int8 pools require the quantized
        blocks plus their ``k_scale``/``v_scale`` (handoff codec v2) and
        scatter both verbatim — bit-exact, no re-quantization.  Raises
        :class:`OutOfKVBlocks` like a local admission when the pool cannot
        cover it."""
        self._kv_alone("a KV import (handoff, resume)")
        prompt = np.asarray(prompt, np.int32).ravel()
        L = int(prompt.size)
        if L < 1:
            raise GraphUnitError("empty prompt")
        bs = self.kv_block_size
        nb = -(-L // bs)
        k = np.asarray(k)
        v = np.asarray(v)
        expect = (self.cfg.n_layers, nb, bs, self.cfg.n_kv_heads, self.cfg.head_dim)
        if tuple(k.shape) != expect or tuple(v.shape) != expect:
            raise GraphUnitError(
                f"imported KV shape {tuple(k.shape)} does not match this "
                f"pool's {expect} (config or block-size skew)"
            )
        if bool(self.kv_dtype) != (k_scale is not None):
            raise GraphUnitError(
                f"imported KV dtype skew: pool is "
                f"{self.kv_dtype or 'float'} but the handoff "
                f"{'carries' if k_scale is not None else 'lacks'} int8 "
                "scales; pools must share kv_cache_dtype"
            )
        if k_scale is not None:
            k_scale = np.asarray(k_scale)
            v_scale = np.asarray(v_scale)
            if tuple(k_scale.shape) != expect[:4] or tuple(v_scale.shape) != expect[:4]:
                raise GraphUnitError(
                    f"imported KV scale shape {tuple(k_scale.shape)} does "
                    f"not match this pool's {expect[:4]}"
                )
        row, prefix_len = self.reserve_for_prompt(
            slot, prompt, L + max(0, int(reserve_tokens)), adapter=adapter
        )
        skip = prefix_len // bs
        if str(k.dtype) == "bfloat16":
            # frame-safe transport form; _exec_import views it back
            k = k.view(np.uint16)
            v = v.view(np.uint16)
        payload = {
            "slot": int(slot),
            "length": L,
            "row": np.asarray(row, np.int32),
            "phys": np.asarray(row[skip:nb], np.int32),
            "k": np.ascontiguousarray(k[:, skip:]),
            "v": np.ascontiguousarray(v[:, skip:]),
        }
        if k_scale is not None:
            if str(k_scale.dtype) == "bfloat16":
                k_scale = k_scale.view(np.uint16)
                v_scale = v_scale.view(np.uint16)
            payload["k_scale"] = np.ascontiguousarray(k_scale[:, skip:])
            payload["v_scale"] = np.ascontiguousarray(v_scale[:, skip:])
        if self.spec_draft:
            row_h = self._hist_seed(prompt)
            if first_token is not None:
                row_h[L % self.spec_hist] = int(first_token)
            payload["hist_seed"] = row_h
        if self.spec_method == "heads":
            # carried Medusa hidden (handoff codec v5) — or zeros for a
            # pre-v5 frame: the first verify pass refreshes it, so an old
            # frame only costs the FIRST block's acceptance, never output
            hl = (spec_state or {}).get("hlast")
            payload["hlast"] = (
                np.asarray(hl)
                if hl is not None
                else np.zeros(self.cfg.hidden, np.float32)
            )
        if self.driver is not None:
            self.driver.lead(self._mh_import_key, payload)
        else:
            self._exec_import(payload)
        if self.spec_method == "draft":
            # rebuild the draft pool's context from the carried token
            # history: without it the draft proposes from zero context
            # (output-identical, acceptance-poor) until rows refill
            self.draft_prefill_dispatch(slot, prompt)
        self._pos_ceiling[int(slot)] = L
        self.imports += 1

    @staticmethod
    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def _import_scatter(k, v, pos, table, phys, impk, impv, slot, length, row):
        """Donated in-place scatter of imported blocks + slot pos/table —
        one compiled program per novel-block count, no pool copy."""
        k = k.at[:, phys].set(impk.astype(k.dtype))
        v = v.at[:, phys].set(impv.astype(v.dtype))
        pos = pos.at[slot].set(length)
        table = table.at[slot].set(row)
        return k, v, pos, table

    @staticmethod
    @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
    def _import_scatter_q(
        k, v, ks, vs, pos, table, phys, impk, impv, impks, impvs, slot,
        length, row,
    ):
        """Int8-pool variant: the quantized blocks AND their scales scatter
        verbatim — the handoff's bytes become the pool's bytes."""
        k = k.at[:, phys].set(impk)
        v = v.at[:, phys].set(impv)
        ks = ks.at[:, phys].set(impks.astype(ks.dtype))
        vs = vs.at[:, phys].set(impvs.astype(vs.dtype))
        pos = pos.at[slot].set(length)
        table = table.at[slot].set(row)
        return k, v, ks, vs, pos, table

    @staticmethod
    def _unpack_bf16(arr: np.ndarray, want_dtype) -> np.ndarray:
        if str(want_dtype) == "bfloat16" and arr.dtype == np.uint16:
            import ml_dtypes

            return arr.view(ml_dtypes.bfloat16)
        return arr

    @classmethod
    def _unpack_blocks(cls, arr, pool) -> np.ndarray:
        """A frame's K or V blocks ``(layers, n, block_size, kv_heads,
        head_dim)`` as rows of ``pool`` (:meth:`_fetch_blocks` backwards)."""
        arr = cls._unpack_bf16(np.asarray(arr), pool.dtype)
        return arr.reshape(arr.shape[:3] + pool.shape[3:])

    def _exec_import(self, payload: dict) -> None:
        """Symmetric import body (runs on every slice process): scatter the
        imported blocks (+ scales on an int8 pool) and set the slot's
        pos/table (+ proposer history when speculation is on)."""
        import jax.numpy as jnp

        with self._lock:
            c = self._cache
            slot = int(payload["slot"])
            phys = np.asarray(payload["phys"], np.int32)
            newk, newv = c["k"], c["v"]
            newks, newvs = c.get("k_scale"), c.get("v_scale")
            pos, table = c["pos"], c["table"]
            quant = self.kv_dtype is not None
            k = v = ks = vs = None
            if phys.size:
                k = self._unpack_blocks(payload["k"], newk)
                v = self._unpack_blocks(payload["v"], newv)
                if quant:
                    ks = self._unpack_bf16(
                        np.asarray(payload["k_scale"]), newks.dtype
                    )
                    vs = self._unpack_bf16(
                        np.asarray(payload["v_scale"]), newvs.dtype
                    )
            if phys.size and self.mesh is None:
                # single-device fast path: donated fused scatter (no pool
                # copy; the pool buffers update in place)
                args = (
                    jnp.asarray(phys), jnp.asarray(k), jnp.asarray(v),
                )
                tail = (
                    np.int32(slot), np.int32(payload["length"]),
                    np.asarray(payload["row"], np.int32),
                )
                if quant:
                    (newk, newv, newks, newvs, pos, table) = (
                        GenerativeModel._import_scatter_q(
                            newk, newv, newks, newvs, pos, table,
                            args[0], args[1], args[2],
                            jnp.asarray(ks), jnp.asarray(vs), *tail,
                        )
                    )
                else:
                    newk, newv, pos, table = GenerativeModel._import_scatter(
                        newk, newv, pos, table, *args, *tail
                    )
            else:
                if phys.size:
                    newk = newk.at[:, phys].set(jnp.asarray(k).astype(newk.dtype))
                    newv = newv.at[:, phys].set(jnp.asarray(v).astype(newv.dtype))
                    # the scatter ran outside jit; pin the result back to
                    # the pool's sharding so the donated decode programs
                    # keep their compiled layouts
                    newk = jax.device_put(newk, c["k"].sharding)
                    newv = jax.device_put(newv, c["v"].sharding)
                    if quant:
                        newks = newks.at[:, phys].set(
                            jnp.asarray(ks).astype(newks.dtype)
                        )
                        newvs = newvs.at[:, phys].set(
                            jnp.asarray(vs).astype(newvs.dtype)
                        )
                        newks = jax.device_put(newks, c["k_scale"].sharding)
                        newvs = jax.device_put(newvs, c["v_scale"].sharding)
                pos = pos.at[slot].set(np.int32(payload["length"]))
                table = table.at[slot].set(np.asarray(payload["row"], np.int32))
                if self.mesh is not None:
                    pos = jax.device_put(pos, c["pos"].sharding)
                    table = jax.device_put(table, c["table"].sharding)
            out = dict(c)
            out.update(k=newk, v=newv, pos=pos, table=table)
            if quant:
                out["k_scale"] = newks
                out["v_scale"] = newvs
            if self.spec_draft and "hist_seed" in payload:
                hist = c["hist"].at[int(slot)].set(
                    np.asarray(payload["hist_seed"], np.int32)
                )
                if self.mesh is not None:
                    hist = jax.device_put(hist, c["hist"].sharding)
                out["hist"] = hist
            if "hlast" in payload and "hlast" in c:
                hl = self._unpack_bf16(
                    np.asarray(payload["hlast"]), c["hlast"].dtype
                )
                hlast = c["hlast"].at[int(slot)].set(
                    jnp.asarray(hl).astype(c["hlast"].dtype)
                )
                if self.mesh is not None:
                    hlast = jax.device_put(hlast, c["hlast"].sharding)
                out["hlast"] = hlast
            self._cache = out

    # --------------------------------------------- tiered prefix store
    # (docs/CACHING.md "Tiered prefix store"): demotion catches index
    # evictions into host DRAM; promotion scatters them back; the peer
    # tier exports/installs whole chains across replicas.  Every device
    # touch below happens at a scheduler sync point (reservations and
    # external installs), never inside the fused decode loop, so the
    # ≤1-host-sync-per-block audit holds with tiers on.

    def _demote_and_free(self, shortfall: int) -> None:
        """Evict up to ``shortfall`` blocks' worth of zero-ref prefix
        chains into the free pool, demoting the victims' KV into the
        host-DRAM store first (ONE batched device fetch for the whole
        victim set).  Without the DRAM tier this is plain eviction."""
        if self.prefix_index is None or shortfall <= 0:
            return
        victims = self.prefix_index.evict_entries(shortfall)
        if not victims:
            return
        if self.host_store is not None:
            try:
                phys = np.asarray([b for _k, _d, b in victims], np.int32)
                k, v, ks, vs = self._fetch_blocks(phys)
                # shallowest level first so each chain stays contiguous
                # in the store (a rejected level truncates the chain's
                # tail instead of stranding it)
                order = sorted(
                    range(len(victims)),
                    key=lambda j: (victims[j][0][0], len(victims[j][0][1])),
                )
                rejected: list[tuple] = []
                for i in order:
                    key, depth, _block = victims[i]
                    if any(
                        key[0] == r[0] and key[1].startswith(r[1])
                        for r in rejected
                    ):
                        continue
                    ok = self.host_store.put(
                        key, depth,
                        np.ascontiguousarray(k[:, i]),
                        np.ascontiguousarray(v[:, i]),
                        np.ascontiguousarray(ks[:, i]) if ks is not None else None,
                        np.ascontiguousarray(vs[:, i]) if vs is not None else None,
                    )
                    if not ok:
                        rejected.append(key)
            except Exception:
                log.warning(
                    "generative model %r: DRAM prefix demotion failed; "
                    "dropping %d evicted blocks", self.name, len(victims),
                    exc_info=True,
                )
        self._free_blocks.extend(b for _k, _d, b in victims)

    def _match_tier(
        self, prompt: np.ndarray, n_matched: int, n_promoted: int, salt: bytes
    ) -> str:
        """Which tier satisfied the slot's prefix match: ``peer`` when a
        matched level was installed by a peer pull no admission has used
        yet (the credit is consumed — later hits are plain ``hbm``),
        ``dram`` when levels were promoted from the host store, ``hbm``
        for a plain index match, ``none`` otherwise."""
        if n_matched and self._peer_chains:
            from seldon_core_tpu.cache.tiers import HostPrefixStore

            toks = np.asarray(prompt, np.int32).ravel()
            consumed = False
            for lvl in range(1, n_matched + 1):
                key = HostPrefixStore.level_key(
                    toks, lvl, self.kv_block_size, salt
                )
                if key in self._peer_chains:
                    self._peer_chains.discard(key)
                    consumed = True
            if consumed:
                self.peer_hits += 1
                return "peer"
        if n_promoted:
            return "dram"
        return "hbm" if n_matched else "none"

    def _promote_payload(self, blocks: list, entries: list) -> dict:
        """Stack the store entries' per-block arrays into the scatter
        payload shape ``(layers, n, block_size, kv_heads, head_dim)``."""
        payload = {
            "phys": np.asarray(blocks, np.int32),
            "k": np.ascontiguousarray(np.stack([e[2] for e in entries], 1)),
            "v": np.ascontiguousarray(np.stack([e[3] for e in entries], 1)),
        }
        if self.kv_dtype:
            payload["k_scale"] = np.ascontiguousarray(
                np.stack([e[4] for e in entries], 1)
            )
            payload["v_scale"] = np.ascontiguousarray(
                np.stack([e[5] for e in entries], 1)
            )
        return payload

    @staticmethod
    @partial(jax.jit, donate_argnums=(0, 1))
    def _promote_scatter(k, v, phys, impk, impv):
        """Donated in-place scatter of promoted blocks — no pos/table
        writes (prefill sets those when the slot dispatches)."""
        k = k.at[:, phys].set(impk.astype(k.dtype))
        v = v.at[:, phys].set(impv.astype(v.dtype))
        return k, v

    @staticmethod
    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def _promote_scatter_q(k, v, ks, vs, phys, impk, impv, impks, impvs):
        """Int8-pool variant: quantized blocks AND scales scatter
        verbatim — the store's bytes become the pool's bytes."""
        k = k.at[:, phys].set(impk)
        v = v.at[:, phys].set(impv)
        ks = ks.at[:, phys].set(impks.astype(ks.dtype))
        vs = vs.at[:, phys].set(impvs.astype(vs.dtype))
        return k, v, ks, vs

    def _exec_promote(self, payload: dict) -> None:
        """Scatter promoted/pulled chain blocks into the pool (single
        fused device op; mesh path pins the result back to the pool's
        sharding like :meth:`_exec_import`)."""
        import jax.numpy as jnp

        with self._lock:
            c = self._cache
            phys = np.asarray(payload["phys"], np.int32)
            if not phys.size:
                return
            newk, newv = c["k"], c["v"]
            newks, newvs = c.get("k_scale"), c.get("v_scale")
            quant = self.kv_dtype is not None
            k = self._unpack_blocks(payload["k"], newk)
            v = self._unpack_blocks(payload["v"], newv)
            ks = vs = None
            if quant:
                ks = self._unpack_bf16(
                    np.asarray(payload["k_scale"]), newks.dtype
                )
                vs = self._unpack_bf16(
                    np.asarray(payload["v_scale"]), newvs.dtype
                )
            if self.mesh is None:
                args = (jnp.asarray(phys), jnp.asarray(k), jnp.asarray(v))
                if quant:
                    newk, newv, newks, newvs = (
                        GenerativeModel._promote_scatter_q(
                            newk, newv, newks, newvs,
                            args[0], args[1], args[2],
                            jnp.asarray(ks), jnp.asarray(vs),
                        )
                    )
                else:
                    newk, newv = GenerativeModel._promote_scatter(
                        newk, newv, *args
                    )
            else:
                newk = newk.at[:, phys].set(jnp.asarray(k).astype(newk.dtype))
                newv = newv.at[:, phys].set(jnp.asarray(v).astype(newv.dtype))
                newk = jax.device_put(newk, c["k"].sharding)
                newv = jax.device_put(newv, c["v"].sharding)
                if quant:
                    newks = newks.at[:, phys].set(
                        jnp.asarray(ks).astype(newks.dtype)
                    )
                    newvs = newvs.at[:, phys].set(
                        jnp.asarray(vs).astype(newvs.dtype)
                    )
                    newks = jax.device_put(newks, c["k_scale"].sharding)
                    newvs = jax.device_put(newvs, c["v_scale"].sharding)
            out = dict(c)
            out.update(k=newk, v=newv)
            if quant:
                out["k_scale"] = newks
                out["v_scale"] = newvs
            self._cache = out

    def export_prefix_kv(
        self,
        tokens: np.ndarray,
        adapter: str | None = None,
        max_blocks: int = 64,
    ) -> tuple | None:
        """Serve a peer's prefix pull: the longest chain this replica
        holds for ``tokens`` (HBM index levels, extended by contiguous
        DRAM-store levels), as ``(depth, k, v, k_scale, v_scale)`` with
        KV shaped ``(layers, depth, block_size, kv_heads, head_dim)``.
        Returns None on no match — including a wrong-adapter probe, whose
        salt never matches the exporting adapter's chains.  HBM levels
        are REF-PINNED for the duration of the device fetch, so a
        concurrent admission's eviction cannot free or demote them
        mid-export."""
        if self._multihost or self.prefix_index is None:
            return None
        from seldon_core_tpu.cache.prefix import adapter_salt

        salt = adapter_salt(adapter)
        tokens = np.asarray(tokens, np.int32).ravel()
        cap = min(
            int(max_blocks),
            int(tokens.size) // self.kv_block_size,
            self.max_blocks_per_slot,
        )
        if cap < 1:
            return None
        k = v = ks = vs = None
        pinned = self.prefix_index.acquire(tokens, cap, salt=salt)
        depth = len(pinned)
        if pinned:
            try:
                phys = np.asarray([b for _k, _d, b in pinned], np.int32)
                k, v, ks, vs = self._fetch_blocks(phys)
            finally:
                self.prefix_index.release(tokens, depth, salt=salt)
        if self.host_store is not None and depth < cap:
            # DRAM levels that contiguously extend the HBM chain ride the
            # same frame — the puller sees one deeper chain
            ext = self.host_store.match(tokens, depth + 1, cap, salt=salt)
            if ext:
                ek = np.stack([e[2] for e in ext], 1)
                ev = np.stack([e[3] for e in ext], 1)
                k = ek if k is None else np.concatenate([k, ek], axis=1)
                v = ev if v is None else np.concatenate([v, ev], axis=1)
                if self.kv_dtype:
                    eks = np.stack([e[4] for e in ext], 1)
                    evs = np.stack([e[5] for e in ext], 1)
                    ks = eks if ks is None else np.concatenate([ks, eks], 1)
                    vs = evs if vs is None else np.concatenate([vs, evs], 1)
                depth += len(ext)
        if not depth:
            return None
        self.peer_serves += 1
        return depth, k, v, ks, vs

    def install_prefix_chain(
        self,
        tokens: np.ndarray,
        k: np.ndarray,
        v: np.ndarray,
        k_scale: "np.ndarray | None" = None,
        v_scale: "np.ndarray | None" = None,
        adapter: str | None = None,
    ) -> int:
        """Install a peer-pulled prefix chain into the pool + index
        (called from the scheduler's sync point, never concurrently with
        an admission).  Only levels deeper than what is already resident
        are installed, as ZERO-REF index entries — evictable like any
        absorbed prompt.  Returns the number of levels installed; any
        failure frees every block it took (zero leaks) and the caller
        falls back to plain prefill."""
        self._kv_alone("a peer prefix install")
        if self.prefix_index is None:
            raise GraphUnitError(
                f"model {self.name!r} has no prefix index to install into"
            )
        if self._multihost:
            raise GraphUnitError(
                "peer prefix install is not supported on a multi-host slice"
            )
        from seldon_core_tpu.cache.prefix import adapter_salt
        from seldon_core_tpu.cache.tiers import HostPrefixStore

        if adapter and (
            self.lora_pool is None or adapter not in self.lora_pool
        ):
            raise GraphUnitError(
                f"pulled chain names adapter {adapter!r} but it is not "
                "resident on this pool"
            )
        tokens = np.asarray(tokens, np.int32).ravel()
        bs = self.kv_block_size
        k = np.asarray(k)
        v = np.asarray(v)
        depth = int(k.shape[1]) if k.ndim == 5 else -1
        expect = (
            self.cfg.n_layers, depth, bs, self.cfg.n_kv_heads,
            self.cfg.head_dim,
        )
        if depth < 1 or tuple(k.shape) != expect or tuple(v.shape) != expect:
            raise GraphUnitError(
                f"pulled chain KV shape {tuple(k.shape)} does not match "
                f"this pool's {expect} (config or block-size skew)"
            )
        if int(tokens.size) < depth * bs:
            raise GraphUnitError("pulled chain tokens do not cover its blocks")
        if bool(self.kv_dtype) != (k_scale is not None):
            raise GraphUnitError(
                f"pulled chain dtype skew: pool is "
                f"{self.kv_dtype or 'float'} but the frame "
                f"{'carries' if k_scale is not None else 'lacks'} int8 "
                "scales"
            )
        salt = adapter_salt(adapter)
        have = self.prefix_index.peek_depth(tokens, depth, salt=salt)
        if have >= depth:
            return 0
        n_new = depth - have
        if len(self._free_blocks) < n_new:
            self._demote_and_free(n_new - len(self._free_blocks))
        if len(self._free_blocks) < n_new:
            return 0  # pool too hot to cache a pull; nothing taken
        got = self._free_blocks[-n_new:]
        del self._free_blocks[-n_new:]
        try:
            payload = {
                "phys": np.asarray(got, np.int32),
                "k": np.ascontiguousarray(k[:, have:]),
                "v": np.ascontiguousarray(v[:, have:]),
            }
            if k_scale is not None:
                payload["k_scale"] = np.ascontiguousarray(
                    np.asarray(k_scale)[:, have:]
                )
                payload["v_scale"] = np.ascontiguousarray(
                    np.asarray(v_scale)[:, have:]
                )
            self._exec_promote(payload)
            rejected = self.prefix_index.insert(tokens, got, have, salt=salt)
        except Exception:
            self._free_blocks.extend(got)
            raise
        if rejected:
            # level raced into the index between peek and insert (no such
            # caller today — installs and admissions share the sync
            # point); the duplicate blocks are unreferenced, free them
            self._free_blocks.extend(rejected)
        absorbed = n_new - len(rejected)
        for lvl in range(have + 1, depth + 1):
            self._peer_chains.add(
                HostPrefixStore.level_key(tokens, lvl, bs, salt)
            )
        self.peer_installs += absorbed
        return absorbed

    def admit_dispatch(
        self,
        slot: int,
        prompt: np.ndarray,
        temperature: float,
        seed: int,
        reserve_tokens: int = 0,
        adapter: str | None = None,
    ):
        """Enqueue one prefill WITHOUT fetching its sampled token (a device
        array is returned).  Several admissions dispatched back-to-back cost
        ONE host round trip when their tokens are fetched together —
        a fetch per admit would stall the host once per admission.
        ``reserve_tokens`` sizes the block reservation beyond the
        prompt (the request's max_new_tokens); ``adapter`` binds the slot
        to a resident LoRA adapter for the request's lifetime."""
        prompt = np.asarray(prompt, np.int32).ravel()
        L = prompt.shape[0]
        if L < 1:
            raise GraphUnitError("empty prompt")
        if self.prefill_chunk and L > self.prefill_chunk:
            # chunked admission, dispatched back-to-back (callers that can
            # interleave — the scheduler — use admit_chunk_plan directly
            # and pace one chunk per decode sync point instead)
            plan = self.admit_chunk_plan(
                slot, prompt, temperature, seed, reserve_tokens,
                adapter=adapter,
            )
            tok = None
            for i in range(len(plan["payloads"])):
                tok = self.prefill_chunk_dispatch(plan, i)
            return tok
        blocks_row, prefix_len = self.reserve_for_prompt(
            slot, prompt, L + max(0, int(reserve_tokens)), adapter=adapter
        )
        self._pos_ceiling[int(slot)] = L  # prefill wrote rows [0, L)
        if prefix_len > 0:
            # KV prefix reuse: prefill only the novel suffix; the reused
            # blocks already hold K/V for [0, prefix_len)
            suffix = prompt[prefix_len:]
            bucket = self.fit_bucket(suffix.size)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : suffix.size] = suffix
            bs = self.kv_block_size
            pb = prefix_len // bs
            lb = bucket // bs
            suffix_blocks = np.zeros(lb, np.int32)
            avail = blocks_row[pb : pb + lb]
            suffix_blocks[: avail.size] = avail  # overflow pads -> sink 0
            payload = {
                "padded": padded,
                "prefix_len": prefix_len,
                "length": L,
                "slot": int(slot),
                "blocks": blocks_row,
                "suffix_blocks": suffix_blocks,
                "window": self._prefix_window(prefix_len),
                "temperature": float(temperature),
                "seed": int(seed),
            }
            if self._lora is not None:
                payload["aid"] = int(self._slot_aidx[int(slot)])
            if self.spec_draft:
                payload["hist_seed"] = self._hist_seed(prompt)
            if self.spec_method == "draft":
                # draft pool has no prefix reuse: it prefills the FULL
                # prompt (the draft model is tiny; correctness is
                # unaffected either way)
                self.draft_prefill_dispatch(slot, prompt)
            if self.driver is not None:
                return self.driver.lead(self._mh_prefill_suffix_key, payload)
            return self._exec_prefill_suffix(payload)
        bucket = self.fit_bucket(L)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = prompt
        payload = {
            "padded": padded,
            "length": L,
            "slot": int(slot),
            "blocks": blocks_row,
            "temperature": float(temperature),
            "seed": int(seed),
        }
        if self._lora is not None:
            payload["aid"] = int(self._slot_aidx[int(slot)])
        if self.spec_draft:
            payload["hist_seed"] = self._hist_seed(prompt)
        if self.spec_method == "draft":
            self.draft_prefill_dispatch(slot, prompt)
        if self.driver is not None:
            return self.driver.lead(self._mh_prefill_key, payload)
        return self._exec_prefill(payload)

    # ------------------------------------------------------ chunked prefill

    def admit_chunk_plan(
        self,
        slot: int,
        prompt: np.ndarray,
        temperature: float,
        seed: int,
        reserve_tokens: int = 0,
        adapter: str | None = None,
    ) -> dict:
        """Reserve ``slot``'s blocks and lay out the admission as a list of
        prefill-chunk payloads (docs/PERFORMANCE.md §7).  Nothing touches
        the device here: the scheduler dispatches one chunk per decode sync
        point via :meth:`prefill_chunk_dispatch`, so a long prompt can
        never stall in-flight streams for more than one chunk's latency.
        KV prefix reuse composes — a matched prefix skips its chunks
        entirely and only the novel suffix is chunked.  The written K/V and
        the first sampled token are bit-identical to the monolithic prefill
        (every chunk past the first is the pinned-equal suffix program over
        the slot's own blocks; the final chunk samples with the admission's
        seed exactly like the monolithic program)."""
        prompt = np.asarray(prompt, np.int32).ravel()
        L = int(prompt.size)
        if L < 1:
            raise GraphUnitError("empty prompt")
        blocks_row, prefix_len = self.reserve_for_prompt(
            slot, prompt, L + max(0, int(reserve_tokens)), adapter=adapter
        )
        self._pos_ceiling[int(slot)] = L
        C = self.prefill_chunk or L
        spans = []
        s = prefix_len
        while s < L:
            e = min(s + C, L)
            spans.append((s, e))
            s = e
        bs = self.kv_block_size
        payloads: list[tuple[str, dict]] = []
        for idx, (s, e) in enumerate(spans):
            seg = prompt[s:e]
            bucket = self.fit_bucket(seg.size)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : seg.size] = seg
            meta = {
                "i": idx,
                "last": idx == len(spans) - 1,
                "reused": prefix_len > 0,
            }
            if s == 0:
                payloads.append(("prefill", {
                    "padded": padded,
                    "length": int(e),
                    "slot": int(slot),
                    "blocks": blocks_row,
                    "temperature": float(temperature),
                    "seed": int(seed),
                    "chunk": meta,
                }))
            else:
                pb = s // bs
                lb = bucket // bs
                suffix_blocks = np.zeros(lb, np.int32)
                avail = blocks_row[pb : pb + lb]
                suffix_blocks[: avail.size] = avail  # overflow pads -> sink
                payloads.append(("suffix", {
                    "padded": padded,
                    "prefix_len": int(s),
                    "length": int(e),
                    "slot": int(slot),
                    "blocks": blocks_row,
                    "suffix_blocks": suffix_blocks,
                    "window": self._prefix_window(s),
                    "temperature": float(temperature),
                    "seed": int(seed),
                    "chunk": meta,
                }))
            if self._lora is not None:
                payloads[-1][1]["aid"] = int(self._slot_aidx[int(slot)])
            if self.spec_draft:
                payloads[-1][1]["hist_seed"] = self._hist_seed(prompt[:e])
        return {"slot": int(slot), "payloads": payloads,
                "prefix_len": prefix_len,
                "prompt": prompt if self.spec_method == "draft" else None}

    def prefill_chunk_dispatch(self, plan: dict, i: int):
        """Dispatch chunk ``i`` of an :meth:`admit_chunk_plan` admission.
        Returns the chunk's sampled token as a DEVICE array — only the
        final chunk's is the request's real first token; intermediate
        chunks' samples are discarded unfetched, so chunking adds zero host
        syncs over the monolithic path."""
        kind, payload = plan["payloads"][i]
        if i == 0 and self.spec_method == "draft":
            # one full-prompt draft prefill rides the first chunk: the
            # draft model is ~n_layers/8 of the target, so it does not
            # reintroduce the stall chunking removed — and deferring it
            # (arbiter) stays an acceptance-only decision
            self.draft_prefill_dispatch(plan["slot"], plan["prompt"])
        if kind == "prefill":
            if self.driver is not None:
                return self.driver.lead(self._mh_prefill_key, payload)
            return self._exec_prefill(payload)
        if self.driver is not None:
            return self.driver.lead(self._mh_prefill_suffix_key, payload)
        return self._exec_prefill_suffix(payload)

    def _hist_seed(self, prompt: np.ndarray) -> np.ndarray:
        """Host-side proposer-ring row for an admission: the prompt tail at
        its ``p % H`` rows (the first sampled token lands in-program)."""
        from seldon_core_tpu.executor.speculative import seed_history

        return seed_history(prompt, self.spec_hist)

    # ---------------------------------------------- device-frontier stats

    def kv_bytes_per_block(self) -> int:
        """HBM bytes one KV block costs in this pool's layout (scales
        included on an int8 pool) — sizes the HBM tier's byte telemetry."""
        return sum(
            int(self._cache[key].nbytes) // self.kv_blocks
            for key in self._pool_names + ("k_scale", "v_scale")
            if key in self._cache
        )

    def kv_bytes_per_slot(self) -> int:
        """HBM bytes one max_seq slot costs in this pool's layout."""
        fam = self.family
        if hasattr(fam, "paged_kv_slot_bytes"):
            dt = str(self._cache["k_scale"].dtype) if self.kv_dtype else str(
                self._cache[self._pool_names[0]].dtype
            )
            return int(
                fam.paged_kv_slot_bytes(
                    self.cfg, self.kv_block_size, kv_dtype=self.kv_dtype,
                    dtype=dt,
                )
            )
        return self.kv_bytes_per_block() * self.max_blocks_per_slot

    def kv_slots_per_chip(self, hbm_bytes: int | None = None) -> int:
        """Max-seq sequences this pool layout fits per chip after the
        weights — the capacity number int8 quantization ~doubles.  The HBM
        budget defaults to ``SCT_HBM_GB`` (16 GiB, a v5e chip)."""
        if hbm_bytes is None:
            hbm_bytes = int(
                float(os.environ.get("SCT_HBM_GB", "16")) * (1 << 30)
            )
        return max(
            0, int((hbm_bytes - self.param_bytes) // self.kv_bytes_per_slot())
        )

    def reservation_snapshot(self, slot: int) -> dict | None:
        """Host-side reservation bookkeeping for ``slot`` (None when it
        holds none) — feeds the timeline ledger's admit event with the
        prefix-reuse depth and block split, from values the host already
        holds (no device touch)."""
        slot = int(slot)
        if self._slot_row.get(slot) is None:
            return None
        matched = self._slot_matched.get(slot, 0)
        promoted = self._slot_promoted.get(slot, 0)
        return {
            "blocks_reused": matched,
            "blocks_promoted": promoted,
            "blocks_allocated": len(self._slot_blocks.get(slot, ())),
            "prefix_tokens": (matched + promoted) * self.kv_block_size,
            # which tier satisfied the prefix match (hbm/dram/peer/none)
            "tier": self._slot_tier.get(slot, "none"),
        }

    def pool_snapshot(self) -> dict:
        """The KV/HBM pool ledger (docs/OBSERVABILITY.md): block occupancy
        by holder (free / prefix index / slot reservations), high-water
        mark, byte classes (weights / KV pool / int8 scales), and the
        prefix-index churn counters.  Also refreshes the ``seldon_kv_*``
        gauges — called at /stats/breakdown and /prometheus scrape time,
        never on the decode hot path."""
        total = self.kv_blocks - 1
        free = len(self._free_blocks)
        prefix_held = len(self.prefix_index) if self.prefix_index is not None else 0
        slot_held = sum(len(b) for b in self._slot_blocks.values())
        kv_bytes = self._pool_bytes()
        scale_bytes = (
            int(self._cache["k_scale"].nbytes) + int(self._cache["v_scale"].nbytes)
            if "k_scale" in self._cache
            else 0
        )
        host_snap = None
        if self.host_store is not None:
            from seldon_core_tpu.executor.memory import host_memory

            host_snap = host_memory().snapshot()
        snap = {
            "blocks": {
                "total": total,
                "free": free,
                "prefix_index": prefix_held,
                "slots": slot_held,
                "high_water": self._blocks_high_water,
                "block_size": self.kv_block_size,
            },
            "bytes": {
                "weights": self.param_bytes,
                "kv_pool": kv_bytes,
                "kv_scales": scale_bytes,
                **self._slot_state_class(),
                "adapter_pool": self.lora_bytes,
                "prefix_dram": (
                    self.host_store.bytes if self.host_store is not None else 0
                ),
                "per_slot": self.kv_bytes_per_slot(),
            },
            # chip-level arbitration (executor/memory.py): every resident
            # deployment's classes against the shared HBM budget
            "hbm": self.memory.snapshot(),
            # host-DRAM arbitration for the tiered prefix store
            "host": host_snap,
            "prefix_evictions": (
                self.prefix_index.evicted if self.prefix_index is not None else 0
            ),
            "prefix_insertions": (
                self.prefix_index.inserted if self.prefix_index is not None else 0
            ),
        }
        m = DEFAULT_METRICS
        for state, val in (
            ("free", free),
            ("prefix_index", prefix_held),
            ("slots", slot_held),
        ):
            m.kv_blocks.labels(self.name, state).set(val)
        m.kv_blocks_high_water.labels(self.name).set(self._blocks_high_water)
        for cls, val in (
            ("weights", self.param_bytes),
            ("kv_pool", kv_bytes),
            ("kv_scales", scale_bytes),
            ("adapter_pool", self.lora_bytes),
            *self._slot_state_class().items(),
        ):
            m.kv_bytes.labels(self.name, cls).set(val)
        m.kv_prefix_evictions.labels(self.name).set(snap["prefix_evictions"])
        return snap

    def program_snapshot(self) -> dict:
        """Program-cache telemetry: hits vs fresh compiles across the
        dict-cached program families, per-variant compile seconds (warmup
        or first serving call), and the bounded recent-compiles ring —
        ``warmup: false`` entries are the mid-traffic recompiles that also
        produced a ``program.compile`` span."""
        return {
            "compiles": self.program_compiles,
            "hits": self.program_hits,
            "cached": (
                1  # the monolithic prefill program
                + len(self._decode_jit)
                + len(self._decode_k_jit)
                + len(self._prefill_suffix_jit)
            ),
            "variant_seconds": dict(self.warmup_program_seconds),
            "recent_compiles": list(self._program_events),
            # the tiled prompt kernel's grid by traced shape (this process's
            # calls): steps taken, tiles multiplied, tiles masked; and over
            # the prompts admitted (warm-up's left out) the steps of their
            # rungs and the tiles their real lengths left to multiply
            "tile_plans": {
                **{k: dict(v) for k, v in TILE_PLANS.items()},
                "admitted": dict(self.prefill_tiles),
            },
        }

    def spec_snapshot(self) -> dict:
        """Device-frontier state for ``GET /stats/breakdown`` and bench:
        speculation acceptance + quantized-pool capacity accounting."""
        ratio = (
            self.spec_emitted_tokens / self.spec_verify_passes
            if self.spec_verify_passes
            else None
        )
        return {
            "spec_draft": self.spec_draft,
            "spec_ngram": self.spec_ngram if self.spec_draft else None,
            "spec_hist": self.spec_hist if self.spec_draft else None,
            # learned speculation (docs/PERFORMANCE.md §6): which proposer
            # this deployment runs + its geometry, and the acceptance
            # ledger keyed by it — one deployment runs ONE proposer, so
            # the per-method split is the labeled ledger itself
            "spec_method": self.spec_method,
            "spec_heads": self.spec_heads or None,
            "spec_draft_model": (
                f"{self._draft_geom[0]}:{self._draft_geom[1]}"
                if self._draft_geom else None
            ),
            "spec_verify_passes": self.spec_verify_passes,
            "spec_emitted_tokens": self.spec_emitted_tokens,
            "accepted_tokens_per_step": (
                round(ratio, 4) if ratio is not None else None
            ),
            "accepted_tokens_per_step_by_method": (
                {
                    self.spec_method: round(ratio, 4),
                }
                if ratio is not None and self.spec_method
                else {}
            ),
            "kv_dtype": self.kv_dtype or str(self._cache[self._pool_names[0]].dtype),
            "kv_bytes_per_slot": self.kv_bytes_per_slot(),
            "kv_slots_per_chip": self.kv_slots_per_chip(),
            # chunked prefill + decode kernel state (docs/PERFORMANCE.md §7)
            "prefill_chunk": self.prefill_chunk or None,
            "prefill_chunks": self.prefill_chunks,
            # what the ladder's padding costs: tokens of prompts and
            # suffixes prefilled, the sum of the rungs they ran in, and the
            # dispatches of each rung (warm-up's left out)
            "prefill_rows": {
                **self.prefill_rows,
                "by_rung": dict(self.prefill_rows["by_rung"]),
            },
            "decode_kernel": self.decode_kernel,
            # what the decode programs were built with, and the share of
            # its window the read has to touch (live / window)
            "decode_read": "kernel" if self.decode_kernel else "gather",
            "kv_blocks_live": self.kv_blocks_live,
            "kv_blocks_window": self.kv_blocks_window,
            # the tile the kernel ran: key rows a step, from the pool's shape
            "decode_tile_rows": self.decode_tile_rows(),
            # which product a prompt's expert layer runs, rung by rung
            "prefill_experts": self.prefill_experts(),
            # the leaves the family's ``pack_params`` re-laid out at build
            "params_packed": self.params_packed(),
            # the family's own device counters (routing of an expert
            # layer), as of the last fetched decode block
            "counters": self.counters_snapshot(),
            # batched multi-LoRA (docs/MULTITENANT.md): the adapter-pool
            # ledger — resident/evicted counts, bytes, per-adapter slot
            # occupancy and tokens served
            "lora_rank": self.lora_rank or None,
            "adapters": self.adapters_snapshot(),
            # per-slot inter-token latency (scheduler delivery gaps): the
            # number TTFT/device-step histograms cannot see — a prefill
            # stalling the decode pipeline lands here
            "itl_p50_ms": (
                round(self._itl_pct(50) * 1e3, 3)
                if self._itl else None
            ),
            "itl_p99_ms": (
                round(self._itl_pct(99) * 1e3, 3)
                if self._itl else None
            ),
            "itl_samples": len(self._itl),
            # generation-forensics ledgers (docs/OBSERVABILITY.md): KV/HBM
            # pool occupancy + byte classes, and program-cache churn
            "pool": self.pool_snapshot(),
            "programs": self.program_snapshot(),
            # per-deployment isolation ledgers (docs/PACKING.md): THIS
            # model's rows from the HBM and host-DRAM byte ledgers — on a
            # packed chip they prove byte-level isolation per co-tenant
            "memory": self.memory_snapshot(),
        }

    def memory_snapshot(self) -> dict:
        """This deployment's rows in the chip-wide byte ledgers."""
        from seldon_core_tpu.executor.memory import host_memory

        return {
            "owner": self._mem_key,
            "hbm": self.memory.snapshot()["owners"].get(self._mem_key),
            "host": host_memory().snapshot()["owners"].get(self._mem_key),
        }

    def _prefix_window(self, prefix_len: int) -> int:
        """Smallest power-of-two multiple of the block size covering
        ``prefix_len`` (static per compiled suffix program), capped at
        max_seq."""
        w = self.kv_block_size
        while w < prefix_len:
            w *= 2
        return min(w, self.cfg.max_seq)

    def _exec_prefill_suffix(self, payload: dict):
        """Symmetric suffix-prefill body (runs on every slice process)."""
        bucket = int(payload["padded"].shape[1])
        window = int(payload["window"])
        label = f"suffix:b{bucket}:w{window}{self.variant_sfx}"
        key = (bucket, window) + self._program_config
        fn = self._prefill_suffix_jit.get(key)
        fresh = fn is None
        if fresh:
            fn = jax.jit(
                self._prefill_suffix_factory(window), donate_argnums=(12,)
            )
            self._prefill_suffix_jit[key] = fn
            self.program_compiles += 1
        else:
            self.program_hits += 1
        with self._lock:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                tok, self._cache = fn(
                    self.params,
                    payload["padded"],
                    np.int32(payload["prefix_len"]),
                    np.int32(payload["length"]),
                    np.int32(payload["slot"]),
                    np.asarray(payload["blocks"], np.int32),
                    np.asarray(payload["suffix_blocks"], np.int32),
                    np.float32(payload["temperature"]),
                    np.int32(payload["seed"]),
                    np.asarray(
                        payload.get("hist_seed", _NO_HIST), np.int32
                    ),
                    self._aid_scalar(payload),
                    self._lora,
                    self._cache,
                )
            if fresh:
                self._note_compile(label, time.perf_counter() - t0)
            self._count_prefill(payload, reused=True)
        return tok

    def admit(
        self,
        slot: int,
        prompt: np.ndarray,
        temperature: float,
        seed: int,
        reserve_tokens: int = 0,
    ) -> int:
        """Prefill ``prompt`` (1-D int ids) into ``slot``; returns the first
        sampled token."""
        return int(
            self.admit_dispatch(slot, prompt, temperature, seed, reserve_tokens)
        )

    def _exec_embed(self, payload: dict):
        """Pooled-embedding forward body (runs on every slice process)."""
        tokens = np.asarray(payload["padded"], np.int32)
        bucket = int(tokens.shape[1])
        label = f"embed:b{bucket}{self.variant_sfx}"
        fresh = bucket not in self._embed_buckets_seen
        if fresh:
            self._embed_buckets_seen.add(bucket)
            self.program_compiles += 1
        else:
            self.program_hits += 1
        with self._lock:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                vec = self._embed_jit(
                    self.params, tokens, np.int32(payload["length"])
                )
            if fresh:
                self._note_compile(label, time.perf_counter() - t0)
            self.embeds += 1
        return vec

    def embed_dispatch(self, prompt: np.ndarray):
        """Enqueue one pooled-embedding forward; returns the (E,) device
        vector WITHOUT fetching (the scheduler batches fetches across the
        embed wave — one sync for N dispatches)."""
        if not hasattr(self.family, "embed_pooled"):
            raise GraphUnitError(
                f"generative family {self.family.__name__} has no "
                "pooled-embedding path"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise GraphUnitError("empty prompt")
        L = int(prompt.size)
        bucket = self.fit_bucket(L)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = prompt
        payload = {"padded": padded, "length": L}
        if self.driver is not None:
            return self.driver.lead(self._mh_embed_key, payload)
        return self._exec_embed(payload)

    def embed(self, prompt: np.ndarray) -> np.ndarray:
        """Fetch one prompt's mean-pooled final hidden state (E,) float32."""
        vec = self.embed_dispatch(prompt)
        # sct: host-sync-ok unbatched embed fetch
        return np.asarray(jax.device_get(vec), np.float32)

    def decode_tile_rows(self) -> int | None:
        """Key rows one step of the paged decode kernel attends for this
        unit's K/V pool: what ``ops/paged_attention.py::blocks_per_step``,
        the function the kernel itself asks, says of the pool's own block
        size, row and dtype.  None where the decode read is the gather, or
        the pool holds no K by head (``kimi_k2``'s latent rows are read by
        a kernel of their own)."""
        k = self._cache.get("k")
        if not self.decode_kernel or k is None:
            return None
        bs, row = k.shape[2:]  # one device: a row holds its heads side by side
        return blocks_per_step(bs, row * k.dtype.itemsize) * bs

    def prefill_experts(self) -> dict | None:
        """Which product the routed expert layer of a prompt runs at each
        rung of the ladder (by the rung's rows: ``"in_place"``,
        ``"ragged_dot"``, ``"touched"`` or ``"dense"``): what
        ``models/moe.py::experts_plan`` told the rung's program as it was
        traced (``moe.PLANS``, written by ``routed_experts``), so None for a
        rung no program has been traced for yet.  None for a family without
        routed experts (its ``COUNTERS`` do not start with ``moe``'s)."""
        from seldon_core_tpu.models import moe

        names = tuple(getattr(self.family, "COUNTERS", ()))
        if names[:len(moe.COUNTERS)] != moe.COUNTERS:
            return None
        share = f":K{self.cfg.experts_per_tok}:X{self.cfg.held[1]}"
        return {  # a JSON object's keys are strings
            str(b): moe.PLANS.get(f"prefill:T{b}{share}") for b in self.prefill_buckets
        }

    def params_packed(self) -> dict:
        """The leaves the family's ``pack_params`` lays out (its ``PACKED``),
        by path, with the shapes they have in the tree the programs are
        handed, read once at build; ``{}`` for a family without the hook."""
        return self._params_packed

    def _note_read(self, active: np.ndarray, window: int) -> None:
        """Bump the decode read's two block counters for one dispatch."""
        bs = self.kv_block_size
        act = np.asarray(active, bool)
        self.kv_blocks_live += int((-(-self._pos_ceiling[act] // bs)).sum())
        self.kv_blocks_window += self.n_slots * (int(window) // bs)

    def _window_for(self, active: np.ndarray, extra: int) -> int:
        """Smallest power-of-two cache window covering every ACTIVE slot's
        position ceiling after ``extra`` more tokens (min 64, capped at
        max_seq).  Computed on the coordinator and shipped in the payload so
        every host compiles the same static shape.  The paged kernel reads
        by each slot's own position, whatever the window: one program, at
        max_seq."""
        if self.decode_kernel:
            return self.cfg.max_seq
        act = np.asarray(active, bool)
        hi = int(self._pos_ceiling[act].max()) if act.any() else 0
        need = hi + extra + 1
        w = 64
        while w < need:
            w *= 2
        return min(w, self.cfg.max_seq)

    def _exec_decode(self, payload: dict):
        window = int(payload.get("window") or self.cfg.max_seq)
        label = f"decode:w{window}{self.variant_sfx}"
        key = (window,) + self._program_config
        fn = self._decode_jit.get(key)
        fresh = fn is None
        if fresh:
            fn = jax.jit(self._decode_factory(window), donate_argnums=(7,))
            self._decode_jit[key] = fn
            self.program_compiles += 1
        else:
            self.program_hits += 1
        with self._lock:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                res = fn(
                    self.params,
                    np.asarray(payload["tokens"], np.int32),
                    np.asarray(payload["active"], bool),
                    np.asarray(payload["temperature"], np.float32),
                    np.int32(payload["seed"]),
                    self._aid_vec(payload),
                    self._lora,
                    self._cache,
                )
            if self.conf_signal:
                toks, conf, self._cache = res
            else:
                toks, self._cache = res
                conf = None
            if fresh:
                self._note_compile(label, time.perf_counter() - t0)
            self.steps += 1
        return (toks, conf) if self.conf_signal else toks

    def step(
        self,
        tokens: np.ndarray,
        active: np.ndarray,
        temperature: np.ndarray,
        seed: int,
        window: int | None = None,
    ) -> np.ndarray:
        """One decode step for all slots -> next token per slot (S,)."""
        payload = {
            "tokens": np.asarray(tokens, np.int32),
            "active": np.asarray(active, bool),
            "temperature": np.asarray(temperature, np.float32),
            "seed": int(seed),
            "window": window or self._window_for(active, 1),
        }
        if self._lora is not None:
            payload["aid"] = self._slot_aidx.copy()
        self._note_read(active, payload["window"])
        t0 = time.perf_counter()
        if self.driver is not None:
            res = self.driver.lead(self._mh_decode_key, payload)
        else:
            res = self._exec_decode(payload)
        self._pos_ceiling[np.asarray(active, bool)] += 1
        if self.conf_signal:
            # tokens + confidence margins ride ONE fetch: the single-step
            # audit budget (one sync per step) holds with cascades on
            toks, conf = res
            # sct: host-sync-ok unfused single-step fetch
            out_np, conf_np = jax.device_get((toks, conf))
            # sct: host-sync-ok host copies of the fetch above, no new sync
            out = np.asarray(out_np)
            # sct: host-sync-ok host copy of the fetch above, no new sync
            self.last_conf_seq = np.asarray(conf_np, np.float32)[None]
        else:
            out = np.asarray(  # sct: host-sync-ok unfused single-step fetch
                jax.device_get(res)
            )
            self.last_conf_seq = None
        self._record_step(time.perf_counter() - t0)
        return out

    def step_k(
        self,
        tokens: np.ndarray,
        active: np.ndarray,
        temperature: np.ndarray,
        seed: int,
        eos: np.ndarray,
        remaining: np.ndarray,
        k: int,
        window: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """``k`` decode steps in one dispatch -> ``(k, S)`` sampled tokens
        plus the ``(k, S)`` was-active-at-step mask that says which of them
        are real.  ``eos`` is per-slot (-1 = none), ``remaining`` the
        per-slot token budget — both enforced on device so a slot stops
        consuming cache the step it finishes."""
        return self.step_k_fetch(
            self.step_k_dispatch(
                tokens, active, temperature, seed, eos, remaining, k,
                window=window,
            )
        )

    def step_k_dispatch(
        self,
        tokens: np.ndarray,
        active: np.ndarray,
        temperature: np.ndarray,
        seed: int,
        eos: np.ndarray,
        remaining: np.ndarray,
        k: int,
        window: int | None = None,
    ) -> tuple:
        """Enqueue one k-step decode block WITHOUT fetching its tokens (JAX
        dispatch is async: this returns device arrays immediately).  The
        handle goes to :meth:`step_k_fetch`; between the two the host is
        free to deliver the previous block's tokens — and, in steady state,
        to dispatch the NEXT block from the on-device carry
        (:meth:`step_k_continue`) so the chip never idles on the host."""
        payload = {
            "tokens": np.asarray(tokens, np.int32),
            "active": np.asarray(active, bool),
            "temperature": np.asarray(temperature, np.float32),
            "seed": int(seed),
            "eos": np.asarray(eos, np.int32),
            "remaining": np.asarray(remaining, np.int32),
            "k": int(k),
            # a speculative block can emit up to k * (1 + draft) tokens —
            # the window must cover the ceiling either way
            "window": window or self._window_for(active, k * self._tps),
        }
        if self._lora is not None:
            payload["aid"] = self._slot_aidx.copy()
        t0 = time.perf_counter()
        if self.driver is not None:
            res = self.driver.lead(self._mh_decode_k_key, payload)
        else:
            res = self._exec_decode_k(payload)
        toks_seq, act_seq = res[0], res[1]
        conf_seq = res[2] if len(res) > 2 else None
        act = np.asarray(active, bool)
        with self._lock:
            self._pos_ceiling[act] += k * self._tps
        self._note_read(act, payload["window"])
        return (toks_seq, act_seq, conf_seq, t0, act, int(k), self._ctr_dev)

    def step_k_continue(
        self, active: np.ndarray, seed: int, k: int, window: int | None = None
    ) -> tuple:
        """Dispatch the next k-step block straight from the previous
        block's on-device ``(tokens, active, remaining)`` carry — no host
        round trip touches the critical path.  The caller guarantees no
        host-side state changed since that block was dispatched (no
        admission, no reap, no slot release); eos/budget transitions are
        already device-visible, so a slot that finished mid-block simply
        rides along inactive (its writes go to the sink block)."""
        payload = {
            "k": int(k),
            "seed": int(seed),
            "window": window or self._window_for(active, k * self._tps),
        }
        t0 = time.perf_counter()
        if self.driver is not None:
            res = self.driver.lead(self._mh_decode_cont_key, payload)
        else:
            res = self._exec_decode_cont(payload)
        toks_seq, act_seq = res[0], res[1]
        conf_seq = res[2] if len(res) > 2 else None
        act = np.asarray(active, bool)
        # under the lock: a block dispatched behind one that is about to
        # end shares the ceiling with that block's fetch, on another thread
        with self._lock:
            self._pos_ceiling[act] += k * self._tps
        self._note_read(act, payload["window"])
        self.overlapped += 1
        return (toks_seq, act_seq, conf_seq, t0, act, int(k), self._ctr_dev)

    def step_k_fetch(self, handle: tuple) -> tuple[np.ndarray, np.ndarray]:
        """Materialize a dispatched block's ``(rows, S)`` tokens + emitted
        mask (``rows = k`` plain, ``k * (1 + spec_draft)`` speculative).
        ONE device_get for both arrays: two separate fetches would pay two
        host round trips per block."""
        toks_seq, act_seq, conf_seq, t0, disp_active, k, ctr_dev = handle
        # the runtime audit (tests/test_perf.py) budgets exactly one
        # host sync per fused k-block: this is it — confidence margins
        # (conf_signal) ride the SAME fetch, never a second one
        pull = (
            (toks_seq, act_seq, conf_seq)
            if conf_seq is not None
            else (toks_seq, act_seq)
        )
        # sct: host-sync-ok THE one fused-block fetch
        fetched, ctr_np = jax.device_get((pull, ctr_dev))
        toks_np, act_np = fetched[0], fetched[1]
        self.last_conf_seq = (
            np.asarray(fetched[2], np.float32) if len(fetched) > 2 else None
        )
        if ctr_np is not None:
            now = np.asarray(ctr_np).astype(np.uint64)
            self._ctr_total += (now - self._ctr_last) % np.uint64(1 << 32)
            self._ctr_last = now
        act_np = np.asarray(act_np)
        if self.spec_draft and disp_active is not None and disp_active.any():
            # speculation accounting + ceiling tightening: dispatch assumed
            # the worst case k*(1+d) per slot; the fetched emitted mask says
            # what actually landed.  The ceiling stays an overestimate of
            # the true device position throughout (never an underestimate).
            emitted = act_np.sum(axis=0).astype(np.int64)
            with self._lock:
                self._pos_ceiling[disp_active] -= (
                    k * self._tps - emitted[disp_active]
                )
            # acceptance counts PRODUCTIVE (pass, slot) pairs only — a slot
            # that finished its budget mid-block rides the rest of the
            # fused block inactive in the plain path too, so charging those
            # idle passes would understate what drafting actually bought
            productive = int(
                act_np.reshape(k, self._tps, -1).any(axis=1).sum()
            )
            self.spec_emitted_tokens += int(emitted.sum())
            self.spec_verify_passes += productive
            ratio = self.spec_emitted_tokens / max(1, self.spec_verify_passes)
            DEFAULT_METRICS.spec_emitted.labels(self.name).inc(
                int(emitted.sum())
            )
            DEFAULT_METRICS.spec_verify_passes.labels(self.name).inc(
                productive
            )
            DEFAULT_METRICS.spec_accepted_per_step.labels(self.name).set(ratio)
            # per-proposer split (ngram/heads/draft) of the same ledger
            method = self.spec_method or "ngram"
            DEFAULT_METRICS.spec_emitted_by_method.labels(
                self.name, method
            ).inc(int(emitted.sum()))
            DEFAULT_METRICS.spec_verify_passes_by_method.labels(
                self.name, method
            ).inc(productive)
            DEFAULT_METRICS.spec_accepted_per_step_by_method.labels(
                self.name, method
            ).set(ratio)
        self._record_step(time.perf_counter() - t0)
        return np.asarray(toks_np), act_np

    def _decode_k_fn(self, k: int, window: int) -> tuple[Any, bool]:
        # static sampling/speculation/quantization config rides the key so
        # no two configurations can ever share a compiled block program
        key = (k, window) + self._program_config
        fn = self._decode_k_jit.get(key)
        if fn is None:
            # donate the carry args (tokens/active/remaining) along with the
            # cache: each block consumes its predecessor's buffers in place,
            # so the overlapped pipeline holds one live carry, not two
            fn = jax.jit(
                self._decode_k_factory(k, window),
                donate_argnums=(1, 2, 6, 10),
            )
            self._decode_k_jit[key] = fn
            self.program_compiles += 1
            return fn, True
        self.program_hits += 1
        return fn, False

    def _exec_decode_k(self, payload: dict):
        k = int(payload["k"])
        window = int(payload.get("window") or self.cfg.max_seq)
        fn, fresh = self._decode_k_fn(k, window)
        label = f"decode_k:k{k}:w{window}{self.variant_sfx}"
        self.decode_label = label  # the device ledger books the block under it
        with self._lock:
            temps = np.asarray(payload["temperature"], np.float32)
            eos = np.asarray(payload["eos"], np.int32)
            aid = self._aid_vec(payload)
            # the carry vectors go in placed like the carry the program
            # hands back (committed, the per-slot sharding of ``pos``):
            # jit keys its programs on that, and host arrays here would
            # make the continue path — which feeds the device carry —
            # compile every (k, window) program a second time, mid-traffic
            per_slot = self._cache["pos"].sharding
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                res = fn(
                    self.params,
                    jax.device_put(
                        np.asarray(payload["tokens"], np.int32), per_slot
                    ),
                    jax.device_put(
                        np.asarray(payload["active"], bool), per_slot
                    ),
                    temps,
                    np.int32(payload["seed"]),
                    eos,
                    jax.device_put(
                        np.asarray(payload["remaining"], np.int32), per_slot
                    ),
                    aid,
                    self._lora,
                    self._spec_ps,
                    self._cache,
                )
            if self.conf_signal:
                (toks_seq, act_seq, conf_seq,
                 tok_c, act_c, rem_c, self._cache) = res
            else:
                (toks_seq, act_seq, tok_c, act_c, rem_c, self._cache) = res
                conf_seq = None
            if fresh:
                self._note_compile(label, time.perf_counter() - t0)
            self._carry = (tok_c, act_c, rem_c)
            # adapter bindings only change at sync points (admission /
            # release), so the continue path reuses the dispatched ids
            self._carry_aux = (temps, eos, aid)
            self._ctr_dev = self._counters_copy()
            self.steps += k
        if self.conf_signal:
            return toks_seq, act_seq, conf_seq
        return toks_seq, act_seq

    def _exec_decode_cont(self, payload: dict):
        """Symmetric continue body (runs on every slice process): the next
        block's inputs are THIS process's stored device carry."""
        k = int(payload["k"])
        window = int(payload.get("window") or self.cfg.max_seq)
        fn, fresh = self._decode_k_fn(k, window)
        label = f"decode_k:k{k}:w{window}{self.variant_sfx}"
        self.decode_label = label  # the device ledger books the block under it
        with self._lock:
            if self._carry is None or self._carry_aux is None:
                raise RuntimeError(
                    f"generative model {self.name!r}: decode continue "
                    "without a carried block"
                )
            tok_c, act_c, rem_c = self._carry
            temps, eos, aid = self._carry_aux
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                res = fn(
                    self.params,
                    tok_c,
                    act_c,
                    temps,
                    np.int32(payload["seed"]),
                    eos,
                    rem_c,
                    aid,
                    self._lora,
                    self._spec_ps,
                    self._cache,
                )
            if self.conf_signal:
                (toks_seq, act_seq, conf_seq,
                 tok_c, act_c, rem_c, self._cache) = res
            else:
                (toks_seq, act_seq, tok_c, act_c, rem_c, self._cache) = res
                conf_seq = None
            if fresh:
                self._note_compile(label, time.perf_counter() - t0)
            self._carry = (tok_c, act_c, rem_c)
            self._ctr_dev = self._counters_copy()
            self.steps += k
        if self.conf_signal:
            return toks_seq, act_seq, conf_seq
        return toks_seq, act_seq

    def _counters_copy(self):
        """The family's counters as the block just dispatched leaves them,
        as an array of its own: the cache's buffer is donated to the next
        dispatch, which may come before this block's fetch.  The copy is a
        program of its own, queued behind the block: it is dispatched under
        an annotation of its own, or a trace that pairs programs with
        dispatch annotations in order hands it the NEXT block's (a chained
        block's annotation is there before this block ends)."""
        ctr = self._cache.get("counters") if self._ctr_names else None
        if ctr is None:
            return None
        import jax.numpy as jnp

        with jax.profiler.TraceAnnotation("decode:counters"):
            return jnp.copy(ctr)

    def counters_snapshot(self) -> dict | None:
        """``{name: count}`` of the family's device counters as of the last
        fetched decode block (None: the family has none)."""
        if not self._ctr_names:
            return None
        return {n: int(v) for n, v in zip(self._ctr_names, self._ctr_total)}

    def warmup(self) -> int:
        """Compile the decode program and every prefill bucket.

        Held under the model lock end-to-end: traffic that sneaks in before
        readiness flips serializes against the warmup compiles instead of
        racing the donated cache buffers.  If any request already touched the
        cache (traffic hit an unready pod directly), warmup no-ops — it works
        through slot 0 and a position reset, which would corrupt an in-flight
        generation; the programs compile organically in that case.
        """
        with self._lock:
            if self.prefills or self.steps:
                return 0
            n = 0
            self.warmup_programs = []
            # program-variant tag: the static config each compiled program
            # bakes in — /stats/warmup shows it so readiness demonstrably
            # covered the speculative-verify and int8 variants actually
            # served (not just their plain-path namesakes).  Compiles in
            # here are warmup-attributed (no program.compile span); their
            # per-variant seconds land in warmup_program_seconds for the
            # program-cache telemetry to join.
            self._in_warmup = True
            sfx = self.variant_sfx
            # with chunking on, an admission longer than one chunk compiles
            # the chunk-0 bucket plus suffix programs per chunk boundary
            # window — exactly the serving set; the variant list names them
            # so readiness provably covered the chunk pipeline
            suffix_before = set(self._prefill_suffix_jit)
            for b in self.prefill_buckets:
                t0 = time.perf_counter()
                self.admit(0, np.ones(b, np.int32), 0.0, 0)
                if not self.prefill_chunk or b <= self.prefill_chunk:
                    # monolithic program for this bucket really compiled
                    # (longer admissions run the chunk pipeline instead)
                    self.warmup_programs.append(f"prefill:b{b}{sfx}")
                    self.warmup_program_seconds.setdefault(
                        f"prefill:b{b}{sfx}",
                        round(time.perf_counter() - t0, 3),
                    )
                    n += 1
            if self.prefill_chunk:
                for key in sorted(
                    set(self._prefill_suffix_jit) - suffix_before
                ):
                    self.warmup_programs.append(
                        f"prefill:b{key[0]}:w{key[1]}{sfx}"
                    )
                    n += 1
            # every attention-window bucket compiles up front: a window
            # first hit mid-serving would stall that decode block for the
            # compile (seconds on a big model), wrecking its requests' p99.
            # Only the program the scheduler will actually run compiles —
            # step_k when decode_block > 1, the single-token step otherwise.
            for w in self._window_buckets():
                if self.decode_block > 1:
                    self.step_k(
                        np.zeros(self.n_slots, np.int32),
                        np.zeros(self.n_slots, bool),
                        np.zeros(self.n_slots, np.float32),
                        0,
                        np.full(self.n_slots, -1, np.int32),
                        np.zeros(self.n_slots, np.int32),
                        self.decode_block,
                        window=w,
                    )
                    self.warmup_programs.append(
                        f"decode_k:k{self.decode_block}:w{w}{sfx}"
                    )
                else:
                    self.step(
                        np.zeros(self.n_slots, np.int32),
                        np.zeros(self.n_slots, bool),
                        np.zeros(self.n_slots, np.float32),
                        0,
                        window=w,
                    )
                    self.warmup_programs.append(f"decode:w{w}{sfx}")
                n += 1
            # KV prefix reuse on: the suffix-prefill program for each
            # prefix window would otherwise first-compile on the first
            # shared-prefix request mid-serving (seconds on a big model).
            # Warm the canonical shape — smallest suffix bucket per window
            # (the "long system prompt + short novel question" pattern);
            # other suffix buckets compile organically.  Garbage K/V lands
            # in the reserved sink block 0, never read; the prefill
            # counters are restored so reuse accounting stays honest.
            if (
                self.prefix_index is not None
                and os.environ.get("SCT_WARMUP_SUFFIX", "1") != "0"
            ):
                bucket = self.prefill_buckets[0]
                pf, pfr = self.prefills, self.prefills_reused
                for pw in self._prefix_windows():
                    payload = {
                        "padded": np.zeros((1, bucket), np.int32),
                        "prefix_len": pw,
                        "length": pw,
                        "slot": 0,
                        "blocks": np.zeros(self.max_blocks_per_slot, np.int32),
                        "suffix_blocks": np.zeros(
                            bucket // self.kv_block_size, np.int32
                        ),
                        "window": pw,
                        "temperature": 0.0,
                        "seed": 0,
                    }
                    if self.spec_draft:
                        payload["hist_seed"] = np.zeros(
                            self.spec_hist, np.int32
                        )
                    if self.driver is not None:
                        self.driver.lead(self._mh_prefill_suffix_key, payload)
                    else:
                        self._exec_prefill_suffix(payload)
                    self.warmup_programs.append(
                        f"suffix:b{bucket}:w{pw}{sfx}"
                    )
                    n += 1
                self.prefills, self.prefills_reused = pf, pfr
            # pooled-embedding programs: one per prompt bucket, same set the
            # /embeddings route serves (pure forward — no slot, no reset
            # interaction; warmed last so generation readiness is unchanged
            # when the endpoint is off)
            if self.embed_enabled:
                for b in self.prefill_buckets:
                    t0 = time.perf_counter()
                    self.embed(np.ones(b, np.int32))
                    self.warmup_program_seconds[f"embed:b{b}{sfx}"] = (
                        time.perf_counter() - t0
                    )
                    self.warmup_programs.append(f"embed:b{b}{sfx}")
                    n += 1
            # warmup wrote garbage into slot 0 and advanced nothing real
            self.reset()
            self._in_warmup = False
            return n

    def _prefix_windows(self) -> list[int]:
        """Every window :meth:`_prefix_window` can return: block-size
        powers-of-two up to max_seq (bounded — 8 values at max_seq 2048
        with 16-token blocks)."""
        out = []
        w = self.kv_block_size
        while w < self.cfg.max_seq:
            out.append(w)
            w *= 2
        out.append(self.cfg.max_seq)
        return out

    def _window_buckets(self) -> list[int]:
        """Every window :meth:`_window_for` can return."""
        if self.decode_kernel:
            return [self.cfg.max_seq]
        out = []
        w = 64
        while w < self.cfg.max_seq:
            out.append(w)
            w *= 2
        out.append(self.cfg.max_seq)
        return out

    def _exec_reset(self, payload: dict) -> None:
        with self._lock:
            zero = jax.device_put(
                np.zeros(self.n_slots, np.int32), self._cache["pos"].sharding
            )
            out = {**self._cache, "pos": zero}
            if "d_pos" in out:
                # the draft clock resets with the target's (rows above it
                # become unreachable, same as the main pool)
                out["d_pos"] = jax.device_put(
                    np.zeros(self.n_slots, np.int32),
                    self._cache["d_pos"].sharding,
                )
            self._cache = out

    def reset(self) -> None:
        """Zero every slot position and reclaim every block reservation
        (cache contents become unreachable)."""
        self._pos_ceiling[:] = 0
        for slot in list(self._slot_blocks):
            self.release_slot(slot)
        if self.prefix_index is not None:
            # drop everything release_slot absorbed (warmup admits garbage
            # prompts; a reset must leave the index empty) — zero-ref only,
            # and after the release loop every entry IS zero-ref
            self._free_blocks.extend(self.prefix_index.flush())
        if self.host_store is not None:
            # a reset empties every tier: demoted warmup chains must not
            # survive to be promoted into a clean pool
            self.host_store.flush()
        self._peer_chains.clear()
        self._slot_tier.clear()
        self._slot_promoted.clear()
        self._pending_draft_prefill.clear()
        if self.driver is not None:
            self.driver.lead(self._mh_reset_key, {})
            return
        self._exec_reset({})

    def prefix_snapshot(self) -> dict | None:
        """The KV prefix-reuse index state for ``GET /stats/cache``."""
        if self.prefix_index is None:
            return None
        snap = self.prefix_index.snapshot()
        snap["free_blocks"] = len(self._free_blocks)
        snap["pool_blocks"] = self.kv_blocks - 1
        snap["prefills"] = self.prefills
        snap["prefills_reused"] = self.prefills_reused
        snap["kv_imports"] = self.imports
        # compact routing digest: the gateway's prefix-aware router polls
        # this to steer shared-prefix requests at the warm replica
        snap["digest"] = self.prefix_index.digest()
        # per-tier telemetry (docs/CACHING.md "Tiered prefix store"): the
        # same six fields for every tier, zero-filled where a tier has no
        # such flow, so dashboards can stack them without schema checks
        idx = self.prefix_index.snapshot()
        tiers: dict[str, dict] = {
            "hbm": {
                "hits": idx["hits"],
                "misses": idx["misses"],
                "promotions": 0,
                "demotions": idx["evicted"],
                "bytes": len(self.prefix_index) * self.kv_bytes_per_block(),
                "pull_count": 0,
            },
            "peer": {
                "hits": self.peer_hits,
                "misses": 0,
                "promotions": self.peer_installs,
                "demotions": 0,
                "bytes": 0,
                "pull_count": self.peer_serves,
            },
        }
        if self.host_store is not None:
            st = self.host_store.snapshot()
            tiers["dram"] = {
                "hits": st["hits"],
                "misses": st["misses"],
                "promotions": st["promotions"],
                "demotions": st["demotions"],
                "bytes": st["bytes"],
                "pull_count": 0,
                "entries": st["entries"],
                "budget_bytes": st["budget_bytes"],
                "evictions": st["evictions"],
                "rejected": st["rejected"],
            }
            # the DRAM digest rides the same gossip as the HBM one: a
            # replica holding a chain in DRAM still serves it warm (one
            # promotion scatter), so the router should route/pull for it
            tiers["dram"]["digest"] = self.host_store.digest()
        snap["tiers"] = tiers
        m = DEFAULT_METRICS
        for tier, t in tiers.items():
            m.prefix_tier_hits.labels(self.name, tier).set(t["hits"])
            m.prefix_tier_promotions.labels(self.name, tier).set(
                t["promotions"]
            )
            m.prefix_tier_demotions.labels(self.name, tier).set(
                t["demotions"]
            )
            m.prefix_tier_bytes.labels(self.name, tier).set(t["bytes"])
        return snap


@dataclasses.dataclass(eq=False)  # identity eq: fields hold arrays/futures
class _Request:
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float
    eos_id: int | None
    future: asyncio.Future
    out: list[int] = dataclasses.field(default_factory=list)
    # streaming hook: called with each sampled token as it lands (in
    # event-loop context, decode_block tokens at a time per device fetch)
    on_token: "Callable[[int], None] | None" = None
    # flight-recorder timestamps: submission, first sampled token, and the
    # last delivery (feeds the per-slot inter-token-latency ledger)
    t0: float = 0.0
    t_first_token: float = 0.0
    t_last_tok: float = 0.0
    # the submitting request's live span (captured at submit, same loop):
    # first-token lands on it as an event even though the scheduler loop
    # runs outside the request's contextvar scope
    span: Any = None
    # QoS: priority class + absolute monotonic deadline (None = no SLO),
    # captured from the request context at submit
    priority: str = qos.PRIO_INTERACTIVE
    deadline: float | None = None
    # disagg (docs/DISAGGREGATION.md): a prefill-only request resolves with
    # (slot, first_token) after its prefill and PINS the slot for a KV
    # export; an imported request skips prefill entirely — its KV blocks
    # and first token arrived from another engine's handoff
    prefill_only: bool = False
    imported: dict | None = None
    # batched multi-LoRA (docs/MULTITENANT.md): the named adapter this
    # request decodes through (None = base model / null adapter row)
    adapter: str | None = None
    # generation-forensics ledger entry (obs/timeline.py; None when the
    # ledger is off) and the terminal reason _token_done computed — every
    # event is stamped from host-held values only
    timeline: Any = None
    done_reason: str | None = None
    # per-request cost accumulators (obs/metering.py): device seconds
    # attributed by token share, prompt tokens actually prefilled, and
    # prefix-tier tokens saved — stamped onto the timeline terminal so a
    # single trace shows its own cost
    u_device_s: float = 0.0
    u_tokens_prefill: int = 0
    u_saved_tokens: int = 0
    u_saved_tier: str = ""
    u_terminal_metered: bool = False
    # embeddings (docs/GRAPHS.md): a pooled-embedding request rides the
    # same bounded intake + QoS pops but consumes no slot or KV — the run
    # loop batches the wave at a sync point and resolves with the vector
    embed_only: bool = False
    # cascade confidence (docs/GRAPHS.md): sum/count of per-token top-2
    # logit margins delivered to this request, accumulated by _deliver
    # from the stash the fused-block fetch fills — zero extra syncs
    conf_sum: float = 0.0
    conf_n: int = 0


class _Part:
    """One named part of the scheduler's run loop, for the three readers of
    "what was the host doing": the profiler's trace (a ``TraceAnnotation``,
    so the part lies on the clock of the device's programs; ``note`` goes
    on it), the stall watchdog (the scheduler's current part and since
    when), the device ledger (``obs/device.py``: an idle gap of the device
    is shared out over the parts that overlap it) and, where ``stage``
    names one, the flight recorder.  ``t0`` and
    ``t1`` are the part's two instants on ``time.perf_counter``: the run
    loop reads them where it needs the stamp, and takes none beside them.
    Outside a trace an annotation is a flag test.  No name may match
    ``benchmark/trace.py``'s ``LABEL``: that reduction pairs such labels
    with device programs in dispatch order (see ``_counters_copy``)."""

    __slots__ = ("sched", "name", "stage", "ann", "t0", "t1")

    # what the run loop is in between two parts
    LOOP = "sched:loop"

    def __init__(self, sched, name: str, stage: str | None = None, **note):
        self.sched, self.name, self.stage = sched, name, stage
        self.ann = jax.profiler.TraceAnnotation(name, **note)
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_Part":
        self.t0 = time.perf_counter()
        self.sched._part_now = (self.name, self.t0)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.ann.__exit__(*exc)
        self.t1 = time.perf_counter()
        self.sched._part_now = (self.LOOP, self.t1)
        self.sched.device.part(self.name, self.t0, self.t1)
        if self.stage is not None:
            RECORDER.record_stage(self.stage, self.t1 - self.t0)


class GenerationScheduler:
    """Continuous-batching front: admits requests into free slots while
    decode steps keep running for in-flight ones.

    QoS: the intake is BOUNDED (``maxsize``, env ``SCT_GEN_QUEUE_MAX``) —
    overflow raises a typed :class:`~seldon_core_tpu.qos.QueueFull` the
    engine maps to 429; batch-priority work may only fill half the bound so
    it can never starve interactive admission.  Queue pops are
    priority-ordered, expired requests are failed with a 504 *before* a
    prefill or decode step is spent on them, and a client that disconnects
    before its slot is assigned is withdrawn from the queue entirely."""

    def __init__(
        self,
        model: GenerativeModel,
        *,
        maxsize: int | None = None,
        overlap: bool | None = None,
    ):
        self.model = model
        # overlapped pipeline (docs/PERFORMANCE.md): dispatch block N+1
        # from block N's device carry, not from rebuilt host arrays.  On by
        # default for fused blocks; SCT_GEN_OVERLAP=0 (or the ``overlap``
        # graph parameter) restores the strictly sequential loop.
        if overlap is None:
            overlap = os.environ.get("SCT_GEN_OVERLAP", "1") != "0"
        self.overlap = bool(overlap) and model.decode_block > 1
        # waiting requests (priority-sorted at pop time) + a wake event the
        # run loop parks on when fully idle
        self._waiting: list[_Request] = []
        self._wake = asyncio.Event()
        self._maxsize = (
            int(maxsize)
            if maxsize is not None
            else int(os.environ.get("SCT_GEN_QUEUE_MAX", "256"))
        )
        self._batch_cap = max(1, self._maxsize // 2) if self._maxsize else 0
        # requests admitted to a slot but not to the KV pool (OutOfKVBlocks):
        # retried ahead of the queue as completions free blocks
        self._overflow: list[_Request] = []
        # disagg: slots pinned by a prefill-only admission (KV export in
        # progress) — excluded from admission until released, and released
        # only at a sync point so block reuse never races a dispatched
        # decode block
        self._external: set[int] = set()
        self._external_release: list[int] = []
        # chunked prefill (docs/PERFORMANCE.md §7): admissions whose prompt
        # is mid-prefill — one chunk advances per decode sync point so a
        # long admission never stalls in-flight streams for more than one
        # chunk's latency.  Their slots are reserved but not decode-active.
        self._prefilling: list[dict] = []
        self._prefill_slots: set[int] = set()
        # peer-pulled prefix chains waiting to install (docs/CACHING.md
        # "Tiered prefix store"): the scatter grabs pool blocks, so it
        # only runs at a sync point, like external releases
        self._prefix_installs: list[tuple] = []
        self._task: asyncio.Task | None = None
        self._closed = False
        # chip packing (docs/PACKING.md): when attached to a DeviceArbiter
        # the run loop brackets every fused block with the device grant,
        # and the arbiter may preempt this deployment — active slots
        # export into the host-DRAM suspend store (whole-slot handoff
        # frames) and resume bit-exactly at a later sync point
        self._arbiter = None
        self._arb_key: str | None = None
        # batch-class registrant for the co-resident draft model's prompt
        # prefills (spec_method='draft'; attach_arbiter sets it)
        self._arb_draft_key: str | None = None
        self._preempt = False
        self._suspended: list[dict] = []
        self._suspend_store = None
        self._suspend_seq = 0
        # queue-wait EWMA (host bookkeeping only): the deadline-pressure
        # signal the arbiter reads; time-decayed so a drained burst stops
        # preempting co-tenants
        self._qwait_ewma: float | None = None
        self._qwait_stamp = 0.0
        self.suspends = 0
        self.resumes = 0
        self.suspend_rejected = 0
        # live migration (docs/RESILIENCE.md): drain_begin pauses
        # admission and parks every active slot; the engine's /admin/drain
        # endpoint then ships the frames to a peer (or drain_finish
        # resumes them locally).  _quiesced fires in the run loop once no
        # slot is device-resident.
        self._draining = False
        self._quiesced = asyncio.Event()
        self.drains = 0
        self.drained_out = 0
        # decode-block boundaries by outcome (boundary_snapshot)
        self.boundaries: dict[str, int] = {}
        # the part of the run loop the scheduler is in and since when
        # (``_Part``), the slots' live mask, and the watchdog that reads
        # both from a thread of its own while the run task lives
        self._part_now: tuple[str, float] = (_Part.LOOP, 0.0)
        # the device's time, from the stamps of this loop and its workers:
        # busy by kind and program, idle by part (``device_snapshot``)
        self.device = DeviceLedger()
        self._active = np.zeros(0, bool)
        self._watchdog = StallWatchdog(model.name, self._watched)
        # Random base so temperature>0 sampling differs across restarts and
        # replicas; within one process the sequence stays deterministic.
        self._seed = int.from_bytes(os.urandom(4), "little")

    def _next_seed(self) -> int:
        self._seed = (self._seed + 1) % (2**31 - 1)
        return self._seed

    def _part(self, name: str, stage: str | None = None, **note) -> _Part:
        return _Part(self, name, stage, **note)

    def _watched(self) -> tuple[str, float, bool]:
        """What the stall watchdog reads, from its own thread: the current
        part, since when, and whether a slot is live or a request waits."""
        part, since = self._part_now
        busy = bool(
            self._waiting or self._overflow or self._prefilling
            or self._active.any()
        )
        return part, since, busy

    def _note_part(self, req: "_Request", stage: str, t0: float, t1: float) -> None:
        """A request's own part of the host path (``slot-wait``,
        ``ingress``, ``first-write``): a sample of its stage, and the same
        on the request's timeline and generation span, so one trace shows
        its wait beside the causes it has.  The event's ``ts`` is the
        part's end and ``ms`` its length; a request that has ended already
        (one token, written after its terminal event) keeps ``terminal``
        last and gets the sample alone."""
        RECORDER.record_stage(stage, t1 - t0)
        if not req.u_terminal_metered:
            self._tl(req, stage, ms=round((t1 - t0) * 1e3, 3))

    def _first_written(self, req: "_Request") -> None:
        """The consumer's word that its write of the first token returned
        (the engine's SSE handler calls it through ``submit``'s ``info``):
        the ``first-write`` stage starts at the scheduler's own stamp."""
        if req.t_first_token:
            self._note_part(
                req, STAGE_FIRST_WRITE, req.t_first_token, time.perf_counter()
            )

    @staticmethod
    def _stamped(fn, *args):
        """``fn(*args)`` on a worker's thread, with the instant it returned
        THERE: the device ledger's stamp of a dispatch or a completion,
        which leaves the run loop's resumption out."""
        out = fn(*args)
        return out, time.perf_counter()

    def _sent_block(self, at: float, k: int) -> None:
        """A decode block went out, its dispatch call returning at ``at``.
        getattr: duck-typed stand-in models (tests) predate the label."""
        self.device.sent(
            at, "decode",
            getattr(self.model, "decode_label", None) or f"decode_k:k{k}",
            k, part=self._part_now,
        )

    def _rungs(self) -> dict:
        """Prompt dispatches so far by rung (the model's host integers).
        getattr: duck-typed stand-in models (tests) predate the count."""
        rows = getattr(self.model, "prefill_rows", None) or {}
        return dict(rows.get("by_rung") or {})

    def _sent_prompts(self, at: float, before: dict, n: int, waits: bool = True) -> None:
        """A round of ``n`` prompt-side programs went out back to back, the
        first dispatch call returning at ``at``: one ``prefill`` interval
        under its rung's label where it ran one rung (``before``:
        :meth:`_rungs` ahead of it), ``other`` where it ran no prompt (KV
        imported, a resumed suspend record)."""
        ran = {r: c - before.get(r, 0) for r, c in self._rungs().items()
               if c > before.get(r, 0)}
        if len(ran) == 1:
            label = f"prefill:b{next(iter(ran))}"
        else:
            label = "prefill:mixed" if ran else "import"
        self.device.sent(
            at, "prefill" if ran else "other", label,
            n=max(n, sum(ran.values())), part=self._part_now, waits=waits,
        )

    def device_snapshot(self) -> dict:
        """The device ledger (``GET /stats/breakdown``, ``/stats/summary``:
        ``generation.<unit>.device``)."""
        return self.device.snapshot(time.perf_counter(), self._part_now)

    def stall_snapshot(self) -> dict:
        """Stalls the watchdog has named since boot (``GET
        /stats/breakdown``): ``count``, ``longest_s``, the last one's part."""
        return self._watchdog.snapshot()

    # ------------------------------------------- lifecycle timeline feeds
    # (obs/timeline.py; docs/OBSERVABILITY.md "generation forensics").
    # Every event is stamped from values the host ALREADY holds — fetched
    # token counts, reservation bookkeeping, queue state — never a device
    # array: the <=1-sync-per-fused-block audit runs with the ledger on.

    def _begin_tl(self, req: _Request, kind: str = "generate") -> None:
        req.timeline = TIMELINE.begin(
            current_trace_id(),
            model=self.model.name,
            kind=kind,
            prompt_tokens=int(req.prompt.size),
            max_new_tokens=int(req.max_new_tokens),
            priority=req.priority,
        )

    def _tl(self, req: _Request, name: str, span: bool = True, **attrs) -> None:
        """One lifecycle event: the timeline entry plus (bounded) the same
        event folded onto the request's generation span."""
        if req.timeline is not None:
            req.timeline.event(name, **attrs)
        if span and req.span is not None and len(req.span.span.events) < 256:
            req.span.event(name, **attrs)

    def _usage_attrs(self, req: _Request) -> dict:
        """The request's final cost totals, stamped onto its terminal
        event so one trace shows what it spent (host-held values only)."""
        out = {
            "device_ms": round(req.u_device_s * 1e3, 3),
            "tokens_in": int(req.prompt.size),
            "tokens_out": len(req.out),
        }
        if req.u_saved_tokens:
            out["tokens_saved"] = int(req.u_saved_tokens)
            out["saved_tier"] = req.u_saved_tier
        return out

    def _meter_terminal(self, req: _Request, reason: str) -> None:
        """Fold the request's outcome into the usage meter exactly once
        (first terminal wins, matching the timeline)."""
        if reason in ("eos", "budget", "exported"):
            METER.add(
                self.model.name, req.adapter or "", req.priority,
                requests_completed=1,
            )
        elif reason == "shed":
            METER.add(
                self.model.name, req.adapter or "", req.priority,
                requests_shed=1,
            )
        else:  # deadline-reap / disconnect / error: spent, not delivered
            METER.add(
                self.model.name, req.adapter or "", req.priority,
                requests_reaped=1, tokens_wasted=len(req.out),
            )

    def _meter_admit(self, req: _Request, snap: dict | None) -> None:
        """Fold one admission's prefill cost into the usage meter: prompt
        tokens actually prefilled on device, and prefix-tier tokens SAVED
        (hbm/dram/peer — reuse of KV someone already paid for), both from
        the host-side reservation bookkeeping the admit event reads."""
        snap = snap or {}
        prompt_n = int(req.prompt.size)
        saved = min(int(snap.get("prefix_tokens") or 0), prompt_n)
        tier = str(snap.get("tier") or "none")
        fields: dict = {"tokens_prefill": max(0, prompt_n - saved)}
        if saved and tier in ("hbm", "dram", "peer"):
            fields[f"tokens_saved_{tier}"] = saved
            req.u_saved_tokens += saved
            req.u_saved_tier = tier
        req.u_tokens_prefill = fields["tokens_prefill"]
        METER.add(
            self.model.name, req.adapter or "", req.priority, **fields
        )

    def _end_tl(self, req: _Request, reason: str, **attrs) -> None:
        if req.done_reason is None:
            req.done_reason = reason
        if not req.u_terminal_metered:
            # exactly-once outcome metering: done_reason may have been
            # stamped by the device-visible transition (_token_done)
            # before this terminal event runs
            req.u_terminal_metered = True
            self._meter_terminal(req, req.done_reason)
        attrs["usage"] = self._usage_attrs(req)
        if req.timeline is not None:
            req.timeline.end(reason, **attrs)
        if req.span is not None and len(req.span.span.events) < 256:
            req.span.event("terminal", reason=reason, **attrs)

    def _note_shed(
        self, priority: str, depth: int, cap: int, adapter: str | None = None
    ) -> None:
        """A QueueFull shed leaves a terminal-only timeline entry so the
        trace's forensics say WHY the request never ran — and a shed-cost
        row in the usage meter (zero device time, by construction)."""
        METER.add(
            self.model.name, adapter or "", priority, requests_shed=1
        )
        tl = TIMELINE.begin(
            current_trace_id(), model=self.model.name, priority=priority
        )
        if tl is not None:
            tl.end(
                "shed", depth=depth, cap=cap,
                usage={"device_ms": 0.0, "tokens_in": 0, "tokens_out": 0},
            )

    async def submit(
        self,
        prompt: np.ndarray,
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: int | None = None,
        on_token: "Callable[[int], None] | None" = None,
        adapter: str | None = None,
        info: dict | None = None,
        t_ingress: float | None = None,
    ) -> np.ndarray:
        """Generate up to ``max_new_tokens`` ids for a 1-D prompt.

        ``on_token`` (optional) fires per sampled token in event-loop
        context — the streaming hook; tokens arrive ``decode_block`` at a
        time per device fetch.  ``adapter`` names a resident LoRA adapter
        to decode through (docs/MULTITENANT.md).  ``info`` (optional) is an
        out-param dict stamped with per-request extras on completion —
        today the cascade confidence signal (docs/GRAPHS.md): mean top-2
        logit margin over delivered tokens, when ``conf_signal`` is on; and,
        from the start, ``first_written``: the caller that writes the
        tokens out calls it when its write of the first one has returned
        (the ``first-write`` stage).  ``t_ingress`` (optional) is the
        ``time.perf_counter`` instant the request entered the serving
        handler: the ``ingress`` stage ends at this submit's own stamp."""
        if self._closed:
            raise RuntimeError("GenerationScheduler is closed")
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1:
            raise GraphUnitError("empty prompt")
        vocab = self.model.cfg.vocab_size
        if prompt.min() < 0 or prompt.max() >= vocab:
            # JAX gather would silently clamp out-of-range ids into arbitrary
            # embedding rows — garbage generations with status 200
            raise GraphUnitError(
                f"token ids must be in [0, {vocab}); got "
                f"[{int(prompt.min())}, {int(prompt.max())}]"
            )
        if prompt.size >= self.model.cfg.max_seq:
            raise GraphUnitError(
                f"prompt length {prompt.size} must be < max_seq "
                f"{self.model.cfg.max_seq}"
            )
        if max_new_tokens < 1:
            return np.zeros(0, np.int32)
        # the cache cannot grow past max_seq
        max_new_tokens = min(
            int(max_new_tokens), self.model.cfg.max_seq - int(prompt.size)
        )
        # brownout: under sustained overload the active admission
        # controller clamps answer length before availability degrades
        max_new_tokens = qos.clamp_max_new_tokens(max_new_tokens)
        priority = qos.get_priority()
        depth = len(self._waiting) + len(self._overflow)
        cap = (
            self._maxsize
            if priority == qos.PRIO_INTERACTIVE
            else self._batch_cap
        )
        if self._maxsize and depth >= cap:
            self._note_shed(priority, depth, cap, adapter)
            raise qos.QueueFull(
                f"generation queue is full ({depth} waiting, cap {cap} "
                f"for {priority})"
            )
        self._ensure_run_task()
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        from seldon_core_tpu.obs import current_span

        req = _Request(
            prompt, max_new_tokens, float(temperature), eos_id, fut,
            on_token=on_token, t0=time.perf_counter(),
            span=current_span(),
            priority=priority, deadline=qos.get_deadline(),
            adapter=adapter or None,
        )
        self._begin_tl(req)
        self._tl(req, "queued", span=False, depth=len(self._waiting))
        if t_ingress is not None:
            self._note_part(req, STAGE_INGRESS, t_ingress, req.t0)
        if info is not None:
            info["first_written"] = partial(self._first_written, req)
        self._waiting.append(req)
        self._wake.set()
        try:
            out = await fut
            if info is not None and req.conf_n:
                info["confidence"] = req.conf_sum / req.conf_n
                info["conf_tokens"] = req.conf_n
            return out
        except asyncio.CancelledError:
            # cancel-on-disconnect: the client is gone — withdraw before a
            # slot/prefill is spent (in-slot requests are reaped by the run
            # loop's sweep via the now-cancelled future)
            if req in self._waiting:
                self._waiting.remove(req)
            if req in self._overflow:
                self._overflow.remove(req)
            self._end_tl(req, "disconnect", stage="queue")
            raise

    # ------------------------------------------------------ disagg entries

    def _validate_prompt(self, prompt: np.ndarray) -> np.ndarray:
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1:
            raise GraphUnitError("empty prompt")
        vocab = self.model.cfg.vocab_size
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise GraphUnitError(
                f"token ids must be in [0, {vocab}); got "
                f"[{int(prompt.min())}, {int(prompt.max())}]"
            )
        if prompt.size >= self.model.cfg.max_seq:
            raise GraphUnitError(
                f"prompt length {prompt.size} must be < max_seq "
                f"{self.model.cfg.max_seq}"
            )
        return prompt

    def _enqueue(self, req: _Request) -> None:
        depth = len(self._waiting) + len(self._overflow)
        cap = (
            self._maxsize
            if req.priority == qos.PRIO_INTERACTIVE
            else self._batch_cap
        )
        if self._maxsize and depth >= cap:
            self._note_shed(req.priority, depth, cap, req.adapter)
            raise qos.QueueFull(
                f"generation queue is full ({depth} waiting, cap {cap} "
                f"for {req.priority})"
            )
        self._ensure_run_task()
        self._tl(req, "queued", span=False, depth=len(self._waiting))
        self._waiting.append(req)
        self._wake.set()

    async def _await_withdrawing(self, req: _Request):
        try:
            return await req.future
        except asyncio.CancelledError:
            if req in self._waiting:
                self._waiting.remove(req)
            if req in self._overflow:
                self._overflow.remove(req)
            self._end_tl(req, "disconnect", stage="queue")
            raise

    async def submit_prefill(
        self, prompt: np.ndarray, *, temperature: float = 0.0,
        adapter: str | None = None,
    ) -> tuple[int, int]:
        """Disagg prefill-only admission (docs/DISAGGREGATION.md): prefill
        ``prompt`` into a free slot and return ``(slot, first_token)``
        WITHOUT decoding.  The slot is PINNED — excluded from later
        admissions, its blocks unreclaimable — until
        :meth:`release_external` returns it, so a KV export can read the
        blocks at leisure and a failed handoff leaks nothing."""
        if self._closed:
            raise RuntimeError("GenerationScheduler is closed")
        prompt = self._validate_prompt(prompt)
        from seldon_core_tpu.obs import current_span

        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        req = _Request(
            prompt, 1, float(temperature), None, fut,
            t0=time.perf_counter(), span=current_span(),
            priority=qos.get_priority(), deadline=qos.get_deadline(),
            adapter=adapter or None,
        )
        req.prefill_only = True
        self._begin_tl(req, kind="prefill")
        self._enqueue(req)
        return await self._await_withdrawing(req)

    async def submit_embed(self, prompt: np.ndarray) -> np.ndarray:
        """Pooled-embedding admission (docs/GRAPHS.md): ride the same
        bounded intake, QoS priority pops, and deadline reaping as
        generation, but consume no slot or KV — the run loop batches the
        waiting embed wave at its next sync point and resolves each with
        its (E,) float32 vector."""
        if self._closed:
            raise RuntimeError("GenerationScheduler is closed")
        prompt = self._validate_prompt(prompt)
        from seldon_core_tpu.obs import current_span

        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        req = _Request(
            prompt, 1, 0.0, None, fut,
            t0=time.perf_counter(), span=current_span(),
            priority=qos.get_priority(), deadline=qos.get_deadline(),
        )
        req.embed_only = True
        self._begin_tl(req, kind="embed")
        self._enqueue(req)
        return await self._await_withdrawing(req)

    async def _admit_embeds(self, reqs: list["_Request"]) -> None:
        """Serve one wave of embed-only requests: dispatch every forward
        first (async), then ONE device_get for the whole wave — N prompts
        cost one host sync, mirroring the fused-block discipline."""

        def dispatch_and_fetch():
            placed: list[tuple[_Request, Any]] = []
            errors: list[tuple[_Request, Exception]] = []
            sent_at = 0.0
            for req in reqs:
                try:
                    placed.append((req, self.model.embed_dispatch(req.prompt)))
                except Exception as e:  # per-request: one bad prompt
                    errors.append((req, e))  # must not fail the wave
                sent_at = sent_at or time.perf_counter()
            # sct: host-sync-ok embed wave sync point
            vecs = jax.device_get([v for _, v in placed]) if placed else []
            return placed, errors, vecs, sent_at, time.perf_counter()

        with self._part("sched:embeds", n=len(reqs)):
            placed, errors, vecs, sent_at, done_at = await asyncio.to_thread(
                dispatch_and_fetch
            )
            self.device.sent(
                sent_at, "other", "embed", n=len(reqs), part=self._part_now
            )
        # the wave's seconds on the device, split by prompt tokens
        batch_s = self.device.done(done_at)
        total_toks = sum(int(r.prompt.size) for r, _ in placed) or 1
        for (req, _), vec in zip(placed, vecs):
            share_s = batch_s * int(req.prompt.size) / total_toks
            req.u_device_s += share_s
            METER.add(
                self.model.name, req.adapter or "", req.priority,
                device_s=share_s, tokens_prefill=int(req.prompt.size),
            )
            self._note_queue_wait(req)
            self._tl(req, "embed", tokens=int(req.prompt.size))
            arr = np.asarray(vec, np.float32)
            if not req.future.done():
                req.future.set_result(arr)
            self._end_tl(req, "embedded", dim=int(arr.shape[-1]))
        for req, e in errors:
            if not req.future.done():
                req.future.set_exception(e)
            self._end_tl(req, "error", stage="embed")

    async def submit_imported(
        self,
        prompt: np.ndarray,
        *,
        first_token: int,
        k: np.ndarray,
        v: np.ndarray,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: int | None = None,
        on_token: "Callable[[int], None] | None" = None,
        k_scale: np.ndarray | None = None,
        v_scale: np.ndarray | None = None,
        adapter: str | None = None,
        spec_state: dict | None = None,
    ) -> np.ndarray:
        """Disagg decode-side admission: continue a generation whose
        prompt KV (``k``/``v``) and first sampled token arrived from a
        prefill engine's handoff.  The blocks import into this pool at the
        scheduler's next sync point; the result (first token included) is
        exactly what a unified engine returns for the same request."""
        if self._closed:
            raise RuntimeError("GenerationScheduler is closed")
        prompt = self._validate_prompt(prompt)
        max_new_tokens = min(
            max(1, int(max_new_tokens)),
            self.model.cfg.max_seq - int(prompt.size),
        )
        max_new_tokens = qos.clamp_max_new_tokens(max_new_tokens)
        from seldon_core_tpu.obs import current_span

        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        req = _Request(
            prompt, max_new_tokens, float(temperature), eos_id, fut,
            on_token=on_token, t0=time.perf_counter(), span=current_span(),
            priority=qos.get_priority(), deadline=qos.get_deadline(),
            adapter=adapter or None,
        )
        req.imported = {
            "first_token": int(first_token), "k": k, "v": v,
            "k_scale": k_scale, "v_scale": v_scale,
            "spec": spec_state,
        }
        self._begin_tl(req, kind="imported")
        self._enqueue(req)
        return await self._await_withdrawing(req)

    def release_external(self, slot: int) -> None:
        """Return a :meth:`submit_prefill`-pinned slot to the pool.  The
        actual release happens at the run loop's next sync point — block
        reuse must never race a dispatched decode block — and is idempotent
        there."""
        self._external_release.append(int(slot))
        self._wake.set()

    def _drain_external_releases(self) -> None:
        while self._external_release:
            slot = self._external_release.pop()
            self._external.discard(slot)
            self.model.release_slot(slot)

    async def install_prefix(
        self,
        tokens: np.ndarray,
        k: np.ndarray,
        v: np.ndarray,
        *,
        k_scale: np.ndarray | None = None,
        v_scale: np.ndarray | None = None,
        adapter: str | None = None,
    ) -> int:
        """Install a peer-pulled prefix chain into the pool + index at
        the run loop's next sync point (the scatter takes free blocks, so
        it must never race a dispatched decode block).  Resolves to the
        number of chain levels installed (0 when everything was already
        resident or the pool is too hot to cache the pull)."""
        if self._closed:
            raise RuntimeError("GenerationScheduler is closed")
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._prefix_installs.append(
            (
                {
                    "tokens": tokens, "k": k, "v": v,
                    "k_scale": k_scale, "v_scale": v_scale,
                    "adapter": adapter,
                },
                fut,
            )
        )
        self._ensure_run_task()
        self._wake.set()
        return await fut

    async def _drain_prefix_installs(self) -> None:
        while self._prefix_installs:
            payload, fut = self._prefix_installs.pop(0)
            try:
                n = await asyncio.to_thread(
                    self.model.install_prefix_chain,
                    payload["tokens"], payload["k"], payload["v"],
                    payload["k_scale"], payload["v_scale"],
                    payload["adapter"],
                )
            except Exception as e:
                if not fut.done():
                    fut.set_exception(e)
                continue
            if not fut.done():
                fut.set_result(n)

    # ------------------------------------------- chip packing (arbitration)
    # docs/PACKING.md: the scheduler side of SLO-arbitrated time-sharing —
    # the device grant brackets every fused block, and preemption/resume
    # are verbs the arbiter invokes between blocks, never inside one.

    def attach_arbiter(
        self,
        arbiter,
        *,
        priority: str = qos.PRIO_INTERACTIVE,
        slo_ms: float | None = None,
    ) -> None:
        """Join a packed chip: register with ``arbiter`` under this
        model's name (the arbiter de-duplicates colliding names) and
        start bracketing fused blocks with its grant.  With a co-resident
        draft model (``spec_method='draft'``) a SECOND, batch-class
        registrant covers its prompt prefills: they stop running inline
        at admission and drain at sync points under the draft grant, so
        interactive verify blocks never queue behind draft warm-up work
        (docs/PACKING.md + PERFORMANCE.md §6)."""
        self._arbiter = arbiter
        self._arb_key = arbiter.register(
            self.model.name, scheduler=self, priority=priority, slo_ms=slo_ms
        )
        if getattr(self.model, "spec_method", None) == "draft":
            self._arb_draft_key = arbiter.register(
                f"{self.model.name}/draft", scheduler=self,
                priority=qos.PRIO_BATCH,
            )
            self.model.defer_draft_prefill = True

    def detach_arbiter(self) -> None:
        if self._arbiter is not None:
            if self._arb_draft_key is not None:
                self._arbiter.unregister(self._arb_draft_key)
                self._arb_draft_key = None
                self.model.defer_draft_prefill = False
            self._arbiter.unregister(self._arb_key)
            self._arbiter = None
            self._arb_key = None

    async def _drain_draft_prefills(self) -> None:
        """Run deferred draft-model prefills under the batch-class draft
        grant (sync points only — never between a dispatch and its
        fetch, so the one-sync-per-block audit holds)."""
        if not getattr(self.model, "_pending_draft_prefill", None):
            return
        # local refs: close() detaches the arbiter concurrently with the
        # run loop, and the release must pair with the acquire we made
        arb, key = self._arbiter, self._arb_draft_key
        if arb is not None and key is not None:
            await arb.acquire(key)
            try:
                await asyncio.to_thread(self.model.drain_draft_prefills)
            finally:
                arb.release(key)
            return
        await asyncio.to_thread(self.model.drain_draft_prefills)

    async def _arb_acquire(self) -> None:
        if self._arbiter is not None:
            # _arb_release() pairs it on every park and error path
            # sct: pairing-ok ownership transfer to _arb_release()
            await self._arbiter.acquire(self._arb_key)

    def _arb_release(self) -> None:
        # idempotent: every park and error path releases defensively — a
        # parked co-tenant must never wait on a scheduler that is itself
        # waiting
        if self._arbiter is not None:
            self._arbiter.release(self._arb_key)

    def _arb_contended(self) -> bool:
        return self._arbiter is not None and self._arbiter.contended(
            self._arb_key
        )

    def queue_pressure(self) -> float:
        """Deadline pressure in seconds: max of the (time-decayed)
        queue-wait EWMA and the oldest live waiter's age.  Host
        bookkeeping only — the arbiter polls this at grant edges."""
        now = time.perf_counter()
        oldest = max(
            (now - r.t0 for r in self._waiting if not r.future.done()),
            default=0.0,
        )
        ewma = 0.0
        if self._qwait_ewma is not None:
            # 1 s half-life: a drained burst's pressure fades instead of
            # preempting co-tenants forever
            ewma = self._qwait_ewma * (0.5 ** max(0.0, now - self._qwait_stamp))
        return max(ewma, oldest)

    def _note_queue_wait(self, req: _Request) -> None:
        """Fold one admission's queue wait into the EWMA.  Resumed
        suspend records skip it: their t0 is the ORIGINAL submission, so
        counting them would report the suspension as queue pressure."""
        if req.imported is not None and req.imported.get("resumed"):
            return
        wait = max(0.0, time.perf_counter() - req.t0)
        e = self._qwait_ewma
        self._qwait_ewma = wait if e is None else (0.8 * e + 0.2 * wait)
        self._qwait_stamp = time.perf_counter()

    def _note_slot_wait(self, req: _Request, taken_t: float) -> None:
        """``slot-wait``: submit -> taken into an admission batch at a sync
        point (``taken_t``, the round's own start).  Its own stage: the
        QoS estimate and the gateway's router read ``queue-wait``'s EWMA,
        and feeding that would change admission.  Resumed suspend records
        are skipped as ``_note_queue_wait`` skips them."""
        if req.imported is not None and req.imported.get("resumed"):
            return
        self._note_part(req, STAGE_SLOT_WAIT, req.t0, taken_t)

    def request_preempt(self) -> None:
        """Arbiter verb: suspend this deployment's active slots at the
        next sync point and hold admissions until resumed."""
        kv_alone = getattr(self.model, "_kv_alone", None)
        if kv_alone is not None:
            kv_alone("a preemption (SuspendStore)")
        self._preempt = True
        self._wake.set()

    def request_resume(self) -> None:
        """Arbiter verb: lift the preemption — suspended records re-queue
        at the next sync point and resume bit-exactly."""
        self._preempt = False
        self._wake.set()

    def _suspend_budget_bytes(self) -> int:
        return int(
            float(os.environ.get("SCT_PACK_SUSPEND_GB", "1") or 1) * (1 << 30)
        )

    def _get_suspend_store(self):
        if self._suspend_store is None:
            from seldon_core_tpu.cache.tiers import SuspendStore

            # getattr: duck-typed stand-in models (tests) predate the
            # host-DRAM ledger
            self._suspend_store = SuspendStore(
                self._suspend_budget_bytes(),
                on_bytes=getattr(self.model, "note_suspend_bytes", None),
            )
        return self._suspend_store

    async def _suspend_active(self, slots, cur, temps, active) -> int:
        """The preemption verb's device half, at a sync point only: for
        every active slot, export its KV (prompt + emitted tokens so far)
        as ONE disagg handoff frame — int8 blocks + scales verbatim —
        park it in the suspend store, and free the slot's blocks.  The
        request object stays alive (future, streaming hook, span,
        timeline); only its device residency is taken.  A record the
        store cannot hold leaves its slot RUNNING — best-effort
        preemption never kills a generation.  Returns slots suspended."""
        from seldon_core_tpu.disagg.handoff import encode_handoff

        store = self._get_suspend_store()
        n_susp = 0
        for i in range(len(slots)):
            req = slots[i]
            if req is None or not active[i] or not req.out:
                continue
            self._tl(req, EVENT_PREEMPT, victim=self.model.name)
            n = len(req.out)
            # KV covers prompt + out[:-1] (the carry token's KV is not
            # written yet); out[-1] rides as the frame's first_token, so
            # the resume reserves (L+n-1) + (max_new-n+1) = L + max_new —
            # exactly the uninterrupted reservation
            hist = np.concatenate(
                [req.prompt, np.asarray(req.out[:-1], np.int32)]
            )

            def export(slot=i, hist=hist, req=req, carry=int(req.out[-1]), n=n):
                kv = self.model.export_slot_kv(slot, int(hist.size))
                k, v = kv[0], kv[1]
                ks, vs = (kv[2], kv[3]) if len(kv) == 4 else (None, None)
                spec = getattr(
                    self.model, "export_spec_state", lambda s: None
                )(slot)
                return encode_handoff(
                    hist, carry, k, v,
                    block_size=self.model.kv_block_size,
                    max_new_tokens=req.max_new_tokens - n + 1,
                    temperature=req.temperature,
                    eos_id=req.eos_id,
                    k_scale=ks, v_scale=vs,
                    priority=req.priority,
                    adapter=req.adapter,
                    spec_state=spec,
                )

            try:
                frame = await asyncio.to_thread(export)
            except Exception:
                log.exception(
                    "suspend export failed for slot %d; leaving it resident", i
                )
                continue
            self._suspend_seq += 1
            key = (id(req), self._suspend_seq)
            if not store.put(key, frame):
                # over the suspend budget: this slot keeps running
                self.suspend_rejected += 1
                self._tl(req, "suspend-rejected", bytes=len(frame))
                continue
            # free_block_count is a property; stand-in models may lack it
            before = int(getattr(self.model, "free_block_count", 0) or 0)
            self.model.release_slot(i)
            freed = int(getattr(self.model, "free_block_count", 0) or 0) - before
            self._suspended.append({
                "req": req, "key": key, "bytes": len(frame),
                "t_park": time.perf_counter(),
            })
            slots[i] = None
            active[i] = False
            self.suspends += 1
            n_susp += 1
            self._tl(
                req, EVENT_SUSPEND,
                victim=self.model.name, tokens=n,
                blocks_freed=int(freed), bytes=len(frame),
            )
        return n_susp

    def _meter_unpark(self, rec: dict) -> None:
        """Charge a suspend record's byte-seconds the moment it leaves the
        store (resume, reap, drain, or close) — bytes held x wall seconds
        parked, host bookkeeping only."""
        t0 = rec.get("t_park")
        if not t0:
            return
        req = rec["req"]
        METER.add(
            self.model.name, req.adapter or "", req.priority,
            suspend_byte_s=rec["bytes"] * (time.perf_counter() - t0),
        )

    def _drain_resumes(self) -> None:
        """Resume verb, at a sync point with preemption lifted: decode
        each suspend record back into an imported admission — the donated
        fused-scatter path — and re-queue the ORIGINAL request (its t0
        sorts it ahead of younger work in its class)."""
        from seldon_core_tpu.disagg.handoff import decode_handoff

        while self._suspended:
            rec = self._suspended.pop(0)
            self._meter_unpark(rec)
            req = rec["req"]
            frame = (
                self._suspend_store.take(rec["key"])
                if self._suspend_store is not None
                else None
            )
            if req.future.done():
                self._end_tl(req, "disconnect", stage="suspended")
                continue
            if frame is None:
                req.future.set_exception(
                    GraphUnitError("suspend record lost from the store")
                )
                self._end_tl(req, "error", stage="suspended")
                continue
            payload = decode_handoff(frame)
            req.imported = {
                "first_token": int(payload["first_token"]),
                "k": payload["k"],
                "v": payload["v"],
                "k_scale": payload.get("k_scale"),
                "v_scale": payload.get("v_scale"),
                "prompt": np.asarray(payload["prompt"], np.int32),
                "reserve_tokens": int(payload["max_new_tokens"]),
                "resumed": True,
                "spec": payload.get("spec_state"),
            }
            self.resumes += 1
            self._tl(req, "resume-queued", span=False)
            self._waiting.append(req)

    def _reap_suspended(self) -> None:
        """QoS sweep over parked suspend records: a cancelled or expired
        request must not hold suspend-store bytes until resume."""
        if not self._suspended:
            return
        now = time.monotonic()
        keep = []
        for rec in self._suspended:
            req = rec["req"]
            if req.future.done():
                if self._suspend_store is not None:
                    self._suspend_store.take(rec["key"])
                self._meter_unpark(rec)
                self._end_tl(req, "disconnect", stage="suspended")
                continue
            if req.deadline is not None and now >= req.deadline:
                if self._suspend_store is not None:
                    self._suspend_store.take(rec["key"])
                self._meter_unpark(rec)
                req.future.set_exception(qos.DeadlineExceeded(
                    f"deadline expired while suspended after "
                    f"{len(req.out)} tokens"
                ))
                DEFAULT_METRICS.qos_deadline_miss.labels(
                    self.model.name, "suspended"
                ).inc()
                qos.note_deadline_miss("suspended", req.priority)
                self._end_tl(
                    req, "deadline-reap", stage="suspended",
                    tokens=len(req.out),
                )
                continue
            keep.append(rec)
        self._suspended[:] = keep

    # -- live migration (docs/RESILIENCE.md "drain runbook") ---------------

    def drain_begin(self) -> None:
        """Admin verb, the device half of live migration: pause admission
        and suspend every active slot at the next sync point (the same
        bit-exact export preemption uses).  Pair with :meth:`drain_finish`
        once the frames have moved to a peer — or immediately, to resume
        everything locally when there is no peer."""
        self._draining = True
        # clear, never replace: drain_wait_quiesced may already hold this
        # event, and a waiter on a replaced one would hang forever
        self._quiesced.clear()
        self.drains += 1
        self._preempt = True
        self._wake.set()
        if self._task is None or self._task.done():
            # idle scheduler: the run loop only exists while work is in
            # flight, so nothing is device-resident and no loop turn will
            # ever fire the event — quiesce immediately instead of making
            # an idle victim's drain (the autoscaler's common shrink case)
            # sit out the full timeout
            self._quiesced.set()

    async def drain_wait_quiesced(self, timeout_s: float = 30.0) -> bool:
        """Block until no slot is device-resident (suspend records are
        parked; slots the store refused ran to completion)."""
        try:
            await asyncio.wait_for(self._quiesced.wait(), timeout_s)
            return True
        except asyncio.TimeoutError:
            return False

    def drain_take(self) -> list[tuple["_Request", bytes]]:
        """Pop every parked suspend record as ``(request, frame)`` — the
        migration payload, bit-exact v4 handoff frames.  Ownership of each
        request's completion moves to the caller (the drain endpoint
        relays the peer's continuation through
        :meth:`complete_migrated`)."""
        out: list[tuple[_Request, bytes]] = []
        while self._suspended:
            rec = self._suspended.pop(0)
            self._meter_unpark(rec)
            req = rec["req"]
            frame = (
                self._suspend_store.take(rec["key"])
                if self._suspend_store is not None
                else None
            )
            if req.future.done():
                self._end_tl(req, "disconnect", stage="suspended")
                continue
            if frame is None:
                req.future.set_exception(
                    GraphUnitError("suspend record lost from the store")
                )
                self._end_tl(req, "error", stage="suspended")
                continue
            self.drained_out += 1
            self._tl(req, "drain-export", bytes=len(frame))
            out.append((req, frame))
        return out

    def drain_abort(self, pairs: list[tuple["_Request", bytes]]) -> None:
        """The peer refused or died mid-migration: re-park the frames so
        :meth:`drain_finish` resumes them locally — a failed migration
        must never kill a generation."""
        store = self._get_suspend_store()
        for req, frame in pairs:
            if req.future.done():
                continue
            self._suspend_seq += 1
            key = (id(req), self._suspend_seq)
            if store.put(key, frame):
                self._suspended.append({
                    "req": req, "key": key, "bytes": len(frame),
                    "t_park": time.perf_counter(),
                })
                self._tl(req, "drain-abort", span=False)
            else:
                req.future.set_exception(
                    GraphUnitError("drain abort: suspend store full")
                )
                self._end_tl(req, "error", stage="suspended")

    def complete_migrated(self, req: "_Request", tokens) -> None:
        """Finish a migrated request with the peer's continuation.
        ``tokens[0]`` is the carry token (already delivered here before
        the drain); the rest stream through the request's hook and the
        future resolves with the full output — the client sees ONE
        uninterrupted stream."""
        for t in tokens[1:]:
            if self._token_done(req, int(t)):
                break
        req.done_reason = req.done_reason or "budget"
        self._complete(req)
        self._finish_tl(req)

    def drain_finish(self) -> None:
        """Lift the drain: admission resumes, and any records still
        parked (the no-peer path, or after :meth:`drain_abort`) re-queue
        and resume locally bit-exactly."""
        self._draining = False
        self._preempt = False
        self._wake.set()

    def adopt_seed(self, seed: int) -> None:
        """Drain cutover, REPLACEMENT-replica side: adopt the source's
        sampling-seed counter so migrated sampled streams continue with
        the exact keys the uninterrupted run would have used (greedy
        streams don't care).  Meant for a fresh engine taking over; any
        counter value is *valid* — this only pins determinism."""
        self._seed = int(seed) % (2**31 - 1)

    def packing_snapshot(self) -> dict:
        """Per-deployment packing ledger (``GET /stats/breakdown``)."""
        return {
            "arbitrated": self._arbiter is not None,
            "preempted": self._preempt,
            "draining": self._draining,
            "drains": self.drains,
            "drained_out": self.drained_out,
            "suspended": len(self._suspended),
            "suspends": self.suspends,
            "resumes": self.resumes,
            "suspend_rejected": self.suspend_rejected,
            "queue_pressure_ms": round(self.queue_pressure() * 1e3, 3),
            "suspend_store": (
                self._suspend_store.snapshot()
                if self._suspend_store is not None
                else None
            ),
        }

    def _ensure_run_task(self) -> None:
        """(Re)spawn the run-loop task on the CURRENT event loop.

        A fresh task gets a fresh wake event: asyncio primitives bind to
        the loop that first awaits them, and a scheduler driven through
        several short-lived loops (``asyncio.run`` per call — component
        tests, CLI tools) would otherwise park the new task on an event
        bound to a dead loop and crash it with a cross-loop RuntimeError
        that ``close()`` later re-raises."""
        if self._task is None or self._task.done():
            self._wake = asyncio.Event()
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def close(self) -> None:
        self._closed = True
        self.detach_arbiter()
        if self._task is not None:
            self._task.cancel()
            # a cancel landing while the loop sits on an already-completed
            # wait_for is swallowed (bpo-42130); wake it so the loop's own
            # _closed check at the top of the iteration still exits
            self._wake.set()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        err = RuntimeError("GenerationScheduler closed")
        for req in self._waiting:
            if not req.future.done():
                req.future.set_exception(err)
        self._waiting.clear()
        for _payload, fut in self._prefix_installs:
            if not fut.done():
                fut.set_exception(err)
        self._prefix_installs.clear()

    # ---------------------------------------------------------------- loop

    def _finish_tl(self, req: _Request) -> None:
        """Terminal timeline event for a completed request — called AFTER
        the block event that delivered its last token, so the event order
        reads admit -> blocks -> terminal."""
        self._end_tl(req, req.done_reason or "budget", tokens=len(req.out))

    def _complete(self, req: _Request) -> None:
        if not req.future.done():
            req.future.set_result(np.asarray(req.out, np.int32))
        if req.out and req.t0:
            dur = time.perf_counter() - req.t0
            m = DEFAULT_METRICS
            m.generated_tokens.labels(self.model.name).inc(len(req.out))
            if dur > 0:
                m.tokens_per_s.labels(self.model.name).set(len(req.out) / dur)

    def _token_done(self, req: _Request, tok: int) -> bool:
        if not req.out and req.t0:
            # first sampled token: the serving TTFT (queue wait + prefill
            # + the first decode fetch); later deliveries measure against
            # this for the inter-token-latency ledger
            req.t_first_token = time.perf_counter()
            req.t_last_tok = req.t_first_token
            ttft = req.t_first_token - req.t0
            RECORDER.record_stage(STAGE_TTFT, ttft)
            # exemplar-linked observation (SCT_METRICS_EXEMPLARS): the
            # bucket carries this request's trace id, so a p99 spike on
            # the /prometheus histogram links straight to its
            # GET /stats/timeline?trace= forensics
            from seldon_core_tpu.utils.metrics import observe_exemplar

            observe_exemplar(
                DEFAULT_METRICS.ttft.labels(self.model.name), ttft,
                req.timeline.trace_id if req.timeline is not None else None,
            )
            if req.span is not None:
                req.span.event("first-token", ttft_ms=round(ttft * 1e3, 3))
        req.out.append(tok)
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception:  # a broken listener must not stall the loop
                log.exception("on_token hook failed; detaching it")
                req.on_token = None
        if req.eos_id is not None and tok == req.eos_id:
            req.done_reason = "eos"
            return True
        if len(req.out) >= req.max_new_tokens:
            req.done_reason = "budget"
            return True
        return False

    def _reap_queues(self) -> None:
        """Pre-admission QoS sweep: drop abandoned requests (client gone →
        cancelled future) and fail expired ones with a 504 from the queue,
        BEFORE a prefill is spent on them."""
        now = time.monotonic()
        for q in (self._waiting, self._overflow):
            keep = []
            for req in q:
                if req.future.done():
                    continue  # cancelled before admission: nothing to undo
                if req.deadline is not None and now >= req.deadline:
                    req.future.set_exception(qos.DeadlineExceeded(
                        f"deadline expired after "
                        f"{time.perf_counter() - req.t0:.3f}s waiting in the "
                        "generation queue"
                    ))
                    DEFAULT_METRICS.qos_deadline_miss.labels(
                        self.model.name, "generation-queue"
                    ).inc()
                    qos.note_deadline_miss("generation-queue", req.priority)
                    if req.span is not None:
                        req.span.event(
                            "qos-drop", reason="deadline",
                            stage="generation-queue",
                        )
                    self._end_tl(req, "deadline-reap", stage="queue")
                    continue
                keep.append(req)
            q[:] = keep

    def _reap_slots(self, slots, active) -> int:
        """In-flight QoS sweep: a slot whose client vanished or whose
        deadline passed must stop consuming decode steps mid-generation.
        Returns the number of slots reaped — a host-side reap invalidates
        the device carry (the chip still thinks the slot is active), so the
        overlap pipeline must rebuild its next dispatch from host state."""
        reaped = 0
        now = time.monotonic()
        for i in range(len(slots)):
            req = slots[i]
            if req is None or not active[i]:
                continue
            expired = req.deadline is not None and now >= req.deadline
            if not expired and not req.future.done():
                continue
            if expired and not req.future.done():
                req.future.set_exception(qos.DeadlineExceeded(
                    f"deadline expired mid-generation after "
                    f"{len(req.out)} tokens"
                ))
                DEFAULT_METRICS.qos_deadline_miss.labels(
                    self.model.name, "decode"
                ).inc()
                qos.note_deadline_miss("decode", req.priority)
                if req.span is not None:
                    req.span.event("qos-drop", reason="deadline", stage="decode")
                self._end_tl(
                    req, "deadline-reap", stage="decode", tokens=len(req.out)
                )
            else:
                self._end_tl(
                    req, "disconnect", stage="decode", tokens=len(req.out)
                )
            slots[i] = None
            active[i] = False
            self.model.release_slot(i)
            reaped += 1
        return reaped

    def _deliver(self, toks_seq, act_seq, slots, cur, active, block_s=0.0) -> None:
        """Fan one fetched block's ``(k, S)`` tokens out to their requests.
        Completions here (eos / budget) are DEVICE-visible transitions —
        the chip flipped the slot inactive at the same step — so the device
        carry stays consistent and the overlap pipeline keeps running; the
        freed slot's blocks are only re-reserved at the next sync point.
        ``block_s``: the seconds the block occupied the device (the device
        ledger's word), which the usage meter shares out."""
        S = len(slots)
        now = time.perf_counter()
        reqs = list(slots)  # completions below null the live entries
        counts = [0] * S
        # cascade confidence (docs/GRAPHS.md): the block's per-token top-2
        # logit margins, stashed by the same fetch that brought the tokens
        # — accumulated here per delivered token, zero extra syncs.
        # getattr: duck-typed stand-in models (tests) predate the signal.
        conf_seq = getattr(self.model, "last_conf_seq", None)
        if conf_seq is not None and conf_seq.shape != toks_seq.shape:
            conf_seq = None  # stale stash (shape mismatch): never misattribute
        for step_i in range(toks_seq.shape[0]):
            for i in range(S):
                if not act_seq[step_i, i] or slots[i] is None:
                    continue
                req = slots[i]
                tok = int(toks_seq[step_i, i])
                cur[i] = tok
                counts[i] += 1
                if conf_seq is not None:
                    req.conf_sum += float(conf_seq[step_i, i])
                    req.conf_n += 1
                if self._token_done(req, tok):
                    self._complete(req)
                    slots[i] = None
                    active[i] = False
                    self.model.release_slot(i)
        # per-slot inter-token latency: one sample per (block, slot) — the
        # delivery gap spread over the tokens it carried.  A prefill (or
        # anything else) stalling the pipeline between blocks inflates
        # every live slot's sample; TTFT and device-step never see it.
        # getattr: duck-typed stand-in models (tests) predate the ledger.
        note_itl = getattr(self.model, "note_itl", None)
        # timeline: one "block" event per (fetched block, slot) from the
        # ALREADY-fetched emitted mask — with speculation on it carries the
        # per-block draft/accept split (passes that ran vs tokens emitted),
        # host-side arithmetic only
        spec_d = getattr(self.model, "spec_draft", 0)
        tps = getattr(self.model, "_tps", 1)
        # per-adapter served-token ledger (docs/MULTITENANT.md); getattr:
        # duck-typed stand-in models predate multi-LoRA
        note_adapter = getattr(self.model, "note_adapter_tokens", None)
        # usage attribution (obs/metering.py): the seconds this fused block
        # occupied the device split across the slots it served BY TOKEN
        # SHARE — a slot that emitted 3 of the block's 12 tokens is charged
        # 25% of the block.
        block_tokens = sum(counts)
        # getattr: duck-typed stand-in models predate the gauge
        record_mfu = getattr(self.model, "record_mfu", None)
        if record_mfu is not None:
            record_mfu(block_tokens, block_s)
        if block_s and not block_tokens:
            # a block that emitted nothing (every slot went inactive at
            # dispatch) still spent the device: charge the base row so
            # attribution stays conservation-exact against the wall total
            METER.add(self.model.name, device_s=block_s)
        for i in range(S):
            req = reqs[i]
            if req is None or not counts[i]:
                continue
            if req.adapter and note_adapter is not None:
                note_adapter(req.adapter, counts[i])
            if req.t_last_tok and note_itl is not None:
                note_itl((now - req.t_last_tok) / counts[i])
            req.t_last_tok = now
            accepted = 0
            if spec_d and toks_seq.shape[0] % tps == 0:
                passes = int(
                    np.asarray(act_seq[:, i])
                    .reshape(-1, tps)
                    .any(axis=1)
                    .sum()
                )
                accepted = max(0, counts[i] - passes)
            share_s = (
                block_s * counts[i] / block_tokens if block_tokens else 0.0
            )
            req.u_device_s += share_s
            # per-proposer acceptance attribution (ISSUE 20 satellite):
            # the active spec_method is a build-time constant, so the
            # whole block's accepted tokens belong to one proposer row
            mkw = {}
            if accepted:
                m = getattr(self.model, "spec_method", None) or "ngram"
                mkw[f"tokens_spec_accepted_{m}"] = accepted
            METER.add(
                self.model.name, req.adapter or "", req.priority,
                device_s=share_s, tokens_decode=counts[i],
                tokens_spec_accepted=accepted,
                **mkw,
            )
            if req.timeline is not None or req.span is not None:
                attrs = {"tokens": counts[i]}
                if spec_d and toks_seq.shape[0] % tps == 0:
                    attrs.update(
                        passes=passes,
                        drafted=passes * spec_d,
                        accepted=accepted,
                    )
                self._tl(req, "block", **attrs)
            if slots[i] is None and req.done_reason is not None:
                # completed in this block: terminal AFTER its block event
                self._finish_tl(req)

    def _fail_inflight(self, slots, active, exc: BaseException) -> None:
        """A failed device step poisons every in-flight request,
        mid-prefill admissions included (their blocks release with the
        blanket slot sweep below)."""
        for ent in self._prefilling:
            if not ent["req"].future.done():
                ent["req"].future.set_exception(exc)
            self._end_tl(ent["req"], "error", stage="prefill")
        self._prefilling.clear()
        self._prefill_slots.clear()
        for i in range(len(slots)):
            if slots[i] is not None:
                if not slots[i].future.done():
                    slots[i].future.set_exception(exc)
                self._end_tl(slots[i], "error", stage="decode")
            slots[i] = None
            self.model.release_slot(i)
        active[:] = False

    # block-boundary outcomes that are no break of the chain (the rest are
    # the causes an ``overlap-break`` timeline event names)
    _CHAINED = ("chained-early", "chained-due", "chained-late")
    _NO_BREAK = frozenset(_CHAINED + ("idle", "overlap-off"))
    # how long before the in-flight block's expected end a held decision
    # falls: the hand-off to a thread and the dispatch (1.1 ms on a v5e
    # host, PERF.md §6, PR 31) and the estimate's own error, which grows
    # with the block
    _LEAD_S, _LEAD_SHARE = 0.002, 0.03

    def _chain_break(self, carry_dirty: bool) -> str | None:
        """Why the next block cannot come off the device carry, whoever
        waits: the host decided something the chip cannot see."""
        if carry_dirty:
            return "carry-dirty"
        if self._preempt or self._arb_contended():
            # packed chip: a co-tenant wants (or was granted) the device —
            # yield at the block boundary instead of chaining another block
            return "arbiter-yield"
        if self._external_release:
            return "handoff-release"
        if self._prefilling:
            return "chunked-prefill"
        return None

    def _outlasts(self, slots, active, k: int) -> np.ndarray:
        """The live slots that cannot end inside the block in flight: no
        ``eos_id``, and a budget past the block's worst case (``k`` tokens
        a slot, ``k * (1 + draft)`` with speculation)."""
        worst = k * getattr(self.model, "_tps", 1)
        return np.array([
            bool(live) and req is not None and req.eos_id is None
            and req.max_new_tokens - len(req.out) > worst
            for req, live in zip(slots, active)
        ])

    async def _nobody_came(
        self, due: float, carry_dirty: bool, tokens: asyncio.Future
    ) -> bool:
        """Hold the decision on the next block until the one in flight is
        about to end (``due``, on ``time.perf_counter``), so a request that
        arrives meanwhile is seen.  True: nobody waits and nothing broke
        the chain — dispatch now, behind the block in flight.  False: leave
        it to the sync point, or to the block's ``tokens`` (its fetch came
        back first: the estimate was late, and the next one starts from
        this block)."""
        while True:
            # no await between the clear and the checks: a submit landing
            # after them sets the event the wait below returns on
            self._wake.clear()
            if (
                tokens.done() or self._waiting or self._overflow
                or self._chain_break(carry_dirty)
            ):
                return False
            left = due - time.perf_counter()
            if left <= 0:
                return True
            woke = asyncio.ensure_future(self._wake.wait())
            try:
                await asyncio.wait(
                    {tokens, woke}, timeout=left,
                    return_when=asyncio.FIRST_COMPLETED,
                )
            finally:
                woke.cancel()

    @staticmethod
    def _ended_in(toks_seq, act_seq, slots, active) -> np.ndarray:
        """The live slots a fetched block finished (budget or eos), read
        off its tokens and emitted mask as ``_deliver`` is about to find
        them — the chip flipped them inactive at the same step."""
        ended = np.zeros(len(slots), bool)
        for i in np.flatnonzero(active):
            req = slots[i]
            if req is None:
                continue
            took = np.asarray(act_seq[:, i], bool)
            ended[i] = len(req.out) + int(took.sum()) >= req.max_new_tokens or (
                req.eos_id is not None
                and bool((toks_seq[took, i] == req.eos_id).any())
            )
        return ended

    def _admission_break(self, live: np.ndarray) -> str | None:
        """The cause to name when the sync point at this boundary could
        admit a request that waits: a slot is free (``live`` is the slots
        still running after the block in hand) or the request needs none."""
        if not (self._waiting or self._overflow):
            return None
        free = len(live) - int(live.sum()) - len(self._external)
        if free > 0 or any(r.embed_only for r in self._waiting):
            return "admission" if self._waiting else "kv-starved"
        return None

    async def _chain(self, active, k: int, how: str) -> tuple[tuple | None, str]:
        """Dispatch the next block from the device carry -> ``(handle,
        how)``, or ``(None, "dispatch-error")``."""
        try:
            nxt, sent_at = await asyncio.to_thread(
                self._stamped, self.model.step_k_continue, active,
                self._next_seed(), k,
            )
        except asyncio.CancelledError:
            raise
        except Exception:
            log.exception(
                "overlapped dispatch failed; falling back to sequential"
            )
            return None, "dispatch-error"
        self._sent_block(sent_at, k)
        return nxt, how

    def boundary_snapshot(self) -> dict:
        """Decode-block boundaries by outcome (``GET /stats/breakdown``):
        the next block chained off the device carry when this one was
        dispatched (``chained_early``: a full house of known budgets), when
        it was about to end and nobody had come (``chained_due``) or once
        its tokens were in hand (``chained_late``); a sync point, by the
        cause that broke the chain; or nothing dispatched because no slot
        was live (``idle``)."""
        b = self.boundaries
        return {
            **{c.replace("-", "_"): b.get(c, 0) for c in self._CHAINED},
            "idle": b.get("idle", 0),
            "sync": {
                c: n for c, n in sorted(b.items())
                if c not in self._CHAINED and c != "idle"
            },
        }

    async def _run(self) -> None:
        S = self.model.n_slots
        slots: list[_Request | None] = [None] * S
        cur = np.zeros(S, np.int32)
        temps = np.zeros(S, np.float32)
        active = np.zeros(S, bool)
        k = self.model.decode_block
        # overlapped pipeline state: the dispatched-but-unfetched block, and
        # whether the device carry still matches host bookkeeping (a reap or
        # admission makes the next dispatch rebuild from host arrays)
        pending: tuple | None = None
        carry_dirty = True
        # host-clock estimate of when the block in flight ends: when it
        # started (its dispatch, or its predecessor's tokens coming back,
        # whichever is later) plus what the last fetched block took
        pending_t = fetched_t = block_s = 0.0
        # a sync point in the making: when the tokens of the block before
        # it were in hand (``fetched_t``), until the next decode block is
        # dispatched — the time live streams have no block in flight
        sync_t: float | None = None
        self._active = active
        self._watchdog.start(threading.get_ident())
        try:
            while True:
                if self._closed:
                    # close() may have lost its cancel to a completed
                    # wait_for (bpo-42130); route through the same cleanup
                    raise asyncio.CancelledError
                self._reap_queues()
                self._reap_suspended()
                if pending is None and self._external_release:
                    # handoff slots released with no block in flight: safe
                    # to return their blocks to the pool right here
                    self._drain_external_releases()
                if pending is None and self._prefix_installs:
                    # peer-pulled chains: the install scatter takes pool
                    # blocks, legal only with no decode block in flight
                    await self._drain_prefix_installs()
                if pending is None:
                    # draft prefills deferred by the arbiter (batch-class
                    # registrant) run at this sync point, off the decode
                    # block's critical path
                    await self._drain_draft_prefills()
                if pending is None and self._preempt and active.any():
                    # preemption verb (docs/PACKING.md): at this sync
                    # point, export every active slot into the suspend
                    # store and free its blocks — the device carry no
                    # longer matches host bookkeeping afterwards
                    if await self._suspend_active(slots, cur, temps, active):
                        carry_dirty = True
                if pending is None and self._suspended and not self._preempt:
                    # resume verb: suspended records re-queue as imported
                    # admissions (donated fused-scatter path, bit-exact)
                    self._drain_resumes()
                if (
                    pending is None
                    and self._preempt
                    and not active.any()
                    and not self._prefilling
                ):
                    # preempted: the arbiter gave the device to a
                    # co-tenant — hold admissions (and the grant) until
                    # request_resume lifts the flag.  The timeout keeps
                    # deadline reaping of parked/suspended work at ~50ms
                    # granularity; spinning would starve the co-tenant's
                    # event-loop turns.
                    self._arb_release()
                    if self._draining and not self._quiesced.is_set():
                        # drain verb: nothing device-resident any more —
                        # every active slot is parked (or ran to completion
                        # when the store refused it); the migration's
                        # export half may proceed
                        self._quiesced.set()
                    for q in (self._waiting, self._overflow):
                        for r in q:
                            self._tl(
                                r, "paused", span=False, cause="preempted"
                            )
                    self._wake.clear()
                    if self._arbiter is not None:
                        # off-edge policy tick: with the interactive side
                        # gone quiet there may be no grant edge left to
                        # trigger our resume
                        self._arbiter.poll()
                    if not self._preempt:
                        continue
                    sync_t = None
                    with self._part("idle-park"):
                        try:
                            await asyncio.wait_for(
                                self._wake.wait(), timeout=0.05
                            )
                        except asyncio.TimeoutError:
                            pass
                    continue
                if (
                    pending is None
                    and not active.any()
                    and not self._overflow
                    and not self._waiting
                    and not self._prefilling
                    and not self._prefix_installs
                ):
                    # fully idle: park until a submit wakes us (no await
                    # between the emptiness check and clear, so a submit
                    # landing now still sets the event we wait on).  The
                    # device grant goes back first — an idle co-tenant
                    # must never hold the chip.
                    self._arb_release()
                    self._wake.clear()
                    sync_t = None
                    with self._part("idle-park"):
                        await self._wake.wait()
                    self._reap_queues()
                if pending is None:
                    # sync point: admissions and dispatch only happen with
                    # no block in flight — a prefill (or a freed block's
                    # reuse) must never race a dispatched decode.
                    # Admit whatever is waiting into remaining free slots —
                    # block-starved overflow first, then the wait list in
                    # (priority, arrival) order so batch traffic can never
                    # starve interactive; all prefills dispatch back-to-back
                    # and their first tokens are fetched in ONE device
                    # round trip
                    batch: list[_Request] = []
                    # capacity excludes slots pinned by in-flight handoffs
                    # and slots mid-chunked-prefill; a preempted scheduler
                    # admits NOTHING (its free blocks belong to the
                    # co-tenant until the arbiter resumes it)
                    cap_free = (
                        0
                        if self._preempt
                        else S - int(active.sum()) - len(self._external)
                        - len(self._prefill_slots)
                    )
                    # embed-only requests consume no slot or KV: the whole
                    # waiting wave serves this sync point regardless of
                    # cap_free (a preempted scheduler holds them — the
                    # device belongs to the co-tenant)
                    embeds: list[_Request] = []
                    if not self._preempt:
                        embeds = [r for r in self._waiting if r.embed_only]
                        for r in embeds:
                            self._waiting.remove(r)
                    while self._overflow and len(batch) < cap_free:
                        batch.append(self._overflow.pop(0))
                    retried = len(batch)  # taken before, and sent back
                    if self._waiting and len(batch) < cap_free:
                        self._waiting.sort(
                            key=lambda r: (qos.priority_rank(r.priority), r.t0)
                        )
                        while self._waiting and len(batch) < cap_free:
                            batch.append(self._waiting.pop(0))
                    if batch or embeds or self._prefilling or active.any():
                        # packed chip (docs/PACKING.md): all device work
                        # below — prefills, chunk advances, the fused
                        # block dispatch — runs under the device grant;
                        # a co-tenant's block never interleaves inside it
                        await self._arb_acquire()
                    if embeds:
                        await self._admit_embeds(embeds)
                    live_before = int(active.sum())
                    if batch:
                        with self._part(
                            "sched:admit", STAGE_ADMIT_ROUND, n=len(batch)
                        ) as rnd:
                            for req in batch[retried:]:
                                self._note_slot_wait(req, rnd.t0)
                            await self._admit_batch(
                                batch, slots, cur, temps, active
                            )
                    if self._prefilling:
                        # chunked prefill: ONE chunk per sync point — the
                        # admission cost a decode stall can see is bounded
                        # by a chunk, not a prompt (docs/PERFORMANCE.md §7)
                        with self._part("sched:advance-prefill"):
                            await self._advance_prefill(
                                slots, cur, temps, active
                            )
                    self._reap_slots(slots, active)
                    if (
                        int(active.sum()) > live_before
                        and self._waiting
                        and not self._prefilling
                        and not self._preempt
                        and not self._arb_contended()
                    ):
                        # somebody came while the prefills ran: the sync
                        # point is taken again (it admits them if a slot is
                        # left, and falls through if none is) before a
                        # block is dispatched ahead of them.  Each pass
                        # fills a slot, so there are at most as many
                        # passes as slots.
                        continue
                    if not active.any():
                        # nothing to dispatch: the grant goes back before
                        # any park or spin below
                        self._arb_release()
                        sync_t = None
                        if self._prefilling:
                            # chunks still advancing: loop straight back —
                            # each iteration does real device work
                            continue
                        if self._overflow and not self._external:
                            # nothing in flight can ever free blocks: these
                            # requests exceed the pool outright
                            err = GraphUnitError(
                                "request KV reservation exceeds the configured "
                                f"pool ({self.model.kv_blocks - 1} blocks of "
                                f"{self.model.kv_block_size})"
                            )
                            for req in self._overflow:
                                if not req.future.done():
                                    req.future.set_exception(err)
                            self._overflow.clear()
                        elif (
                            (self._overflow or self._waiting)
                            and self._external
                            and not self._external_release
                        ):
                            # every admittable slot (or the blocks) is
                            # pinned by an in-flight handoff: park until a
                            # release or submit wakes us — spinning here
                            # would monopolize the event loop and starve
                            # the very release callback we wait for.  The
                            # timeout keeps deadline reaping of parked
                            # queue entries at ~50ms granularity.
                            for q in (self._waiting, self._overflow):
                                for r in q:
                                    # deduped repeat on the timeline; never
                                    # folded onto the span (a long park
                                    # would flood it)
                                    self._tl(
                                        r, "paused", span=False,
                                        cause="externals-pinned",
                                    )
                            self._wake.clear()
                            with self._part("idle-park"):
                                try:
                                    await asyncio.wait_for(
                                        self._wake.wait(), timeout=0.05
                                    )
                                except asyncio.TimeoutError:
                                    pass
                        continue
                    seed = self._next_seed()
                    if k <= 1:
                        # single-step path (decode_block=1): dispatch, fetch
                        # and deliver inline — no fused block to overlap
                        with self._part("sched:dispatch") as sent:
                            try:
                                toks, done_at = await asyncio.to_thread(
                                    self._stamped, self.model.step,
                                    cur, active, temps, seed,
                                )
                            except asyncio.CancelledError:
                                raise
                            except Exception as exc:
                                log.exception(
                                    "decode step failed; failing %d in-flight requests",
                                    int(active.sum()),
                                )
                                self._arb_release()
                                self._fail_inflight(slots, active, exc)
                                continue
                            # one call dispatches and fetches: the step has
                            # the device from the call's start to its end
                            self.device.sent(
                                sent.t0, "decode", "decode:k1", 1,
                                part=self._part_now,
                            )
                        self._deliver(
                            toks[None], active.copy()[None], slots, cur, active,
                            self.device.done(done_at),
                        )
                        self._reap_slots(slots, active)
                        # single-step path: every step IS a sync point, so
                        # the grant rotates per step on a packed chip
                        self._arb_release()
                        continue
                    # one dispatch yields up to k tokens per slot; the
                    # device enforces per-slot eos + budget so finished
                    # slots stop touching the cache mid-block
                    with self._part("sched:dispatch") as sent:
                        eos = np.array(
                            [
                                slots[i].eos_id
                                if slots[i] is not None
                                and slots[i].eos_id is not None
                                else -1
                                for i in range(S)
                            ],
                            np.int32,
                        )
                        remaining = np.array(
                            [
                                max(0, slots[i].max_new_tokens - len(slots[i].out))
                                if slots[i] is not None
                                else 0
                                for i in range(S)
                            ],
                            np.int32,
                        )
                        try:
                            pending, sent_at = await asyncio.to_thread(
                                self._stamped, self.model.step_k_dispatch,
                                cur, active, temps, seed, eos, remaining, k,
                            )
                        except asyncio.CancelledError:
                            raise
                        except Exception as exc:
                            log.exception(
                                "decode dispatch failed; failing %d in-flight requests",
                                int(active.sum()),
                            )
                            self._arb_release()
                            self._fail_inflight(slots, active, exc)
                            continue
                        self._sent_block(sent_at, k)
                    carry_dirty = False
                    pending_t = sent.t1
                    if sync_t is not None:
                        RECORDER.record_stage(STAGE_SYNC_POINT, pending_t - sync_t)
                        sync_t = None
                    continue
                # fetch phase — THE overlap: block N+1 is dispatched straight
                # from block N's on-device carry.  WHEN is decided from what
                # could be admitted at N's end (docs/PERFORMANCE.md §1):
                # nothing, whatever arrives -> chain at once, so the chip
                # never waits for the host; something -> hold the decision
                # while N runs, so a request that has a slot never waits for
                # a block chained ahead of it: chain when N is about to end
                # if nobody came and a slot is sure to stay live, else
                # decide with N's tokens in hand.
                nxt: tuple | None = None
                outcome: str | None = None
                tokens = None  # N's fetch, where it runs beside the decision
                if not self.overlap:
                    outcome = "overlap-off"
                elif not active.any():
                    outcome = "idle"
                elif self._chain_break(carry_dirty) is None:
                    stays = self._outlasts(slots, active, k)
                    how = None
                    if stays.all() and not any(
                        r.embed_only for r in self._waiting
                    ):
                        # every slot is taken and none can end inside N: a
                        # sync point at its end could admit nobody, whoever
                        # waits (an embed-only request needs no slot)
                        how = "chained-early"
                    elif block_s > 0 and stays.any():
                        # a slot is sure to stay live, and somebody may yet
                        # come for a free one: N's fetch waits on its own
                        # thread while the decision is held
                        tokens = asyncio.ensure_future(asyncio.to_thread(
                            self._stamped, self.model.step_k_fetch, pending
                        ))
                        with self._part("sched:hold"):
                            if await self._nobody_came(
                                max(pending_t, fetched_t) + block_s
                                - (self._LEAD_S + self._LEAD_SHARE * block_s),
                                carry_dirty, tokens,
                            ):
                                how = "chained-due"
                    if how is not None:
                        with self._part("sched:chain") as sent:
                            nxt, outcome = await self._chain(active, k, how)
                        nxt_t = sent.t1
                if tokens is None:
                    tokens = asyncio.to_thread(
                        self._stamped, self.model.step_k_fetch, pending
                    )
                try:
                    with self._part("sched:fetch") as fetch:
                        (toks_seq, act_seq), done_at = await tokens
                except asyncio.CancelledError:
                    raise
                except Exception as exc:
                    log.exception(
                        "decode step failed; failing %d in-flight requests",
                        int(active.sum()),
                    )
                    if nxt is not None:
                        # drain the speculative block too (its carry chained
                        # off the failed one; a dangling fetch helps nobody)
                        try:
                            await asyncio.to_thread(self.model.step_k_fetch, nxt)
                        except Exception:
                            pass
                    pending = None
                    carry_dirty = True
                    self.device.lost()
                    self._arb_release()
                    self._fail_inflight(slots, active, exc)
                    continue
                # what the block had of the device: to the worker's stamp
                # where its wait returned, from its predecessor's or its own
                # dispatch's (``block_s`` below, on the loop's own stamps,
                # only times the hold)
                busy_s = self.device.done(done_at)
                block_s = fetch.t1 - max(pending_t, fetched_t)
                fetched_t = fetch.t1
                if outcome is None:
                    # N's tokens are in hand and nothing is chained: the
                    # sync point if it can do something, else the next
                    # block now, and N's delivery while it runs
                    live = active & ~self._ended_in(
                        toks_seq, act_seq, slots, active
                    )
                    outcome = (
                        self._chain_break(carry_dirty)
                        or self._admission_break(live)
                    )
                    if outcome is None and not live.any():
                        outcome = "idle"
                    if outcome is None:
                        with self._part("sched:chain") as sent:
                            nxt, outcome = await self._chain(
                                active, k, "chained-late"
                            )
                        nxt_t = sent.t1
                self.boundaries[outcome] = self.boundaries.get(outcome, 0) + 1
                if outcome not in self._NO_BREAK:
                    # name WHY the chain broke: the cause lands on every
                    # live stream's timeline — the forensics for "this
                    # request's ITL spiked right here"
                    for i in range(S):
                        if slots[i] is not None and active[i]:
                            self._tl(slots[i], "overlap-break", cause=outcome)
                    if outcome == "dispatch-error":
                        carry_dirty = True
                pending = nxt
                if pending is not None:
                    pending_t = nxt_t
                else:
                    # pipeline drained to a sync point: rotate the grant
                    # BEFORE host-side delivery so a parked co-tenant's
                    # dispatch overlaps our bookkeeping
                    self._arb_release()
                    sync_t = fetched_t
                with self._part("sched:deliver"):
                    self._deliver(toks_seq, act_seq, slots, cur, active, busy_s)
                    if self._reap_slots(slots, active):
                        # host-side reap: the chip still thinks those slots
                        # are live — the next dispatch must rebuild from
                        # host state
                        carry_dirty = True
        except asyncio.CancelledError:
            err = RuntimeError("GenerationScheduler closed")
            for ent in self._prefilling:
                if not ent["req"].future.done():
                    ent["req"].future.set_exception(err)
                self._end_tl(ent["req"], "error", cause="closed")
            self._prefilling.clear()
            self._prefill_slots.clear()
            for i, req in enumerate(slots):
                if req is not None:
                    if not req.future.done():
                        req.future.set_exception(err)
                    self._end_tl(req, "error", cause="closed")
                self.model.release_slot(i)
            for req in self._overflow:
                if not req.future.done():
                    req.future.set_exception(err)
                self._end_tl(req, "error", cause="closed")
            self._overflow.clear()
            for rec in self._suspended:
                self._meter_unpark(rec)
                if not rec["req"].future.done():
                    rec["req"].future.set_exception(err)
                self._end_tl(rec["req"], "error", cause="closed")
            self._suspended.clear()
            if self._suspend_store is not None:
                self._suspend_store.flush()
            self._arb_release()
            raise
        finally:
            self._watchdog.stop()

    async def _admit_batch(self, batch, slots, cur, temps, active) -> None:
        free = [
            i
            for i in range(len(slots))
            if not active[i]
            and i not in self._external
            and i not in self._prefill_slots
        ]
        # chunk-pace an admission only when live decode streams exist to
        # protect: an idle scheduler admits monolithically — nothing can
        # stall, the prefill costs fewer dispatches, and sampled streams
        # keep the exact seed-per-block sequence of the unchunked path.
        # getattr: duck-typed stand-in models (tests) predate chunking.
        chunk_c = (
            getattr(self.model, "prefill_chunk", 0) if active.any() else 0
        )

        def dispatch_and_fetch():
            placed = []
            errors = []
            starved = []
            chunked = []
            rungs = self._rungs()
            stamps = []  # the first device call returned; the tokens in hand

            def dispatched():
                if not stamps:
                    stamps.append(time.perf_counter())

            for req, slot in zip(batch, free):
                # duck-typed stand-in models (tests) predate multi-LoRA:
                # only pass the kwarg when the request actually names one
                akw = {"adapter": req.adapter} if req.adapter else {}
                try:
                    if req.imported is not None:
                        # disagg import: the prompt KV arrived from a
                        # prefill engine — reserve + scatter, no prefill.
                        # A resumed suspend record (docs/PACKING.md) rides
                        # the same path with its EXTENDED prompt (original
                        # prompt + tokens emitted before suspension) and
                        # the frame's remaining-token reservation.
                        imp = req.imported
                        # spec kwarg only when a state rode the frame:
                        # duck-typed stand-in models predate speculation
                        skw = (
                            {"spec_state": imp["spec"]}
                            if imp.get("spec") is not None
                            else {}
                        )
                        self.model.attach_imported(
                            slot, imp.get("prompt", req.prompt),
                            imp["k"], imp["v"],
                            reserve_tokens=int(
                                imp.get("reserve_tokens", req.max_new_tokens)
                            ),
                            k_scale=imp.get("k_scale"),
                            v_scale=imp.get("v_scale"),
                            first_token=imp["first_token"],
                            **akw, **skw,
                        )
                        dispatched()
                        placed.append((req, slot, imp["first_token"]))
                        continue
                    if (
                        chunk_c
                        and not req.prefill_only
                        and req.prompt.size > chunk_c
                    ):
                        # chunked prefill: reserve only (host-side) — the
                        # run loop paces the chunks, one per sync point
                        plan = self.model.admit_chunk_plan(
                            slot, req.prompt, req.temperature,
                            self._next_seed(),
                            reserve_tokens=req.max_new_tokens,
                            **akw,
                        )
                        chunked.append((req, slot, plan))
                        continue
                    tok_dev = self.model.admit_dispatch(
                        slot, req.prompt, req.temperature, self._next_seed(),
                        reserve_tokens=req.max_new_tokens,
                        **akw,
                    )
                    dispatched()
                    placed.append((req, slot, tok_dev))
                except OutOfKVBlocks:
                    # pool is momentarily full: hold until completions free
                    # blocks (the run loop fails it if nothing is in flight)
                    starved.append(req)
                except Exception as exc:  # noqa: BLE001 - routed to the future
                    errors.append((req, exc))
            # one round trip fetches every admitted first token (imported
            # first tokens are host ints already; device_get passes them)
            # one round trip per admitted batch, not per token
            # sct: host-sync-ok admission sync point
            toks = jax.device_get([t for _, _, t in placed]) if placed else []
            stamps.append(time.perf_counter())
            return placed, toks, errors, starved, chunked, rungs, stamps

        placed, toks, errors, starved, chunked, rungs, stamps = (
            await asyncio.to_thread(dispatch_and_fetch)
        )
        if len(stamps) == 2:
            # the round on the device: from its first dispatch (or the
            # predecessor's end) to its first tokens in hand
            self._sent_prompts(stamps[0], rungs, len(placed))
            self.device.done(stamps[1])
        # timeline admit events come from host-side reservation bookkeeping
        # (reuse depth, block split) — getattr: stand-in models predate it
        resnap = getattr(self.model, "reservation_snapshot", lambda s: None)
        # stamp the active proposer on admit events so a timeline reader
        # can attribute acceptance-rate shifts to the speculation config
        specm = getattr(self.model, "spec_method", None)
        smkw = {"spec_method": specm} if specm else {}
        for req, slot, plan in chunked:
            if req.future.done():  # client vanished while we reserved
                self.model.release_slot(slot)
                self._end_tl(req, "disconnect", stage="prefill")
                continue
            self._note_queue_wait(req)
            self._prefilling.append(
                {"req": req, "slot": slot, "plan": plan, "i": 0}
            )
            self._prefill_slots.add(slot)
            akw = {"adapter": req.adapter} if req.adapter else {}
            snap = resnap(slot) or {}
            self._meter_admit(req, snap)
            self._tl(
                req, "admit", slot=slot, chunked=True,
                chunks=len(plan["payloads"]), **akw, **snap, **smkw,
            )
        for req in starved:
            self._tl(req, "kv-starved", span=False)
        self._overflow.extend(starved)
        for req, exc in errors:
            if not isinstance(exc, GraphUnitError):
                log.exception("prefill admission failed", exc_info=exc)
            if not req.future.done():
                req.future.set_exception(exc)
            self._end_tl(req, "error", stage="admit")
        for (req, slot, _), tok in zip(placed, toks):
            if req.prefill_only:
                # disagg handoff: pin the slot (blocks stay reserved for
                # the KV export) and hand (slot, first_token) back; a
                # client that vanished mid-prefill releases immediately
                if req.future.done():
                    self.model.release_slot(slot)
                    self._end_tl(req, "disconnect", stage="prefill")
                else:
                    self._external.add(slot)
                    akw = {"adapter": req.adapter} if req.adapter else {}
                    snap = resnap(slot) or {}
                    self._meter_admit(req, snap)
                    self._tl(
                        req, "admit", slot=slot, prefill_only=True,
                        **akw, **snap, **smkw,
                    )
                    req.future.set_result((slot, int(tok)))
                    self._end_tl(req, "exported", slot=slot)
                continue
            self._note_queue_wait(req)
            attrs = resnap(slot) or {}
            if req.imported is None:
                # imported admissions (disagg handoff / resumed suspends)
                # prefilled nothing here — the paying engine metered it
                self._meter_admit(req, attrs)
            if req.adapter:
                attrs["adapter"] = req.adapter
            if req.imported is not None and req.imported.get("resumed"):
                # resumed suspend record (docs/PACKING.md): the carry
                # token was already delivered to the client before the
                # suspension — running it through _token_done again would
                # double-deliver it.  Re-arm the slot directly; the
                # remaining-token budget derives from len(out) as usual.
                req.imported = None  # free the record's host arrays
                req.t_last_tok = time.perf_counter()  # ITL skips the gap
                self._tl(
                    req, EVENT_RESUME, slot=slot, tokens=len(req.out),
                    **attrs,
                )
                slots[slot] = req
                cur[slot] = int(tok)
                temps[slot] = req.temperature
                active[slot] = True
                continue
            if req.imported is not None:
                attrs["imported"] = True
            self._tl(req, "admit", slot=slot, **attrs, **smkw)
            if self._token_done(req, int(tok)):
                self._complete(req)
                self._finish_tl(req)
                self.model.release_slot(slot)
                continue
            slots[slot] = req
            cur[slot] = int(tok)
            temps[slot] = req.temperature
            active[slot] = True

    async def _advance_prefill(self, slots, cur, temps, active) -> None:
        """Advance chunked prefills by ONE chunk (Sarathi-style stall-free
        admission, docs/PERFORMANCE.md §7).  Runs only at sync points, so a
        chunk and a decode block are queued back-to-back on the device and
        the in-flight streams pay at most one chunk of extra latency per
        block.  Intermediate chunks are dispatched without a host fetch;
        only the final chunk's sampled token is materialized — the same one
        host sync an unchunked admission costs."""
        now = time.monotonic()
        keep = []
        for ent in self._prefilling:
            req = ent["req"]
            if req.future.done():  # cancel-on-disconnect mid-prefill
                self._prefill_slots.discard(ent["slot"])
                self.model.release_slot(ent["slot"])
                self._end_tl(req, "disconnect", stage="prefill", chunks=ent["i"])
                continue
            if req.deadline is not None and now >= req.deadline:
                req.future.set_exception(qos.DeadlineExceeded(
                    f"deadline expired after {ent['i']} prefill chunks"
                ))
                DEFAULT_METRICS.qos_deadline_miss.labels(
                    self.model.name, "prefill"
                ).inc()
                qos.note_deadline_miss("prefill", req.priority)
                if req.span is not None:
                    req.span.event(
                        "qos-drop", reason="deadline", stage="prefill"
                    )
                self._prefill_slots.discard(ent["slot"])
                self.model.release_slot(ent["slot"])
                self._end_tl(
                    req, "deadline-reap", stage="prefill", chunks=ent["i"]
                )
                continue
            keep.append(ent)
        self._prefilling[:] = keep
        if not self._prefilling:
            return
        ent = self._prefilling[0]
        req, slot, plan = ent["req"], ent["slot"], ent["plan"]
        last = ent["i"] == len(plan["payloads"]) - 1

        rungs = self._rungs()

        def one_chunk():
            tok_dev = self.model.prefill_chunk_dispatch(plan, ent["i"])
            sent_at = time.perf_counter()
            # only the last chunk is waited for: the others are booked with
            # whatever the device ledger next hears done
            return int(tok_dev) if last else None, sent_at, time.perf_counter()

        try:
            tok, sent_at, done_at = await asyncio.to_thread(one_chunk)
        except asyncio.CancelledError:
            raise
        except Exception as exc:
            if not isinstance(exc, GraphUnitError):
                log.exception("chunked prefill failed")
            self._prefilling.pop(0)
            self._prefill_slots.discard(slot)
            self.model.release_slot(slot)
            if not req.future.done():
                req.future.set_exception(exc)
            self._end_tl(req, "error", stage="prefill", chunks=ent["i"])
            return
        self._sent_prompts(sent_at, rungs, 1, waits=last)
        if last:
            self.device.done(done_at)
        self._tl(
            req, "chunk", i=ent["i"], of=len(plan["payloads"]), last=last
        )
        ent["i"] += 1
        if not last:
            return
        self._prefilling.pop(0)
        self._prefill_slots.discard(slot)
        if self._token_done(req, tok):
            self._complete(req)
            self._finish_tl(req)
            self.model.release_slot(slot)
            return
        slots[slot] = req
        cur[slot] = tok
        temps[slot] = req.temperature
        active[slot] = True


PAD_ID = -1  # right-pad for ragged generated rows in dense responses

_STREAM_END = object()  # queue sentinel: the submit task completed


class GenerativeComponent(SeldonComponent):
    """Graph unit serving a generative decoder.

    Wire contract (MODEL unit, ``predict``):

    * ``data.ndarray`` (B, L) int token ids -> (B, <=max_new) generated ids,
      rows right-padded with ``-1`` where EOS ended a row early;
    * ``strData`` JSON ``{"tokens": [[...], ...] | [...],
      "max_new_tokens": N, "temperature": t, "eos_id": e}`` ->
      ``strData`` JSON ``{"tokens": [[...], ...]}`` — per-request options.
    """

    # metrics() exposes cumulative step counters only (safe to race);
    # serializing would defeat continuous batching
    SAFE_ANNOTATIONS = True

    def __init__(
        self,
        model: GenerativeModel,
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: int | None = None,
        queue_max: int | None = None,
        overlap: bool | None = None,
        adapter: str | None = None,
        pack_class: str | None = None,
        pack_slo_ms: float | None = None,
    ):
        self.model = model
        self.scheduler = GenerationScheduler(
            model, maxsize=queue_max, overlap=overlap
        )
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        # greedy decode is a pure function of the prompt, so a temperature-0
        # deployment participates in the caching plane (exact + semantic
        # response tiers both gate on whole-graph determinism); sampled
        # decode (default temperature > 0) draws from a per-process seed and
        # must never be cached.  Per-request temperature overrides are safe:
        # cache keys cover the full body, so an override that turns sampling
        # on can at worst replay its own first sample, never another
        # request's bytes.  Instance-level on purpose — the walker reads the
        # flag per component.
        self.DETERMINISTIC = self.temperature == 0.0
        self.eos_id = eos_id
        # deployment-default LoRA adapter (docs/MULTITENANT.md): requests
        # may override per call with the strData "adapter" field; the A/B
        # and canary machinery splits traffic between two adapter ids of
        # one base deployment by giving each predictor a different default
        self.adapter = adapter or None
        # chip packing (docs/PACKING.md): this deployment's QoS class and
        # queue-wait SLO band on a packed device.  Registration with the
        # process arbiter is explicit (register_packed / the engine's
        # multi-deployment boot) or via SCT_PACK=1 — a sole-tenant
        # deployment never touches the arbiter.
        self.pack_class = (
            qos.parse_priority(pack_class) if pack_class else None
        )
        self.pack_slo_ms = float(pack_slo_ms) if pack_slo_ms else None
        if os.environ.get("SCT_PACK", "0") == "1":
            self.register_packed()

    def register_packed(self, arbiter=None) -> None:
        """Attach this deployment's scheduler to the device arbiter
        (process-wide one by default) under its packing class/SLO."""
        if self.scheduler._arbiter is not None:
            return
        if arbiter is None:
            from seldon_core_tpu.executor.arbiter import get_arbiter

            arbiter = get_arbiter()
        self.scheduler.attach_arbiter(
            arbiter,
            priority=self.pack_class or qos.PRIO_INTERACTIVE,
            slo_ms=self.pack_slo_ms,
        )

    def warmup(self) -> int:
        return self.model.warmup()

    def warmup_variants(self) -> list[str]:
        """Per-(bucket, program) compile attribution for /stats/warmup —
        names the speculative-verify and int8 variants explicitly so
        readiness provably covered every program actually served."""
        return list(self.model.warmup_programs)

    async def close(self) -> None:
        await self.scheduler.close()
        self.model.release_memory()

    def metrics(self) -> list[dict[str, Any]]:
        out = [
            {"key": f"{self.model.name}_decode_steps", "type": "GAUGE", "value": self.model.steps},
            {"key": f"{self.model.name}_prefills", "type": "GAUGE", "value": self.model.prefills},
            {"key": f"{self.model.name}_overlapped_blocks", "type": "GAUGE", "value": self.model.overlapped},
            {"key": f"{self.model.name}_kv_imports", "type": "GAUGE", "value": self.model.imports},
        ]
        if self.model.spec_draft and self.model.spec_verify_passes:
            out.append({
                "key": f"{self.model.name}_accepted_tokens_per_step",
                "type": "GAUGE",
                "value": self.model.spec_emitted_tokens
                / self.model.spec_verify_passes,
            })
        if self.model.prefix_index is not None:
            out.append({
                "key": f"{self.model.name}_prefills_reused",
                "type": "GAUGE",
                "value": self.model.prefills_reused,
            })
        if self.model.prefill_chunk:
            out.append({
                "key": f"{self.model.name}_prefill_chunks",
                "type": "GAUGE",
                "value": self.model.prefill_chunks,
            })
        if self.model.embed_enabled or self.model.embeds:
            out.append({
                "key": f"{self.model.name}_embeds",
                "type": "GAUGE",
                "value": self.model.embeds,
            })
        return out

    async def _generate_rows(
        self,
        rows: list[np.ndarray],
        max_new_tokens: int,
        temperature: float,
        eos_id: int | None,
        adapter: str | None = None,
        infos: list[dict] | None = None,
    ) -> list[np.ndarray]:
        if infos is not None:
            infos.clear()
            infos.extend({} for _ in rows)
        return list(
            await asyncio.gather(
                *(
                    self.scheduler.submit(
                        row,
                        max_new_tokens=max_new_tokens,
                        temperature=temperature,
                        eos_id=eos_id,
                        adapter=adapter,
                        info=infos[i] if infos is not None else None,
                    )
                    for i, row in enumerate(rows)
                )
            )
        )

    async def embed_rows(self, rows: list[np.ndarray]) -> np.ndarray:
        """Mean-pooled final hidden states for a batch of prompts — the
        /embeddings serving path (docs/GRAPHS.md): each row rides the
        scheduler's bounded intake and QoS pops, the run loop serves the
        wave with one device sync.  Returns (B, E) float32."""
        outs = await asyncio.gather(
            *(self.scheduler.submit_embed(row) for row in rows)
        )
        return np.stack([np.asarray(o, np.float32) for o in outs])

    @staticmethod
    def _pad_rows(outs: list[np.ndarray]) -> np.ndarray:
        width = max((o.size for o in outs), default=0)
        dense = np.full((len(outs), width), PAD_ID, np.int32)
        for i, o in enumerate(outs):
            dense[i, : o.size] = o
        return dense

    async def predict(self, X: np.ndarray, names: list[str]) -> np.ndarray:
        X = np.atleast_2d(np.asarray(X))
        if not np.issubdtype(X.dtype, np.integer):
            if not np.all(np.equal(np.mod(X, 1), 0)):
                raise GraphUnitError("generative input must be integer token ids")
            X = X.astype(np.int32)
        # rows of a dense batch may carry our own PAD_ID right-padding
        # (e.g. a previous response fed back): strip it per row
        rows = []
        for row in X:
            row = np.asarray(row, np.int32)
            keep = row != PAD_ID
            rows.append(row[: int(keep.cumsum().argmax()) + 1] if keep.any() else row)
        outs = await self._generate_rows(
            rows, self.max_new_tokens, self.temperature, self.eos_id,
            self.adapter,
        )
        return self._pad_rows(outs)

    async def stream_bursts(
        self,
        prompt: np.ndarray,
        *,
        max_new_tokens: int | None = None,
        temperature: float | None = None,
        eos_id: int | None = None,
        adapter: str | None = None,
        t_ingress: float | None = None,
        info: dict | None = None,
    ) -> AsyncIterator[list[int]]:
        """Yield generated token ids as they decode, the ones that are
        ready at a time (the streaming serving path — neither the reference
        nor its successor streams at all).

        Tokens surface ``decode_block`` at a time per device fetch: deploy
        with a small block (e.g. 4-8) when time-to-first-token matters, the
        default large block when bulk throughput does.  A caller that
        writes a burst out in one piece pays one write a block, not one a
        token: with many slots live the per-token writes are most of what
        the event loop does, beside the scheduler that shares it.
        ``t_ingress`` and ``info`` are ``GenerationScheduler.submit``'s: the
        instant the request entered its handler, and the out-param whose
        ``first_written`` the handler calls after its first write.
        """
        q: asyncio.Queue = asyncio.Queue()
        task = asyncio.create_task(
            self.scheduler.submit(
                np.asarray(prompt, np.int32).ravel(),
                max_new_tokens=(
                    self.max_new_tokens if max_new_tokens is None else max_new_tokens
                ),
                temperature=(
                    self.temperature if temperature is None else temperature
                ),
                eos_id=self.eos_id if eos_id is None else eos_id,
                adapter=self.adapter if adapter is None else (adapter or None),
                on_token=q.put_nowait,
                t_ingress=t_ingress,
                info=info,
            )
        )
        task.add_done_callback(lambda t: q.put_nowait(_STREAM_END))
        served = 0
        try:
            item = None
            while item is not _STREAM_END:
                item = await q.get()
                burst: list[int] = []
                while item is not _STREAM_END:
                    burst.append(int(item))
                    if q.empty():
                        break
                    item = q.get_nowait()
                if burst:
                    served += len(burst)
                    yield burst
            # surface a failed submit (bad prompt, closed scheduler) —
            # and tokens the hook delivered between our last get and the
            # sentinel
            result = task.result()
            if len(result) > served:
                yield [int(tok) for tok in result[served:]]
        finally:
            if not task.done():
                task.cancel()

    async def stream(self, prompt: np.ndarray, **options) -> AsyncIterator[int]:
        """:meth:`stream_bursts` a token at a time."""
        bursts = self.stream_bursts(prompt, **options)
        try:
            async for burst in bursts:
                for tok in burst:
                    yield tok
        finally:
            await bursts.aclose()

    async def predict_raw(self, p):
        from seldon_core_tpu.contract.payload import DataKind, Payload

        if p.kind != DataKind.STRING:
            arr = await self.predict(p.array, p.names)
            return p.with_array(arr, names=[])
        try:
            body = json.loads(p.data)
            tokens = body["tokens"]
            if not isinstance(tokens, (list, tuple)):
                raise TypeError("'tokens' must be a list")
            single = bool(tokens) and not isinstance(tokens[0], (list, tuple))
            rows = [np.asarray(tokens, np.int32)] if single else [
                np.asarray(r, np.int32) for r in tokens
            ]
        except (json.JSONDecodeError, TypeError, KeyError, ValueError) as e:
            raise GraphUnitError(f"bad generative request: {e}") from e
        eos = body.get("eos_id", self.eos_id)
        adapter = body.get("adapter", self.adapter)
        # cascade routing (docs/GRAPHS.md): with the on-device confidence
        # signal compiled in, every strData response carries the per-row
        # mean top-2 logit margin — the router reads it from the child's
        # reply, so the payload a non-escalated request returns stays
        # byte-identical to calling the tier directly (tokens unchanged,
        # confidence additive)
        infos: list[dict] | None = [] if self.model.conf_signal else None
        outs = await self._generate_rows(
            rows,
            int(body.get("max_new_tokens", self.max_new_tokens)),
            float(body.get("temperature", self.temperature)),
            int(eos) if eos is not None else None,
            str(adapter) if adapter else None,
            infos=infos,
        )
        result = [o.tolist() for o in outs]
        reply: dict = {"tokens": result[0] if single else result}
        if infos is not None:
            confs = [i.get("confidence") for i in infos]
            reply["confidence"] = confs[0] if single else confs
        return Payload(
            json.dumps(reply),
            [],
            DataKind.STRING,
            p.meta,
        )
