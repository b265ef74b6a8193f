"""Roofline accounting: exact FLOPs from XLA, measured device time, MFU.

The reference never measures device utilization — its benchmark is a
constant-returning stub (reference: docs/benchmarking.md:19-36,
engine/.../predictors/SimpleModelUnit.java:33-46).  Serving a real model on
TPU, "is it fast" has a precise answer: achieved FLOP/s over the chip's
peak (MFU).  This module computes it three ways:

- **FLOPs** come from XLA's own cost model (``compiled.cost_analysis()``)
  on the exact serving program at the exact bucket shape — no hand-derived
  formulas to drift out of date;
- **device time** is measured by pipelining K dispatches and blocking once
  at the end: dispatch is async, so the queue keeps the chip busy and the
  per-step share of the total approximates pure device time;
- **peak** comes from the device kind (bf16 matmul peak per chip).

Also usable as a CLI (``python -m seldon_core_tpu.utils.roofline --family
bert --preset base --batch 32 --dtype bfloat16``) printing one JSON object —
bench.py runs it as a subprocess because a chip belongs to one process at a
time: the measurement and the engine under test each get it in turn.
"""

from __future__ import annotations

import time

import numpy as np

from seldon_core_tpu.utils.device import configure_compile_cache, serving_device

# Per-chip peaks by EXACT ``jax.devices()[0].device_kind``:
# (bf16 matmul FLOP/s, HBM bytes/s).  A TPU that is not in the table is an
# error, not a default — a guessed peak makes every MFU and roofline
# fraction quietly wrong.  Add a chip with the source of its figures.
_CHIP_PEAKS: dict[str, tuple[float, float]] = {
    # Cloud TPU v5e documentation: 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": (197e12, 819e9),
}


def _chip_peaks(device=None) -> tuple[float, float] | None:
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return _CHIP_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device_kind {device.device_kind!r}; "
            f"known: {sorted(_CHIP_PEAKS)} — add it to "
            "utils/roofline.py::_CHIP_PEAKS with the source of its figures"
        ) from None


def chip_peak_flops(device=None) -> float | None:
    """bf16 peak FLOP/s for one chip; None off-TPU (CPU has no useful
    published peak for this comparison); raises for an unknown TPU."""
    peaks = _chip_peaks(device)
    return peaks[0] if peaks else None


def chip_hbm_bandwidth(device=None) -> float | None:
    """HBM bytes/s for one chip; None off-TPU; raises for an unknown TPU.
    Decode is bandwidth-bound — every step must stream the full weight set
    plus the attention window — so the honest decode roofline is bytes/bw,
    not FLOPs."""
    peaks = _chip_peaks(device)
    return peaks[1] if peaks else None


def xla_flops(compiled) -> float | None:
    """FLOPs of one execution of an XLA-compiled program, from the
    compiler's cost model.  Returns None if the backend doesn't report it."""
    flops = (compiled.cost_analysis() or {}).get("flops")
    if flops is None or not np.isfinite(flops) or flops <= 0:
        return None
    return float(flops)


def measure_step_time(dispatch, *, iters: int = 24, warmup: int = 3) -> float:
    """Seconds per device step: ``iters`` dispatches enqueued back to back,
    one ``block_until_ready`` on the last result, total over ``iters``.

    ``dispatch()`` enqueues one step and returns its (device) result.  The
    queue stays full, so host dispatch overlaps device execution and the
    quotient is device time as long as a step outlasts its dispatch.
    Every result is waited for, so nothing rests on the order a backend
    runs independent programs in.  Best of two windows: host jitter only
    ever adds time.
    """
    import jax

    for _ in range(warmup):
        jax.block_until_ready(dispatch())
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready([dispatch() for _ in range(iters)])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def model_roofline(
    family: str,
    *,
    preset: str | None = None,
    batch: int = 32,
    seq: int | None = None,
    dtype: str | None = "bfloat16",
    iters: int = 16,
    **overrides,
) -> dict:
    """Build a model-zoo family at one bucket and measure its roofline.

    Returns a dict with device seconds/step, rows/s, XLA FLOPs per step,
    achieved FLOP/s, chip peak, and MFU (None off-TPU).
    """
    import jax

    from seldon_core_tpu.executor import BucketSpec
    from seldon_core_tpu.models import registry

    cfg = registry.resolve_config(family, preset, **overrides)
    model = registry.build_compiled(
        family, preset=preset, cfg=cfg, dtype=dtype, buckets=BucketSpec((batch,))
    )
    example = registry.example_input(family, cfg, batch)
    if seq is not None and example.ndim == 2 and example.dtype == np.int32:
        # token models: example_input's seq is a placeholder; serve at `seq`
        example = np.ones((batch, seq), np.int32)

    x0 = model._place(example)
    # one compile, used for BOTH the cost model and the timing loop
    exe = model._jitted.lower(model.params, x0).compile()
    flops = xla_flops(exe)

    sec = measure_step_time(lambda: exe(model.params, x0), iters=iters)
    peak = chip_peak_flops()
    achieved = flops / sec if flops else None
    return {
        "family": family,
        "preset": preset or "default",
        "batch": batch,
        "seq": seq,
        "dtype": dtype or "float32",
        "device_s_per_step": round(sec, 6),
        "device_ms_per_step": round(sec * 1e3, 3),
        "rows_per_s_device": round(batch / sec, 1),
        "flops_per_step": flops,
        "flops_per_row": round(flops / batch) if flops else None,
        "achieved_tflops": round(achieved / 1e12, 2) if achieved else None,
        "peak_tflops": round(peak / 1e12, 1) if peak else None,
        "mfu": round(achieved / peak, 4) if achieved and peak else None,
        # every result names the device it ran on: a CPU run can never
        # pass for a chip number
        "device": serving_device(),
    }


def generative_roofline(
    family: str = "llama",
    *,
    preset: str | None = None,
    n_slots: int = 8,
    decode_block: int = 32,
    dtype: str | None = "bfloat16",
    prompt_len: int = 8,
    iters: int = 8,
    decode_kernel: bool | None = None,
    **overrides,
) -> dict:
    """Decode-loop roofline for a generative family: tokens/s at full slot
    occupancy and MFU from XLA's cost model of the decode program.
    ``decode_kernel`` times the fused Pallas paged decode-attention step
    instead of the XLA gather path — comparing the two runs' ``hbm_frac``
    is the kernel-on-vs-off roofline fraction the bench records."""
    import jax

    from seldon_core_tpu.models import registry

    comp = registry.build_generative_component(
        family,
        preset=preset,
        n_slots=n_slots,
        decode_block=decode_block,
        dtype=dtype,
        max_new_tokens=decode_block,
        decode_kernel=decode_kernel,
        **overrides,
    )
    model = comp.model
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, model.cfg.vocab_size, size=prompt_len)
    last = [int(model.admit(s, prompt, 0.0, s)) for s in range(n_slots)]

    # time the decode-k program directly at full slot occupancy;
    # _exec_decode_k returns device arrays, so steps pipeline and one final
    # block amortizes the host dispatch out of the measurement.
    # The attention window is what serving would pick for these positions.
    active = np.ones(n_slots, bool)
    payload = {
        "tokens": np.asarray(last, np.int32),
        "active": active,
        "temperature": np.zeros(n_slots, np.float32),
        "seed": 0,
        "eos": np.full(n_slots, -1, np.int32),
        "remaining": np.full(n_slots, 1 << 30, np.int32),
        "k": decode_block,
        "window": model._window_for(active, decode_block),
    }
    sec = measure_step_time(
        lambda: model._exec_decode_k(payload)[0], iters=iters
    )

    # time one prefill (smallest bucket covering the prompt): the TTFT
    # floor.  The prefill program donates the cache, so calls chain.
    prefill_payload = {
        "padded": np.zeros((1, model.fit_bucket(prompt_len)), np.int32),
        "length": prompt_len,
        "slot": 0,
        "blocks": model.reserve_blocks(0, prompt_len + decode_block),
        "temperature": 0.0,
        "seed": 0,
    }
    prefill_sec = measure_step_time(
        lambda: model._exec_prefill(prefill_payload),
        iters=max(4, iters // 2),
    )

    tokens_per_step = n_slots * decode_block
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(model.params)
    )
    # decode FLOPs ≈ 2·params per token (matmul-dominated; attention adds
    # O(ctx·hidden) per token, small at these context lengths)
    flops = 2.0 * n_params * tokens_per_step
    peak = chip_peak_flops()
    achieved = flops / sec

    # HBM roofline: every decode step streams the weights once plus each
    # slot's attention window (K and V) from the paged pool
    p_leaves = jax.tree.leaves(model.params)
    param_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in p_leaves)
    cache_itemsize = model._cache["k"].dtype.itemsize
    window = payload["window"]
    cfg = model.cfg
    kv_read = (
        2 * cfg.n_layers * n_slots * window * cfg.n_kv_heads * cfg.head_dim
        * cache_itemsize
    )
    bw = chip_hbm_bandwidth()
    step_floor_s = (param_bytes + kv_read) / bw if bw else None
    hbm_tok_s = n_slots / step_floor_s if step_floor_s else None
    tok_s = tokens_per_step / sec
    return {
        "family": family,
        "preset": preset or "default",
        "n_slots": n_slots,
        "decode_block": decode_block,
        "window": window,
        "device_s_per_block": round(sec, 6),
        "tokens_per_s_device": round(tok_s, 1),
        "n_params": n_params,
        "flops_per_token": round(2.0 * n_params),
        "achieved_tflops": round(achieved / 1e12, 3),
        "peak_tflops": round(peak / 1e12, 1) if peak else None,
        "mfu": round(achieved / peak, 4) if peak else None,
        # bandwidth view: what fraction of the memory-bound ceiling decode hits
        "hbm_bytes_per_step": param_bytes + kv_read,
        "hbm_gb_s": round(bw / 1e9, 0) if bw else None,
        "hbm_roofline_tok_s": round(hbm_tok_s, 1) if hbm_tok_s else None,
        "hbm_frac": round(tok_s / hbm_tok_s, 4) if hbm_tok_s else None,
        # serving latency floors (device-side; wire adds codec + network)
        "prefill_ms": round(prefill_sec * 1e3, 3),
        "ttft_floor_ms": round((prefill_sec + sec / decode_block) * 1e3, 3),
        "block_ms": round(sec * 1e3, 3),
        "kv_block_size": model.kv_block_size,
        "kv_blocks": model.kv_blocks,
        "decode_kernel": model.decode_kernel,
        # every result names the device it ran on: a CPU run can never
        # pass for a chip number
        "device": serving_device(),
    }


def generative_sweep(
    family: str = "llama",
    *,
    preset: str | None = None,
    points: "list[tuple[int, int]] | None" = None,
    dtype: str | None = "bfloat16",
    prompt_len: int = 8,
    iters: int = 8,
    **overrides,
) -> list[dict]:
    """Operating-point table over (n_slots, decode_block): device tok/s,
    HBM fraction, block latency and TTFT floor per point — the data behind
    choosing a serving configuration instead of defaulting one."""
    import gc as _gc

    out = []
    for n_slots, decode_block in points or [(8, 16), (16, 16), (32, 16), (32, 32), (64, 32)]:
        r = generative_roofline(
            family,
            preset=preset,
            n_slots=n_slots,
            decode_block=decode_block,
            dtype=dtype,
            prompt_len=prompt_len,
            iters=iters,
            **overrides,
        )
        out.append({
            k: r.get(k)
            for k in (
                "n_slots", "decode_block", "window", "tokens_per_s_device",
                "hbm_frac", "hbm_roofline_tok_s", "block_ms", "prefill_ms",
                "ttft_floor_ms",
            )
        })
        _gc.collect()  # free the previous point's params + cache buffers
    return out


def main(argv: list[str] | None = None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--family", required=True)
    ap.add_argument("--preset", default=None)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--generative", action="store_true")
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--decode-block", type=int, default=32)
    ap.add_argument(
        "--decode-kernel", action="store_true",
        help="time the fused Pallas paged decode-attention step instead "
        "of the XLA gather path (generative only)",
    )
    ap.add_argument(
        "--sweep",
        default=None,
        help="operating-point sweep: comma list of SLOTSxBLOCK "
        "(e.g. 8x16,16x16,32x32); prints {'sweep': [...]}",
    )
    ap.add_argument("--max-seq", type=int, default=None)
    args = ap.parse_args(argv)
    configure_compile_cache()
    overrides = {"max_seq": args.max_seq} if args.max_seq else {}
    if args.sweep:
        points = [
            (int(s), int(b))
            for s, b in (p.lower().split("x") for p in args.sweep.split(","))
        ]
        out = generative_sweep(
            args.family,
            preset=args.preset,
            points=points,
            dtype=args.dtype,
            iters=args.iters,
            **overrides,
        )
        print(json.dumps({"sweep": out}))
        return
    if args.generative:
        out = generative_roofline(
            args.family,
            preset=args.preset,
            n_slots=args.n_slots,
            decode_block=args.decode_block,
            dtype=args.dtype,
            iters=args.iters,
            decode_kernel=args.decode_kernel or None,
            **overrides,
        )
    else:
        out = model_roofline(
            args.family,
            preset=args.preset,
            batch=args.batch,
            seq=args.seq,
            dtype=args.dtype,
            iters=args.iters,
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
