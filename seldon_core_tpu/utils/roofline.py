"""Chip peaks: the published bf16 FLOP/s and HBM bytes/s of the device served on.

The denominator of the serving MFU gauges (``executor/batcher.py``,
``utils/metrics.py``) and the engine's boot check that the chip is a known
one (``engine/app.py``).  Speeds themselves are measured by ``benchmark/``
and recorded in PERF.md; this module times nothing.
"""

from __future__ import annotations

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
