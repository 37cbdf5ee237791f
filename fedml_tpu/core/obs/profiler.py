"""Device facts: the per-chip peaks table, MFU arithmetic, memory sampling.

What is left of the profiling plane. Host time reaches the device's
timeline through the tracer (``core/obs/trace.py``: a context-manager span
is also a ``jax.profiler`` annotation), and the share of the chip's peak a
round reaches is the benchmark's ``round_mfu`` (``benchmarks/flops/``), so
nothing here blocks on a dispatch or keeps a FLOPs model. ``bench.py``,
``chip_smoke.py`` and ``roofline.py`` still read the peaks table and
``mfu_value``.
"""

from __future__ import annotations

import logging
from typing import Any, Optional

from . import metrics as obs_metrics

logger = logging.getLogger(__name__)

# Per-chip peaks by the EXACT ``device_kind`` JAX reports: (bf16 TFLOP/s,
# HBM GB/s), from Google Cloud's published TPU specifications. The one
# table: bench.py and the roofline balance read it through
# ``peak_tflops`` / ``hbm_gbps``, so the bench's MFU, the profiling gauge
# and the roofline cannot disagree. A v5e reports "TPU v5 lite" and a v5p "TPU v5"; matching by
# substring would hand one the other's peak. A TPU kind that is not here
# has no peak (None), never a neighbour's. The "cpu" row is a nominal host
# figure that lets the off-chip tests exercise the MFU/roofline plumbing;
# the roofline record flags it ``static_only``.
DEVICE_PEAKS = {
    "TPU v2": (45.0, 700.0),
    "TPU v3": (123.0, 900.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),
    "cpu": (0.5, 25.0),
}


def peak_tflops(device) -> Optional[float]:
    """Per-chip bf16 peak for a jax device, or None for unknown kinds
    (report MFU as null, never a guess)."""
    return DEVICE_PEAKS.get(str(device.device_kind), (None, None))[0]


def hbm_gbps(device) -> Optional[float]:
    """Per-chip HBM bandwidth for a jax device, or None for unknown
    kinds."""
    return DEVICE_PEAKS.get(str(device.device_kind), (None, None))[1]


def mfu_value(flops: float, wall_s: float, n_devices: int,
              peak_tflops_per_chip: Optional[float] = None,
              device: Any = None) -> Optional[float]:
    """MFU = achieved FLOP/s ÷ (peak per chip × chips). ``flops`` is the
    total useful work executed in ``wall_s`` across all devices — the
    engine's FLOPs model already excludes padded batches and chaos-dropped
    steps, so this stays honest under injection."""
    if not flops or not wall_s or wall_s <= 0:
        return None
    if peak_tflops_per_chip is None:
        if device is None:
            import jax
            device = jax.devices()[0]
        peak_tflops_per_chip = peak_tflops(device)
    if not peak_tflops_per_chip:
        return None
    achieved_tflops = (flops / wall_s) / 1e12
    return achieved_tflops / (peak_tflops_per_chip * max(int(n_devices), 1))


def sample_hbm_peak_gb() -> Optional[float]:
    """Sample the fullest local device's ``memory_stats()`` into three
    gauges (GiB): the peak bytes in use, the peak reservation (where the
    TPU runtime keeps a program's temporaries, outside ``bytes_in_use``),
    and their sum. Returns the peak in use, or None where the backend
    keeps no statistics (the CPU's does not; the gauges then stay
    absent). Both counters are process-monotonic. One call took 1.5
    microseconds on the v5e's host (PERF.md, PR 26), so the engine takes
    one at the close of every ``round`` / ``block`` span. A backend that
    raises from ``memory_stats()`` counts as one that keeps none: a gauge
    never fails a round."""
    import jax
    best = None
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats or "peak_bytes_in_use" not in stats:
            continue
        pair = (int(stats["peak_bytes_in_use"]),
                int(stats.get("peak_bytes_reserved", 0)))
        if best is None or sum(pair) > sum(best):
            best = pair
    if best is None:
        return None
    obs_metrics.record_hbm_peak(best[0] / 2 ** 30, best[1] / 2 ** 30)
    return round(best[0] / 2 ** 30, 4)
