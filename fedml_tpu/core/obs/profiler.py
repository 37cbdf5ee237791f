"""Critical-path profiling at the engine dispatch seam.

The MFU-gap investigation's missing tool: the flagship ``fedavg_resnet56``
has sat at 6.9% MFU for four bench rounds while ResNet-18 hits 40% on the
same engine — i.e. the gap is host/input-side, and a single opaque
``wall_s`` per dispatch cannot localize it. This module splits a
dispatch's wall time into

* ``host_s`` — the host-side dispatch call (arg staging, trace/lowering,
  enqueue; jax returns before the device finishes), and
* ``device_wait_s`` — the tail the host then waits for the device
  (``block_until_ready``), i.e. device compute not overlapped by host
  work,

wraps the dispatch in a ``jax.profiler`` annotation (so a TensorBoard
trace captured around a run carries the same names), and converts the
engine's existing FLOPs model (``round_cost_flops`` — unchanged, so the
BENCH trajectory stays comparable) into a per-round MFU gauge + ``kind:
profile`` JSONL record.

Device profiling is OPT-IN (``obs_profile_device: true``): blocking on
every dispatch defeats the async-dispatch overlap the engines are built
around (most of all the async pour's train/aggregate overlap), so the
default path measures nothing it didn't before.
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

_cfg = {"device": False}


def set_device_profiling(on: bool) -> None:
    _cfg["device"] = bool(on)


def device_profiling_enabled() -> bool:
    return _cfg["device"]


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


def trace_annotation(name: str):
    """Names a dispatch region in a profiler (XPlane) trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def sample_hbm_peak_gb() -> Optional[float]:
    """Per-device peak HBM (GiB) from memory_stats, or None off-TPU; the
    counter is process-monotonic, so deltas attribute intervals."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if not peak:
            return None
        gb = peak / 2 ** 30
        obs_metrics.record_hbm_peak(gb)
        return round(gb, 4)
    except Exception:
        return None


def record_dispatch_profile(name: str, rounds: int, host_s: float,
                            device_wait_s: Optional[float],
                            flops_per_round: Optional[float],
                            n_devices: int,
                            compiles: int = 0) -> Optional[float]:
    """Emit one ``profile`` record (+ MFU/TFLOPs gauges when the FLOPs
    model is available). Returns the per-round MFU or None.

    ``total_s = host_s + device_wait_s`` is the honest wall cost of the
    dispatch when the host blocked (device profiling on); with only
    ``host_s`` known the MFU is not computed — an enqueue time is not a
    round time."""
    total_s = host_s + (device_wait_s or 0.0)
    mfu = None
    tflops = None
    if (flops_per_round and rounds and device_wait_s is not None
            and total_s > 0):
        flops = float(flops_per_round) * int(rounds)
        tflops = (flops / total_s) / 1e12
        mfu = mfu_value(flops, total_s, n_devices)
        if mfu is not None:
            obs_metrics.record_round_mfu(mfu, tflops=tflops)
    rec = {"dispatch": str(name), "rounds": int(rounds),
           "host_s": round(float(host_s), 6),
           "total_s": round(total_s, 6)}
    if device_wait_s is not None:
        rec["device_wait_s"] = round(float(device_wait_s), 6)
    if compiles:
        rec["compiles"] = int(compiles)
    if tflops is not None:
        rec["tflops"] = round(tflops, 4)
    if mfu is not None:
        rec["mfu"] = round(mfu, 5)
    from .. import mlops
    mlops._emit("profile", rec)
    return mfu
