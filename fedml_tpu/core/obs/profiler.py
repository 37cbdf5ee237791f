"""Device facts: the device-memory sample.

What is left of the profiling plane. Host time reaches the device's
timeline through the tracer (``core/obs/trace.py``: a context-manager span
is also a ``jax.profiler`` annotation). The chip's peaks and the work a
round does are the benchmark's (``benchmarks/harness/peaks.json``,
``benchmarks/flops/``: ``round_mfu``), so nothing here blocks on a
dispatch, keeps a peaks table or keeps a FLOPs model.
"""

from __future__ import annotations

from typing import Optional

from . import metrics as obs_metrics


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
