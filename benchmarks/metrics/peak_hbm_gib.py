"""``peak_hbm_gib``: the fullest chip's peak device memory in GiB as the
program itself samples it at the close of every round
(``fedml_tpu/core/obs/profiler.py::sample_hbm_peak_gb``): the peak bytes in
use plus the runtime's peak reservation for program temporaries, which
``memory_peak_bytes`` (bytes in use alone) leaves out. Source: program
counter (the ``fed_hbm_total_peak_gb`` gauge). Moves ``round_s``: memory is
what bounds the batch a round can take. Reads nothing (None) where the
program set no such gauge: on a backend without ``memory_stats()``, with
``obs_metrics`` off (no gauges), or with ``obs_tracing`` off (the sample is
taken at the close of a ``round`` span, and there is none)."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
    except ImportError:
        return None
    return REGISTRY.gauge("fed_hbm_total_peak_gb").value()
