"""``flash_window_block_share``: the score blocks a head that the window
kernels' plan computes, as a percentage of those the causal plan computes
at the same length and block sizes: the gauge
``fed_flash_window_block_share`` (``core/obs/metrics.py``), which
``llm/attention.py::flash_causal_attention`` sets on the host when it
traces a call with a window, times 100. The band itself is a far smaller
share of the half-square (6% at a window of 128 in 4,096 positions): what
lies between the two is work the blocks' size wastes. Source: program
counter. Moves ``round_s``. Reads nothing where no window call was traced
(a program without the gauge, a model without a window)."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
        window = REGISTRY.gauge("fed_flash_window").value()
        share = REGISTRY.gauge("fed_flash_window_block_share").value()
    except (ImportError, AttributeError):
        return None
    if not window or not share:
        return None
    return 100.0 * share
