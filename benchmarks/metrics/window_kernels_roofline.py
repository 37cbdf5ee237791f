"""``window_kernels_roofline``: the three sliding-window attention kernels'
share of their roofline, in percent: for every invocation in the traced
window the least time the chip could take (the larger of its FLOPs over the
peak bf16 FLOP/s and its bytes over the peak HBM bytes/s, from shapes:
``flops/<config>.py::window_kernel_work``, row i over its ``min(i + 1,
window)`` keys, keys and values at the heads the model has), summed, over
the kernels' summed device time. Source: device trace. Moves ``round_s``.

The kernels are found BY NAME: ``llm/attention.py`` gives the
``pallas_call``s of a call with a window the names ``flash_win_fwd``,
``flash_win_dq``, ``flash_win_dkv``, which XLA keeps in the instruction's
name; ``flash_kernels_roofline`` matches ``flash_fwd`` / ``flash_dq`` /
``flash_dkv`` and does not take them. Reads nothing where the trace has no
such kernel (a program without the window, a model without one) or the
configuration's ``flops`` file no ``window_kernel_work``."""

KERNELS = (("flash_win_fwd", "fwd"), ("flash_win_dq", "dq"),
           ("flash_win_dkv", "dkv"))


def kind_of(name):
    """'fwd' | 'dq' | 'dkv' | None for a reduced operation name."""
    head = name.split(" ", 1)[0]
    for needle, kind in KERNELS:
        if needle in head:
            return kind
    return None


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    work_fn = getattr(ctx["flops_module"], "window_kernel_work", None)
    if not trace or not peaks or work_fn is None:
        return None
    work = work_fn(ctx["cell"].config, ctx["cell"].traffic)
    seconds = least = 0.0
    for name, (count, total_s) in trace["op_calls"].items():
        kind = kind_of(name)
        if kind is None:
            continue
        flops, bytes_ = work[kind]
        least += count * max(flops / peaks["bf16_flops_per_s"],
                             bytes_ / peaks["hbm_bytes_per_s"])
        seconds += total_s
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
