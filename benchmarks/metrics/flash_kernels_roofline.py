"""``flash_kernels_roofline``: the three flash-attention kernels' share of
their roofline, in percent: for every invocation in the traced window the
least time the chip could take (the larger of its FLOPs over the peak bf16
FLOP/s and its bytes over the peak HBM bytes/s, from shapes:
``flops/<config>.py::flash_kernel_work``, at one head size or at
``d_qk != d_v``), summed, over the kernels' summed device time. Source:
device trace. Moves ``round_s``.

The kernels are found BY NAME: ``llm/attention.py`` gives its
``pallas_call``s the names ``flash_fwd``, ``flash_dq``, ``flash_dkv``, and
XLA keeps them in the instruction's name (``jvp_flash_fwd_.1``,
``transpose_jvp_flash_dq__.3``). A trace without such names (a program
whose kernels carry none) reads nothing. ``flash_roofline`` tells the
kernels by what they produce and takes XLA's zero-time ``custom-call.N``
for dQ kernels; this reader is the one to keep."""

KERNELS = (("flash_fwd", "fwd"), ("flash_dq", "dq"), ("flash_dkv", "dkv"))


def kind_of(name):
    """'fwd' | 'dq' | 'dkv' | None for a reduced operation name."""
    head = name.split(" ", 1)[0]
    for needle, kind in KERNELS:
        if needle in head:
            return kind
    return None


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    work_fn = getattr(ctx["flops_module"], "flash_kernel_work", None)
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
