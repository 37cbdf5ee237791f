"""``sparse_kernels_roofline``: the four sparse attention kernels' share of
their roofline, in percent: for every invocation in the traced window the
least time the chip could take (the larger of its FLOPs over the peak bf16
FLOP/s and its bytes over the peak HBM bytes/s, from shapes:
``flops/<config>.py::sparse_kernel_work``: the index scores over every
causal pair, the attention over the SELECTED keys alone), summed, over the
kernels' summed device time. Source: device trace. Moves ``round_s``.

The kernels are found BY NAME: ``llm/sparse_attention.py`` names its
``pallas_call``s ``sparse_index``, ``sparse_fwd``, ``sparse_dq`` and
``sparse_dkv``, which XLA keeps in the instruction's name; no other
reader's needle (``flash_``, ``kda_``, ``ssd_``, ``moe_grouped``) matches
them. Reads nothing where the trace has no such kernel (a program without
sparse attention, a model without it) or the configuration's ``flops``
file no ``sparse_kernel_work``."""

KERNELS = (("sparse_index", "index"), ("sparse_fwd", "fwd"),
           ("sparse_dq", "dq"), ("sparse_dkv", "dkv"))


def kind_of(name):
    """'index' | 'fwd' | 'dq' | 'dkv' | None for a reduced operation
    name."""
    head = name.split(" ", 1)[0]
    for needle, kind in KERNELS:
        if needle in head:
            return kind
    return None


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    work_fn = getattr(ctx["flops_module"], "sparse_kernel_work", None)
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
