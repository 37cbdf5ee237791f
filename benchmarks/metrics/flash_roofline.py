"""``flash_roofline``: the three flash-attention kernels' share of their
roofline, in percent: for every invocation seen in the traced window, the
least time the chip could take for it (the larger of its FLOPs over the peak
bf16 FLOP/s and its bytes over the peak HBM bytes/s, both from shapes:
``flops/<config>.py::flash_kernel_work``), summed, over the kernels' summed
device time. Source: device trace. Moves ``round_s``.

The kernels carry no name of their own in ``llm/attention.py``; in the trace
each is a ``custom-call`` (named ``attn.N`` or ``pallas_call.N`` after its
scope) and they are told apart by what they produce:
forward ``(bf16[b*h, s, d], f32[b*h, s, 1])``, dQ one ``bf16[b*h, s, d]``,
dK/dV two of them. A trace without such calls reads nothing.
"""

import re

_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def kind_of(name):
    """'fwd' | 'dq' | 'dkv' | None for a reduced operation name."""
    if " custom-call " not in name + " ":
        return None
    shapes = [(t, tuple(int(n) for n in dims.split(",") if n))
              for t, dims in _SHAPE.findall(name)]
    if len(shapes) == 1 and shapes[0][0] == "bf16" and len(shapes[0][1]) == 3:
        return "dq"
    if len(shapes) == 2 and all(len(d) == 3 for _, d in shapes):
        if shapes[1][0] == "f32" and shapes[1][1][-1] == 1:
            return "fwd"
        if shapes[0][0] == shapes[1][0] == "bf16":
            return "dkv"
    return None


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks:
        return None
    cell = ctx["cell"]
    work = ctx["flops_module"].flash_kernel_work(cell.config, cell.traffic)
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
