"""``round_mfu``: the whole round's share of the chip's peak: the FLOPs the
configuration's round needs (``flops/<config>.py``, shapes only) over the
traced window's seconds a round, the peak bf16 FLOP/s and the chips. Source:
the host's clock around the traced rounds (each ended by its readback) and
the table of peaks. Moves ``round_s``."""


def read(ctx):
    if not ctx["peaks"] or not ctx["traced_rounds"]:
        return None
    round_s = ctx["traced_seconds"] / ctx["traced_rounds"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["flops_per_round"] / (round_s * peak)
