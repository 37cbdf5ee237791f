"""``round_gap_ms``: mean idle time on the device between the end of one
execution of the round program and the start of the next, in the traced
window. Source: device trace (the ``XLA Modules`` line; the round program is
the module that takes most device time). Moves ``round_s``."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["round_gaps_ns"]:
        return None
    gaps = trace["round_gaps_ns"]
    return sum(gaps) / len(gaps) * 1e-6
