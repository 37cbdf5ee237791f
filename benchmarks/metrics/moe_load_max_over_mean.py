"""``moe_load_max_over_mean``: how uneven the router's load on the held
experts is: the fullest held expert's tokens over the mean held expert's,
each a mean over the expert layers and train steps of the last round the
program recorded (gauges ``fed_moe_load_max`` / ``fed_moe_load_mean``,
which the round program's own result sets; ``core/obs/metrics.py``). 1 is
an even load; the grouped products' row tiles and the fullest expert's
share of a step grow with it. Source: program counter. Moves ``round_s``.
Reads nothing where the program has no such gauges."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
        top = REGISTRY.gauge("fed_moe_load_max").value()
        mean = REGISTRY.gauge("fed_moe_load_mean").value()
    except (ImportError, AttributeError):
        return None
    if not top or not mean:
        return None
    return top / mean
