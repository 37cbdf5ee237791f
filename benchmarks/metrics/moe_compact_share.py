"""``moe_compact_share``: the share, in percent, of passes through an expert
layer (a layer and train step) whose routing fit the compact row buffers
(``llm/moe.py::compact_rows``: twice a uniform router's held slots plus
every expert's tail tile) and so ran at that size, not at the worst-case
one: ``fed_moe_compact_steps_total`` over ``fed_moe_layer_steps_total``,
counters that the round program's own result feeds
(``core/obs/metrics.py``), over every round the program recorded. 100 is
every pass at the compact size; under it the router's skew sends some
passes through buffers several times the rows in use. Source: program
counter. Moves ``round_s``. Reads nothing where the program counted no
pass through an expert layer (a program without these counters, a model
without experts)."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
        compact = REGISTRY.counter("fed_moe_compact_steps_total").value()
        passes = REGISTRY.counter("fed_moe_layer_steps_total").value()
    except (ImportError, AttributeError):
        return None
    if not passes:
        return None
    return 100.0 * compact / passes
