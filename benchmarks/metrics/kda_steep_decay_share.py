"""``kda_steep_decay_share``: the share, in percent, of the live (position,
key channel) log-decays of the unbounded KDA gate that lie under -5, the
floor of the bounded gate: ``fed_kda_steep_decays_total`` over
``fed_kda_decays_total``, counters that the round program's own result
feeds (the pass before the KDA kernels counts them a tile;
``core/obs/metrics.py::record_kda_decays``). It is the share of the work
for which the bounded form's chunk step would be wrong, the number a choice
of form by chunk would move. Source: program counter. Moves ``round_s``.
Reads nothing where the program counted no such decays (a program without
the counters, a model with the bounded gate)."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
        steep = REGISTRY.counter("fed_kda_steep_decays_total").value()
        live = REGISTRY.counter("fed_kda_decays_total").value()
    except (ImportError, AttributeError):
        return None
    if not live:
        return None
    return 100.0 * steep / live
