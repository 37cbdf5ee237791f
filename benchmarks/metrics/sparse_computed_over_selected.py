"""``sparse_computed_over_selected``: the score positions the sparse
attention kernels compute a head (every causal block of their plan whole)
over those the indexer's selection keeps (``min(topk, t + 1)`` a query):
the gauge ``fed_sparse_computed_over_selected`` (``core/obs/metrics.py``),
which ``llm/sparse_attention.py::sparse_attend`` sets on the host when it
traces a call of the kernels. 1 would be a kernel that computes the
selected scores alone; a kernel that skips blocks no row selects from reads
lower. Source: program counter. Moves ``round_s``. Reads nothing where no
such call was traced (a program without the gauge, a model without sparse
attention)."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
        ratio = REGISTRY.gauge("fed_sparse_computed_over_selected").value()
    except (ImportError, AttributeError):
        return None
    return ratio or None
