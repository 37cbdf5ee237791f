"""``scope_attn_index_ms``: device milliseconds a traced round in the scope
``attn.index``: the lightning indexer of the sparse attention layers (its
projections, LayerNorm and rotary, the index scores over every causal pair
and the exact top-k selection, the ``sparse_index`` kernel). Source: device
trace. Moves ``round_s``. Reads nothing without the program's scope table
or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "attn.index")
