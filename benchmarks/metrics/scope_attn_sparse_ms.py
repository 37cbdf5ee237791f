"""``scope_attn_sparse_ms``: device milliseconds a traced round in the scope
``attn.sparse``: the whole sparse attention module (the q k v projections,
the q/k norms, rotary, the attention kernels over the selected keys, the
output projection) with what ``attn.index`` (the indexer) and ``lora`` (the
side paths) take left out. An operation counts under its innermost scope
only (``harness/scope_time.py``). Source: device trace. Moves ``round_s``.
Reads nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "attn.sparse")
