"""``scope_attn_full_ms``: device milliseconds a traced round in the scope
``attn.full``: the whole full-attention module (projections, rotary,
``jnp.repeat`` of grouped heads, layout copies, the flash kernels, the output
projection), the ``lora`` side paths inside it left out.
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "attn.full")
