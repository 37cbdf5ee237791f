"""``scope_lora_ms``: device milliseconds a traced round in the scope
``lora``: the rank-r side paths of every adapted projection (``_add_lora``),
wherever they are nested.
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "lora")
