"""``scope_head_ms``: device milliseconds a traced round in the scopes
``head`` (``ln_f``, the head product, the float32 logits, the loss) and
``embed`` (the token embedding and its backward scatter).
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "head", "embed")
