"""``scope_moe_latent_ms``: device milliseconds a traced round in the scope
``moe.latent``: the two projections around the routed experts of a latent
expert layer (hidden -> latent before them, latent -> hidden after their
gated sum), the ``lora`` side paths inside it left out. An operation counts
under its innermost scope only (``harness/scope_time.py``). Source: device
trace. Moves ``round_s``. Reads nothing without the program's scope table
or a trace, and nothing from a program without the scope."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "moe.latent") or None
