"""``scope_attn_latent_ms``: device milliseconds a traced round in the scope
``attn.latent``: the whole latent-attention module (the down and up
projections and their norms, rotary, the concatenations, layout copies, the
flash kernels, the output projection), the ``lora`` side paths left out.
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "attn.latent")
