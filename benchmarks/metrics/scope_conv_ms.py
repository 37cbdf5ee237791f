"""``scope_conv_ms``: device milliseconds a traced round in the scope
``cv.conv``: ResNet's convolutions, the shortcut projections among them,
forward and backward.
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "cv.conv")
