"""``scope_moe_route_ms``: device milliseconds a traced round in the scope
``moe.route``: the router's product, the top-k (the group limit's sorts
among them) and the gates.
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "moe.route")
