"""``scope_engine_ms``: device milliseconds a traced round in the scopes
the round engine around local training: ``engine.slot`` (a client's data and
state slices), ``engine.accumulate`` (the weighted sum of its update and
metrics), ``engine.server`` (the psums, the division, the server update),
``local.batch`` (epoch order, the batch gather, the keys) and
``local.update`` (the optimizer step and the metric sums).
An operation counts under its innermost scope only
(``harness/scope_time.py``). Source: device trace. Moves ``round_s``. Reads
nothing without the program's scope table or a trace."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, *scope_time.ENGINE)
