"""``host_input_ms``: mean duration of the program's ``host.input`` spans in
the window's rounds, in milliseconds: the client schedule, the fault ledger
and the three ``device_put``s of the schedule, which precede every round's
dispatch (``TPUSimulator._run_round_traced``). Source: program span. Moves
``round_s``. Reads nothing where ``round_host_ms`` reads nothing."""

from harness import program_spans


def read(ctx):
    spans = program_spans.window_spans(ctx, "host.input")
    return None if spans is None else program_spans.mean_ms(spans)
