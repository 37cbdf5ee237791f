"""``round_host_ms``: mean host time of ``TPUSimulator.run_round`` in the
window, in milliseconds: the duration of the program's own ``round`` spans
(``fedml_tpu/core/obs/trace.py``'s in-memory ring) whose ``round_idx`` is at
least the cell's ``check_rounds``. It ends when the round's program is
enqueued, before the readback, so it is what the host adds between two round
programs on its side of ``run_round``. Source: program span. Moves
``round_s``. Reads nothing (None, never 0) from a program without the ring,
with ``obs_tracing`` off, or once the ring has dropped the window's spans."""

from harness import program_spans


def read(ctx):
    spans = program_spans.window_spans(ctx, "round")
    return None if spans is None else program_spans.mean_ms(spans)
