"""``setup_build_s``: seconds the program took to build its simulator: the
duration of the first ``setup.simulator`` span (``TPUSimulator.__init__``:
placing the training set on the device, initialising the server's and the
clients' state, building the jitted programs; compiling the round program is
not in it, the first ``dispatch`` pays that). Source: program span. Moves
``setup_s``. Reads nothing (None) from a program that records no such span."""

from harness import program_spans


def read(ctx):
    spans = program_spans.finished("setup.simulator")
    if spans is None:
        return None
    return (spans[0]["end_ns"] - spans[0]["start_ns"]) * 1e-9
