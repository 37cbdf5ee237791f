"""``setup_trace_lower_s``: seconds JAX spent, inside the program's set-up,
tracing its functions to jaxprs and lowering them to MLIR. A start pays both
whether the persistent cache is warm or not, which ``setup_compile_s``
cannot show. The program's compile listener
(``fedml_tpu.core.mlops.install_compile_counter``) adds each phase's seconds
to the innermost span open on the thread that compiled; this sums
``trace_s`` + ``lower_s`` over the set-up's spans, the ``setup.*`` ones and
those of the rounds before ``check_rounds`` (``dispatch`` holds the round
program's, ``host.keys`` and ``host.stage`` the small ones'). So a trace
after the set-up, or one of the driver's own outside every program span, is
not in it. ``trace_s`` counts a function traced inside another once, by the
listener's reckoning from the events' durations (PERF.md section 3). Source:
program counter. Moves ``setup_s``. Reads nothing (None) from a program
whose spans carry no such seconds, or with ``obs_tracing`` off."""

from harness import program_spans


def read(ctx):
    seconds = [s["attrs"][phase]
               for s in program_spans.setup_spans(ctx) or ()
               for phase in ("trace_s", "lower_s")
               if phase in s.get("attrs", {})]
    return sum(seconds) if seconds else None
