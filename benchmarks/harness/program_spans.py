"""What the readers of the program's own spans share: the finished spans of
``fedml_tpu/core/obs/trace.py``'s in-memory ring, divided by the cell's
``check_rounds`` into the set-up's (the ``setup.*`` spans and every span of
a round before ``check_rounds``) and the window's (the rounds from
``check_rounds`` on). Each gives None, never an empty list and never a
raise, from a program without the ring, with ``obs_tracing`` off, or once
the ring has dropped the spans asked for: the reader then reports nothing
and the line leaves its metric out."""


def finished(name=None):
    """The program's finished spans (only those called ``name`` if given),
    oldest first, or None where it keeps none."""
    try:
        from fedml_tpu.core.obs import trace as obs_trace
        spans = obs_trace.finished(name)
    except (ImportError, AttributeError):
        return None
    return spans or None


def _first_window_round(ctx):
    return int(ctx["cell"].cell["check_rounds"])


def window_spans(ctx, name):
    """The ``name`` spans of the window's rounds."""
    first = _first_window_round(ctx)
    return [s for s in finished(name) or ()
            if s.get("attrs", {}).get("round_idx", -1) >= first] or None


def setup_spans(ctx):
    """Every span of the set-up: building the simulator (``setup.*``) and
    the rounds before the window, the ``round`` spans and their children."""
    first = _first_window_round(ctx)
    return [s for s in finished() or ()
            if s["name"].startswith("setup.")
            or s.get("attrs", {}).get("round_idx", first) < first] or None


def mean_ms(spans):
    return sum(s["end_ns"] - s["start_ns"] for s in spans) / len(spans) * 1e-6
