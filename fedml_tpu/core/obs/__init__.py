"""Unified observability layer — three planes over one JSONL sink.

1. **Tracing** (:mod:`.trace`): real spans with trace/span IDs and W3C
   ``traceparent`` propagation on :class:`Message`, so one federated
   round reconstructs as a single trace tree across processes; async
   pours LINK the upload spans they consume, staleness per link.
2. **Metrics** (:mod:`.metrics`): a typed counter/gauge/histogram
   registry absorbing the scattered one-shot records — wire bytes by
   message type, pour staleness and buffer occupancy, arrival rates,
   selection decisions, compile count and seconds by phase, dispatch
   wall time, checkpoint flush time, HBM peak — with Prometheus text
   exposition and a periodic JSONL snapshot.
3. **Device facts** (:mod:`.profiler`): the device-memory sample the
   engine takes at the close of every round. Host time lands on the
   device's timeline through the tracer: a context-manager span is also
   a ``jax.profiler`` annotation. Peaks and FLOPs counts live with the
   benchmark (``benchmarks/harness/peaks.json``, ``benchmarks/flops/``).

:mod:`.recompile` names the argument shapes that moved when a dispatch
compiles past its program's first compile. :mod:`.scopes` holds the closed
vocabulary of ``jax.named_scope`` names the round program is written under
and makes, from a program's own compiled text, the table that says which
scope each HLO instruction belongs to: joined to a profiler trace it gives
device time by scope (``benchmarks/tools/scope_table.py``). ``scripts/trace_report.py``
reads a run's JSONL and prints the per-round critical path.
:mod:`.schema` is the one table every record kind validates against.

Knobs (``arguments.py``): tracing + metrics default ON (cheap — spans
are dicts, metric hooks are dict lookups). ``configure(args)`` is called
by ``mlops.init``; without it the defaults apply, so library use without
init still traces.
"""

from __future__ import annotations

from . import (flight, metrics, profiler, recompile, schema, scopes,  # noqa: F401
               trace)
from .flight import FlightRecorder, Watchdog                    # noqa: F401
from .metrics import REGISTRY                                   # noqa: F401
from .trace import (NOOP_SPAN, SpanContext, add_event, current_span,  # noqa: F401
                    extract, inject, parse_traceparent, span, tracer)


def configure(args=None) -> None:
    """Wire the obs knobs from the flat config (idempotent; called by
    ``mlops.init``). ``args=None`` restores the documented defaults."""
    trace.set_enabled(bool(getattr(args, "obs_tracing", True)))
    metrics.set_enabled(bool(getattr(args, "obs_metrics", True)))
    metrics.set_flush_every(
        int(getattr(args, "obs_metrics_flush_rounds", 10) or 0))
    # wall-clock snapshot cadence for workloads with no round boundary
    # (serving, cross-device handshakes, agents): the round flusher
    # never fires there, so a crash would lose everything since init
    metrics.set_flush_interval(
        float(getattr(args, "obs_metrics_flush_s", 60.0) or 0.0))
