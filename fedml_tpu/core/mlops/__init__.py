"""Observability façade (reference ``core/mlops/`` 4.5k LoC).

Re-exports the reference's user-facing surface —
``mlops.init/log/event/log_metric/log_round_info/log_model/...``
(``core/mlops/__init__.py:99-1466``) — over pluggable local sinks instead of
the MQTT+platform pipeline: a JSON-lines event/metric log per run (the
replacement for the MQTT topics the reference publishes to), optional wandb
(gated — not installed here), and the JAX profiler for device-side traces
(the TPU-native replacement for the reference's wall-clock profiler events,
``mlops_profiler_event.py:74-97``).

System perf sampling (``mlops_device_perfs.py``) maps to a psutil sampler
thread; device utilization comes from jax memory stats.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

# the obs planes hang off the same sink: mlops stays the user-facing
# façade and the JSONL funnel, core/obs owns tracing/metrics/profiling
# (obs only imports mlops lazily at emission time — no cycle)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace

logger = logging.getLogger(__name__)

_state: Dict[str, Any] = {"run_id": "0", "sink": None, "enabled": False,
                          "sys_thread": None}


class JsonSink:
    """Append-only JSON-lines sink — one file per run, thread-safe."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._lock = threading.Lock()
        atexit.register(self.close)

    def emit(self, record: Dict[str, Any]) -> None:
        with self._lock:
            self._f.write(json.dumps(record) + "\n")

    def close(self) -> None:
        try:
            self._f.close()
        except Exception:
            pass


def init(args) -> None:
    """(reference ``mlops.init`` :99) — wire sinks from args. Tracking is on
    by default (as in the reference) and off with ``enable_tracking: false``;
    an unwritable log dir degrades to disabled instead of failing init."""
    _state["run_id"] = str(getattr(args, "run_id", "0"))
    _state["enabled"] = bool(getattr(args, "enable_tracking", True))
    # observability knobs (core/obs): tracing + metrics cadence + device
    # profiling — configured here so every entry point that calls
    # mlops.init wires the whole layer in one place
    from .. import obs
    obs.configure(args)
    if not _state["enabled"]:
        _state["sink"] = None
        return
    log_dir = os.path.expanduser(
        getattr(args, "log_file_dir", None) or "~/.cache/fedml_tpu/logs")
    path = os.path.join(log_dir, f"run_{_state['run_id']}.jsonl")
    prev = _state.get("sink")
    if prev is not None:
        prev.close()
    try:
        _state["sink"] = JsonSink(path)
    except OSError as e:
        logger.warning("mlops sink unavailable (%s); tracking disabled", e)
        _state["sink"] = None
        _state["enabled"] = False
    # remote half of observability: tail+POST the run's JSONL to a log
    # server when configured (reference mlops_runtime_log_daemon.py:219).
    # A re-init for a new run stops (and flushes) the previous shipper —
    # otherwise every init leaks a polling thread for the process lifetime.
    prev_shipper = _state.pop("shipper", None)
    if prev_shipper is not None:
        prev_shipper.stop()
    log_url = (getattr(args, "log_server_url", None)
               or os.environ.get("FEDML_TPU_LOG_SERVER_URL"))
    if log_url and _state["sink"] is not None:
        from .log_daemon import start_log_shipper
        _state["shipper"] = start_log_shipper(
            path, log_url, run_id=_state["run_id"],
            device_id=str(getattr(args, "device_id", 0)))
    if bool(getattr(args, "sys_perf_profiling", False)):
        start_sys_perf()


def _emit(kind: str, payload: Dict[str, Any]) -> None:
    sink = _state.get("sink")
    if sink is None:
        return
    payload = dict(payload)
    payload.update({"kind": kind, "ts": time.time(),
                    "run_id": _state["run_id"]})
    sink.emit(payload)


def log(metrics: Dict[str, Any], step: Optional[int] = None) -> None:
    """(reference ``mlops.log`` :178)"""
    _emit("metric", {"metrics": metrics, "step": step})


def log_metric(metrics: Dict[str, Any], step: Optional[int] = None) -> None:
    log(metrics, step)


def log_round_info(total_rounds: int, round_idx: int) -> None:
    """(reference ``log_round_info`` :1004). Doubles as the metrics
    registry's round-boundary clock: every engine/server already calls
    it once per round, so the periodic ``metrics_snapshot`` JSONL flush
    rides it with zero extra wiring."""
    _emit("round", {"round_idx": round_idx, "total_rounds": total_rounds})
    obs_metrics.maybe_flush(int(round_idx))


def log_comm_round(round_idx: int, wire_bytes: int,
                   compression: Optional[str] = None,
                   by_type: Optional[Dict[str, Any]] = None) -> None:
    """Bytes-on-wire for one FL round, as recorded by the ``WireStats``
    ledger at the ``Message.encode`` seam (``wire_bytes`` is the diff of
    the ledger across the round; ``by_type`` optionally carries the
    per-message-type breakdown of a full snapshot)."""
    _emit("comm", {"round_idx": round_idx, "wire_bytes": int(wire_bytes),
                   "compression": compression, "by_type": by_type})


def log_chaos(round_idx: Optional[int] = None,
              injected: Optional[Dict[str, Any]] = None,
              observed: Optional[Dict[str, Any]] = None,
              link: Optional[Dict[str, Any]] = None,
              arrivals: Optional[list] = None,
              serving: Optional[Dict[str, Any]] = None) -> None:
    """Fault-ledger record from the chaos subsystem: what the
    :class:`~fedml_tpu.core.chaos.FaultPlan` injected this round vs what
    the runtime observed at the aggregation seam (or one link fault event).
    A tolerance bug shows up as the two disagreeing in the run log.

    ``arrivals`` carries a buffered-async pour's per-update records
    (client, staleness at aggregation time, arrival timestamp, dispatch
    version) — the raw material for reconstructing arrival distributions
    in post-mortems and the async bench."""
    rec: Dict[str, Any] = {}
    if round_idx is not None:
        rec["round_idx"] = int(round_idx)
    if injected is not None:
        rec["injected"] = injected
    if observed is not None:
        rec["observed"] = observed
    if link is not None:
        rec["link"] = link
    if serving is not None:
        rec["serving"] = serving
    if arrivals is not None:
        rec["arrivals"] = arrivals
        # pour-shaped records feed the staleness / buffer-occupancy
        # histograms (both async seams funnel through record_pour here)
        stal = [a.get("staleness", 0) for a in arrivals
                if isinstance(a, dict)]
        buffered = (observed or {}).get("buffered", 0)
        obs_metrics.record_pour(stal, int(buffered), len(arrivals))
    _emit("chaos", rec)


def log_selection(round_idx: int, strategy: str,
                  sampled: Optional[list] = None,
                  excluded: Optional[list] = None,
                  target_n: Optional[int] = None,
                  dropout_posterior: Optional[float] = None,
                  **extra: Any) -> None:
    """One participant-selection decision (core/selection): which clients
    the strategy scheduled, which it benched (reputation exclusions — the
    in-program-dropout path), the adaptive cohort target, and the pooled
    dropout posterior that sized it."""
    rec: Dict[str, Any] = {"round_idx": int(round_idx),
                           "strategy": str(strategy)}
    if sampled is not None:
        rec["sampled"] = [int(c) for c in sampled]
    if excluded is not None:
        rec["excluded"] = [int(c) for c in excluded]
    if target_n is not None:
        rec["target_n"] = int(target_n)
    if dropout_posterior is not None:
        rec["dropout_posterior"] = float(dropout_posterior)
    rec.update(extra)
    obs_metrics.record_selection(strategy, len(sampled or ()),
                                 len(excluded or ()))
    _emit("selection", rec)


def log_dispatch(name: str, wall_s: float, rounds: int = 1,
                 compiles: int = 0,
                 phases: Optional[Dict[str, float]] = None) -> None:
    """One device dispatch at the engine seam: host-side wall time of the
    dispatch call, how many FL rounds it carried (fused blocks > 1), and
    how many XLA compiles it triggered (the recompile counter — a steady
    state of 0 is the invariant; anything else is shape instability).
    ``phases`` (:func:`compile_phases_since`) feeds the metrics registry
    the seconds each compile phase took inside the dispatch."""
    obs_metrics.record_dispatch(name, wall_s, rounds, compiles, phases)
    _emit("dispatch", {"dispatch": name, "wall_s": round(float(wall_s), 6),
                       "rounds": int(rounds), "compiles": int(compiles)})


# --- XLA compile counter ---------------------------------------------------
# Process-wide totals of what JAX reports through jax.monitoring about
# making a program: seconds tracing it to a jaxpr, lowering that to MLIR,
# compiling it in the backend ('/jax/core/compile/backend_compile_duration'
# fires once per compile request that misses the in-memory cache, and
# covers a load from the persistent cache too) and, inside that, loading it
# from the persistent cache. Engines snapshot the totals around dispatches
# to expose a per-dispatch recompile delta; tests pin the count to catch
# shape-instability regressions that would otherwise recompile silently
# every round. The same seconds land on the innermost span open on the
# thread that compiled, so a recompile names its dispatch and round.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_PHASE_OF_EVENT = {
    _TRACE_EVENT: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE_EVENT: "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_compile_counter: Dict[str, Any] = {
    "installed": False, "lock": threading.Lock(),
    "totals": {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
               "cache_load_s": 0.0, "compiles": 0, "cache_hits": 0}}
# per thread: the outermost traces seen so far as (start, seconds). JAX
# times a jitted function traced inside another one twice, once on its own
# and once inside the outer trace's duration, and reports the inner one
# first; an event therefore swallows the entries that started inside it.
_traces = threading.local()
_TRACES_KEPT = 4096


def _outermost_trace_s(secs: float) -> float:
    """The part of a trace event's duration not reported before."""
    now = time.perf_counter()
    start = now - secs
    stack = getattr(_traces, "stack", None)
    if stack is None:
        stack = _traces.stack = []
    inner = 0.0
    while stack and stack[-1][0] >= start - 5e-5:  # listener latency
        inner += stack.pop()[1]
    stack.append((start, secs))
    if len(stack) > 2 * _TRACES_KEPT:
        del stack[:-_TRACES_KEPT]
    return max(secs - inner, 0.0)


def _count(key: str, amount) -> None:
    with _compile_counter["lock"]:
        _compile_counter["totals"][key] += amount
    sp = obs_trace.current_span()
    if sp is not None:
        sp.add_to_attr(key, amount)


def _on_event_duration(event: str, secs: float, **kw) -> None:
    phase = _PHASE_OF_EVENT.get(event)
    if phase is None:
        return
    if event == _TRACE_EVENT:
        secs = _outermost_trace_s(secs)
    elif event == _COMPILE_EVENT:
        _count("compiles", 1)
    _count(phase, float(secs))


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _count("cache_hits", 1)


def install_compile_counter() -> None:
    """Idempotent: registers the two jax.monitoring listeners once per
    process. Safe to call before any jit runs."""
    if _compile_counter["installed"]:
        return
    _compile_counter["installed"] = True  # on failure too: don't retry
    try:
        import jax.monitoring as _jm
        _jm.register_event_duration_secs_listener(_on_event_duration)
        _jm.register_event_listener(_on_event)
    except Exception as e:  # pragma: no cover - jax without monitoring
        logger.warning("compile counter unavailable (%s); dispatch "
                       "records will report compiles=0", e)


def compile_phases() -> Dict[str, Any]:
    """What making programs has cost this process since
    :func:`install_compile_counter`: seconds in ``trace_s`` (nested traces
    counted once), ``lower_s``, ``compile_s`` (backend compile requests,
    loads from the persistent cache among them) and ``cache_load_s`` (those
    loads alone), with the ``compiles`` and ``cache_hits`` counts."""
    with _compile_counter["lock"]:
        return dict(_compile_counter["totals"])


def compile_phases_since(before: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of :func:`compile_phases` that moved since the snapshot
    ``before``, with by how much; empty when nothing was traced, lowered
    or compiled."""
    now = compile_phases()
    return {k: v - before[k] for k, v in now.items() if v != before[k]}


def compile_count() -> int:
    """Backend compiles observed so far in this process (0 until
    :func:`install_compile_counter` has run)."""
    return int(_compile_counter["totals"]["compiles"])


def log_training_status(status: str, run_id: Optional[str] = None) -> None:
    _emit("status", {"role": "client", "status": status})


def log_aggregation_status(status: str, run_id: Optional[str] = None) -> None:
    _emit("status", {"role": "server", "status": status})


def log_model_info(round_idx: int, model_path: str) -> None:
    _emit("model", {"round_idx": round_idx, "path": model_path})


def log_health(component: str, status: str,
               detail: Optional[Dict[str, Any]] = None) -> None:
    """One component health transition: watchdog trips (``stalled`` /
    ``nan_logits``), serving ``/healthz`` state changes. Post-mortems
    grep these to bracket when a process went bad."""
    _emit("health", {"component": str(component), "status": str(status),
                     "detail": detail})


# --- event spans (reference MLOpsProfilerEvent) ----------------------------

class event:
    """Span context manager / pair API — now a SHIM over the real tracer
    (``core/obs/trace``):

        with mlops.event("train", round_idx=3): ...
    or  mlops.event("train", started=True); ...; mlops.event("train",
        started=False)

    The old implementation kept a class-level ``{name: start_time}`` dict,
    so two concurrent same-name spans (cross-silo server handler threads,
    the async pour timer racing an upload thread) clobbered each other's
    start times and one duration came out garbage. Every event is now a
    real tracer span with its own handle: the context-manager form holds
    the span on the instance (no shared state at all), and the pair form
    keeps per-``(thread, name)`` LIFO stacks under a lock — an end pops
    the SAME thread's innermost open span of that name (cross-thread
    closes fall back to any-thread LIFO, for the rare legacy caller that
    splits a pair across threads). The legacy ``event_start``/
    ``event_end`` records still flow for old readers; the span record
    carries the trace-grade truth."""

    _open_lock = threading.Lock()
    # (thread_id, name) -> stack of open spans; None key = cross-thread
    # fallback pool per name
    _open: Dict[Any, list] = {}

    def __init__(self, name: str, started: Optional[bool] = None,
                 value: Any = None, **extra: Any):
        self.name = name
        self.extra = extra
        self._span = None
        if started is True:
            sp = obs_trace.tracer.start_span(name, attrs=dict(extra))
            with event._open_lock:
                event._open.setdefault(
                    (threading.get_ident(), name), []).append(
                    (sp, time.time()))
            _emit("event_start", {"event": name, "value": value, **extra})
        elif started is False:
            handle = self._pop_open(name)
            dur = None
            if handle is not None:
                sp, t0 = handle
                sp.end()
                # duration from the shim's own clock, so it survives
                # obs_tracing: false (the span is a no-op then)
                dur = time.time() - t0
            _emit("event_end", {"event": name, "value": value,
                                "duration_s": dur, **extra})

    @classmethod
    def _pop_open(cls, name: str):
        tid = threading.get_ident()
        with cls._open_lock:
            stack = cls._open.get((tid, name))
            if not stack:
                # legacy cross-thread pair: any thread's innermost span
                for key in reversed(list(cls._open)):
                    if key[1] == name and cls._open[key]:
                        stack = cls._open[key]
                        break
            if not stack:
                return None
            sp = stack.pop()
            if not stack:
                cls._open = {k: v for k, v in cls._open.items() if v}
            return sp

    def __enter__(self):
        self._span = obs_trace.tracer.start_span(self.name,
                                                 attrs=dict(self.extra))
        self._span.__enter__()
        self._t0 = time.time()
        _emit("event_start", {"event": self.name, **self.extra})
        return self

    def __exit__(self, *exc):
        dur = time.time() - self._t0
        self._span.__exit__(*exc)
        _emit("event_end", {"event": self.name, "duration_s": dur,
                            **self.extra})
        return False


# --- system perf daemon (reference mlops_device_perfs.py) ------------------

_sys_perf_state = {"psutil_warned": False, "sample_warned": False}


def _sys_sample() -> Dict[str, Any]:
    """One host+device sample. psutil is OPTIONAL: an environment without
    it used to kill the sampler thread with an unlogged ImportError on the
    very first sample — now the host-side fields degrade away ONCE,
    loudly, and the jax-only device stats keep flowing."""
    rec: Dict[str, Any] = {}
    try:
        import psutil
        vm = psutil.virtual_memory()
        rec.update({"cpu_pct": psutil.cpu_percent(interval=None),
                    "mem_pct": vm.percent,
                    "mem_used_gb": round(vm.used / 2**30, 3)})
    except Exception as e:
        if not _sys_perf_state["psutil_warned"]:
            _sys_perf_state["psutil_warned"] = True
            logger.warning(
                "sys_perf: psutil unavailable (%s: %s) — degrading to "
                "jax-only device stats", type(e).__name__, e)
        rec["degraded"] = True
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        if "bytes_in_use" in stats:
            rec["device_mem_gb"] = round(stats["bytes_in_use"] / 2**30, 3)
    except Exception:
        pass
    return rec


def start_sys_perf(interval_s: float = 10.0) -> None:
    if _state.get("sys_thread"):
        return

    def loop():
        # identity check: a stop+start within one interval must not leave
        # the old thread alive emitting duplicates
        while _state.get("sys_thread") is threading.current_thread():
            try:
                _emit("sys_perf", _sys_sample())
            except Exception:
                # the sampler must never die silently: one WARNING with
                # the traceback, then keep sampling (a transient device
                # query failure is not a reason to go dark for the run)
                if not _sys_perf_state["sample_warned"]:
                    _sys_perf_state["sample_warned"] = True
                    logger.warning("sys_perf sample failed; sampler "
                                   "continues", exc_info=True)
            time.sleep(interval_s)

    t = threading.Thread(target=loop, daemon=True)
    _state["sys_thread"] = t
    t.start()


def stop_sys_perf() -> None:
    _state["sys_thread"] = None


# --- JAX profiler bridge ---------------------------------------------------

def start_device_trace(log_dir: Optional[str] = None) -> str:
    """Start a JAX/XLA profiler trace (TensorBoard-viewable) — the
    TPU-native replacement for wall-clock profiling."""
    import jax
    path = os.path.expanduser(log_dir or "~/.cache/fedml_tpu/traces")
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    return path


def stop_device_trace() -> None:
    import jax
    jax.profiler.stop_trace()
