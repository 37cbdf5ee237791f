"""Test harness: force an 8-device virtual CPU platform BEFORE any test
imports jax, so every test can exercise real multi-chip sharding semantics
without TPU hardware (SURVEY §4: parity tests run on
``--xla_force_host_platform_device_count``).

This is the ONLY place the virtual-device recipe lives: ``JAX_PLATFORMS``
and ``XLA_FLAGS`` are set before jax is imported, and
``jax.config.update("jax_platforms", "cpu")`` after it, so a machine that
does have a chip still runs the tests on the CPU mesh.
"""

import atexit
import collections
import os
import shutil
import subprocess
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # One persistent compile cache per test session, placed the way the
    # package expects it from outside (fedml_tpu._place_compile_cache then
    # sets nothing). Hundreds of tests rebuild the same small engine and
    # serving programs, in this process and in replica subprocesses; a hit
    # is a file read where a compile is seconds, and tier-1 runs within a
    # minute of its time limit. The directory is new per session and
    # removed at exit, so no XLA:CPU executable outlives the machine that
    # compiled it.
    _cache = tempfile.mkdtemp(prefix="fedml_tpu_test_jax_cache_")
    atexit.register(shutil.rmtree, _cache, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "collective_call_terminate" not in flags:
    # XLA:CPU kills the process when a collective waits >40 s for a slow
    # peer. On the virtual 8-device mesh a conv-heavy example (resnet18)
    # legitimately keeps busy devices computing for minutes while padded
    # devices idle at the all-reduce — raise the limits; slowness on a
    # TEST mesh is not an error condition.
    #
    # These flags are version-dependent, and XLA ABORTS the process on an
    # unknown flag at first backend init (parse_flags_from_env.cc) — which
    # would kill the whole pytest run. Probe support in a throwaway
    # subprocess and only keep them if that survives; support is a pure
    # function of the installed jaxlib, so cache the verdict per version.
    candidate = (flags
                 + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
                 + " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
    import hashlib
    # key the verdict on the EXACT candidate string, not just the jaxlib
    # version: pre-existing env XLA_FLAGS are embedded in the candidate, so
    # a verdict from one environment must not be reused in another
    try:  # no dist metadata for conda/source/vendored jaxlib builds —
        # the hash of the candidate still keys the cache, just coarser
        import importlib.metadata
        jaxlib_ver = importlib.metadata.version("jaxlib")
    except Exception:
        jaxlib_ver = "unknown"
    cand_key = hashlib.sha256(candidate.encode()).hexdigest()[:12]
    cache = os.path.join(
        tempfile.gettempdir(),
        f"fedml_tpu_xla_flag_probe_{jaxlib_ver}_{cand_key}")
    try:
        verdict = open(cache).read().strip()
    except OSError:
        cacheable = True
        try:
            probe = subprocess.run(
                [sys.executable, "-c", "import jax; jax.devices()"],
                env={**os.environ, "XLA_FLAGS": candidate},
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                timeout=120)
            verdict = "ok" if probe.returncode == 0 else "bad"
            if probe.returncode < 0:
                # killed by a signal (OOM/SIGKILL): environment trouble,
                # not a flag verdict — don't cache it
                cacheable = False
        except subprocess.TimeoutExpired:
            # a loaded host, not a flag verdict: skip the flags this run
            # but don't poison the cache with a permanent 'bad'
            verdict, cacheable = "bad", False
        if cacheable:
            try:
                with open(cache, "w") as f:
                    f.write(verdict)
            except OSError:
                pass  # unwritable tmp: just probe again next run
    if verdict == "ok":
        flags = candidate
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


class _CompileDelta(int):
    """An int whose repr carries the latest recompile-forensics records:
    a failing ``assert delta() == 0`` then NAMES the program and the
    changed abstract shapes instead of printing a bare counter."""

    def __repr__(self):  # pytest shows repr() of compared operands
        n = int(self)
        if n == 0:
            return str(n)
        from fedml_tpu.core.obs import recompile
        recs = recompile.recent_recompiles()
        if not recs:
            return (f"{n} (no recompile-forensics record — the compile "
                    "came from a seam outside the dispatch trackers)")
        det = "; ".join(
            f"{r['program']}: " + (", ".join(
                f"{c['arg']} {c['was']} -> {c['now']}"
                for c in (r.get("changed") or [])[:4])
                or (r.get("note") or "?"))
            for r in recs[-3:])
        return f"{n} (recompile forensics: {det})"


@pytest.fixture
def xla_compile_counter():
    """Counts XLA backend compiles via the process-wide jax.monitoring
    listener at the mlops seam. Use ``reset()`` after warmup, then assert
    ``delta() == 0`` across steady-state work — a nonzero delta is a
    shape-instability regression that would otherwise recompile silently
    every round. On failure the delta's repr prints the recompile
    forensics (core/obs/recompile), naming the shapes that moved."""
    from fedml_tpu.core import mlops

    mlops.install_compile_counter()

    class _Counter:
        def __init__(self):
            self._start = mlops.compile_count()

        def reset(self):
            self._start = mlops.compile_count()

        def delta(self):
            return _CompileDelta(mlops.compile_count() - self._start)

    return _Counter()


@pytest.fixture
def tracking_counts(monkeypatch):
    """``{"spans": n, "sink": m, "names": Counter}``: spans built (and
    how many of each name) and records handed to the JSONL sink since
    the fixture was made. What tracking costs is held by these counts,
    not by a timing on a loaded machine."""
    from fedml_tpu.core import mlops
    from fedml_tpu.core.obs import trace as obs_trace

    counts = {"spans": 0, "sink": 0, "names": collections.Counter()}
    span_init, sink_emit = obs_trace.Span.__init__, mlops.JsonSink.emit

    def counting_init(self, name, *a, **kw):
        counts["spans"] += 1
        counts["names"][str(name)] += 1
        span_init(self, name, *a, **kw)

    def counting_emit(self, record):
        counts["sink"] += 1
        sink_emit(self, record)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting_init)
    monkeypatch.setattr(mlops.JsonSink, "emit", counting_emit)
    return counts
