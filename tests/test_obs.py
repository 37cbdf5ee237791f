"""Observability layer (core/obs): tracer spans/links/propagation, the
typed metrics registry + Prometheus exposition, the in-memory span ring
and the program's spans on the profiler's clock, the compile-phase and
memory counters, JSONL schema validation (replaying a real engine run), the
mlops.event concurrency fix, sys_perf degradation, and what tracking
costs (nothing when off, by counts)."""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from fedml_tpu.arguments import Arguments
from fedml_tpu.core import mlops, obs
from fedml_tpu.core.obs import flight as obs_flight
from fedml_tpu.core.obs import metrics as obs_metrics
from fedml_tpu.core.obs import schema as obs_schema
from fedml_tpu.core.obs import trace as obs_trace

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _obs_defaults():
    """Every test starts from the documented defaults and leaves no sink
    attached (other test modules rely on tracking being inert)."""
    obs.configure(None)
    obs_trace.clear_finished()
    yield
    obs.configure(None)
    mlops.init(Arguments(enable_tracking=False))


def _init_sink(tmp_path, run_id, **overrides):
    args = Arguments(log_file_dir=str(tmp_path), run_id=run_id, **overrides)
    mlops.init(args)
    return os.path.join(str(tmp_path), f"run_{run_id}.jsonl")


def _read_records(path, kind=None):
    recs = [json.loads(l) for l in open(path) if l.strip()]
    return [r for r in recs if kind is None or r["kind"] == kind]


def _tiny_sim(**overrides):
    """The 8-client logistic-regression simulator the engine tests of this
    file share (4 clients a round, one round a dispatch unless told)."""
    from fedml_tpu import data as data_mod
    from fedml_tpu import model as model_mod
    from fedml_tpu.core.algframe.client_trainer import (
        ClassificationTrainer)
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = Arguments(**{**dict(
        dataset="synthetic_mnist", model="lr", client_num_in_total=8,
        client_num_per_round=4, comm_round=2, epochs=1, batch_size=16,
        learning_rate=0.1, frequency_of_the_test=0, random_seed=0),
        **overrides})
    fed, out_dim = data_mod.load(args)
    bundle = model_mod.create(args, out_dim)
    spec = ClassificationTrainer(bundle.apply)
    return TPUSimulator(args, fed, bundle, create_optimizer(args, spec),
                        spec)


def _hyper():
    import jax.numpy as jnp

    from fedml_tpu.core.algframe.types import TrainHyper
    return TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)


class TestTracer:
    def test_nesting_and_emission(self, tmp_path):
        path = _init_sink(tmp_path, "tr_nest")
        with obs_trace.span("outer", attrs={"k": 1}) as outer:
            assert obs_trace.current_span() is outer
            with obs_trace.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        assert obs_trace.current_span() is None
        spans = _read_records(path, "span")
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert spans[0]["parent_id"] == spans[1]["span_id"]
        for s in spans:
            assert not obs_schema.validate_record(s), \
                obs_schema.validate_record(s)

    def test_root_forces_new_trace(self):
        with obs_trace.span("a") as a:
            with obs_trace.span("b", root=True) as b:
                assert b.trace_id != a.trace_id
                assert b.parent_id is None

    def test_traceparent_roundtrip(self):
        sp = obs_trace.tracer.start_span("x")
        ctx = obs_trace.parse_traceparent(sp.traceparent())
        assert ctx.trace_id == sp.trace_id
        assert ctx.span_id == sp.span_id
        sp.end()

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-zzzz-1234-01", 42,
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01"])
    def test_malformed_traceparent_degrades_to_none(self, bad):
        assert obs_trace.parse_traceparent(bad) is None

    def test_message_inject_extract(self):
        from fedml_tpu.core.distributed.communication.message import Message
        msg = Message("t", 0, 1)
        with obs_trace.span("send") as sp:
            obs_trace.inject(msg)
        back = Message.decode(msg.encode())
        ctx = obs_trace.extract(back)
        assert ctx.span_id == sp.span_id
        assert ctx.trace_id == sp.trace_id

    def test_links_and_events(self, tmp_path):
        path = _init_sink(tmp_path, "tr_links")
        donor = obs_trace.tracer.start_span("upload")
        donor.end()
        with obs_trace.span("pour", root=True) as sp:
            sp.add_link(donor, staleness=3, client=7)
            sp.add_event("retry", attempt=1)
            # a link from a raw traceparent string too (the wire shape)
            sp.add_link(donor.traceparent(), staleness=0)
        pour = [s for s in _read_records(path, "span")
                if s["name"] == "pour"][0]
        assert len(pour["links"]) == 2
        assert pour["links"][0]["span_id"] == donor.span_id
        assert pour["links"][0]["attrs"]["staleness"] == 3
        assert pour["events"][0]["name"] == "retry"

    def test_disabled_tracing_is_inert(self, tmp_path):
        path = _init_sink(tmp_path, "tr_off", obs_tracing=False)
        with obs_trace.span("a") as sp:
            assert sp is obs_trace.NOOP_SPAN
            sp.add_event("x")
            sp.add_link(None)
            assert sp.traceparent() is None
        from fedml_tpu.core.distributed.communication.message import Message
        msg = Message("t", 0, 1)
        obs_trace.inject(msg)
        assert msg.get(Message.MSG_ARG_KEY_TRACEPARENT) is None
        assert not _read_records(path, "span")

    def test_noop_parent_does_not_mint_null_trace(self):
        """A _NoopSpan handle stored while tracing was off (the server
        managers' class-level defaults) must not become a parent with
        trace_id=None when tracing is on — that span record would
        violate the schema's HEX32 requirement."""
        sp = obs_trace.tracer.start_span("child",
                                         parent=obs_trace.NOOP_SPAN)
        try:
            assert sp.trace_id is not None and len(sp.trace_id) == 32
            assert sp.parent_id is None
        finally:
            sp.end()

    def test_end_is_idempotent(self):
        sp = obs_trace.tracer.start_span("once")
        d1 = sp.end()
        assert d1 is not None and sp.end() is None

    def test_mis_nested_exit_removes_right_span(self):
        a = obs_trace.tracer.start_span("a")
        b = obs_trace.tracer.start_span("b")
        a.__enter__()
        b.__enter__()
        a.__exit__(None, None, None)  # out of order
        assert obs_trace.current_span() is b
        b.__exit__(None, None, None)
        assert obs_trace.current_span() is None


class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("t_bytes", labels=("mt",))
        c.inc(10, mt="a")
        c.inc(5, mt="a")
        c.inc(1, mt="b")
        assert c.value(mt="a") == 15 and c.value(mt="b") == 1
        g = reg.gauge("t_mfu")
        g.set(0.4)
        assert g.value() == 0.4
        h = reg.histogram("t_stal", buckets=(1, 4, 16))
        for v in (0, 1, 3, 5, 100):
            h.observe(v)
        snap = h.snapshot()[0]
        assert snap["counts"] == [2, 1, 1, 1]  # <=1, <=4, <=16, +Inf
        assert snap["count"] == 5 and snap["sum"] == 109

    def test_counter_rejects_negative_and_type_conflicts(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("t_c")
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(ValueError):
            reg.gauge("t_c")
        with pytest.raises(ValueError):
            reg.counter("t_c", labels=("x",))

    def test_exposition_format(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("t_total", help="things", labels=("k",)).inc(3, k="v")
        reg.histogram("t_h", buckets=(1.0, 2.0)).observe(1.5)
        text = reg.exposition()
        assert "# HELP t_total things" in text
        assert "# TYPE t_total counter" in text
        assert 't_total{k="v"} 3.0' in text
        assert 't_h_bucket{le="1.0"} 0' in text
        assert 't_h_bucket{le="2.0"} 1' in text
        assert 't_h_bucket{le="+Inf"} 1' in text
        assert "t_h_sum 1.5" in text and "t_h_count 1" in text

    def test_snapshot_flush_record_validates(self, tmp_path):
        path = _init_sink(tmp_path, "m_flush")
        obs_metrics.REGISTRY.counter("t_flush_total").inc(2)
        obs_metrics.REGISTRY.flush(step=7)
        recs = _read_records(path, "metrics_snapshot")
        assert recs and recs[-1]["step"] == 7
        assert "t_flush_total" in recs[-1]["metrics"]
        assert not obs_schema.validate_record(recs[-1])

    def test_histogram_bucket_mismatch_raises(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("t_bk", buckets=(1.0, 2.0))
        # buckets=None on a re-get means "whatever is registered";
        # identical (even unsorted/int) bounds also re-get
        assert reg.histogram("t_bk") is h
        assert reg.histogram("t_bk", buckets=(2, 1)) is h
        # DIFFERENT bounds raise — observations would silently land in
        # buckets the caller never asked for
        with pytest.raises(ValueError):
            reg.histogram("t_bk", buckets=(1.0, 4.0))

    def test_wire_seam_feeds_registry(self):
        from fedml_tpu.core.distributed.communication.message import Message
        c = obs_metrics.REGISTRY.counter("fed_wire_bytes_total",
                                         labels=("msg_type",))
        before = c.value(msg_type="obs_wire_t")
        blob = Message("obs_wire_t", 0, 1).encode()
        assert c.value(msg_type="obs_wire_t") == before + len(blob)

    def test_maybe_flush_dedup_resets_per_run(self, tmp_path):
        """configure() (every mlops.init) resets the round-dedup: a
        second run in the same process must flush at its round 0 even
        though the first run also flushed at round 0."""
        path = _init_sink(tmp_path, "m_runs", obs_metrics_flush_rounds=5)
        obs_metrics.maybe_flush(0)
        obs_metrics.maybe_flush(0)  # same-round burst: deduped
        n1 = len(_read_records(path, "metrics_snapshot"))
        assert n1 == 1
        path2 = _init_sink(tmp_path, "m_runs2",
                           obs_metrics_flush_rounds=5)  # "new run"
        obs_metrics.maybe_flush(0)
        assert len(_read_records(path2, "metrics_snapshot")) == 1

    def test_engine_run_ends_with_final_snapshot(self, tmp_path):
        """The last cadence boundary is rarely the last round: run() must
        close with an unconditional snapshot or the tail rounds' metrics
        die with the process."""
        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer)
        from fedml_tpu.optimizers.registry import create_optimizer
        from fedml_tpu.simulation.tpu.engine import TPUSimulator

        args = Arguments(dataset="synthetic_mnist", model="lr",
                         client_num_in_total=8, client_num_per_round=4,
                         comm_round=4, epochs=1, batch_size=16,
                         learning_rate=0.1, frequency_of_the_test=0,
                         random_seed=0, rounds_per_dispatch=2,
                         obs_metrics_flush_rounds=10,  # boundary: round 0
                         log_file_dir=str(tmp_path), run_id="m_final")
        mlops.init(args)
        fed, out_dim = data_mod.load(args)
        bundle = model_mod.create(args, out_dim)
        spec = ClassificationTrainer(bundle.apply)
        TPUSimulator(args, fed, bundle,
                     create_optimizer(args, spec), spec).run()
        snaps = _read_records(
            os.path.join(str(tmp_path), "run_m_final.jsonl"),
            "metrics_snapshot")
        assert snaps and snaps[-1]["step"] == 3  # final round, not 0
        assert "fed_dispatch_wall_seconds" in snaps[-1]["metrics"]

    def test_disabled_metrics_hooks_are_inert(self):
        obs_metrics.set_enabled(False)
        try:
            c = obs_metrics.REGISTRY.counter("fed_wire_bytes_total",
                                             labels=("msg_type",))
            before = c.value(msg_type="off_t")
            obs_metrics.record_wire("off_t", 123)
            assert c.value(msg_type="off_t") == before
        finally:
            obs_metrics.set_enabled(True)


class TestWallClockFlusher:
    def test_flushes_without_round_boundaries(self, tmp_path):
        """Serving / cross-device / agents never call log_round_info:
        the wall-clock cadence must snapshot their metrics anyway."""
        path = _init_sink(tmp_path, "wall_f", obs_metrics_flush_s=0.3)
        obs_metrics.REGISTRY.counter("t_wall_total").inc(3)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if _read_records(path, "metrics_snapshot"):
                break
            time.sleep(0.05)
        snaps = _read_records(path, "metrics_snapshot")
        assert snaps, "no wall-clock metrics_snapshot within 5 s"
        assert "t_wall_total" in snaps[-1]["metrics"]
        assert not obs_schema.validate_record(snaps[-1])

    def test_idle_process_stays_silent(self, tmp_path):
        """No instrument change since the last snapshot → no re-emission
        (a fleet of idle replicas must not spam identical snapshots)."""
        path = _init_sink(tmp_path, "wall_idle", obs_metrics_flush_s=0.2)
        obs_metrics.REGISTRY.counter("t_idle_total").inc()
        deadline = time.time() + 5.0
        while time.time() < deadline and not _read_records(
                path, "metrics_snapshot"):
            time.sleep(0.05)
        n = len(_read_records(path, "metrics_snapshot"))
        assert n >= 1
        time.sleep(0.7)   # several cadences with zero activity
        assert len(_read_records(path, "metrics_snapshot")) == n

    def test_zero_disables(self, tmp_path):
        path = _init_sink(tmp_path, "wall_off", obs_metrics_flush_s=0)
        obs_metrics.REGISTRY.counter("t_off_total").inc()
        time.sleep(0.4)
        assert not _read_records(path, "metrics_snapshot")


class TestFlightRecorder:
    def test_ring_bounds_and_dump_validates(self, tmp_path):
        _init_sink(tmp_path, "fl_ring")
        rec = obs_flight.FlightRecorder("t_engine", capacity=8)
        for i in range(20):
            rec.note("step", tokens=i, occupancy=2)
        assert len(rec) == 8   # bounded: only the last moments survive
        path = rec.dump(str(tmp_path / "flight.jsonl"))
        lines = open(path).read().splitlines()
        assert len(lines) == 8
        problems = obs_schema.validate_lines(lines)
        assert not problems, problems
        recs = [json.loads(l) for l in lines]
        assert [r["seq"] for r in recs] == sorted(r["seq"] for r in recs)
        assert recs[-1]["data"]["tokens"] == 19  # newest kept
        assert all(r["component"] == "t_engine" for r in recs)

    def test_empty_ring_dumps_nothing(self, tmp_path):
        rec = obs_flight.FlightRecorder("t_empty")
        assert rec.dump(str(tmp_path / "nope.jsonl")) is None
        assert not os.path.exists(tmp_path / "nope.jsonl")

    def test_log_health_record_validates(self, tmp_path):
        path = _init_sink(tmp_path, "fl_health")
        mlops.log_health("serving_engine", "stalled",
                         detail={"occupancy": 3})
        rec = _read_records(path, "health")[-1]
        assert not obs_schema.validate_record(rec)
        assert rec["component"] == "serving_engine"
        assert rec["status"] == "stalled"


class TestWatchdog:
    def _state(self, **kw):
        base = {"occupancy": 2, "last_progress_ts": time.time(),
                "poisoned": False}
        base.update(kw)
        return base

    def test_stall_trip_dump_and_rearm(self, tmp_path):
        path = _init_sink(tmp_path, "wd_stall")
        rec = obs_flight.FlightRecorder("t_wd", capacity=4)
        rec.note("step", tokens=1)
        state = self._state(last_progress_ts=time.time() - 99)
        dump = str(tmp_path / "wd_flight.jsonl")
        wd = obs_flight.Watchdog("t_wd", lambda: state, recorder=rec,
                                 stall_s=1.0, dump_path=dump)
        assert wd.check() == "stalled"
        assert wd.trips == 1
        # same episode: no re-trip, no second dump spam
        assert wd.check() is None
        # the black box landed and validates line by line
        assert not obs_schema.validate_lines(
            open(dump).read().splitlines())
        # progress resumes -> re-arms -> a NEW stall trips again
        state["last_progress_ts"] = time.time()
        assert wd.check() is None
        state["last_progress_ts"] = time.time() - 99
        assert wd.check() == "stalled"
        assert wd.trips == 2
        # trips flowed to the registry and the health record stream
        c = obs_metrics.REGISTRY.counter("obs_watchdog_trips_total",
                                         labels=("component", "reason"))
        assert c.value(component="t_wd", reason="stalled") >= 2
        healths = _read_records(path, "health")
        assert healths and healths[-1]["status"] == "stalled"
        assert not obs_schema.validate_record(healths[-1])

    def test_nan_trips_even_with_progress(self):
        state = self._state(poisoned=True)
        wd = obs_flight.Watchdog("t_nan", lambda: state, stall_s=1.0)
        assert wd.check() == "nan_logits"

    def test_idle_engine_never_trips(self):
        state = self._state(occupancy=0,
                            last_progress_ts=time.time() - 999)
        wd = obs_flight.Watchdog("t_idle", lambda: state, stall_s=1.0)
        assert wd.check() is None

    def test_probe_failure_is_survivable(self):
        def boom():
            raise RuntimeError("probe exploded")
        wd = obs_flight.Watchdog("t_boom", boom, stall_s=1.0)
        assert wd.check() is None  # no trip, no raise


class TestProfiler:
    @pytest.mark.parametrize("stats, want", [
        # two chips: the fuller one by in-use + reserved is reported
        ([{"peak_bytes_in_use": 2 << 30, "peak_bytes_reserved": 1 << 30},
          {"peak_bytes_in_use": 1 << 30, "peak_bytes_reserved": 4 << 30}],
         (1.0, 4.0, 5.0)),
        # a backend that keeps no statistics (the CPU's): no gauge, not 0
        ([None], None),
        # one that raises instead counts the same, and fails no round
        ([RuntimeError("no statistics"),
          {"peak_bytes_in_use": 3 << 30, "peak_bytes_reserved": 0}],
         (3.0, 0.0, 3.0)),
        ([RuntimeError("no statistics")], None),
    ])
    def test_memory_gauges_after_a_round(self, monkeypatch, stats, want):
        self._round_with_devices(monkeypatch, stats)
        got = self._memory_gauges()
        if want is None:
            assert got == (None, None, None)
        else:
            assert got == pytest.approx(want)

    def test_memory_is_sampled_at_a_spans_close_only(self, monkeypatch):
        """The sample belongs to the close of a ``round`` span: with
        ``obs_tracing: false`` there is none, no device is asked and the
        gauges stay absent."""
        obs_trace.set_enabled(False)
        asked = self._round_with_devices(
            monkeypatch, [{"peak_bytes_in_use": 1 << 30}])
        assert asked == [] and self._memory_gauges() == (None, None, None)

    @staticmethod
    def _memory_gauges():
        return tuple(obs_metrics.REGISTRY.gauge(n).value() for n in (
            "fed_hbm_peak_gb", "fed_hbm_reserved_peak_gb",
            "fed_hbm_total_peak_gb"))

    @staticmethod
    def _round_with_devices(monkeypatch, stats):
        """One engine round on devices whose ``memory_stats()`` give (or
        raise) ``stats``; returns the list of devices that were asked."""
        import jax

        asked = []

        class Dev:
            def __init__(self, st):
                self._st = st

            def memory_stats(self):
                asked.append(self)
                if isinstance(self._st, Exception):
                    raise self._st
                return self._st

        sim = _tiny_sim()
        obs_metrics.REGISTRY.reset()
        monkeypatch.setattr(jax, "local_devices",
                            lambda: [Dev(st) for st in stats])
        sim.run_round(0, _hyper())
        return asked


class TestSpanRing:
    def test_finished_by_name_with_tree_and_stamps(self):
        t0 = time.time_ns()
        with obs_trace.span("outer", attrs={"round_idx": 3}) as outer:
            with obs_trace.span("inner"):
                pass
        with obs_trace.span("inner"):
            pass
        assert [r["name"] for r in obs_trace.finished()] == [
            "inner", "outer", "inner"]
        inner, stray = obs_trace.finished("inner")
        (rec,) = obs_trace.finished("outer")
        assert inner["parent_id"] == outer.span_id == rec["span_id"]
        assert stray["parent_id"] is None
        assert rec["attrs"] == {"round_idx": 3}
        assert (t0 <= rec["start_ns"] <= inner["start_ns"]
                <= inner["end_ns"] <= rec["end_ns"] <= time.time_ns())
        assert obs_trace.finished("nope") == []

    def test_ring_is_bounded(self):
        for i in range(obs_trace.RING_SIZE + 5):
            obs_trace.tracer.start_span("s", attrs={"i": i}).end()
        recs = obs_trace.finished("s")
        assert len(recs) == obs_trace.RING_SIZE
        assert recs[0]["attrs"]["i"] == 5        # the oldest five fell off
        assert recs[-1]["attrs"]["i"] == obs_trace.RING_SIZE + 4

    def test_annotation_only_for_the_context_manager_form(self, monkeypatch):
        """TraceMe is thread-scoped: a ``with`` span is annotated for its
        lifetime as ``fed.<name>`` (with ``round_idx`` where it has one), a
        bare handle, which may end on another thread, is not."""
        seen = []

        class Fake:
            def __init__(self, name, **kw):
                self.what = (name, kw)

            def __enter__(self):
                seen.append(("enter",) + self.what)

            def __exit__(self, *exc):
                seen.append(("exit",) + self.what)

        monkeypatch.setattr(obs_trace, "_annotation_cls", Fake)
        with obs_trace.span("round", attrs={"round_idx": 7, "role": "x"}):
            with obs_trace.span("host.keys"):
                pass
        obs_trace.tracer.start_span("wait.uploads").end()
        assert seen == [("enter", "fed.round", {"round_idx": 7}),
                        ("enter", "fed.host.keys", {}),
                        ("exit", "fed.host.keys", {}),
                        ("exit", "fed.round", {"round_idx": 7})]

    def test_tracing_off_fills_no_ring_and_opens_no_annotation(
            self, monkeypatch):
        opened = []
        monkeypatch.setattr(obs_trace, "_annotation",
                            lambda *a: opened.append(a))
        obs_trace.set_enabled(False)
        sim = _tiny_sim()
        sim.run_round(0, _hyper())
        with obs_trace.span("x"):
            pass
        assert obs_trace.finished() == [] and opened == []

    def test_span_record_carries_ns_stamps(self, tmp_path):
        path = _init_sink(tmp_path, "tr_ns")
        with obs_trace.span("a"):
            pass
        (rec,) = _read_records(path, "span")
        assert not obs_schema.validate_record(rec)
        assert isinstance(rec["start_ns"], int)
        assert 0 <= rec["end_ns"] - rec["start_ns"] < 10 ** 9
        assert rec["start_ts"] == pytest.approx(rec["start_ns"] * 1e-9)
        # the two fields are part of the schema, not extras
        del rec["end_ns"]
        assert any("end_ns" in e for e in obs_schema.validate_record(rec))

    def test_ids_without_a_system_call_and_reseeded_after_fork(self):
        """Ids come from a per-process generator (a system call costs 5-6
        microseconds on the chip's host); a forked child inherits its
        state and must not repeat the parent's ids."""
        import re
        assert re.fullmatch(r"[0-9a-f]{32}", obs_trace._rand_hex(16))
        inherited = obs_trace._ids.getstate()
        parents_next = obs_trace._rand_hex(8)
        assert re.fullmatch(r"[0-9a-f]{16}", parents_next)
        obs_trace._ids.setstate(inherited)    # what fork() hands a child
        obs_trace._after_fork()
        assert obs_trace._rand_hex(8) != parents_next
        assert obs_trace._proc["pid"] == os.getpid()

    def test_setup_init_span_obeys_the_knob(self, tmp_path):
        import fedml_tpu
        fedml_tpu.init(Arguments(log_file_dir=str(tmp_path), run_id="i1"))
        assert len(obs_trace.finished("setup.init")) == 1
        obs_trace.clear_finished()
        fedml_tpu.init(Arguments(log_file_dir=str(tmp_path), run_id="i2",
                                 obs_tracing=False))
        assert obs_trace.finished() == []


class TestProgramSpans:
    """The engine's own spans: the round's host phases, the set-up phases
    and the compile phases, in memory and on the profiler's clock."""

    ROUND_TREE = {"host.input": "round", "host.schedule": "host.input",
                  "host.stage": "host.input", "host.keys": "round",
                  "dispatch": "round", "host.post": "round"}

    def _tree_of(self, spans, root_name="round"):
        """{span name: parent's name} of each root's subtree, checked to
        be the same for every root."""
        by_id = {s["span_id"]: s for s in spans}
        trees = {}
        for s in spans:
            if s["name"] == root_name or s["parent_id"] not in by_id:
                continue
            trees.setdefault(s["trace_id"], {})[s["name"]] = \
                by_id[s["parent_id"]]["name"]
        assert trees and all(t == next(iter(trees.values()))
                             for t in trees.values()), trees
        return next(iter(trees.values()))

    def test_round_phases_nest_under_round_with_round_idx(self):
        sim = _tiny_sim()
        for r in range(2):
            sim.run_round(r, _hyper())
        spans = [s for s in obs_trace.finished()
                 if not s["name"].startswith("setup.")]
        assert self._tree_of(spans) == self.ROUND_TREE
        assert sorted(s["attrs"]["round_idx"] for s in spans) == \
            [0] * 7 + [1] * 7
        for root in obs_trace.finished("round"):
            kids = [s for s in spans if s["trace_id"] == root["trace_id"]
                    and s is not root]
            assert all(root["start_ns"] <= k["start_ns"]
                       and k["end_ns"] <= root["end_ns"] for k in kids)

    def test_fused_block_uses_the_same_phase_names(self):
        sim = _tiny_sim(comm_round=4, rounds_per_dispatch=4)
        sim.run_rounds_fused(0, 4, _hyper())
        spans = [s for s in obs_trace.finished()
                 if not s["name"].startswith("setup.")]
        assert self._tree_of(spans, "block") == {
            k: ("block" if v == "round" else v)
            for k, v in {**self.ROUND_TREE,
                         "host.readback": "round"}.items()}
        # the dispatch names the block's first round as `round_idx`
        assert all(s["attrs"]["rounds"] == 4 and 0 == s["attrs"].get(
            "start_round", s["attrs"].get("round_idx")) for s in spans)

    def test_run_reads_back_under_a_span(self):
        sim = _tiny_sim(comm_round=2, rounds_per_dispatch=1)
        sim.run()
        reads = obs_trace.finished("host.readback")
        assert [s["attrs"]["round_idx"] for s in reads] == [0, 1]
        assert all(s["parent_id"] is None for s in reads)

    def test_run_opens_no_readback_span_over_a_blocks_host_copies(self):
        """A fused block reads its metrics back once, under its own
        ``host.readback``; ``run()`` then only converts numpy and opens
        no span a round for that."""
        sim = _tiny_sim(comm_round=4, rounds_per_dispatch=2)
        sim.run()
        alone = [s["attrs"]["round_idx"] for s in obs_trace.finished("round")]
        blocks = obs_trace.finished("block")
        reads = obs_trace.finished("host.readback")
        assert blocks and len(alone) + 2 * len(blocks) == 4
        # one read a dispatch: a root for each round dispatched alone, the
        # block's own child for each block, and none for a block's rounds
        assert sorted(s["attrs"]["round_idx"] for s in reads
                      if s["parent_id"] is None) == sorted(alone)
        assert [s["parent_id"] for s in reads if s["parent_id"]] == \
            [b["span_id"] for b in blocks]

    def test_setup_spans_once_per_simulator(self):
        _tiny_sim()
        _tiny_sim()
        sims = obs_trace.finished("setup.simulator")
        assert len(sims) == 2 and all(s["parent_id"] is None for s in sims)
        for name in ("setup.place_data", "setup.init_state",
                     "setup.build_programs"):
            kids = obs_trace.finished(name)
            assert [k["parent_id"] for k in kids] == \
                [s["span_id"] for s in sims], name
        inside = sum(k["end_ns"] - k["start_ns"]
                     for k in obs_trace.finished()
                     if k["parent_id"] == sims[0]["span_id"])
        assert inside <= sims[0]["end_ns"] - sims[0]["start_ns"]

    def test_compiling_dispatch_carries_its_phases(self):
        """The round that compiles says so on its ``dispatch`` span, by
        phase; the process totals hold at least that; a warm round's span
        carries none of it."""
        # a batch size no other test of this process uses: a fresh program
        sim = _tiny_sim(batch_size=12)
        obs_metrics.REGISTRY.reset()
        before = mlops.compile_phases()
        for r in range(2):
            sim.run_round(r, _hyper())
        cold, warm = obs_trace.finished("dispatch")
        assert cold["attrs"]["round_idx"] == 0
        for phase in ("trace_s", "lower_s", "compile_s"):
            assert cold["attrs"][phase] > 0, phase
            assert phase not in warm["attrs"]
        since = mlops.compile_phases_since(before)
        assert since["compiles"] >= 1
        assert mlops.compile_count() == mlops.compile_phases()["compiles"]
        for phase in ("trace_s", "lower_s", "compile_s"):
            # host.keys compiles a program or two of its own on round 0
            assert since[phase] >= cold["attrs"][phase] > 0
        seconds = obs_metrics.REGISTRY.counter(
            "fed_compile_seconds_total", labels=("phase",))
        assert seconds.value(phase="compile") == pytest.approx(
            cold["attrs"]["compile_s"])
        assert seconds.value(phase="trace") == pytest.approx(
            cold["attrs"]["trace_s"])

    def test_nested_traces_are_counted_once(self):
        """JAX reports a function traced inside another one first and on
        its own, then again inside the outer trace's duration."""
        before = mlops.compile_phases()["trace_s"]
        with obs_trace.span("dispatch") as sp:
            time.sleep(0.02)
            mlops._on_event_duration(mlops._TRACE_EVENT, 0.005)  # inner
            time.sleep(0.005)
            mlops._on_event_duration(mlops._TRACE_EVENT, 0.004)  # inner
            mlops._on_event_duration(mlops._TRACE_EVENT, 0.029)  # outer
        time.sleep(0.002)
        with obs_trace.span("dispatch") as later:
            mlops._on_event_duration(mlops._TRACE_EVENT, 0.001)  # its own
        assert sp.attrs["trace_s"] == pytest.approx(0.029)
        assert later.attrs["trace_s"] == pytest.approx(0.001)
        assert mlops.compile_phases()["trace_s"] - before == \
            pytest.approx(0.030)

    def test_really_nested_jits_trace_no_longer_than_their_dispatch(self):
        """An outer jit that calls jitted functions three deep, traced for
        real: JAX reports every level, the inner ones inside the outer
        ones' durations too, and what lands on the span is at most the
        wall time of the call that traced them all."""
        import jax
        import jax.numpy as jnp

        mlops.install_compile_counter()

        @jax.jit
        def leaf(x):
            for _ in range(60):     # long enough to dwarf timer noise
                x = jnp.tanh(x) + 1.0
            return x

        @jax.jit
        def middle(x):
            return leaf(x) * 2.0 + leaf(x + 1.0)

        @jax.jit
        def outer(x):
            return middle(x) + leaf(x) - middle(x * 3.0)

        reported = []
        listener = lambda ev, secs, **kw: (
            reported.append(secs) if ev == mlops._TRACE_EVENT else None)
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            t0 = time.perf_counter()
            with obs_trace.span("dispatch") as sp:
                outer(jnp.ones((7, 3)))
            wall = time.perf_counter() - t0
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)
        # JAX did count the nested ones twice: the raw sum passes the
        # outermost trace's own duration, which holds them all
        assert len(reported) >= 3 and sum(reported) > max(reported)
        assert max(reported) * 0.999 <= sp.attrs["trace_s"] <= wall
        assert sp.attrs["trace_s"] < sum(reported)

    def test_cache_events_land_on_the_open_span(self):
        before = mlops.compile_phases()
        with obs_trace.span("dispatch") as sp:
            mlops._on_event("/jax/compilation_cache/cache_hits")
            mlops._on_event_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
            mlops._on_event_duration("/jax/some/other_event", 9.0)
        assert sp.attrs == {"cache_hits": 1, "cache_load_s": 0.25}
        assert mlops.compile_phases_since(before) == {
            "cache_hits": 1, "cache_load_s": 0.25}

    @pytest.mark.filterwarnings("ignore:builtin type event_stats")
    def test_rounds_on_the_profilers_clock(self, tmp_path):
        """Three rounds under ``jax.profiler.start_trace``: the host plane
        holds the round's phases as ``fed.*`` events, nested as the span
        tree is, and one constant (taken from the first ``fed.round``)
        relates the ring's ``start_ns`` to the trace's clock."""
        import glob

        import jax

        sim = _tiny_sim()
        sim.run_round(0, _hyper())          # compiles outside the trace
        obs_trace.clear_finished()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for r in (1, 2, 3):
                float(sim.run_round(r, _hyper())["count"])
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        events = []
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                events.extend(
                    (e.name[4:], int(e.start_ns),
                     int(e.start_ns + e.duration_ns),
                     next((v for k, v in e.stats if k == "round_idx"),
                          None))
                    for e in line.events if e.name.startswith("fed."))
        assert sorted(e[0] for e in events) == sorted(
            3 * (["round"] + list(self.ROUND_TREE)))
        ring = {(s["name"], s["attrs"]["round_idx"]): s
                for s in obs_trace.finished()}
        assert len(ring) == len(events) == 21
        by_key = {(n, r): (a, b) for n, a, b, r in events}
        offset = ring["round", 1]["start_ns"] - by_key["round", 1][0]
        # the later rounds' phases lie within 1 ms of the ring's stamps (on
        # an idle machine within 15 microseconds). The median of the 14,
        # since a loaded machine now and then takes the thread away for
        # milliseconds between one span's stamp and its annotation
        late = sorted(abs(a + offset - ring[name, r]["start_ns"])
                      for (name, r), (a, b) in by_key.items() if r > 1)
        assert len(late) == 14 and late[7] < 10 ** 6, late
        for (name, r), (a, b) in by_key.items():
            parent = self.ROUND_TREE.get(name)
            if parent:
                pa, pb_ = by_key[parent, r]
                assert pa <= a and b <= pb_, (name, r)


class TestSchemaReplay:
    def test_engine_run_log_validates_line_by_line(self, tmp_path):
        """The tier-1 replay gate: run a small engine session with
        tracking on and validate EVERY line of the run log against the
        canonical schema table."""
        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer)
        from fedml_tpu.optimizers.registry import create_optimizer
        from fedml_tpu.simulation.tpu.engine import TPUSimulator

        args = Arguments(dataset="synthetic_mnist", model="lr",
                         client_num_in_total=8, client_num_per_round=4,
                         comm_round=4, epochs=1, batch_size=16,
                         learning_rate=0.1, frequency_of_the_test=2,
                         random_seed=0, rounds_per_dispatch=2,
                         log_file_dir=str(tmp_path), run_id="replay",
                         obs_metrics_flush_rounds=2)
        mlops.init(args)
        path = os.path.join(str(tmp_path), "run_replay.jsonl")
        fed, out_dim = data_mod.load(args)
        bundle = model_mod.create(args, out_dim)
        spec = ClassificationTrainer(bundle.apply)
        sim = TPUSimulator(args, fed, bundle,
                           create_optimizer(args, spec), spec)
        sim.run()
        # a sample of every hand-built record kind rides along, so the
        # replay covers the full table, not just what this run emits
        mlops.log_comm_round(0, 1234, compression=None)
        mlops.log_chaos(round_idx=0, injected={"dropped": [1]})
        mlops.log_selection(0, "uniform", sampled=[0, 1], excluded=[],
                            target_n=2)
        mlops.log_training_status("RUNNING")
        mlops.log_model_info(0, "/tmp/x")
        mlops.log_health("serving_engine", "ok", detail={"occupancy": 0})
        mlops.log({"acc": 0.5}, step=0)
        with mlops.event("probe", round_idx=0):
            pass
        mlops._emit("sys_perf", mlops._sys_sample())
        lines = open(path).read().splitlines()
        problems = obs_schema.validate_lines(lines)
        assert not problems, problems[:20]
        kinds = {json.loads(l)["kind"] for l in lines}
        # the three planes all landed in one self-contained log
        assert {"span", "dispatch", "round", "metric",
                "metrics_snapshot"} <= kinds

    def test_unknown_kind_and_bad_types_are_flagged(self):
        assert obs_schema.validate_record({"kind": "nope", "ts": 1.0,
                                           "run_id": "0"})
        errs = obs_schema.validate_record(
            {"kind": "dispatch", "ts": 1.0, "run_id": "0",
             "dispatch": "r", "wall_s": "fast", "rounds": 1,
             "compiles": 0})
        assert any("wall_s" in e for e in errs)
        errs = obs_schema.validate_record(
            {"kind": "span", "ts": 1.0, "run_id": "0", "name": "x",
             "trace_id": "not-hex", "span_id": "b" * 16,
             "parent_id": None, "start_ts": 1.0, "end_ts": 2.0,
             "duration_s": 1.0, "pid": 1})
        assert any("trace_id" in e for e in errs)


class TestEventShim:
    def test_concurrent_same_name_spans_do_not_clobber(self, tmp_path):
        """The satellite fix: two threads bracketing a same-name event
        used to share one class-level start time — the first end stole
        the second start and one duration came out garbage."""
        path = _init_sink(tmp_path, "ev_conc")
        durs = {"fast": 0.05, "slow": 0.25}

        def worker(dur):
            mlops.event("train", started=True)
            time.sleep(dur)
            mlops.event("train", started=False, which=dur)

        ts = [threading.Thread(target=worker, args=(d,))
              for d in durs.values()]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        ends = _read_records(path, "event_end")
        assert len(ends) == 2
        by_which = {e["which"]: e["duration_s"] for e in ends}
        for d in durs.values():
            assert by_which[d] == pytest.approx(d, abs=0.04), by_which
        # the tracer half: two distinct train spans, not one
        spans = [s for s in _read_records(path, "span")
                 if s["name"] == "train"]
        assert len(spans) == 2
        assert spans[0]["span_id"] != spans[1]["span_id"]

    def test_context_manager_form_emits_span_and_legacy_pair(
            self, tmp_path):
        path = _init_sink(tmp_path, "ev_cm")
        with mlops.event("train", round_idx=3):
            time.sleep(0.01)
        assert _read_records(path, "event_start")
        end = _read_records(path, "event_end")[-1]
        assert end["duration_s"] >= 0.01
        sp = [s for s in _read_records(path, "span")
              if s["name"] == "train"][-1]
        assert sp["attrs"]["round_idx"] == 3

    def test_pair_api_duration_survives_tracing_off(self, tmp_path):
        path = _init_sink(tmp_path, "ev_off", obs_tracing=False)
        mlops.event("agg", started=True)
        time.sleep(0.02)
        mlops.event("agg", started=False)
        end = _read_records(path, "event_end")[-1]
        assert end["duration_s"] == pytest.approx(0.02, abs=0.03)

    def test_unmatched_end_is_harmless(self, tmp_path):
        path = _init_sink(tmp_path, "ev_un")
        mlops.event("never_started", started=False)
        end = _read_records(path, "event_end")[-1]
        assert end["duration_s"] is None


class TestSysPerf:
    def test_absent_psutil_degrades_once_to_jax_only(self, monkeypatch,
                                                     caplog):
        import sys as _sys
        monkeypatch.setitem(_sys.modules, "psutil", None)
        monkeypatch.setitem(mlops._sys_perf_state, "psutil_warned", False)
        import logging
        with caplog.at_level(logging.WARNING,
                             logger="fedml_tpu.core.mlops"):
            rec1 = mlops._sys_sample()  # must not raise
            rec2 = mlops._sys_sample()
        assert rec1.get("degraded") is True
        assert "cpu_pct" not in rec1
        warns = [r for r in caplog.records if "psutil" in r.getMessage()]
        assert len(warns) == 1, "degradation must be loud exactly ONCE"
        assert not obs_schema.validate_record(
            {**rec2, "kind": "sys_perf", "ts": 1.0, "run_id": "0"})

    def test_sampler_thread_survives_sample_failure(self, monkeypatch):
        monkeypatch.setitem(mlops._sys_perf_state, "sample_warned", False)
        calls = []

        def boom():
            calls.append(1)
            raise RuntimeError("sample exploded")

        monkeypatch.setattr(mlops, "_sys_sample", boom)
        mlops.stop_sys_perf()
        mlops.start_sys_perf(interval_s=0.01)
        time.sleep(0.08)
        mlops.stop_sys_perf()
        assert len(calls) >= 2, "sampler thread died on first failure"


class TestOverhead:
    def test_tracking_off_does_nothing_and_on_stays_cheap(
            self, tmp_path, tracking_counts):
        """What tracking costs, held by counts: with ``enable_tracking``,
        ``obs_tracing`` and ``obs_metrics`` off an 8-round block builds
        no span, writes to no sink and leaves the registry as it was;
        with them on the same block moves the registry and builds its
        spans by the block, one of each name (8 now), none by the round
        or the client, with a record a span and at most two metrics
        snapshots beside them. One simulator serves both modes (the
        hooks consult process config at call time). The timing bound is
        loose on purpose: other test workers load the machine, so more
        tracking work has to fail by its count (what it costs on the
        chip: PERF.md section 5)."""
        import jax
        import jax.numpy as jnp

        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer)
        from fedml_tpu.core.algframe.types import TrainHyper
        from fedml_tpu.optimizers.registry import create_optimizer
        from fedml_tpu.simulation.tpu.engine import TPUSimulator

        args = Arguments(dataset="digits", model="lr",
                         client_num_in_total=10, client_num_per_round=10,
                         comm_round=10_000, epochs=1, batch_size=32,
                         learning_rate=0.1, frequency_of_the_test=0,
                         random_seed=0, rounds_per_dispatch=8)
        fed, out_dim = data_mod.load(args)
        bundle = model_mod.create(args, out_dim)
        spec = ClassificationTrainer(bundle.apply)
        sim = TPUSimulator(args, fed, bundle,
                           create_optimizer(args, spec), spec)
        hyper = TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)
        on_args = Arguments(log_file_dir=str(tmp_path), run_id="ovh")
        off_args = Arguments(enable_tracking=False, obs_tracing=False,
                             obs_metrics=False)
        counts = tracking_counts
        r = [0]

        def block(mode_args):
            mlops.init(mode_args)
            before = (dict(counts, names=counts["names"].copy()),
                      obs_metrics.REGISTRY.exposition())
            t0 = time.perf_counter()
            sim.run_rounds_fused(r[0], 8, hyper)
            jax.block_until_ready(sim.params)
            wall = time.perf_counter() - t0
            r[0] += 8
            return (wall, counts["spans"] - before[0]["spans"],
                    counts["sink"] - before[0]["sink"],
                    obs_metrics.REGISTRY.exposition() != before[1],
                    counts["names"] - before[0]["names"])

        try:
            block(on_args)      # warm both modes: compile, first spans
            block(off_args)
            on, off = [], []
            for _ in range(4):  # alternate, so drift hits both alike
                off.append(block(off_args))
                on.append(block(on_args))
        finally:
            mlops.init(Arguments(enable_tracking=False))
        for _, spans, sink, registry_moved, _ in off:
            assert (spans, sink, registry_moved) == (0, 0, False), off
        for _, spans, sink, registry_moved, names in on:
            assert registry_moved
            assert set(names.values()) == {1}, names
            assert 0 < spans <= 12 and spans <= sink <= spans + 2, on
        best_on = min(t[0] for t in on)
        best_off = min(t[0] for t in off)
        assert best_on <= 2.0 * best_off + 0.05, (
            f"tracking on {best_on:.4f}s against off {best_off:.4f}s: "
            f"on={on} off={off}")


class TestTraceReport:
    def _mk_span(self, name, trace_id, span_id, parent, t0, t1, **attrs):
        rec = {"kind": "span", "ts": t1, "run_id": "0", "name": name,
               "trace_id": trace_id, "span_id": span_id,
               "parent_id": parent, "start_ts": t0, "end_ts": t1,
               "duration_s": t1 - t0, "pid": 1}
        if attrs:
            rec["attrs"] = attrs
        return rec

    def _round_spans(self, gap=0.001):
        tid, rid = "a" * 32, "1" * 16
        spans = [self._mk_span("round", tid, rid, None, 0.0, 10.0,
                               round_idx=0)]
        spans.append(self._mk_span("broadcast", tid, "2" * 16, rid,
                                   0.0, 1.0))
        spans.append(self._mk_span("wait.uploads", tid, "3" * 16, rid,
                                   1.0 + gap, 8.0))
        spans.append(self._mk_span("train", tid, "4" * 16, "2" * 16,
                                   1.5, 7.0))  # overlaps wait: no dbl count
        spans.append(self._mk_span("aggregate", tid, "5" * 16, rid,
                                   8.0 + gap, 9.0))
        spans.append(self._mk_span("eval", tid, "6" * 16, rid,
                                   9.0 + gap, 10.0))
        return spans

    def test_attribution_and_categories(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import trace_report
        out = io.StringIO()
        rc = trace_report.print_report(self._round_spans(), None,
                                       min_attr=0.95, out=out)
        text = out.getvalue()
        assert rc == 0, text
        assert "round[round_idx=0]" in text
        # the wait column is the 1.0→8.0 straggler window (~7 s); train
        # overlaps it but the union-based attribution never double-counts
        assert "6.999" in text and "attribution mean" in text

    def test_parent_time_is_self_time(self):
        """A parent's time is its duration minus what its children cover:
        ``host.input`` over ``host.schedule`` + ``host.stage`` is not
        counted twice, nor a wire span over the compute inside it."""
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import trace_report
        tid, rid = "a" * 32, "1" * 16
        inp, up = "2" * 16, "5" * 16
        spans = [
            self._mk_span("round", tid, rid, None, 0.0, 10.0, round_idx=0),
            self._mk_span("host.input", tid, inp, rid, 0.0, 4.0),
            self._mk_span("host.schedule", tid, "3" * 16, inp, 0.0, 1.0),
            self._mk_span("host.stage", tid, "4" * 16, inp, 1.0, 3.5),
            self._mk_span("upload", tid, up, rid, 4.0, 10.0),
            self._mk_span("aggregate", tid, "6" * 16, up, 5.0, 9.0)]
        tree = trace_report.Tree(spans)
        a = trace_report.analyze_root(tree, spans[0])
        assert a["categories"] == pytest.approx(
            {"host": 4.0, "wire": 2.0, "compute": 4.0})
        assert a["attributed_frac"] == pytest.approx(1.0)
        own = {t["name"]: self_s for self_s, t in a["top"]}
        assert own == pytest.approx({"aggregate": 4.0, "host.stage": 2.5,
                                     "upload": 2.0})
        assert trace_report.self_intervals(tree, spans[1], 0.0, 10.0) == \
            [(3.5, 4.0)]
        # the set-up and readback roots are reported, not dropped
        roots = [self._mk_span("setup.simulator", "b" * 32, "7" * 16, None,
                               0.0, 3.0),
                 self._mk_span("setup.place_data", "b" * 32, "8" * 16,
                               "7" * 16, 0.0, 1.0),
                 self._mk_span("host.readback", "c" * 32, "9" * 16, None,
                               3.0, 5.0, round_idx=0)]
        out = io.StringIO()
        assert trace_report.print_report(roots, None, 0.95, out=out) == 0
        assert "setup.simulator" in out.getvalue()
        assert "host.readback[round_idx=0]" in out.getvalue()

    def test_eval_checkpoint_roots_reported(self):
        """The engine's post-block per-round eval/checkpoint spans are
        ROOTS (root=True, outside the fused block span) — the report must
        show them, not drop them as unknown root names."""
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import trace_report
        spans = self._round_spans()
        spans.append(self._mk_span("eval", "e" * 32, "a1" * 8, None,
                                   10.0, 10.5, round_idx=0))
        spans.append(self._mk_span("checkpoint", "f" * 32, "b1" * 8, None,
                                   10.5, 10.6, round_idx=0))
        out = io.StringIO()
        rc = trace_report.print_report(spans, None, min_attr=0.0, out=out)
        text = out.getvalue()
        assert rc == 0, text
        assert "eval[round_idx=0]" in text
        assert "checkpoint[round_idx=0]" in text

    def test_low_attribution_fails_gate(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import trace_report
        tid, rid = "b" * 32, "7" * 16
        spans = [self._mk_span("round", tid, rid, None, 0.0, 10.0),
                 self._mk_span("broadcast", tid, "8" * 16, rid, 0.0, 1.0)]
        out = io.StringIO()
        rc = trace_report.print_report(spans, None, min_attr=0.95, out=out)
        assert rc == 2
        assert "FAIL" in out.getvalue()

    def test_orphan_subtree_reported_not_dropped(self):
        """A silo log passed without the server's: the silo.round spans
        reference a parent the report never saw — they must surface as
        orphan roots, not vanish."""
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import trace_report
        tid = "c" * 32
        spans = [self._mk_span("silo.round", tid, "9" * 16,
                               "dead" * 4, 0.0, 1.0),
                 self._mk_span("train", tid, "e" * 16, "9" * 16,
                               0.05, 0.95)]
        out = io.StringIO()
        rc = trace_report.print_report(spans, None, min_attr=0.0, out=out)
        text = out.getvalue()
        assert rc == 0, text
        assert "silo.round" in text
        # a genuinely-parentless stray (comm.send outside any session)
        # still stays out of the round report
        stray = [self._mk_span("comm.send", "d" * 32, "f" * 16,
                               None, 0.0, 0.1)]
        out = io.StringIO()
        rc = trace_report.print_report(stray, None, min_attr=0.0, out=out)
        assert rc == 1 and "no round/pour/block" in out.getvalue()

    def test_cli_end_to_end(self, tmp_path):
        import subprocess
        import sys
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            for s in self._round_spans():
                f.write(json.dumps(s) + "\n")
        script = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "trace_report.py")
        proc = subprocess.run([sys.executable, script, str(path),
                               "--min-attr", "0.95"],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "attribution mean" in proc.stdout
