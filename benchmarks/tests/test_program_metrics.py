"""The five readers of the program's own spans and counters on a hand-built
ring and counter state, ``program_gaps.py`` on the hand-built trace of
``test_trace_reduce.py``, and the manifest with the new entries."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest  # noqa: E402

from fedml_tpu.core import obs  # noqa: E402
from fedml_tpu.core.obs import REGISTRY  # noqa: E402
from fedml_tpu.core.obs import trace as obs_trace  # noqa: E402

program_gaps = manifest.load_module("tools", "program_gaps")
MS = 1_000_000
NEW = ("round_host_ms", "host_input_ms", "setup_build_s",
       "setup_trace_lower_s", "peak_hbm_gib")


def _ctx(check_rounds=2):
    return {"cell": types.SimpleNamespace(cell={"check_rounds": check_rounds})}


def _read(name, ctx=None):
    return manifest.load_module("metrics", name).read(ctx or _ctx())


def _span(name, start_ms, ms, **attrs):
    rec = {"name": name, "trace_id": "a" * 32, "span_id": "b" * 16,
           "parent_id": None, "start_ns": start_ms * MS,
           "end_ns": (start_ms + ms) * MS}
    if attrs:
        rec["attrs"] = attrs
    return rec


@pytest.fixture
def ring(monkeypatch):
    """A ring with two set-up rounds (10 ms of host time each) and three
    window rounds (4, 6 and 8 ms; 1, 2 and 3 ms of them in ``host.input``)."""
    spans = [_span("setup.build_programs", 10, 4000, trace_s=0.5),
             _span("setup.simulator", 0, 5000, role="engine"),
             _span("setup.simulator", 9000, 7000, role="engine")]
    for r, (ms, inp) in enumerate([(10, 9), (10, 9), (4, 1), (6, 2), (8, 3)]):
        spans.append(_span("host.input", 100 * r, inp, round_idx=r))
        spans.append(_span("round", 100 * r, ms, round_idx=r, role="engine"))
    spans.append(_span("host.input", 900, 50, start_round=8, rounds=8))
    # what the compile listener leaves on the spans that traced: the round
    # program's on round 0's dispatch, a small one's on round 1's keys, a
    # recompile inside the window, and one under a span of no round at all
    spans += [
        _span("dispatch", 10, 1, round_idx=0, trace_s=1.0, lower_s=0.25,
              compile_s=9.0, compiles=1),
        _span("host.keys", 109, 1, round_idx=1, trace_s=0.125),
        _span("dispatch", 210, 1, round_idx=2, trace_s=64.0, lower_s=32.0),
        _span("eval", 950, 1, lower_s=16.0)]
    monkeypatch.setattr(obs_trace, "_ring", spans)
    return spans


def test_span_readers_take_the_windows_rounds(ring):
    assert _read("round_host_ms") == pytest.approx(6.0)
    assert _read("host_input_ms") == pytest.approx(2.0)
    # check_rounds picks the window: from round 4 on there is one round
    assert _read("round_host_ms", _ctx(4)) == pytest.approx(8.0)
    assert _read("host_input_ms", _ctx(4)) == pytest.approx(3.0)
    assert _read("setup_build_s") == pytest.approx(5.0)   # the first one
    # the set-up's traces and lowerings: setup.* and the rounds before 2
    assert _read("setup_trace_lower_s") == pytest.approx(1.875)
    assert _read("setup_trace_lower_s", _ctx(3)) == pytest.approx(97.875)


@pytest.mark.parametrize("name", NEW[:4])
def test_span_readers_read_none_without_spans(monkeypatch, name, ring):
    assert _read(name, _ctx(5)) is None or name.startswith("setup_")
    # spans, but none that traced or lowered: nothing to read, not 0
    monkeypatch.setattr(obs_trace, "_ring", ring[1:13])
    assert (_read(name) is None) == (name == "setup_trace_lower_s")
    monkeypatch.setattr(obs_trace, "_ring", [])
    assert _read(name) is None
    # a program from before the ring existed
    monkeypatch.delattr(obs_trace, "finished")
    assert _read(name) is None


def test_memory_reader_reads_the_gauge():
    REGISTRY.reset()
    assert _read("peak_hbm_gib") is None
    REGISTRY.gauge("fed_hbm_total_peak_gb").set(12.5)
    assert _read("peak_hbm_gib") == pytest.approx(12.5)


@pytest.mark.parametrize("tracing", [True, False])
def test_all_five_read_none_with_tracing_off(monkeypatch, tracing):
    """A real engine run (set-up and three rounds) on a device that keeps
    memory statistics: every reader finds its number with the program's
    defaults and none with ``obs_tracing: false``."""
    import jax

    sys.path.insert(0, os.path.join(manifest.REPO, "tests"))
    from test_obs import _hyper, _tiny_sim

    class Dev:
        def memory_stats(self):
            return {"peak_bytes_in_use": 3 << 30,
                    "peak_bytes_reserved": 1 << 30}

    obs.configure(None)
    obs_trace.set_enabled(tracing)
    obs_trace.clear_finished()
    REGISTRY.reset()
    try:
        sim = _tiny_sim(batch_size=10)      # a program of this test's own
        monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
        for r in range(3):
            sim.run_round(r, _hyper())
        got = {name: _read(name) for name in NEW}
    finally:
        obs.configure(None)
        obs_trace.clear_finished()
        REGISTRY.reset()
    if not tracing:
        assert got == dict.fromkeys(NEW)
        return
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["peak_hbm_gib"] == pytest.approx(4.0)


def test_program_gaps_on_the_hand_built_trace():
    from test_trace_reduce import US, _trace

    dev = _trace()["devices"]["/device:TPU:0"]
    host = _trace()["host"] + [
        ("fed.round", 105 * US, 90 * US), ("fed.host.input", 106 * US, 50 * US),
        ("fed.host.stage", 120 * US, 30 * US), ("fed.dispatch", 160 * US, 30 * US),
        ("fed.round", 0, 10 * US)]
    program, pieces = program_gaps.round_gaps(
        {"ops": [(s, s + d) for _, s, d in dev["ops"]],
         "modules": dev["modules"], "host": host})
    assert program == "jit_round"
    # between the two runs of jit_round: idle 100-130 and 140-200 us
    assert [(p["ns"], p["fed"], p["bench"]) for p in pieces] == [
        (30 * US, "fed.host.input", "bench.readback"),
        (60 * US, "fed.dispatch", "bench.round")]
    # each piece divided among the innermost spans it runs through
    assert pieces[0]["split"] == {
        "unattributed": 5 * US, "fed.round": 1 * US,
        "fed.host.input": 14 * US, "fed.host.stage": 10 * US}
    assert pieces[1]["split"] == {
        "fed.host.stage": 10 * US, "fed.host.input": 6 * US,
        "fed.round": 9 * US, "fed.dispatch": 30 * US, "unattributed": 5 * US}
    assert program_gaps.by_span(pieces, 1) == pytest.approx(
        {"fed.dispatch": 0.03, "fed.host.input": 0.02,
         "fed.host.stage": 0.02, "fed.round": 0.01, "unattributed": 0.01})
    assert program_gaps.round_gaps({"ops": [], "modules": [], "host": []}) \
        == (None, [])


def test_manifest_takes_the_new_entries():
    bench = manifest.benchmark()
    assert manifest.check_names(bench) == []
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW:
        assert entries[name]["workloads"] == cells
        assert entries[name]["source"] in ("program_span", "program_counter")
        assert hasattr(manifest.load_module("metrics", name), "read")
