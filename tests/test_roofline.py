"""Compute-plane observability (core/obs/roofline): per-op roofline
attribution, collective-traffic accounting, recompile forensics, and the
``scripts/roofline_report.py`` CLI.

Pins: analytical FLOPs/bytes are EXACT on hand-computable programs
(matmul, psum), while-loop trip counts multiply scanned bodies, the
``kind: roofline`` / ``kind: recompile`` records validate against the
schema on a REAL engine run, capture costs zero compiles at default
knobs, and a forced recompile's forensics record names the changed
abstract shape.
"""

import glob
import json
import os

import numpy as np
import pytest

pytestmark = pytest.mark.obs


def _mk(**kw):
    from fedml_tpu.arguments import Arguments
    base = dict(dataset="synthetic_mnist", model="lr",
                client_num_in_total=8, client_num_per_round=8,
                comm_round=2, epochs=1, batch_size=16, learning_rate=0.1,
                frequency_of_the_test=100, random_seed=0)
    base.update(kw)
    return Arguments(**base)


def _build_sim(args):
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator
    fed, od = load(args)
    bundle = create(args, od)
    spec = ClassificationTrainer(bundle.apply)
    return TPUSimulator(args, fed, bundle, create_optimizer(args, spec),
                        spec)


def _hyper(args):
    import jax.numpy as jnp
    from fedml_tpu.core.algframe.types import TrainHyper
    return TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                      epochs=1)


# ---------------------------------------------------------------------------
class TestCostModel:
    def test_matmul_flops_and_bytes_exact(self):
        """2*M*N*K flops, operands+output bytes — the hand check."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core.obs import roofline
        f = jax.jit(lambda a, b: jnp.dot(a, b))
        co = f.lower(jnp.ones((8, 16)), jnp.ones((16, 4))).compile()
        rec = roofline.analyze_compiled("mm", co, n_devices=1)
        assert rec["total_flops"] == 2 * 8 * 16 * 4
        assert rec["total_bytes"] == 4 * (8 * 16 + 16 * 4 + 8 * 4)
        top = rec["ops"][0]
        assert top["op"] == "dot"
        assert top["operands"] == ["f32[8,16]", "f32[16,4]"]
        assert rec["attributed_share"] == 1.0
        # 1024 flops / 896 bytes is far under any machine balance
        assert top["bound"] == "memory"

    def test_psum_collective_wire_bytes_exact(self):
        """all-reduce over the 8-device CPU mesh: ring traffic is
        2*(g-1)/g * payload per device, group parsed from the HLO."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from fedml_tpu.core.obs import roofline
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs the multi-device CPU mesh")
        mesh = Mesh(np.array(devs), ("d",))

        def body(x, w):
            return jax.lax.psum(x @ w, "d")

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("d"), P()),
                              out_specs=P()))
        co = f.lower(jnp.ones((4 * len(devs), 16)),
                     jnp.ones((16, 8))).compile()
        rec = roofline.analyze_compiled("psum", co, n_devices=len(devs))
        colls = rec["collectives"]
        assert len(colls) == 1 and colls[0]["op"] == "all-reduce"
        g = len(devs)
        assert colls[0]["group"] == g
        payload = 4 * 4 * 8     # f32[4,8] per-device partial
        want = 2.0 * (g - 1) / g * payload
        assert rec["collective_wire_bytes"] == pytest.approx(want)
        # per-device dot: 2 * 4 * 16 * 8, plus the reduce adds
        assert rec["total_flops"] >= 2 * 4 * 16 * 8

    def test_scan_trip_count_multiplies_body(self):
        """A lax.scan body attributes trip_count x its per-iteration
        cost (XLA's known_trip_count or the parsed loop bound)."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core.obs import roofline

        def run(c, xs):
            return jax.lax.scan(lambda c, x: (c * 1.5 + x, jnp.float32(0)),
                                c, xs)[0]

        co = jax.jit(run).lower(jnp.ones((64,)),
                                jnp.ones((7, 64))).compile()
        rec = roofline.analyze_compiled("scan", co, n_devices=1)
        body_rows = [r for r in rec["ops"] if r["mult"] == 7]
        assert body_rows, rec["ops"]
        # c*1.5 + x = 2 flops/element * 64 * 7 iterations
        assert rec["total_flops"] >= 2 * 64 * 7

    def test_window_reads_charged_the_window(self):
        """A fused dynamic-slice of a big stacked array is charged the
        slice, not the stack — otherwise per-slot data slicing would
        drown the compute it feeds."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core.obs import roofline

        big = jnp.ones((64, 256))

        def run(big, i):
            return jnp.sum(jax.lax.dynamic_slice_in_dim(big, i, 1) * 2.0)

        co = jax.jit(run).lower(big, jnp.int32(3)).compile()
        rec = roofline.analyze_compiled("slice", co, n_devices=1)
        # full stack = 64 KiB; the window is one 1 KiB row (+ output)
        assert rec["total_bytes"] < 64 * 256 * 4 / 2

    def test_peaks_table_is_keyed_by_exact_device_kind(self):
        """One table, looked up by the exact ``device_kind`` the chip
        reports: the v5e ("TPU v5 lite") must not lend its peak to the
        v5p ("TPU v5"), an unknown TPU kind has no peak at all, and a
        CPU balance is static-only while a TPU one is not."""
        from fedml_tpu.core.obs import profiler, roofline

        class Dev:
            def __init__(self, kind):
                self.device_kind = kind

        v5e = Dev("TPU v5 lite")
        assert profiler.peak_tflops(v5e) == 197.0
        assert profiler.hbm_gbps(v5e) == 819.0
        assert profiler.peak_tflops(Dev("TPU v5")) != 197.0
        for kind in ("TPU v5e", "TPU v7", "tpu v5 lite"):
            assert profiler.peak_tflops(Dev(kind)) is None, kind
            assert profiler.hbm_gbps(Dev(kind)) is None, kind
        assert roofline.machine_balance(Dev("TPU v7")).static_only
        cpu = roofline.machine_balance(Dev("cpu"))
        assert cpu.static_only and cpu.flops_per_byte is not None
        v4 = roofline.machine_balance(Dev("TPU v4"))
        assert not v4.static_only
        assert v4.peak_tflops == 275.0 and v4.hbm_gbps == 1228.0


# ---------------------------------------------------------------------------
class TestEngineCapture:
    def test_engine_run_emits_schema_valid_roofline_records(
            self, tmp_path, xla_compile_counter):
        """Real engine run with obs_roofline: every JSONL line validates
        (the replay gate for the new kinds), the round program's record
        attributes >=90% of predicted time, and the round program is
        compiled once between the AOT capture and the two dispatches
        (jax 0.9 hands the captured executable to the dispatch, so the
        dispatch records report no compile of their own)."""
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import roofline, schema
        args = _mk(obs_roofline=True, log_file_dir=str(tmp_path))
        mlops.init(args)
        sim = _build_sim(args)
        hyper = _hyper(args)
        sim.run_round(0, hyper)
        xla_compile_counter.reset()
        sim.run_round(1, hyper)
        assert xla_compile_counter.delta() == 0
        assert sim.dispatch_stats["compiles"] == 0

        rep = roofline.report("round")
        assert rep is not None
        assert rep["attributed_share"] >= 0.9
        assert rep["static_only"] is True      # CPU mesh: loud, flagged
        assert rep["ops"] and rep["total_flops"] > 0
        logs = glob.glob(str(tmp_path / "**" / "*.jsonl"), recursive=True)
        assert logs
        kinds = set()
        for p in logs:
            with open(p) as f:
                lines = f.readlines()
            assert schema.validate_lines(lines) == []
            for line in lines:
                if line.strip():
                    kinds.add(json.loads(line).get("kind"))
        assert "roofline" in kinds

        from fedml_tpu.core.obs.metrics import REGISTRY
        g = REGISTRY.gauge("roofline_predicted_mfu", labels=("program",))
        assert g.value(program="round") is not None

    def test_default_knobs_capture_nothing_and_compile_nothing(
            self, tmp_path, xla_compile_counter):
        """obs_roofline off (default): no roofline records, no extra
        compiles — the compile-once invariant is untouched."""
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import roofline
        args = _mk(log_file_dir=str(tmp_path))
        mlops.init(args)
        sim = _build_sim(args)
        assert sim._roofline.enabled is False
        hyper = _hyper(args)
        sim.run_round(0, hyper)
        xla_compile_counter.reset()
        sim.run_round(1, hyper)
        assert xla_compile_counter.delta() == 0
        assert sim.dispatch_stats["compiles"] == 1
        for p in glob.glob(str(tmp_path / "**" / "*.jsonl"),
                           recursive=True):
            with open(p) as f:
                assert not any('"kind": "roofline"' in ln for ln in f)


# ---------------------------------------------------------------------------
class TestRecompileForensics:
    def test_forced_recompile_names_the_changed_shape(self, tmp_path):
        """A real jitted program re-dispatched at a new abstract shape:
        the forensics record names the leaf and the old -> new shape,
        and validates against the schema."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import roofline, schema
        mlops.init(_mk(log_file_dir=str(tmp_path)))
        mlops.install_compile_counter()
        tracker = roofline.DispatchTracker(enabled=False)
        f = jax.jit(lambda x: x * 2.0)
        recs = []
        for shape in ((4,), (8,)):
            x = jnp.zeros(shape)
            sig = roofline.dispatch_signature((x,))
            c0 = mlops.compile_count()
            f(x)
            recs.append(tracker.observe("prog", sig,
                                        mlops.compile_count() - c0))
        assert recs[0] is None          # first compile: pinned expectation
        rec = recs[1]
        assert rec is not None and rec["program"] == "prog"
        assert rec["changed"], rec
        ch = rec["changed"][0]
        assert "4" in ch["was"] and "8" in ch["now"]
        assert schema.validate_record({**rec, "kind": "recompile",
                                       "ts": 0.0, "run_id": "t"}) == []
        assert rec in roofline.recent_recompiles()

    def test_engine_seam_emits_forensics_on_width_change(self, tmp_path):
        """Dispatch the engine's real round program at a widened
        schedule: the recompile record lands in the run log naming the
        schedule leaves that moved."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core import mlops
        args = _mk(log_file_dir=str(tmp_path))
        mlops.init(args)
        sim = _build_sim(args)
        hyper = _hyper(args)
        sim.run_round(0, hyper)

        # re-dispatch with every schedule tensor one slot wider (the
        # padded slot is inactive, so semantics are unchanged — only
        # the abstract shape moves)
        sampled, (idx, active, work), _ = sim._schedule_for(1)
        pad = ((0, 0), (0, 1))
        idx = jax.device_put(jnp.asarray(np.pad(idx, pad)),
                             sim.client_sharding)
        active = jax.device_put(jnp.asarray(np.pad(active, pad)),
                                sim.client_sharding)
        work = jax.device_put(jnp.asarray(np.pad(work, pad)),
                              sim.client_sharding)
        key = jax.random.fold_in(sim.rng, 1)
        sim._traced("round", 1, sim._round_fn, sim.params,
                    sim.server_state, sim.train_data, sim.client_states,
                    idx, active, work, key,
                    hyper.replace(round_idx=jnp.int32(1)))
        recs = []
        for p in glob.glob(str(tmp_path / "**" / "*.jsonl"),
                           recursive=True):
            with open(p) as f:
                recs += [json.loads(ln) for ln in f if ln.strip()]
        forensics = [r for r in recs if r.get("kind") == "recompile"]
        assert forensics, "no recompile record emitted"
        rec = forensics[-1]
        assert rec["program"] == "round"
        changed_args = " ".join(c["arg"] for c in rec["changed"])
        assert "[4]" in changed_args or "[5]" in changed_args \
            or "[6]" in changed_args or rec["changed"]

    def test_compile_delta_repr_carries_forensics(self):
        """The conftest counter's failing delta prints the forensics —
        every existing compile-once test upgrades for free."""
        from tests.conftest import _CompileDelta
        from fedml_tpu.core.obs import roofline
        roofline._recent_recompiles.append(
            {"program": "demo", "compiles": 1, "total_compiles": 2,
             "expected": 1,
             "changed": [{"arg": "[0]", "was": "f32[4]",
                          "now": "f32[8]"}], "note": None})
        try:
            assert repr(_CompileDelta(0)) == "0"
            r = repr(_CompileDelta(1))
            assert "demo" in r and "f32[4]" in r and "f32[8]" in r
        finally:
            roofline._recent_recompiles.pop()


# ---------------------------------------------------------------------------
class TestServingCapture:
    def test_decode_and_prefill_programs_capture(self):
        """The serving scheduler's dispatch seam captures the decode
        step and prefill programs when the module default is on."""
        import jax
        from fedml_tpu.arguments import Arguments
        from fedml_tpu.llm.federated import build_llm
        from fedml_tpu.serving.batch import DecodeScheduler
        from fedml_tpu.core.obs import roofline
        args = Arguments(
            dataset="llm_synthetic", model="causal_lm",
            client_num_in_total=2, client_num_per_round=2, comm_round=1,
            epochs=1, batch_size=4, learning_rate=1e-3, random_seed=3,
            llm_hidden_size=32, llm_num_layers=2, llm_num_heads=2,
            llm_intermediate_size=64, llm_max_seq_len=64, lora_rank=4)
        _, bundle, _, tok = build_llm(args)
        roofline.set_default_enabled(True)
        try:
            sched = DecodeScheduler(bundle.module, bundle.cfg,
                                    bundle.base_params, None, slots=2,
                                    block_size=16, prefill_chunk=8)
            ids = [1] + tok.encode("roofline capture") + [3]
            slot, _ = sched.admit(ids, max_new_tokens=2)
            sched.step()
            sched.release(slot)
        finally:
            roofline.set_default_enabled(False)
        for prog in ("llm_decode_step", "llm_prefill_chunk"):
            rep = roofline.report(prog)
            assert rep is not None, prog
            assert rep["total_flops"] > 0
            assert rep["attributed_share"] >= 0.9


# ---------------------------------------------------------------------------
class TestReportCLI:
    def _write_log(self, path, attributed=1.0):
        rec = {"kind": "roofline", "ts": 0.0, "run_id": "t",
               "program": "round", "device_kind": "cpu", "n_devices": 8,
               "static_only": True, "peak_tflops": 0.5, "hbm_gbps": 25.0,
               "balance_flops_per_byte": 20.0,
               "total_flops": 2.0e9, "total_bytes": 1.0e8,
               "predicted_s": 0.004, "predicted_mfu": 0.069,
               "attributed_share": attributed,
               "memory_bound_share": 0.82, "compute_bound_share": 0.18,
               "collective_wire_bytes": 1792.0,
               "xla_flops": None, "xla_bytes": None,
               "ops": [
                   {"name": "convolution.1", "op": "convolution",
                    "op_name": "conv_general_dilated", "out": "f32[32,8,8,64]",
                    "operands": ["f32[32,8,8,64]", "f32[3,3,64,64]"],
                    "flops": 1.9e9, "bytes": 5.0e7, "mult": 30,
                    "intensity": 38.0, "bound": "memory",
                    "time_s": 0.002, "share": 0.5, "estimated": False},
                   {"name": "fusion.2", "op": "fusion", "op_name": "relu",
                    "out": "f32[32,8,8,64]",
                    "operands": ["f32[32,8,8,64]"],
                    "flops": 1.0e8, "bytes": 5.0e7, "mult": 30,
                    "intensity": 2.0, "bound": "memory",
                    "time_s": 0.002, "share": 0.5, "estimated": False}],
               "collectives": [
                   {"op": "all-reduce", "operands": ["f32[256]"],
                    "group": 8, "count": 1, "payload_bytes": 1024.0,
                    "wire_bytes": 1792.0}]}
        fore = {"kind": "recompile", "ts": 0.0, "run_id": "t",
                "program": "round", "compiles": 1, "total_compiles": 2,
                "expected": 1,
                "changed": [{"arg": "[4]", "was": "s32[8,2]",
                             "now": "s32[8,4]"}], "note": None}
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
            f.write(json.dumps(fore) + "\n")

    def test_report_golden_sections(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "scripts"))
        import roofline_report
        log = str(tmp_path / "run.jsonl")
        self._write_log(log)
        rc = roofline_report.main([log, "--top", "4"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "== round — cpu x8" in out
        assert "STATIC-ONLY" in out
        assert "convolution(f32[32,8,8,64],f32[3,3,64,64])" in out
        assert "memory 82.0%" in out
        assert "all-reduce" in out and "1.79kB" in out
        assert "recompile forensics" in out
        assert "s32[8,2] -> s32[8,4]" in out

    def test_min_attr_gate(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "scripts"))
        import roofline_report
        log = str(tmp_path / "run.jsonl")
        self._write_log(log, attributed=0.7)
        assert roofline_report.main([log, "--min-attr", "0.9"]) == 2
        capsys.readouterr()
        self._write_log(log, attributed=0.95)
        assert roofline_report.main([log, "--min-attr", "0.9"]) == 0
        assert "coverage OK" in capsys.readouterr().out

    def test_compare_mode(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "scripts"))
        import roofline_report
        a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        self._write_log(a)
        self._write_log(b)
        assert roofline_report.main([a, "--compare", b]) == 0
        out = capsys.readouterr().out
        assert "predicted_mfu" in out and "collective_wire_bytes" in out


# ---------------------------------------------------------------------------
class TestBenchDiffMarkers:
    def test_roofline_metric_directions(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "scripts"))
        import bench_diff
        assert not bench_diff.lower_is_better("roofline_predicted_mfu")
        assert bench_diff.lower_is_better("memory_bound_share")
        assert bench_diff.lower_is_better("recompiles")
        assert bench_diff.lower_is_better("collective_wire_bytes")
        assert not bench_diff.lower_is_better(
            "fedavg_robust_rfa_weak_scaling_efficiency")
        assert not bench_diff.lower_is_better(
            "llm_serving_adapter_churn_tokens_per_s.tokens_per_s")
        assert bench_diff.lower_is_better("swap_stall_s")
