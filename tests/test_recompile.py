"""Recompile forensics (core/obs/recompile): a forced recompile's record
names the changed abstract shape and validates against the schema; the
engine and serving seams take a signature only on a dispatch that
compiled, and compare it with the one their program last compiled at.
"""

import glob
import json

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


@pytest.fixture
def signature_calls(monkeypatch):
    """The argument tuples ``dispatch_signature`` was called with."""
    from fedml_tpu.core.obs import recompile
    calls = []
    real = recompile.dispatch_signature

    def counting(args):
        calls.append(args)
        return real(args)

    monkeypatch.setattr(recompile, "dispatch_signature", counting)
    return calls


def _observed_dispatch(tracker, f, x):
    from fedml_tpu.core import mlops
    c0 = mlops.compile_count()
    f(x)
    return tracker.observe("prog", (x,), mlops.compile_count() - c0)


class TestRecompileForensics:
    def test_forced_recompile_names_the_changed_shape(self, tmp_path):
        """A real jitted program re-dispatched at a new abstract shape:
        the forensics record names the leaf and the old -> new shape,
        and validates against the schema."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import recompile, schema
        mlops.init(_mk(log_file_dir=str(tmp_path)))
        mlops.install_compile_counter()
        tracker = recompile.RecompileTracker()
        f = jax.jit(lambda x: x * 2.0)
        recs = [_observed_dispatch(tracker, f, jnp.zeros(shape))
                for shape in ((4,), (8,))]
        assert recs[0] is None          # first compile: pinned expectation
        rec = recs[1]
        assert rec is not None and rec["program"] == "prog"
        assert rec["changed"], rec
        ch = rec["changed"][0]
        assert "4" in ch["was"] and "8" in ch["now"]
        assert schema.validate_record({**rec, "kind": "recompile",
                                       "ts": 0.0, "run_id": "t"}) == []
        assert rec in recompile.recent_recompiles()

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
        moved = {c["arg"]: (c["was"], c["now"]) for c in rec["changed"]}
        assert set(moved) == {"[4]", "[5]", "[6]"}, moved
        assert all(was != now for was, now in moved.values())

    def test_compile_delta_repr_carries_forensics(self):
        """The conftest counter's failing delta prints the forensics —
        every existing compile-once test upgrades for free."""
        from tests.conftest import _CompileDelta
        from fedml_tpu.core.obs import recompile
        recompile._recent_recompiles.append(
            {"program": "demo", "compiles": 1, "total_compiles": 2,
             "expected": 1,
             "changed": [{"arg": "[0]", "was": "f32[4]",
                          "now": "f32[8]"}], "note": None})
        try:
            assert repr(_CompileDelta(0)) == "0"
            r = repr(_CompileDelta(1))
            assert "demo" in r and "f32[4]" in r and "f32[8]" in r
        finally:
            recompile._recent_recompiles.pop()

    def test_compares_with_the_last_compiled_signature(
            self, signature_calls):
        """Shapes A, B, A, C: the third dispatch hits the cache, so it
        compiles nothing and walks nothing; the fourth names C against
        B, the signature of the last dispatch that compiled."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import recompile
        mlops.install_compile_counter()
        tracker = recompile.RecompileTracker()
        f = jax.jit(lambda x: x + 1.0)
        recs, walks = [], []
        for n in (3, 5, 3, 7):
            recs.append(_observed_dispatch(tracker, f, jnp.zeros((n,))))
            walks.append(len(signature_calls))
        assert walks == [1, 2, 2, 3]
        assert recs[0] is None and recs[2] is None
        assert recs[1]["changed"] == [
            {"arg": "[0]", "was": "float32[3]", "now": "float32[5]"}]
        assert recs[3]["changed"] == [
            {"arg": "[0]", "was": "float32[5]", "now": "float32[7]"}]
        assert recs[3]["total_compiles"] == 3


def _steady_rounds():
    """One round of the engine, then the seam's cost over three more."""
    args = _mk(comm_round=4)
    sim = _build_sim(args)
    hyper = _hyper(args)
    sim.run_round(0, hyper)
    yield
    for r in (1, 2, 3):
        sim.run_round(r, hyper)
    yield sim.dispatch_stats["dispatches"]


def _steady_decode_steps():
    """One decode step of the serving scheduler, then three more."""
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.serving.batch import DecodeScheduler
    args = Arguments(
        dataset="llm_synthetic", model="causal_lm",
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        epochs=1, batch_size=4, learning_rate=1e-3, random_seed=3,
        llm_hidden_size=32, llm_num_layers=2, llm_num_heads=2,
        llm_intermediate_size=64, llm_max_seq_len=64, lora_rank=4)
    _, bundle, _, tok = build_llm(args)
    sched = DecodeScheduler(bundle.module, bundle.cfg, bundle.base_params,
                            None, slots=2, block_size=16, prefill_chunk=8)
    ids = [1] + tok.encode("steady state") + [3]
    sched.admit(ids, max_new_tokens=8)
    sched.step()
    yield
    before = sched.steps_run
    for _ in range(3):
        sched.step()
    yield sched.steps_run - before


@pytest.mark.parametrize("seam", [_steady_rounds, _steady_decode_steps],
                         ids=["engine_round", "serving_decode_step"])
def test_steady_state_dispatch_takes_no_signature(
        seam, signature_calls, xla_compile_counter):
    """After a program's first dispatch, three more compile nothing and
    so never call ``dispatch_signature``."""
    run = seam()
    next(run)
    assert signature_calls, "the first dispatch compiled: one signature"
    del signature_calls[:]
    xla_compile_counter.reset()
    dispatched = next(run)
    assert dispatched >= 3
    assert xla_compile_counter.delta() == 0
    assert signature_calls == []
