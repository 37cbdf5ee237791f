"""Chaos subsystem: seeded fault injection + fault-tolerant rounds.

Covers (1) FaultPlan determinism and statistics, (2) the no-op guarantee —
all chaos knobs at defaults leave the simulator bit-identical and the
transport unwrapped, (3) availability faults as data in the jitted round
programs (dropout masking + renormalization, straggler step truncation),
(4) the chaos comm interceptor, the shared backoff helper and the
aggregator's clamped timeout wait, (5) the seeded crash-at-round + resume
e2e through RoundCheckpointer, and (6) the mlops fault ledger.
"""

import threading
import time

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments
from fedml_tpu.core.chaos import (ChaosCommManager, ChaosCrash, FaultLedger,
                                  FaultPlan)

pytestmark = pytest.mark.chaos


def make_args(**kw):
    base = dict(dataset="synthetic_mnist", model="lr",
                client_num_in_total=8, client_num_per_round=8,
                comm_round=3, epochs=1, batch_size=16, learning_rate=0.1,
                frequency_of_the_test=2, random_seed=42)
    base.update(kw)
    return Arguments(**base)


def leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


# --- FaultPlan ---------------------------------------------------------------

class TestFaultPlan:
    def test_disabled_by_default(self):
        plan = FaultPlan.from_args(make_args())
        assert not plan.enabled
        assert plan.round_faults(0, range(8)).dropped == ()
        assert plan.work_scale(0, 3) == 1.0
        assert plan.link_decision(0, 1, 0).copies == 1

    def test_same_seed_same_trace(self):
        kw = dict(seed=7, dropout_prob=0.3, straggler_prob=0.2,
                  straggler_work=0.5)
        t1 = FaultPlan(**kw).trace(20, range(16))
        t2 = FaultPlan(**kw).trace(20, range(16))
        assert t1 == t2
        assert any(rf.dropped for rf in t1)
        assert any(rf.work_scale for rf in t1)

    def test_expected_work_fraction(self):
        """dropped -> 0, straggler -> straggler_work, healthy -> 1:
        E[work] = (1 - p_drop) * (1 - p_strag + p_strag * w_strag)."""
        plan = FaultPlan(dropout_prob=0.5, straggler_prob=0.5,
                         straggler_work=0.5)
        assert abs(plan.expected_work_fraction - 0.375) < 1e-12
        assert FaultPlan().expected_work_fraction == 1.0
        # and the empirical trace agrees with the expectation
        fracs = [rf.scale_for(c) for rf in plan.trace(200, range(16))
                 for c in range(16)]
        assert abs(np.mean(fracs) - 0.375) < 0.03

    def test_different_seed_different_trace(self):
        t1 = FaultPlan(seed=1, dropout_prob=0.3).trace(20, range(16))
        t2 = FaultPlan(seed=2, dropout_prob=0.3).trace(20, range(16))
        assert t1 != t2

    def test_queries_are_order_independent(self):
        """Statelessness: server and clients may query in any order and
        must agree — each decision is a pure function of the key."""
        plan = FaultPlan(seed=3, dropout_prob=0.4, straggler_prob=0.3)
        fwd = [plan.work_scale(5, c) for c in range(10)]
        rev = [plan.work_scale(5, c) for c in reversed(range(10))][::-1]
        assert fwd == rev

    def test_dropout_rate_matches_probability(self):
        plan = FaultPlan(seed=0, dropout_prob=0.2)
        hits = sum(plan.is_dropped(r, c)
                   for r in range(50) for c in range(40))
        rate = hits / (50 * 40)
        assert 0.15 < rate < 0.25

    def test_link_decisions_seeded(self):
        kw = dict(seed=5, link_loss_prob=0.3, link_dup_prob=0.3)
        d1 = [FaultPlan(**kw).link_decision(0, 1, s) for s in range(50)]
        d2 = [FaultPlan(**kw).link_decision(0, 1, s) for s in range(50)]
        assert d1 == d2
        assert any(d.copies == 0 for d in d1)
        assert any(d.copies == 2 for d in d1)

    def test_crash_due(self):
        plan = FaultPlan(crash_at_round=4)
        assert plan.enabled
        assert plan.crash_due(4)
        assert not plan.crash_due(3) and not plan.crash_due(5)
        assert not FaultPlan().crash_due(0)


# --- defaults are a no-op ----------------------------------------------------

class TestDefaultsNoOp:
    def test_simulator_bit_identical_with_zeroed_knobs(self):
        """Explicitly-zero chaos knobs and absent knobs must produce the
        SAME jitted program inputs — round outputs bit-identical."""
        r_plain = fedml_tpu.run_simulation(backend="tpu", args=make_args())
        r_zero = fedml_tpu.run_simulation(backend="tpu", args=make_args(
            chaos_dropout_prob=0.0, chaos_straggler_prob=0.0,
            chaos_link_loss_prob=0.0, chaos_over_sample=0.0,
            chaos_tolerance=True))
        for a, b in zip(leaves(r_plain["params"]), leaves(r_zero["params"])):
            assert np.array_equal(a, b)

    def test_tolerance_flag_is_noop_without_faults(self):
        """chaos_tolerance only changes which weights enter the
        denominator; with nobody dropped both variants must agree
        bit-for-bit."""
        r_on = fedml_tpu.run_simulation(backend="tpu",
                                        args=make_args(chaos_tolerance=True))
        r_off = fedml_tpu.run_simulation(backend="tpu",
                                         args=make_args(chaos_tolerance=False))
        for a, b in zip(leaves(r_on["params"]), leaves(r_off["params"])):
            assert np.array_equal(a, b)

    def test_transport_not_wrapped_by_default(self):
        from fedml_tpu.core.distributed.communication.inproc import (
            InProcBroker, InProcCommManager)
        from fedml_tpu.core.distributed.fedml_comm_manager import (
            FedMLCommManager)

        class Mgr(FedMLCommManager):
            pass

        args = make_args(training_type="cross_silo")
        args.inproc_broker = InProcBroker()
        m = Mgr(args, rank=0, size=2, backend="INPROC")
        assert isinstance(m.com_manager, InProcCommManager)
        assert not isinstance(m.com_manager, ChaosCommManager)

    def test_transport_wrapped_when_link_faults_on(self):
        from fedml_tpu.core.distributed.communication.inproc import (
            InProcBroker)
        from fedml_tpu.core.distributed.fedml_comm_manager import (
            FedMLCommManager)

        class Mgr(FedMLCommManager):
            pass

        args = make_args(training_type="cross_silo",
                         chaos_link_loss_prob=0.5)
        args.inproc_broker = InProcBroker()
        m = Mgr(args, rank=0, size=2, backend="INPROC")
        assert isinstance(m.com_manager, ChaosCommManager)


# --- availability faults in the jitted round programs ------------------------

class TestSimulatorFaults:
    def test_all_dropped_round_leaves_params_unchanged(self):
        """With every client dropped (tolerance on), the weighted numerator
        AND denominator are zero — the aggregate update is exactly zero and
        the global model must not move."""
        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer)
        from fedml_tpu.core.algframe.types import TrainHyper
        from fedml_tpu.optimizers.registry import create_optimizer
        from fedml_tpu.simulation.tpu.engine import TPUSimulator
        import jax.numpy as jnp

        args = make_args(chaos_dropout_prob=1.0)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        opt = create_optimizer(args, spec)
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        before = leaves(sim.params)
        hyper = TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)
        metrics = sim.run_round(0, hyper)
        assert float(metrics["count"]) == 0.0  # nobody reported metrics
        for a, b in zip(before, leaves(sim.params)):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)

    def test_dropout_renormalizes_to_survivor_average(self):
        """Tolerance on: a round with clients {dropped} must equal a round
        where only the survivors were sampled — masking + in-program
        renormalization IS partial participation."""
        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer)
        from fedml_tpu.core.algframe.types import TrainHyper
        from fedml_tpu.optimizers.registry import create_optimizer
        from fedml_tpu.simulation.tpu.engine import TPUSimulator
        import jax.numpy as jnp

        args = make_args(chaos_dropout_prob=0.35, random_seed=4,
                         chaos_seed=13)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        opt = create_optimizer(args, spec)
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        hyper = TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)
        sampled, (idx, active, work), faults = sim._schedule_for(0)
        assert faults is not None and 0 < len(faults.dropped) < 8
        sim.run_round(0, hyper)
        got = leaves(sim.params)

        # reference: average ONLY the survivors' updates via the SP loop
        sp_args = make_args(random_seed=4)
        fed2, output_dim2 = data_mod.load(sp_args)
        bundle2 = model_mod.create(sp_args, output_dim2)
        spec2 = ClassificationTrainer(bundle2.apply)
        opt2 = create_optimizer(sp_args, spec2)
        from fedml_tpu.core.collectives import tree_weighted_average
        rng = jax.random.PRNGKey(4)
        init_rng, run_rng = jax.random.split(rng)
        params = bundle2.init(init_rng, fed2.train.x[0, 0])
        round_key = jax.random.fold_in(run_rng, 0)
        survivors = [c for c in range(8) if c not in faults.dropped]
        updates, weights = [], []
        for cid in survivors:
            cdata = jax.tree_util.tree_map(lambda a: a[cid], fed2.train)
            key = jax.random.fold_in(round_key, cid)
            out = opt2.local_train(params, opt2.server_init(params),
                                   opt2.client_state_init(params), cdata,
                                   key, hyper.replace(
                                       round_idx=jnp.int32(0)))
            updates.append(out.update)
            weights.append(out.weight)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *updates)
        agg = tree_weighted_average(stacked, jnp.stack(weights))
        want, _ = opt2.server_update(params, opt2.server_init(params), agg,
                                     {}, jnp.int32(0))
        for a, b in zip(got, leaves(want)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)

    def test_straggler_truncates_local_steps(self):
        """work_scale rides TrainHyper into the dynamic while_loop: half
        the work fraction must halve the (metrics-visible) step count."""
        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer, make_trainer_spec)
        from fedml_tpu.core.algframe.types import TrainHyper
        from fedml_tpu.optimizers.registry import create_optimizer
        import jax.numpy as jnp

        args = make_args()
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        spec = make_trainer_spec(fed, bundle)
        opt = create_optimizer(args, spec)
        rng = jax.random.PRNGKey(0)
        params = bundle.init(rng, fed.train.x[0, 0])
        cdata = jax.tree_util.tree_map(lambda a: a[0], fed.train)
        hyper = TrainHyper(learning_rate=jnp.float32(0.1), epochs=2)
        full = opt.local_train(params, opt.server_init(params),
                               opt.client_state_init(params), cdata, rng,
                               hyper)
        half = opt.local_train(params, opt.server_init(params),
                               opt.client_state_init(params), cdata, rng,
                               hyper.replace(work_scale=jnp.float32(0.5)))
        none = opt.local_train(params, opt.server_init(params),
                               opt.client_state_init(params), cdata, rng,
                               hyper.replace(work_scale=jnp.float32(0.0)))
        n_full = float(full.metrics["count"])
        n_half = float(half.metrics["count"])
        assert 0 < n_half < n_full
        assert abs(n_half - n_full / 2) <= n_full / 8  # ~half the steps
        assert float(none.metrics["count"]) == 0.0     # dropped: no steps
        for a, b in zip(leaves(none.update), leaves(params)):
            assert np.all(a == 0)  # zero steps -> zero update

    def test_chaos_run_learns_and_fused_path_used(self):
        """20% dropout + 10% stragglers with tolerance on: the fused
        multi-round dispatch still runs (faults are data) and the model
        still learns."""
        r = fedml_tpu.run_simulation(backend="tpu", args=make_args(
            comm_round=6, chaos_dropout_prob=0.2,
            chaos_straggler_prob=0.1))
        assert r["final_test_acc"] > 0.5

    def test_over_sampling_enlarges_cohort(self):
        from fedml_tpu import data as data_mod
        from fedml_tpu import model as model_mod
        from fedml_tpu.core.algframe.client_trainer import (
            ClassificationTrainer)
        from fedml_tpu.optimizers.registry import create_optimizer
        from fedml_tpu.simulation.tpu.engine import TPUSimulator

        args = make_args(client_num_in_total=16, client_num_per_round=8,
                         chaos_over_sample=0.25, chaos_dropout_prob=0.2)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        opt = create_optimizer(args, spec)
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        assert sim._sample_n == 10  # ceil(8 * 1.25)
        sampled, _, _ = sim._schedule_for(0)
        assert len(sampled) == 10


# --- crash-at-round + resume e2e --------------------------------------------

def _ckpt_args(tmp, **kw):
    base = dict(comm_round=6, checkpoint_dir=str(tmp),
                checkpoint_every_rounds=2, frequency_of_the_test=3,
                random_seed=11)
    base.update(kw)
    return make_args(**base)


def test_crash_resume_reaches_uninterrupted_accuracy(tmp_path):
    """Seeded crash at round 3 (after its checkpoint lands) + resume must
    reproduce the uninterrupted run's final params exactly — determinism
    makes elastic recovery testable."""
    full = fedml_tpu.run_simulation(
        backend="tpu", args=_ckpt_args(tmp_path / "full"))
    crash_dir = tmp_path / "crash"
    with pytest.raises(ChaosCrash) as ei:
        fedml_tpu.run_simulation(
            backend="tpu", args=_ckpt_args(crash_dir,
                                           chaos_crash_at_round=3))
    assert ei.value.round_idx == 3
    # resume with the SAME args: the crash round's checkpoint was flushed
    # before raising, so the restored trajectory starts past it and the
    # crash does not re-fire
    resumed = fedml_tpu.run_simulation(
        backend="tpu", args=_ckpt_args(crash_dir, chaos_crash_at_round=3))
    assert resumed["final_test_acc"] is not None
    for a, b in zip(leaves(full["params"]), leaves(resumed["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_crash_resume_soak_with_dropout(tmp_path):
    """Long variant: crash + resume under 20% dropout and stragglers, SP
    cross-check of the final accuracy band."""
    kw = dict(comm_round=12, chaos_dropout_prob=0.2,
              chaos_straggler_prob=0.1, checkpoint_every_rounds=3,
              frequency_of_the_test=4)
    full = fedml_tpu.run_simulation(
        backend="tpu", args=_ckpt_args(tmp_path / "full", **kw))
    crash_dir = tmp_path / "crash"
    with pytest.raises(ChaosCrash):
        fedml_tpu.run_simulation(
            backend="tpu", args=_ckpt_args(crash_dir,
                                           chaos_crash_at_round=5, **kw))
    resumed = fedml_tpu.run_simulation(
        backend="tpu", args=_ckpt_args(crash_dir, **kw))
    for a, b in zip(leaves(full["params"]), leaves(resumed["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert abs(full["final_test_acc"] - resumed["final_test_acc"]) < 1e-6


# --- async checkpoints + donation -------------------------------------------

def test_async_checkpoint_snapshots_before_donation(tmp_path):
    """The save must copy state to host BEFORE the next round program
    donates (and overwrites) the buffers: the checkpoint written at round
    k must restore round-k params even though rounds k+1.. donated and
    replaced them in HBM."""
    from fedml_tpu import data as data_mod
    from fedml_tpu import model as model_mod
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator
    import jax.numpy as jnp

    args = make_args(donate_buffers=True, checkpoint_dir=str(tmp_path),
                     checkpoint_every_rounds=2)
    fed, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    hyper = TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)
    sim.run_round(0, hyper)
    sim.run_round(1, hyper)
    at_save = leaves(sim.params)
    assert sim.ckpt.maybe_save(1, sim._ckpt_state())
    # keep training: the donated round-1 buffers are gone from HBM
    sim.run_round(2, hyper)
    sim.run_round(3, hyper)
    restored = sim.ckpt.latest(sim._ckpt_state())
    assert restored is not None and restored[0] == 1
    for a, b in zip(at_save, leaves(restored[1]["params"])):
        np.testing.assert_allclose(a, b, rtol=0, atol=0)


# --- interceptor, backoff, aggregator clamp ---------------------------------

class _CaptureComm:
    def __init__(self):
        self.sent = []
        self.observers = []

    def send_message(self, msg):
        self.sent.append((time.monotonic(), msg))

    def add_observer(self, obs):
        self.observers.append(obs)

    def remove_observer(self, obs):
        pass

    def notify(self, msg):
        pass

    def handle_receive_message(self):
        pass

    def stop_receive_message(self):
        pass


class TestInterceptor:
    def _msg(self, receiver=1):
        from fedml_tpu.core.distributed.communication.message import Message
        return Message(7, 0, receiver)

    def test_loss_drops_messages(self):
        inner = _CaptureComm()
        cm = ChaosCommManager(inner, FaultPlan(seed=1, link_loss_prob=1.0),
                              rank=0)
        for _ in range(5):
            cm.send_message(self._msg())
        assert inner.sent == []
        assert len(cm.ledger.links()) == 5

    def test_duplication_sends_twice(self):
        inner = _CaptureComm()
        cm = ChaosCommManager(inner, FaultPlan(seed=1, link_dup_prob=1.0),
                              rank=0)
        cm.send_message(self._msg())
        assert len(inner.sent) == 2

    def test_delay_defers_delivery(self):
        inner = _CaptureComm()
        cm = ChaosCommManager(
            inner, FaultPlan(seed=1, link_delay_prob=1.0,
                             link_delay_s=0.15), rank=0)
        t0 = time.monotonic()
        cm.send_message(self._msg())
        assert inner.sent == []  # not delivered synchronously
        deadline = time.monotonic() + 3.0
        while not inner.sent and time.monotonic() < deadline:
            time.sleep(0.01)
        assert inner.sent and inner.sent[0][0] - t0 >= 0.1

    def test_clean_plan_passes_through(self):
        inner = _CaptureComm()
        cm = ChaosCommManager(inner, FaultPlan(seed=1, link_loss_prob=0.0),
                              rank=0)
        m = self._msg()
        cm.send_message(m)
        assert inner.sent[0][1] is m
        assert cm.ledger.links() == []


class TestBackoff:
    def test_delays_grow_and_cap(self):
        from fedml_tpu.core.distributed.communication.backoff import (
            backoff_delays)
        it = backoff_delays(0.1, 2.0, 0.8, jitter=False)
        ds = [next(it) for _ in range(6)]
        assert ds == [0.1, 0.2, 0.4, 0.8, 0.8, 0.8]

    def test_jitter_bounded_and_seeded(self):
        from fedml_tpu.core.distributed.communication.backoff import (
            backoff_delays)
        it_a = backoff_delays(0.2, 2.0, 2.0, seed=9)
        it_b = backoff_delays(0.2, 2.0, 2.0, seed=9)
        a = [next(it_a) for _ in range(8)]
        b = [next(it_b) for _ in range(8)]
        assert a == b
        caps = [0.2, 0.4, 0.8, 1.6, 2.0, 2.0, 2.0, 2.0]
        assert all(0.0 <= d <= c for d, c in zip(a, caps))

    def test_retry_succeeds_after_failures(self):
        from fedml_tpu.core.distributed.communication.backoff import (
            retry_with_backoff)
        calls = {"n": 0}
        slept = []

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("refused")
            return "ok"

        out = retry_with_backoff(flaky, max_attempts=4, base_s=0.01,
                                 max_s=0.02, retry_on=(OSError,),
                                 sleep=slept.append)
        assert out == "ok" and calls["n"] == 3 and len(slept) == 2

    def test_retry_exhausts_and_raises(self):
        from fedml_tpu.core.distributed.communication.backoff import (
            retry_with_backoff)

        def always():
            raise OSError("down")

        with pytest.raises(OSError):
            retry_with_backoff(always, max_attempts=2, base_s=0.01,
                               max_s=0.01, retry_on=(OSError,),
                               sleep=lambda d: None)

    def test_zero_attempts_fails_fast(self):
        from fedml_tpu.core.distributed.communication.backoff import (
            retry_with_backoff)
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            raise OSError("x")

        with pytest.raises(OSError):
            retry_with_backoff(boom, max_attempts=0, retry_on=(OSError,),
                               sleep=lambda d: None)
        assert calls["n"] == 1


class TestAggregatorTimeout:
    def test_clamped_wait_regression(self):
        """The old inline expression `min(remaining or 1.0, 1.0)` waited a
        FULL second for remaining == 0.0 (falsy!) and passed negative
        timeouts through on underflow; the clamp pins both."""
        from fedml_tpu.cross_silo.server.fedml_aggregator import clamped_wait
        assert clamped_wait(0.0) == 0.05          # not 1.0
        assert clamped_wait(-3.0) == 0.05         # not negative
        assert clamped_wait(0.5) == 0.5
        assert clamped_wait(10.0) == 1.0
        assert clamped_wait(None) == 1.0

    def _agg(self, timeout, quorum_frac=0.0, expected=2):
        from fedml_tpu.cross_silo.server.fedml_aggregator import (
            FedMLAggregator)
        args = make_args(client_num_per_round=expected,
                         round_timeout_s=timeout,
                         round_quorum_frac=quorum_frac,
                         training_type="cross_silo")
        params = {"w": np.zeros((2,), np.float32)}
        return FedMLAggregator(args, params)

    def test_timeout_returns_promptly_with_partial_reports(self):
        agg = self._agg(0.3)
        agg.add_local_trained_result(1, {"w": np.ones((2,), np.float32)},
                                     1.0)
        t0 = time.monotonic()
        assert agg.wait_all_or_timeout() is True
        assert time.monotonic() - t0 < 1.0  # deadline 0.3 + clamp margin

    def test_full_cohort_returns_immediately(self):
        agg = self._agg(30.0)
        for i in (1, 2):
            agg.add_local_trained_result(
                i, {"w": np.ones((2,), np.float32)}, 1.0)
        t0 = time.monotonic()
        assert agg.wait_all_or_timeout() is True
        assert time.monotonic() - t0 < 0.1

    def test_below_quorum_waits_for_late_report(self):
        """quorum 2 of 2: one report at the deadline is not enough — the
        grace interval must pick up the straggler instead of averaging a
        sliver."""
        agg = self._agg(0.3, quorum_frac=1.0)
        agg.add_local_trained_result(1, {"w": np.ones((2,), np.float32)},
                                     1.0)

        def late():
            time.sleep(0.45)
            agg.add_local_trained_result(
                2, {"w": np.ones((2,), np.float32)}, 1.0)

        threading.Thread(target=late, daemon=True).start()
        t0 = time.monotonic()
        assert agg.wait_all_or_timeout() is True
        dt = time.monotonic() - t0
        assert 0.3 < dt < 2.0
        assert len(agg.model_dict) == 2

    def test_zero_reports_gives_up_after_grace(self):
        agg = self._agg(0.2)
        t0 = time.monotonic()
        assert agg.wait_all_or_timeout() is False
        assert 0.3 < time.monotonic() - t0 < 2.0


# --- fault ledger ------------------------------------------------------------

def test_engine_ledger_reconciles_injected_and_observed(tmp_path):
    from fedml_tpu import data as data_mod
    from fedml_tpu import model as model_mod
    from fedml_tpu.core import mlops
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator
    import jax.numpy as jnp
    import json

    args = make_args(chaos_dropout_prob=0.3, chaos_straggler_prob=0.2,
                     run_id="chaos_ledger_test",
                     log_file_dir=str(tmp_path))
    mlops.init(args)
    fed, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    hyper = TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)
    for r in range(4):
        sim.run_round(r, hyper)
    recs = sim.chaos_ledger.rounds()
    assert len(recs) == 4
    for rec in recs:
        inj, obs = rec["injected"], rec["observed"]
        # the program observed exactly sampled - injected-dropped slots
        assert obs["participating"] == obs["sampled"] - len(inj["dropped"])
    # mirrored to the mlops sink
    lines = [json.loads(l) for l in
             open(tmp_path / "run_chaos_ledger_test.jsonl")]
    chaos_recs = [l for l in lines if l.get("kind") == "chaos"]
    assert len(chaos_recs) >= 4
    mlops.init(make_args(enable_tracking=False))  # detach the sink


# --- chaos for the hierarchical and decentralized paths ----------------------
# (ROADMAP leftover closed in ISSUE 5: the link-fault interceptor wraps
# every FedMLCommManager subclass, and the gossip runtime retransmits
# through injected loss via the shared backoff helper)

class TestChaosHierarchicalAndDecentralized:
    def test_gossip_session_survives_link_loss(self):
        """Decentralized gossip has no server to time a round out — a lost
        N2N_PARAMS frame used to deadlock both endpoints. The resend loop
        (backoff-paced, idempotent receivers) must carry the session
        through seeded loss + duplication."""
        from fedml_tpu import data as data_mod, model as model_mod
        from fedml_tpu.cross_silo.decentralized import run_gossip_inproc

        args = make_args(
            training_type="cross_silo", client_num_in_total=4,
            client_num_per_round=4, comm_round=3, topology_neighbors=2,
            chaos_link_loss_prob=0.15, chaos_link_dup_prob=0.1,
            chaos_seed=13)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        result = run_gossip_inproc(args, fed, bundle)
        assert result is not None, "gossip session stalled under link loss"
        assert result["rounds"] == 3
        assert result["final_test_acc"] > 0.5

    def test_gossip_resend_loop_off_without_link_faults(self):
        """Without link-fault knobs the gossip node must not start the
        resend machinery (default path unchanged)."""
        from fedml_tpu import data as data_mod, model as model_mod
        from fedml_tpu.cross_silo.decentralized import GossipNodeManager
        from fedml_tpu.core.distributed.communication.inproc import (
            InProcBroker)

        args = make_args(training_type="cross_silo",
                         client_num_in_total=3, client_num_per_round=3)
        args.inproc_broker = InProcBroker()
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        node = GossipNodeManager(args, fed, bundle, rank=0, size=3,
                                 backend="INPROC")
        assert not node.chaos_plan.injects_link_faults
        assert not isinstance(node.com_manager, ChaosCommManager)
        node.com_manager.stop_receive_message()

    def test_hierarchical_session_survives_link_loss(self):
        """Hierarchical silos ride the same ClientMasterManager FSM: the
        interceptor wraps their transports, and round timeout + quorum +
        the ONLINE re-announce carry the session through injected loss."""
        from fedml_tpu import data as data_mod, model as model_mod
        from fedml_tpu.core.chaos import ChaosCommManager as CCM
        from fedml_tpu.cross_silo.hierarchical.runner import (
            run_hierarchical_cross_silo_inproc)

        args = make_args(
            training_type="cross_silo", client_num_in_total=4,
            client_num_per_round=2, comm_round=2, round_timeout_s=20.0,
            chaos_link_loss_prob=0.1, chaos_link_dup_prob=0.1,
            chaos_seed=17)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        result = run_hierarchical_cross_silo_inproc(args, fed, bundle)
        assert result is not None, "hierarchical session stalled"
        assert len(result["history"]) == 2
