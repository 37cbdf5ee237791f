"""Single-dispatch robust rounds + HBM buffer donation (ISSUE 2).

The fused robust program (train -> on-device attack -> sharded defense ->
central-DP noise -> server transform, one jitted SPMD call) must match the
host-orchestrated path client-for-client — same defense verdicts, so same
params — with and without a model attack and CDP. Buffer donation must be
safe across rounds and checkpoint restore. And the fused programs must
compile exactly once per run (canonical schedule width), which the
xla_compile_counter fixture pins so shape-instability regressions fail
loudly instead of silently recompiling every round.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments


def sim_args(**kw):
    base = dict(dataset="synthetic_mnist", model="lr",
                client_num_in_total=8, client_num_per_round=8,
                comm_round=3, epochs=1, batch_size=32, learning_rate=0.1,
                frequency_of_the_test=3, random_seed=3)
    base.update(kw)
    return Arguments(**base)


def build_sim(args):
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    fed, output_dim = data_mod.load(args)
    bundle = model_mod.create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    return TPUSimulator(args, fed, bundle, create_optimizer(args, spec),
                        spec)


def hyper_for(args):
    from fedml_tpu.core.algframe.types import TrainHyper
    return TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                      epochs=int(args.epochs))


def assert_params_close(a, b, rtol=1e-5, atol=1e-6):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


DEFENSE_KW = dict(enable_defense=True, defense_type="multi_krum",
                  krum_param_m=3, byzantine_client_num=2)
# byzantine_client_num rides DEFENSE_KW (the attacker reads the same key)
ATTACK_KW = dict(enable_attack=True, attack_type="byzantine_flip",
                 attack_scale=5.0)


class TestFusedRobustParity:
    """Fused path == host-dispatch path, client-for-client."""

    def _parity(self, **kw):
        r_fused = fedml_tpu.run_simulation(backend="tpu",
                                           args=sim_args(**kw))
        r_host = fedml_tpu.run_simulation(
            backend="tpu", args=sim_args(robust_fused="host", **kw))
        assert_params_close(r_fused["params"], r_host["params"])

    def test_defense_only_parity(self):
        self._parity(**DEFENSE_KW)

    def test_attack_and_defense_parity(self):
        self._parity(**DEFENSE_KW, **ATTACK_KW)

    def test_cdp_parity(self):
        """Central-DP noise rides the SAME key and mechanism on both
        paths, so even the noised params must agree."""
        self._parity(enable_dp=True, dp_type="central_dp", dp_epsilon=8.0,
                     **DEFENSE_KW, **ATTACK_KW)

    def test_stochastic_attack_parity(self):
        """byzantine_random folds the shard index into the attack key on
        both paths — the noise streams must line up shard-for-shard."""
        self._parity(enable_defense=True, defense_type="coordinate_median",
                     enable_attack=True, attack_type="byzantine_random",
                     byzantine_client_num=2, attack_scale=10.0)

    def test_fused_engine_is_selected_and_single_dispatch(self):
        """auto selects the fused program for a sharded-capable defended
        config, and the whole defended round runs without any
        device->host transfer."""
        args = sim_args(**DEFENSE_KW, **ATTACK_KW)
        sim = build_sim(args)
        assert sim.robust_fused
        hyper = hyper_for(args)
        with jax.transfer_guard_device_to_host("disallow"):
            metrics = sim.run_round(0, hyper)
        assert float(metrics["count"]) > 0  # readback OUTSIDE the guard
        assert sim.dispatch_stats["dispatches"] == 1

    def test_fused_multi_round_block_matches_per_round(self):
        """One 4-round dispatch == four single-round dispatches."""
        args = sim_args(**DEFENSE_KW)
        hyper = hyper_for(args)
        sim_block = build_sim(args)
        sim_loop = build_sim(args)
        metrics = sim_block.run_rounds_fused(0, 4, hyper)
        assert len(metrics) == 4
        assert sim_block.dispatch_stats["dispatches"] == 1
        for r in range(4):
            sim_loop.run_round(r, hyper)
        assert_params_close(sim_block.params, sim_loop.params)

    def test_robust_fused_refuses_unfusable_config(self):
        """robust_fused: fused must refuse (not silently degrade) configs
        that cannot fuse — here the sharded path is forced off."""
        args = sim_args(enable_defense=True, defense_type="multi_krum",
                        sharded_defense="false", robust_fused="fused")
        with pytest.raises(ValueError, match="robust_fused"):
            build_sim(args)

    def test_host_only_robust_configs_fall_back(self, caplog):
        """sharded_defense: false keeps the host kernels — auto must fall
        back to the collect path (not crash) and say WHICH knob forced
        the host path, exactly once."""
        args = sim_args(enable_defense=True, defense_type="multi_krum",
                        sharded_defense="false")
        with caplog.at_level(logging.INFO,
                             logger="fedml_tpu.simulation.tpu.engine"):
            sim = build_sim(args)
            assert sim.robust_mode and not sim.robust_fused
            sim.run_round(0, hyper_for(args))
            sim.run_round(1, hyper_for(args))
        host_logs = [r for r in caplog.records
                     if "HOST-dispatch path" in r.getMessage()]
        assert len(host_logs) == 1
        assert "sharded_defense" in host_logs[0].getMessage()


class TestNewFusedDefenses:
    """ISSUE 4: bulyan / RFA / foolsgold (and the other former host-only
    defenses) fuse — the single-dispatch program must match the
    host-dispatch path client-for-client, stateful history included."""

    def _parity(self, **kw):
        r_fused = fedml_tpu.run_simulation(backend="tpu",
                                           args=sim_args(**kw))
        r_host = fedml_tpu.run_simulation(
            backend="tpu", args=sim_args(robust_fused="host", **kw))
        assert_params_close(r_fused["params"], r_host["params"])
        return r_fused, r_host

    @pytest.mark.parametrize("defense", ["bulyan", "rfa", "foolsgold"])
    def test_defense_parity_under_attack(self, defense):
        """Same seeds, same verdicts: fused == host client-for-client for
        the defenses PR 2 left on the host path, with a byzantine-flip
        attack in the loop (the regime these defenses exist for)."""
        self._parity(enable_defense=True, defense_type=defense,
                     byzantine_client_num=2, **ATTACK_KW)

    @pytest.mark.parametrize("defense", ["cclip", "cross_round", "slsgd"])
    def test_stateful_defense_parity(self, defense):
        """Cross-round device state (cclip momentum, cross_round previous
        updates, slsgd prev-global) must evolve identically on both
        paths across a multi-round run."""
        self._parity(enable_defense=True, defense_type=defense,
                     comm_round=4)

    def test_fused_selected_for_all_builtin_defenses(self):
        """Every defense in DEFENSE_TYPES now takes the fused path under
        robust_fused: auto — the host fallback is gone for built-ins."""
        from fedml_tpu.core.security.defense import DEFENSE_TYPES
        for d in DEFENSE_TYPES:
            sim = build_sim(sim_args(enable_defense=True, defense_type=d))
            assert sim.robust_fused, d

    def test_foolsgold_downweights_sybils_on_device(self):
        """Semantics, not just parity: two colluding clients pushing the
        same poisoned direction every round must end up down-weighted
        versus the honest majority (the history accumulates on device)."""
        args = sim_args(enable_defense=True, defense_type="foolsgold",
                        enable_attack=True, attack_type="byzantine_flip",
                        byzantine_client_num=2, attack_scale=5.0)
        sim = build_sim(args)
        assert sim.robust_fused and sim._defense_state is not None
        hyper = hyper_for(args)
        for r in range(3):
            sim.run_round(r, hyper)
        hist = np.asarray(sim._defense_state["history"])
        assert np.abs(hist).sum() > 0  # accumulated, not amnesiac
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(sim.params))


class TestFoolsgoldCheckpoint:
    """The foolsgold history is engine state now — it must ride
    RoundCheckpointer saves so crash-resume replays identical weights."""

    def test_defense_state_in_ckpt_state(self):
        args = sim_args(enable_defense=True, defense_type="foolsgold")
        sim = build_sim(args)
        st = sim._ckpt_state()
        assert "defense_state" in st and "history" in st["defense_state"]
        sim.run_round(0, hyper_for(args))
        assert np.abs(np.asarray(sim._defense_state["history"])).sum() > 0

    def test_foolsgold_history_checkpoint_roundtrip(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        args = sim_args(enable_defense=True, defense_type="foolsgold",
                        checkpoint_dir=str(tmp_path / "ckpt"),
                        checkpoint_every_rounds=2, comm_round=4)
        fedml_tpu.run_simulation(backend="tpu", args=args)
        args2 = sim_args(enable_defense=True, defense_type="foolsgold",
                         checkpoint_dir=str(tmp_path / "ckpt"),
                         checkpoint_every_rounds=2, comm_round=4)
        sim = build_sim(args2)
        restored = sim.ckpt.latest(sim._ckpt_state())
        assert restored is not None and restored[0] == 3
        assert "defense_state" in restored[1]
        hist = np.asarray(restored[1]["defense_state"]["history"])
        assert np.abs(hist).sum() > 0  # the history came back, not zeros
        sim._load_ckpt_state(restored[1])
        sim.run_round(4, hyper_for(args2))  # donation-safe after restore

    def test_restore_tolerates_missing_defense_state_leaf(self, tmp_path):
        """A checkpoint written WITHOUT a stateful defense (no
        defense_state leaf) must stay loadable when foolsgold is enabled
        on resume: the engine retries without the leaf (cold-start
        history) instead of making the checkpoint unreadable."""
        pytest.importorskip("orbax.checkpoint")
        kw = dict(checkpoint_dir=str(tmp_path / "ckpt"),
                  checkpoint_every_rounds=2)
        fedml_tpu.run_simulation(backend="tpu",
                                 args=sim_args(comm_round=4, **kw))
        r = fedml_tpu.run_simulation(
            backend="tpu", args=sim_args(comm_round=6, enable_defense=True,
                                         defense_type="foolsgold", **kw))
        assert r["final_test_acc"] is not None

    def test_foolsgold_crash_resume_matches_uninterrupted(self, tmp_path):
        """Crash at round 3 (after its checkpoint flushes) + resume must
        land on the SAME params as the uninterrupted run — which can only
        happen if the resumed run restores the similarity history (an
        amnesiac history re-pardons the sybils and diverges)."""
        pytest.importorskip("orbax.checkpoint")
        from fedml_tpu.core.chaos import ChaosCrash
        kw = dict(enable_defense=True, defense_type="foolsgold",
                  enable_attack=True, attack_type="byzantine_flip",
                  byzantine_client_num=2, attack_scale=5.0,
                  comm_round=6, checkpoint_every_rounds=2, random_seed=9)
        full = fedml_tpu.run_simulation(
            backend="tpu",
            args=sim_args(checkpoint_dir=str(tmp_path / "full"), **kw))
        with pytest.raises(ChaosCrash):
            fedml_tpu.run_simulation(
                backend="tpu",
                args=sim_args(checkpoint_dir=str(tmp_path / "crash"),
                              chaos_crash_at_round=3, **kw))
        resumed = fedml_tpu.run_simulation(
            backend="tpu",
            args=sim_args(checkpoint_dir=str(tmp_path / "crash"),
                          chaos_crash_at_round=3, **kw))
        assert_params_close(full["params"], resumed["params"])


class TestContributionFusion:
    """contribution.enabled no longer disqualifies fusion: the round stays
    ONE dispatch (the program emits the post-attack sharded matrix), the
    subset values are evaluated on device, only [K] scores come host."""

    def test_contribution_with_defense_stays_fused_single_dispatch(self):
        args = sim_args(contribution_method="loo", **DEFENSE_KW)
        sim = build_sim(args)
        assert sim.contribution.enabled and sim.robust_fused
        sim.run_round(0, hyper_for(args))
        assert sim.dispatch_stats["dispatches"] == 1  # the round itself
        rec = sim.contribution.history[0]
        assert len(rec["contributions"]) == 8
        assert np.isfinite(rec["contributions"]).all()

    def test_contribution_only_run_fuses_with_mean_kernel(self):
        """No defense configured: the fused program aggregates with the
        mean kernel and still feeds the assessor; blocks fall back to
        per-round dispatches (the assessor needs each round's matrix)."""
        args = sim_args(contribution_method="loo")
        sim = build_sim(args)
        assert sim.robust_mode and sim.robust_fused
        sim.run_rounds_fused(0, 2, hyper_for(args))
        assert len(sim.contribution.history) == 2
        assert sim.dispatch_stats["dispatches"] == 2  # one per round

    def test_contribution_params_parity_fused_vs_host(self):
        """The fused contribution path must not perturb training: params
        match the host-fallback path (collect + host assessment) exactly,
        and both paths rank the same clients."""
        kw = dict(contribution_method="loo", comm_round=2, **DEFENSE_KW)
        r_fused = fedml_tpu.run_simulation(backend="tpu",
                                           args=sim_args(**kw))
        r_host = fedml_tpu.run_simulation(
            backend="tpu", args=sim_args(robust_fused="host",
                                         sharded_defense="false", **kw))
        assert_params_close(r_fused["params"], r_host["params"])

    def test_contribution_values_match_host_fallback(self):
        """Coalition values are computed around the ROUND-START params.
        The fused scores must match the pre-ISSUE-4 host fallback's scores
        — assessing around the post-round params (the round's aggregate
        applied twice) would silently skew every LOO/Shapley value."""
        kw = dict(contribution_method="loo", **DEFENSE_KW)
        sim_f = build_sim(sim_args(**kw))
        sim_h = build_sim(sim_args(robust_fused="host",
                                   sharded_defense="false", **kw))
        assert sim_f.robust_fused and not sim_h.robust_fused
        hyper = hyper_for(sim_args(**kw))
        sim_f.run_round(0, hyper)
        sim_h.run_round(0, hyper)
        cf = np.asarray(sim_f.contribution.history[0]["contributions"])
        ch = np.asarray(sim_h.contribution.history[0]["contributions"])
        np.testing.assert_allclose(cf, ch, atol=1e-5)

    def test_gtg_shapley_rides_fused_path(self):
        args = sim_args(contribution_method="gtg_shapley",
                        shapley_max_perms=4, **DEFENSE_KW)
        sim = build_sim(args)
        assert sim.robust_fused
        sim.run_round(0, hyper_for(args))
        assert len(sim.contribution.history[0]["contributions"]) == 8


class TestDonation:
    """params/server_state/client_states are donated to the round
    programs; outputs replace them 1:1, and the engine must never touch a
    donated buffer again."""

    def test_round_donates_and_never_reuses(self):
        # SCAFFOLD keeps per-client state, so the donated client_states
        # buffer is exercised too (FedAvg's is an empty pytree)
        args = sim_args(federated_optimizer="scaffold")
        sim = build_sim(args)
        hyper = hyper_for(args)
        old_params = jax.tree_util.tree_leaves(sim.params)[0]
        old_states = jax.tree_util.tree_leaves(sim.client_states)[0]
        for r in range(3):  # reuse of a donated buffer would raise here
            sim.run_round(r, hyper)
        assert old_params.is_deleted()
        assert old_states.is_deleted()
        stats = sim._evaluate(sim.params, sim.fed.test["x"],
                              sim.fed.test["y"], sim.fed.test["mask"])
        assert np.isfinite(float(stats["loss_sum"]))

    def test_fused_and_robust_paths_donate_safely(self):
        for kw in ({}, dict(**DEFENSE_KW), dict(**DEFENSE_KW, **ATTACK_KW)):
            args = sim_args(**kw)
            sim = build_sim(args)
            hyper = hyper_for(args)
            old = jax.tree_util.tree_leaves(sim.params)[0]
            sim.run_rounds_fused(0, 3, hyper)
            sim.run_rounds_fused(3, 3, hyper)
            assert old.is_deleted()
            assert all(np.isfinite(np.asarray(l)).all()
                       for l in jax.tree_util.tree_leaves(sim.params))

    def test_run_round_after_checkpoint_restore(self, tmp_path):
        """Restored state is freshly device_put — donation in the next
        round must work on it, and the resumed run must finish."""
        pytest.importorskip("orbax.checkpoint")
        kw = dict(checkpoint_dir=str(tmp_path / "ckpt"),
                  checkpoint_every_rounds=2, comm_round=4)
        fedml_tpu.run_simulation(backend="tpu", args=sim_args(**kw))
        args = sim_args(**kw)
        sim = build_sim(args)  # restores round 3 checkpoint
        restored = sim.ckpt.latest(sim._ckpt_state())
        assert restored is not None and restored[0] == 3
        sim._load_ckpt_state(restored[1])
        sim.run_round(4, hyper_for(args))
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(sim.params))

    def test_donation_off_knob(self):
        args = sim_args(donate_buffers=False)
        sim = build_sim(args)
        old = jax.tree_util.tree_leaves(sim.params)[0]
        sim.run_round(0, hyper_for(args))
        assert not old.is_deleted()


class TestCompileStability:
    """Canonical schedule width: the fused programs compile exactly once
    per run, even when per-round schedules disagree on width."""

    def test_fused_blocks_compile_once(self, xla_compile_counter):
        # subsampled rounds (8 of 16) make per-round schedule widths vary
        # — the canonical-width padding must absorb that
        args = sim_args(client_num_in_total=16, client_num_per_round=8)
        sim = build_sim(args)
        hyper = hyper_for(args)
        sim.run_rounds_fused(0, 4, hyper)  # warmup compiles everything
        assert sim.dispatch_stats["compiles"] >= 1
        xla_compile_counter.reset()
        sim.run_rounds_fused(4, 4, hyper)
        sim.run_rounds_fused(8, 4, hyper)
        assert xla_compile_counter.delta() == 0
        assert sim.dispatch_stats["dispatches"] == 3

    def test_robust_fused_blocks_compile_once(self, xla_compile_counter):
        args = sim_args(client_num_in_total=16, client_num_per_round=8,
                        **DEFENSE_KW, **ATTACK_KW)
        sim = build_sim(args)
        assert sim.robust_fused
        hyper = hyper_for(args)
        sim.run_rounds_fused(0, 4, hyper)
        xla_compile_counter.reset()
        sim.run_rounds_fused(4, 4, hyper)
        sim.run_rounds_fused(8, 4, hyper)
        assert xla_compile_counter.delta() == 0

    @pytest.mark.parametrize("defense", ["bulyan", "rfa", "foolsgold"])
    def test_new_defense_8round_block_compiles_once(
            self, defense, xla_compile_counter):
        """ISSUE 4 acceptance pin: an 8-round fused block with each newly
        fused defense compiles exactly ONE program (the compile counter
        reads 1), and later blocks add zero compiles — stateful history
        threading must not break the canonical-width invariant."""
        args = sim_args(client_num_in_total=16, client_num_per_round=8,
                        enable_defense=True, defense_type=defense,
                        byzantine_client_num=2)
        sim = build_sim(args)
        assert sim.robust_fused
        hyper = hyper_for(args)
        sim.run_rounds_fused(0, 8, hyper)
        assert sim.dispatch_stats["dispatches"] == 1
        assert sim.dispatch_stats["compiles"] == 1
        xla_compile_counter.reset()
        sim.run_rounds_fused(8, 8, hyper)
        assert xla_compile_counter.delta() == 0
        assert sim.dispatch_stats["compiles"] == 1  # still 1: no recompile

    def test_digits_8round_fused_compile_count_pinned(
            self, xla_compile_counter):
        """Regression pin (ISSUE 2 satellite): an 8-round fused digits
        run compiles its fused program exactly ONCE, and later blocks add
        zero compiles — the engine's recompile counter must read 1 across
        the whole multi-block run."""
        pytest.importorskip("sklearn")
        args = sim_args(dataset="digits", client_num_in_total=10,
                        client_num_per_round=10, learning_rate=0.3)
        sim = build_sim(args)
        hyper = hyper_for(args)
        sim.run_rounds_fused(0, 8, hyper)
        # the traced dispatch compiled exactly one program: the fused round
        assert sim.dispatch_stats["compiles"] == 1
        xla_compile_counter.reset()
        sim.run_rounds_fused(8, 8, hyper)
        sim.run_rounds_fused(16, 8, hyper)
        assert xla_compile_counter.delta() == 0
        assert sim.dispatch_stats["compiles"] == 1  # still 1: no recompile


class TestObservability:
    def test_dispatch_records_reach_mlops_sink(self, tmp_path):
        import json
        from fedml_tpu.core import mlops
        args = sim_args(run_id="disp-test", log_file_dir=str(tmp_path))
        mlops.init(args)
        try:
            sim = build_sim(args)
            sim.run_rounds_fused(0, 2, hyper_for(args))
        finally:
            mlops.init(Arguments(enable_tracking=False))
        records = [json.loads(l) for l in
                   (tmp_path / "run_disp-test.jsonl").read_text()
                   .splitlines()]
        disp = [r for r in records if r.get("kind") == "dispatch"]
        assert disp, records
        assert {"dispatch", "wall_s", "rounds", "compiles"} <= set(disp[0])
        assert disp[0]["rounds"] == 2
