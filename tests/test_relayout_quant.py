"""Quantized ``all_to_all`` re-layout on the fused robust path (ISSUE 16
tentpole part 3).

The [S, D] -> [S*n, D/n] re-layout carries (g-1)/g of the update matrix
over the wire every defended round. ``robust_relayout_quant`` shrinks it
— int8 rows with per-row scales (4x) or a bf16 cast (2x) — with
DETERMINISTIC rounding so every device dequantizes identical rows and
the defense verdict stays replicated. Knob off must stay bit-identical;
knob on must keep the RFA geometric-median output within a bounded
error. The byte count on the wire is not read here: no cell of the
benchmark runs a defended round yet (ROADMAP, named debts).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.arguments import Arguments
from fedml_tpu.core.algframe.types import TrainHyper


def sim_args(**kw):
    base = dict(dataset="synthetic_mnist", model="lr",
                client_num_in_total=8, client_num_per_round=8,
                comm_round=4, epochs=1, batch_size=32, learning_rate=0.1,
                frequency_of_the_test=10_000, random_seed=3,
                enable_defense=True, defense_type="rfa",
                enable_attack=True, attack_type="byzantine_flip",
                byzantine_client_num=2, attack_scale=5.0)
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
    return TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                      epochs=int(args.epochs))


def run_legs(n_rounds=4, **kw):
    args = sim_args(**kw)
    sim = build_sim(args)
    sim.run_rounds_fused(0, n_rounds, hyper_for(args))
    return sim


def leaves(sim):
    return jax.tree_util.tree_leaves(sim.params)


@pytest.fixture(scope="module")
def dense_leaves():
    """Final params of the knob-absent (dense f32) defended run — the
    golden both the bit-identity and bounded-error tests compare against
    (module-scoped: one compile serves all of them)."""
    return [np.asarray(a) for a in leaves(run_legs())]


class TestKnobOff:
    def test_explicit_off_is_bit_identical(self, dense_leaves):
        """Knob off reproduces today's byte stream AND today's bits: the
        dense f32 all_to_all is the same program, so the final params
        must be array_equal, not merely close."""
        other = run_legs(robust_relayout_quant="off")
        for a, b in zip(dense_leaves, leaves(other)):
            assert np.array_equal(a, np.asarray(b))

    @pytest.mark.parametrize("knob", [None, "none", "false"])
    def test_off_aliases_resolve_to_dense(self, knob):
        """Every off-spelling resolves to the same dense program (the
        resolver is the single dispatch point, so resolver identity ==
        program identity — proven bit-for-bit above for "off")."""
        sim = build_sim(sim_args(robust_relayout_quant=knob))
        assert sim._relayout_quant is None

    def test_unknown_mode_refuses(self):
        with pytest.raises(ValueError, match="robust_relayout_quant"):
            build_sim(sim_args(robust_relayout_quant="fp4"))

    def test_bfloat16_aliases_bf16(self):
        sim = build_sim(sim_args(robust_relayout_quant="bfloat16"))
        assert sim._relayout_quant == "bf16"

    def test_host_path_warns_and_stays_dense(self, caplog):
        """The host-dispatch robust path has no explicit all_to_all to
        quantize — the knob must warn (once, naming the fix) and keep
        the dense re-layout rather than silently changing numerics."""
        with caplog.at_level(logging.WARNING,
                             logger="fedml_tpu.simulation.tpu.engine"):
            sim = build_sim(sim_args(sharded_defense="false",
                                     robust_relayout_quant="int8"))
        assert sim.robust_mode and not sim.robust_fused
        assert sim._relayout_quant is None
        warned = [r for r in caplog.records
                  if "robust_relayout_quant" in r.getMessage()]
        assert len(warned) == 1
        assert "robust_fused" in warned[0].getMessage()


class TestBoundedError:
    """int8/bf16 re-layout perturbs the RFA geometric-median inputs by at
    most half a quantization step per element — the defended params must
    track the dense run within a bound far tighter than a round's worth
    of learning-rate movement (observed: ~5e-4 int8, ~9e-5 bf16 on this
    config), and the quantized run must still converge finitely."""

    @pytest.mark.parametrize("mode,atol", [("int8", 5e-3), ("bf16", 2e-3)])
    def test_rfa_params_track_dense(self, mode, atol, dense_leaves):
        quant = run_legs(robust_relayout_quant=mode)
        for a, b in zip(dense_leaves, leaves(quant)):
            np.testing.assert_allclose(a, np.asarray(b), atol=atol)
            assert np.isfinite(np.asarray(b)).all()

    def test_int8_roundtrip_elementwise_bound(self):
        """The per-row-scale deterministic quantizer itself: the dequant
        error of any element is at most scale/2 = max|row| / 254, and a
        zero row survives (scale clamps to 1, not 0/0)."""
        x = np.random.RandomState(0).randn(16, 257).astype(np.float32)
        x[3] = 0.0
        amax = np.abs(x).max(axis=1, keepdims=True)
        scale = np.where(amax > 0, amax, 1.0) / 127.0
        deq = np.round(x / scale).astype(np.int8).astype(np.float32) * scale
        assert np.abs(deq - x).max() <= (scale / 2 + 1e-7).max()
        assert np.array_equal(deq[3], np.zeros_like(deq[3]))

    def test_single_dispatch_and_compile_once(self, xla_compile_counter):
        """Quantize/dequantize lives INSIDE the fused program — still one
        dispatch per block and zero recompiles across blocks."""
        args = sim_args(comm_round=12, robust_relayout_quant="int8")
        sim = build_sim(args)
        hyper = hyper_for(args)
        sim.run_rounds_fused(0, 4, hyper)
        assert sim.dispatch_stats["dispatches"] == 1
        xla_compile_counter.reset()
        sim.run_rounds_fused(4, 4, hyper)
        sim.run_rounds_fused(8, 4, hyper)
        assert xla_compile_counter.delta() == 0
