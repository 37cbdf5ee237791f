"""``correct`` has to come out false when it should, at a size a test can hold.

The control: the reference with float8 matmul operands, put in the system's
place, fails one of the cell's numbers on every seed tried. The faults: the
rest of a run (``run.main`` with the look for a chip skipped by
``--rehearse``) with the timed path broken underneath: a step that returns
its state unchanged, and half of each batch left out with the mean taken over
the rest. (One chip: no exchange to leave out. Training: no token to alter.)
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run as bench_run  # noqa: E402
from harness import compare, manifest, traffic as traffic_mod  # noqa: E402

CELLS = [w["name"] for w in manifest.benchmark()["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("seed", [5, 4000000007, 77])
def test_control_in_lower_precision_fails(cell_name, seed):
    import jax

    cell = manifest.Cell(cell_name, rehearse=True)
    ref = manifest.load_module("reference", cell.entry["config"])
    fedavg = manifest.load_module("reference", "fedavg")
    seed32 = traffic_mod.program_seed(seed)
    data = traffic_mod.generate(cell.config, cell.traffic, seed)
    trainable, _ = bench_run.make_weights(jax, ref, cell.config, seed32)
    p0 = jax.tree_util.tree_map(lambda a: jax.device_get(a), trainable)
    rounds = cell.cell["check_rounds"]
    reference = bench_run.run_reference(jax, cell, ref, fedavg, data, p0,
                                        seed32, rounds)
    control = bench_run.run_reference(jax, cell, ref, fedavg, data, p0,
                                      seed32, rounds, quant=fedavg.fp8_quant)
    numbers, _ = compare.compare(control, reference)
    ok, table = compare.verdict(numbers, cell.cell["limits"])
    assert not ok, table
    same, _ = compare.compare(reference, reference)
    assert compare.verdict(same, cell.cell["limits"])[0]


def _run(monkeypatch, capsys, cell, wrap_build):
    real = manifest.load_module

    def patched(kind, name):
        mod = real(kind, name)
        if kind == "drivers":
            mod.build = wrap_build(mod.build)
        return mod

    monkeypatch.setattr(bench_run.manifest, "load_module", patched)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell, "--seed",
                                      "31", "--seconds", "0.2", "--rehearse"])
    assert bench_run.main() == 3
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return lines[-1]


class _UnchangedState:
    """Every round returns the state it was given."""

    def __init__(self, driver):
        self.d = driver
        self.attention_impl = driver.attention_impl

    def step(self, r):
        import jax
        import jax.numpy as jnp
        keep = jax.tree_util.tree_map(jnp.copy, self.d.sim.params)
        out = self.d.step(r)
        self.d.sim.params = keep
        return out

    def trainable(self):
        return self.d.trainable()

    def close(self):
        self.d.close()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_then_broken_runs(monkeypatch, capsys, cell):
    sound = _run(monkeypatch, capsys, cell, lambda build: build)
    assert sound["correct"] is True, sound["compared"]

    unchanged = _run(monkeypatch, capsys, cell,
                     lambda build: lambda *a: _UnchangedState(build(*a)))
    assert unchanged["correct"] is False
    assert unchanged["compared"]["grad_1"]["value"] > 0.9   # reads 1

    def half(build):
        def built(cfg, tr, seed, data, trainable, frozen):
            data = dict(data, mask=data["mask"].copy())
            data["mask"][:, :, data["mask"].shape[2] // 2:] = 0
            return build(cfg, tr, seed, data, trainable, frozen)
        return built

    halved = _run(monkeypatch, capsys, cell, half)
    assert halved["correct"] is False, halved["compared"]
