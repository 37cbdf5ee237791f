"""The compile-cache rule (``fedml_tpu._place_compile_cache``): one site,
placeable from outside through ``JAX_COMPILATION_CACHE_DIR``."""

from __future__ import annotations

import os
import subprocess
import sys

import jax

import fedml_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_wins_through_import_and_engine_build(tmp_path):
    """With the variable set the package sets no directory anywhere: the
    value JAX read from the environment survives ``import fedml_tpu`` and
    building a TPUSimulator (which once re-pointed it per engine)."""
    code = """
import jax, fedml_tpu
from fedml_tpu import data, model
from fedml_tpu.arguments import Arguments
from fedml_tpu.core.algframe.client_trainer import make_trainer_spec
from fedml_tpu.optimizers.registry import create_optimizer
from fedml_tpu.simulation.tpu.engine import TPUSimulator
print("import:", jax.config.jax_compilation_cache_dir)
args = Arguments(dataset="synthetic_mnist", model="lr",
                 client_num_in_total=2, client_num_per_round=2,
                 batch_size=8)
fed, out_dim = data.load(args)
bundle = model.create(args, out_dim)
spec = make_trainer_spec(fed, bundle)
TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)
print("engine:", jax.config.jax_compilation_cache_dir)
"""
    want = str(tmp_path / "placed")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=want,
               JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"import: {want}\n" in out.stdout
    assert f"engine: {want}\n" in out.stdout


def test_default_dir_is_fixed_under_the_checkout(monkeypatch):
    """Variable unset, not CPU-primary: ``<checkout>/.jax_cache``, a path
    built from the package's location alone."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "")
    prev = jax.config.jax_compilation_cache_dir
    try:
        fedml_tpu._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cpu_primary_process_keeps_no_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu,tpu")
    prev = jax.config.jax_compilation_cache_dir
    fedml_tpu._place_compile_cache()
    assert jax.config.jax_compilation_cache_dir == prev
