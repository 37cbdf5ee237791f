"""Pallas TPU kernels, and the one rule for how they are lowered.

The LLM stack keeps its kernels next to its models (``llm/attention.py``);
``conv_block`` here is the fused conv->GroupNorm->residual->ReLU block of
the CIFAR ResNet. Every ``pallas_call`` in the repo asks :func:`interpret`
whether to run interpreted: compiled by Mosaic when the process's default
backend is a TPU, interpreted everywhere else so the parity tests run on
the CPU. :func:`compile_for_tpu` overrides that for ahead-of-time
lowering against a TPU topology from a process that has no chip (the
pre-flight in ``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import contextlib
import contextvars

import jax

_TPU_TARGET: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "fedml_tpu_pallas_tpu_target", default=False)


def interpret() -> bool:
    """True when Pallas kernels must run in interpret mode."""
    return not _TPU_TARGET.get() and jax.default_backend() != "tpu"


@contextlib.contextmanager
def compile_for_tpu():
    """Lower Pallas kernels for Mosaic inside this context even though the
    default backend is not a TPU. Only ``.lower().compile()`` against
    ``jax.experimental.topologies`` devices makes sense here — executing
    the result needs the chip."""
    token = _TPU_TARGET.set(True)
    try:
        yield
    finally:
        _TPU_TARGET.reset(token)


def tpu_compiler_params(dimension_semantics=None):
    """Mosaic parameters shared by the kernels (``None`` when interpreted);
    ``dimension_semantics`` names each grid axis ``parallel`` or
    ``arbitrary`` (a kernel that carries state along an axis in scratch
    needs that axis run in order).

    The scoped-VMEM cap is raised above the 16 MiB default: the flash
    kernels keep the full-length K/V refs resident, and at seq 8192 with
    d=128 that sits a few hundred KiB over the default. A v5e core has
    128 MiB of VMEM; 64 MiB leaves headroom for double-buffering and
    admits sequences to ~64k on one chip (ring attention shards beyond
    that)."""
    if interpret():
        return None
    import jax.experimental.pallas.tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024,
                                dimension_semantics=dimension_semantics)
