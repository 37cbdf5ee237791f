"""Device-mesh construction — the hardware vocabulary of the framework.

The reference scales FL by mapping clients onto processes/GPUs through MPI
ranks or a NCCL process group (``nccl/base_framework/common.py:106-146``).
The TPU-native equivalent is a named `jax.sharding.Mesh`: the ``client`` axis
carries FL round-level parallelism; ``data``/``fsdp``/``tensor``/``sp`` axes
carry intra-silo parallelism for large models (the DeepSpeed/DDP analogue,
reference ``ml/engine/ml_engine_adapter.py:302``, ``train/llm/distributed.py``).

All collectives ride these named axes via ``shard_map``/``pjit`` — XLA lowers
them to ICI/DCN transfers; there is no NCCL/MPI plumbing to manage.
"""

from __future__ import annotations

import logging
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import AXIS_CLIENT, AXIS_DATA, AXIS_FSDP, AXIS_TENSOR

logger = logging.getLogger(__name__)


def build_mesh(
    mesh_shape: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh.

    ``mesh_shape`` maps axis name → size, e.g. ``{"client": 8}`` or
    ``{"client": 16, "fsdp": 8}``. A size of ``-1`` means "the remainder of
    the device count". Default: all local devices on one ``client`` axis —
    the Parrot-NCCL topology (one client slot per chip).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not mesh_shape:
        mesh_shape = {AXIS_CLIENT: n}
    names: List[str] = list(mesh_shape.keys())
    sizes: List[int] = [int(s) for s in mesh_shape.values()]
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = math.prod(s for s in sizes if s != -1)
    sizes = [n // fixed if s == -1 else s for s in sizes]
    if math.prod(sizes) != n:
        raise ValueError(f"mesh shape {dict(zip(names, sizes))} != {n} devices")
    dev_array = np.asarray(devices).reshape(sizes)
    # every engine run names the devices it landed on: the mesh takes
    # whatever jax.devices() returns, and JAX itself falls back to the
    # CPU when no accelerator initialises
    logger.info("mesh %s over %d x %s (%s)", dict(zip(names, sizes)), n,
                devices[0].device_kind, devices[0].platform)
    return Mesh(dev_array, axis_names=tuple(names))


def client_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get(AXIS_CLIENT, 1)


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for globally-replicated state (the broadcast of
    ``nccl/base_framework/common.py:222`` is free replication here)."""
    return NamedSharding(mesh, P())


def client_sharded(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard leading axis over ``client``; used for per-client stacked data
    and schedule tensors."""
    return NamedSharding(mesh, P(AXIS_CLIENT, *([None] * (ndim - 1))))


def data_sharded(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Batch-axis sharding over the ``data`` axis (intra-silo DDP analogue,
    reference ``ml/engine/ml_engine_adapter.py:273``)."""
    axis = AXIS_DATA if AXIS_DATA in mesh.shape else None
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def fsdp_param_sharding(mesh: Mesh, shape: Tuple[int, ...]) -> NamedSharding:
    """ZeRO-3-style parameter sharding: shard the largest divisible axis over
    ``fsdp`` (reference DeepSpeed path ``train/llm/distributed.py:54-70``)."""
    if AXIS_FSDP not in mesh.shape:
        return NamedSharding(mesh, P())
    size = mesh.shape[AXIS_FSDP]
    best = None
    for i, dim in sorted(enumerate(shape), key=lambda t: -t[1]):
        if dim % size == 0:
            best = i
            break
    spec = [None] * len(shape)
    if best is not None:
        spec[best] = AXIS_FSDP
    return NamedSharding(mesh, P(*spec))


def logical_sharding_rules() -> List[Tuple[str, Optional[str]]]:
    """flax logical-axis → mesh-axis rules for the LLM path (TP + FSDP)."""
    return [
        ("batch", AXIS_DATA),
        ("embed", AXIS_FSDP),
        ("mlp", AXIS_TENSOR),
        ("heads", AXIS_TENSOR),
        ("kv", None),
        ("vocab", AXIS_TENSOR),
    ]
