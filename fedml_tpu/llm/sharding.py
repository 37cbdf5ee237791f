"""Parameter/activation sharding for the LLM path — the XLA-FSDP + TP
analogue of the reference's DeepSpeed ZeRO integration
(``train/llm/distributed.py:21-70``; launcher option ``deepspeed`` in the
UnitedLLM config).

Design: Megatron-style tensor parallelism over the ``tensor`` axis
(attention heads / MLP intermediate sharded; paired projections sharded on
the opposite side so each block needs one reduce), ZeRO-3-style parameter
sharding over ``fsdp`` on the remaining large axis, batch over ``data``,
and sequence over ``sp`` for ring attention. The specs are *constraints*:
XLA's SPMD partitioner inserts the all-gathers/reduce-scatters, exactly the
"annotate shardings, let XLA insert collectives" recipe.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import traverse_util
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR
from .attention import ring_axis

PyTree = Any


def _mesh_axis(mesh: Mesh, name: Optional[str]) -> Optional[str]:
    """Use an axis only if the mesh has it with size > 1."""
    return name if (name in mesh.shape and mesh.shape[name] > 1) else None


def llm_param_specs(params: PyTree, mesh: Mesh) -> PyTree:
    """PartitionSpec tree for CausalLM (+ LoRA) params.

    Rules (path suffix → spec over (fsdp, tensor)):
      q/k/v kernel [h, heads, hd]  → (fsdp, tensor, -)
      o kernel     [h_attn, h]     → (tensor, fsdp)
      gate/up      [h, inter]      → (fsdp, tensor)
      down         [inter, h]      → (tensor, fsdp)
      embed/lm_head [vocab, h]     → (tensor, fsdp)
      norms / biases / LoRA factors → replicated (tiny)
    """
    fsdp = _mesh_axis(mesh, AXIS_FSDP)
    tp = _mesh_axis(mesh, AXIS_TENSOR)

    def spec_for(path: Tuple[str, ...], leaf) -> P:
        name = path[-1]
        parent = path[-2] if len(path) >= 2 else ""
        if name in ("lora_a", "lora_b") or leaf.ndim <= 1:
            return P()
        if name == "kernel" and parent in ("q", "k", "v"):
            return P(fsdp, tp, *(None,) * (leaf.ndim - 2))
        if name == "kernel" and parent == "o":
            return P(tp, fsdp)
        if name == "kernel" and parent in ("gate", "up"):
            return P(fsdp, tp)
        if name == "kernel" and parent == "down":
            return P(tp, fsdp)
        if name == "embedding" or parent == "lm_head":
            return P(tp, fsdp)
        # fallback: shard the largest divisible axis over fsdp
        spec = [None] * leaf.ndim
        if fsdp is not None:
            size = mesh.shape[AXIS_FSDP]
            for i in sorted(range(leaf.ndim), key=lambda i: -leaf.shape[i]):
                if leaf.shape[i] % size == 0:
                    spec[i] = fsdp
                    break
        return P(*spec)

    flat = traverse_util.flatten_dict(params)
    specs = {path: spec_for(path, leaf) for path, leaf in flat.items()}
    return traverse_util.unflatten_dict(specs)


def shard_llm_params(params: PyTree, mesh: Mesh) -> PyTree:
    """device_put the param tree onto the mesh per ``llm_param_specs``."""
    specs = llm_param_specs(params, mesh)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def make_sharded_train_step(loss_fn: Callable, optimizer, mesh: Mesh,
                            params_specs: PyTree):
    """jit a (params, opt_state, batch, rng) -> (params, opt_state, loss)
    step with parameter shardings constrained to ``params_specs`` and the
    batch sharded over ``data``. XLA inserts the FSDP gather/scatter and TP
    reduces."""
    data_ax = _mesh_axis(mesh, AXIS_DATA)

    def step(params, opt_state, batch, rng):
        params = jax.lax.with_sharding_constraint(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), params_specs))
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    batch_sharding = {
        "x": NamedSharding(mesh, P(data_ax, None)),
        "y": NamedSharding(mesh, P(data_ax, None)),
        "mask": NamedSharding(mesh, P(data_ax)),
    }
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), params_specs)
    return jax.jit(
        step,
        in_shardings=(param_sh, None, batch_sharding, None),
        out_shardings=(param_sh, None, None))


def make_ring_forward(model_apply: Callable, mesh: Mesh,
                      axis_name: str = AXIS_SEQ) -> Callable:
    """Sequence-parallel forward: tokens [b, S] sharded over ``sp``; each
    shard runs the decoder on its sequence slice with ring attention
    rotating K/V over ICI. ``model_apply(params, tokens, attn_mask)`` runs
    on local shards. Returns ``fwd(params, tokens, attn_mask=None) ->
    logits`` (sharded on the sequence axis); ``attn_mask`` is a [b, S]
    key-padding mask (1 = real token) sharded over ``sp`` alongside the
    tokens — it rotates with K/V inside ring attention."""

    size = mesh.shape[axis_name]

    def local_fwd(params, tokens, attn_mask):
        with ring_axis(axis_name, size):
            return model_apply(params, tokens, attn_mask)

    fwd = jax.shard_map(
        local_fwd, mesh=mesh,
        in_specs=(P(), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name, None),
        check_vma=False)

    def call(params, tokens, attn_mask=None):
        if attn_mask is None:
            attn_mask = jnp.ones(tokens.shape, jnp.int32)
        return fwd(params, tokens, attn_mask)

    return call
