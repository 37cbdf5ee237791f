"""LoRA as a pure pytree transform.

Parity target: the reference's PEFT/LoRA integration
(``train/llm/configurations.py:356`` ``get_peft_config``,
``peft_utils.py`` LORA_LAYER_TYPES) which wraps torch modules in-place.
TPU-native design: LoRA is *data*, not module surgery — a small pytree of
``(lora_a, lora_b)`` factor pairs mirroring the targeted kernels. The
forward keeps them factored: ``LLMBundle.apply`` hands the tree to
``CausalLM(adapters=, lora_scale=alpha / rank)``, which computes
``x W + ((x a) b) * (alpha / rank)`` at every adapted projection, so the
frozen ``W`` is a constant of the program and the backward pass takes only
the rank-r gradients of ``a`` and ``b``. Federated aggregation ships the
adapter tree alone — the cheap all-gather the reference approximates with
ZeRO-3 gathered-parameter contexts (``train/llm/distributed.py:54-70``).
``lora_merge`` folds an adapter into the kernels for export; no training
or serving path calls it.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util

PyTree = Any

# kernel parents targeted by default: attention projections (grouped-query
# q k v o; latent attention's q_a q_b kv_a kv_b o, or q where it has no
# query latent; linear attention's q k v o and its decay projection f) + the
# dense MLP and the shared expert (gate up down; up down where the
# activation is not gated), a state-space mixer's in_proj and out_proj, and
# the projections into and out of the experts' latent (latent_down
# latent_up). Routed experts, the router and its bias, a window layer's
# sink, the attention gates (g), linear attention's beta projection (b) and
# its convolutions, a state-space mixer's convolution, A_log, D, dt_bias and
# gated norm carry no "kernel" leaf under these names and stay frozen
# without adapters.
DEFAULT_TARGETS: Tuple[str, ...] = ("q", "k", "v", "o", "gate", "up", "down",
                                    "q_a", "q_b", "kv_a", "kv_b", "f",
                                    "in_proj", "out_proj", "latent_down",
                                    "latent_up")


def _target_paths(params: PyTree, targets: Sequence[str]):
    flat = traverse_util.flatten_dict(params)
    return [path for path in flat
            if path[-1] == "kernel" and len(path) >= 2
            and path[-2] in targets]


def lora_init(rng: jax.Array, params: PyTree, rank: int = 8,
              targets: Sequence[str] = DEFAULT_TARGETS) -> PyTree:
    """Create a zero-effect adapter tree for the targeted kernels.

    Each target kernel [in, ...out] gets ``lora_a`` [in, rank] (gaussian,
    std 1/rank as in the LoRA paper) and ``lora_b`` [rank, prod(out)]
    (zeros), so the initial merged model equals the base model exactly.
    """
    paths = _target_paths(params, targets)
    if not paths:
        raise ValueError(
            f"no LoRA targets found; targets={tuple(targets)}")
    flat = traverse_util.flatten_dict(params)
    out = {}
    for i, path in enumerate(paths):
        kernel = flat[path]
        d_in = kernel.shape[0]
        d_out = int(np.prod(kernel.shape[1:]))
        k = jax.random.fold_in(rng, i)
        out[path[:-1] + ("lora_a",)] = (
            jax.random.normal(k, (d_in, rank), jnp.float32) / rank)
        out[path[:-1] + ("lora_b",)] = jnp.zeros((rank, d_out), jnp.float32)
    return traverse_util.unflatten_dict(out)


def lora_merge(params: PyTree, lora: PyTree, alpha: float = 16.0) -> PyTree:
    """Return params with ``W + (a @ b) * (alpha / rank)`` at every adapted
    kernel: the export utility for a consumer that wants plain weights.
    Pure; safe under jit."""
    flat = dict(traverse_util.flatten_dict(params))
    lflat = traverse_util.flatten_dict(lora)
    a_paths = [p for p in lflat if p[-1] == "lora_a"]
    for path in a_paths:
        base_path = path[:-1] + ("kernel",)
        a = lflat[path]
        b = lflat[path[:-1] + ("lora_b",)]
        kernel = flat[base_path]
        rank = a.shape[1]
        delta = (a @ b) * (alpha / rank)
        flat[base_path] = kernel + delta.reshape(kernel.shape).astype(
            kernel.dtype)
    return traverse_util.unflatten_dict(flat)


def lora_zero_like(lora: PyTree) -> PyTree:
    """An all-zero adapter with ``lora``'s structure: zero ``lora_b``
    already means zero effect, but zeroing ``lora_a`` too makes the
    identity adapter content-independent — the bank's 'serve the base
    model' row."""
    return jax.tree_util.tree_map(jnp.zeros_like, lora)


def lora_stack(adapters: Sequence[PyTree]) -> PyTree:
    """Stack N structurally-identical adapter trees into ONE pytree whose
    leaves carry a leading ``[A]`` axis — the resident multi-LoRA bank a
    batched serving step gathers from (S-LoRA, Sheng et al. 2023).
    Structures must match exactly (same targets, same rank)."""
    if not adapters:
        raise ValueError("lora_stack needs >= 1 adapter")
    return jax.tree_util.tree_map(
        lambda *ls: jnp.stack([jnp.asarray(l, jnp.float32) for l in ls]),
        *adapters)


def lora_select(stack: PyTree, idx) -> PyTree:
    """Gather per-slot adapters out of a stacked bank: every ``[A, ...]``
    leaf becomes ``[S, ...]`` (or ``[...]`` for a scalar ``idx``). Pure
    gather — safe inside jit with ``idx`` as data, which is what keeps the
    decode step compile-once across any adapter mix."""
    return jax.tree_util.tree_map(lambda l: l[idx], stack)


def lora_param_count(lora: PyTree) -> int:
    return int(sum(np.prod(p.shape)
                   for p in jax.tree_util.tree_leaves(lora)))
