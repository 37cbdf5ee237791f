"""The shared local-SGD loop — the hot loop of FL simulation.

Parity target: the epochs×batches training loop of
``ml/trainer/my_model_trainer_classification.py:21-77``. TPU-first design:
the loop is a single ``lax.scan`` over ``epochs * n_batches`` steps so XLA
compiles one fused program per round; per-epoch batch-order shuffling is done
with a folded PRNG permutation instead of a stateful DataLoader; padded
batches (clients with fewer samples than the static maximum) are no-ops via
masking, which is what makes ragged client data jit-compatible.

Every federated optimizer reuses this loop and customizes it through a
``grad_transform`` hook (FedProx's proximal term, SCAFFOLD's control-variate
correction, Mime's server-stats step).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..obs.scopes import scope
from .types import ClientData, TrainHyper
from .client_trainer import TrainerSpec

PyTree = Any
GradTransform = Callable[[PyTree, PyTree, Dict[str, Any]], PyTree]


def zero_train_metrics(spec: TrainerSpec) -> Dict[str, jnp.ndarray]:
    """The summed training metrics at zero: the three every trainer
    reports and the spec's ``extra_metrics``."""
    return {k: jnp.float32(0) for k in
            ("loss_sum", "correct", "count") + tuple(spec.extra_metrics)}


def run_local_sgd(
    spec: TrainerSpec,
    inner_opt: optax.GradientTransformation,
    params: PyTree,
    cdata: ClientData,
    rng: jax.Array,
    hyper: TrainHyper,
    grad_transform: Optional[GradTransform] = None,
    ctx: Optional[Dict[str, Any]] = None,
    init_opt_state: Optional[PyTree] = None,
) -> Tuple[PyTree, PyTree, Dict[str, jnp.ndarray]]:
    """Run ``hyper.epochs`` of SGD over one client's padded batches.

    Returns ``(params, final_opt_state, metrics)`` where metrics are summed
    counts (loss_sum / correct / count) over all real samples seen.

    Ragged clients: the stacked client tensors pad every client to the
    LARGEST client's batch count, so a fixed-trip ``lax.scan`` would burn a
    full fwd+bwd on every padded batch (on hetero Dirichlet partitions that
    is ~2x the real work — measured 5.6s -> 2.8s per 64-client ResNet-56
    round when skipped). Instead the loop is a ``lax.while_loop`` over the
    *dynamic* real-step count — reverse-mode AD never differentiates through
    the loop (grads are taken per step inside), so ``while_loop`` is legal,
    and under ``jax.vmap`` (the engine's client-batched mode) it becomes a
    lanes-masked batched while that exits when the longest client finishes.

    Per-epoch shuffling with a dynamic batch count uses the sort trick: draw
    a uniform key per padded slot, push padded batches to the end with +2.0,
    and argsort — the first ``real_batches`` positions are then a uniform
    permutation of exactly the real batches.
    """
    opt_state = inner_opt.init(params) if init_opt_state is None else init_opt_state
    n_batches = cdata.x.shape[0]
    # [n_batches] — a batch is real iff it has at least one unmasked sample
    batch_real = jnp.any(cdata.mask > 0, axis=tuple(range(1, cdata.mask.ndim)))
    real_batches = jnp.sum(batch_real.astype(jnp.int32))
    # chaos straggler slowdown as data: work_scale < 1 truncates the
    # dynamic step count (ceil keeps at least one step for any scale > 0).
    # At the default work_scale == 1.0 the product and ceil are exact, so
    # the step count — and therefore the trajectory — is bit-identical to
    # the unscaled loop.
    total_steps = jnp.ceil(
        (hyper.epochs * real_batches).astype(jnp.float32)
        * hyper.work_scale).astype(jnp.int32)
    denom = jnp.maximum(real_batches, 1)
    data_rng, loop_rng = jax.random.split(rng)
    ctx = ctx or {}
    zero_metrics = zero_train_metrics(spec)

    def epoch_order(epoch):
        keys = jax.random.uniform(jax.random.fold_in(data_rng, epoch),
                                  (n_batches,))
        return jnp.argsort(jnp.where(batch_real, keys, keys + 2.0))

    def cond(carry):
        return carry[0] < total_steps

    def body(carry):
        t, params, opt_state, rng, metrics = carry
        with scope("local.batch"):
            rng, step_rng = jax.random.split(rng)
            idx = epoch_order(t // denom)[t % denom]
            batch = {"x": cdata.x[idx], "y": cdata.y[idx],
                     "mask": cdata.mask[idx]}
        with scope("local.grad"):
            (loss, aux), grads = jax.value_and_grad(spec.loss, has_aux=True)(
                params, batch, step_rng)
        with scope("local.update"):
            if grad_transform is not None:
                grads = grad_transform(grads, params, ctx)
            updates, opt_state = inner_opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = {k: metrics[k] + aux[k].astype(jnp.float32)
                       for k in zero_metrics}
        return (t + 1, params, opt_state, rng, metrics)

    (_, params, opt_state, _, metrics) = jax.lax.while_loop(
        cond, body, (jnp.int32(0), params, opt_state, loop_rng, zero_metrics))
    return params, opt_state, metrics


def effective_steps(cdata: ClientData, epochs: int,
                    work_scale=1.0) -> jnp.ndarray:
    """Number of *real* (non-padding) local SGD steps a client runs: padded
    all-zero-mask batches are gated to no-ops in :func:`run_local_sgd`, so
    K = ceil(epochs x real batches x work_scale). SCAFFOLD / FedNova
    normalizations need this exact count — a chaos straggler that ran half
    its steps must be normalized by the steps it RAN, or its control
    variate / a_i coefficient silently mis-scales."""
    real_batches = jnp.sum(jnp.any(cdata.mask > 0, axis=1).astype(jnp.float32))
    return jnp.maximum(jnp.ceil(epochs * real_batches * work_scale), 1.0)


def full_batch_grad_sum(
    spec: TrainerSpec,
    params: PyTree,
    cdata: ClientData,
    rng: jax.Array,
) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
    """Masked SUM of per-sample gradients of the loss at ``params`` (the
    un-normalized numerator of :func:`full_batch_grad`): per-batch mean
    gradients re-weighted by real-sample count and summed. This is the
    quantity that is exactly additive across clients, which is what lets
    the engine's client-slot batch folding replace S per-client passes
    with one S-times-wider pass (ISSUE 16)."""

    def body(carry, inp):
        i, batch = inp
        acc_g, acc_m = carry
        with scope("local.grad"):
            grads, aux = jax.grad(spec.loss, has_aux=True)(
                params, batch, jax.random.fold_in(rng, i))
        n = aux["count"]
        acc_g = jax.tree_util.tree_map(
            lambda a, g: a + g * n.astype(g.dtype), acc_g, grads)
        acc_m = jax.tree_util.tree_map(
            lambda a, m: a + m.astype(a.dtype), acc_m, aux)
        return (acc_g, acc_m), None

    zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
    zero_m = jax.eval_shape(
        lambda: spec.loss(params, jax.tree_util.tree_map(
            lambda a: a[0], {"x": cdata.x, "y": cdata.y, "mask": cdata.mask}),
            rng))[1]
    zero_m = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), zero_m)
    (acc_g, metrics), _ = jax.lax.scan(
        body, (zero_g, zero_m),
        (jnp.arange(cdata.x.shape[0]),
         {"x": cdata.x, "y": cdata.y, "mask": cdata.mask}))
    return acc_g, metrics


def full_batch_grad(
    spec: TrainerSpec,
    params: PyTree,
    cdata: ClientData,
    rng: jax.Array,
) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
    """Masked full-dataset gradient of the loss at ``params`` — the per-batch
    mean gradients are re-weighted by real-sample count so the result equals
    the gradient of the mean loss over all real samples. Used by FedSGD and
    Mime's server-statistics update."""
    acc_g, metrics = full_batch_grad_sum(spec, params, cdata, rng)
    denom = jnp.maximum(metrics["count"], 1.0)
    grads = jax.tree_util.tree_map(
        lambda g: g / denom.astype(g.dtype), acc_g)
    return grads, metrics


def evaluate(
    spec: TrainerSpec,
    params: PyTree,
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Batched evaluation over a [n_batches, bs, ...] dataset; returns summed
    stats (caller divides by count). Counterpart of the reference's
    ``_local_test_on_all_clients`` / trainer ``test`` methods."""

    def body(carry, batch):
        stats = spec.eval_stats(params, batch)
        return carry, stats

    _, stats = jax.lax.scan(body, None, {"x": x, "y": y, "mask": mask})
    return {k: jnp.sum(v) for k, v in stats.items()}
