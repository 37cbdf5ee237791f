"""Plain FedAvg round, shared by every configuration's reference.

One round, as FedML's ``sp_fedavg`` example and McMahan et al. 2017 define it:
every client starts from the global weights, runs its local epochs of
minibatch SGD over its batches in a given order, and the server replaces the
global weights by the average of the clients' weights, each weighted by its
number of samples.

This file imports nothing of ``fedml_tpu``. A model's reference (the files
beside this one) supplies ``grad_fn(trainable, frozen, batch, quant)`` which
returns ``(grads, loss_sum, count)`` for one batch, the gradient being that of
the mean loss over the batch's real rows. ``make_round`` builds the round from
it. Everything runs in float32 with ``highest`` matmul precision; ``quant``
is the identity here and the lower-precision rounding in the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

tree_map = jax.tree_util.tree_map


def make_round(grad_fn, quant=None):
    """-> jitted ``round_fn(trainable, frozen, data, orders, lr)``.

    ``data``: ``x [C, nb, bs, ...]``, ``y``, ``mask [C, nb, bs]``,
    ``num_samples [C]``. ``orders [C, steps]`` int32: the batch each local
    step takes. Returns ``(new_trainable, loss_sum, count)``; the two sums
    run over every real row of every local step, as the system reports them.
    """

    def client(trainable, frozen, cdata, order, lr):
        def step(carry, idx):
            params, loss_sum, count = carry
            batch = tree_map(lambda a: a[idx], cdata)
            grads, ls, n = grad_fn(params, frozen, batch, quant)
            params = tree_map(lambda p, g: p - lr * g, params, grads)
            return (params, loss_sum + ls, count + n), None

        zero = jnp.zeros((), jnp.float32)
        (params, loss_sum, count), _ = jax.lax.scan(
            step, (trainable, zero, zero), order)
        return params, loss_sum, count

    def round_fn(trainable, frozen, data, orders, lr):
        batches = {k: data[k] for k in ("x", "y", "mask")}

        def one(carry, inp):
            acc, loss_sum, count = carry
            cdata, order, n_k = inp
            local, ls, n = client(trainable, frozen, cdata, order, lr)
            acc = tree_map(lambda a, p, g: a + (p - g) * n_k, acc, local,
                           trainable)
            return (acc, loss_sum + ls, count + n), None

        zero = jnp.zeros((), jnp.float32)
        (acc, loss_sum, count), _ = jax.lax.scan(
            one, (tree_map(jnp.zeros_like, trainable), zero, zero),
            (batches, orders, data["num_samples"].astype(jnp.float32)))
        total = jnp.sum(data["num_samples"].astype(jnp.float32))
        new = tree_map(lambda g, a: g + a / total, trainable, acc)
        return new, loss_sum, count

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return round_fn(*args)

    return jax.jit(traced)


def _fp8(x, dtype, top):
    """Round to a float8 type with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def fp8_quant(f):
    """The control's arithmetic: ``f(x, w)`` (a matmul or a convolution) as
    float8 training does it, the nearest precision below the bfloat16 the
    configurations state: both operands rounded to e4m3 on the way in, the
    incoming gradient rounded to e5m2 on the way back, each with one scale
    for the tensor; accumulation stays float32."""

    @jax.custom_vjp
    def op(x, w):
        return f(_fp8(x, jnp.float8_e4m3fn, 448.0),
                 _fp8(w, jnp.float8_e4m3fn, 448.0))

    def fwd(x, w):
        xq = _fp8(x, jnp.float8_e4m3fn, 448.0)
        wq = _fp8(w, jnp.float8_e4m3fn, 448.0)
        return f(xq, wq), (xq, wq)

    def bwd(res, g):
        _, vjp = jax.vjp(f, *res)
        return vjp(_fp8(g, jnp.float8_e5m2, 57344.0))

    op.defvjp(fwd, bwd)
    return op
