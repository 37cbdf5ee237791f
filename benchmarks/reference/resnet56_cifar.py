"""Plain float32 reference of ResNet-56 for CIFAR (He et al. 2016, section
4.2: a 3x3 stem, 3 stages of n basic blocks at 16/32/64 channels, global
average pooling, one dense layer; n = 9 gives 56 layers).

Departure from the paper, as FedML's ``resnet_gn`` and the repo's
``model/cv/resnet.py`` make it: GroupNorm (8 groups, eps 1e-6) in place of
BatchNorm, and a projection (1x1 convolution + GroupNorm) on the two
shortcuts that change shape in place of the paper's zero padding.

Imports nothing of ``fedml_tpu``. The parameter tree is named as flax names
the same stack (``Conv_0``, ``GroupNorm_0``, ``BasicBlock_<i>``, ``Dense_0``),
which lets the driver hand these weights to the system leaf by leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6
GROUPS = 8
HIGHEST = lax.Precision.HIGHEST


def _block_plan(cfg):
    """[(name, c_in, c_out, stride)] for every basic block."""
    plan, c_in, i = [], cfg["stem_channels"], 0
    for stage, c_out in enumerate(cfg["stage_channels"]):
        for block in range(cfg["blocks_per_stage"]):
            stride = 2 if (stage > 0 and block == 0) else 1
            plan.append((f"BasicBlock_{i}", c_in, c_out, stride))
            c_in, i = c_out, i + 1
    return plan


def init_trainable(key, cfg):
    """Weights from the seed, in one traced call: normal kernels of variance
    1/fan_in, GroupNorm scale 1 and bias 0, dense bias 0; the scale of each
    block's last GroupNorm is ``residual_gn_scale`` (see the configuration's
    ``assumed``)."""
    n = [0]

    def kernel(shape):
        n[0] += 1
        fan_in = shape[0] * shape[1] * shape[2] if len(shape) == 4 else shape[0]
        return jax.random.normal(jax.random.fold_in(key, n[0]), shape,
                                 jnp.float32) / jnp.sqrt(float(fan_in))

    def gn(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    stem = cfg["stem_channels"]
    p = {"Conv_0": {"kernel": kernel((3, 3, cfg["input_shape"][2], stem))},
         "GroupNorm_0": gn(stem)}
    for name, c_in, c_out, stride in _block_plan(cfg):
        b = {"Conv_0": {"kernel": kernel((3, 3, c_in, c_out))},
             "GroupNorm_0": gn(c_out),
             "Conv_1": {"kernel": kernel((3, 3, c_out, c_out))},
             "GroupNorm_1": gn(c_out)}
        b["GroupNorm_1"]["scale"] = (b["GroupNorm_1"]["scale"]
                                     * cfg.get("residual_gn_scale", 1.0))
        if stride != 1 or c_in != c_out:
            b["Conv_2"] = {"kernel": kernel((1, 1, c_in, c_out))}
            b["GroupNorm_2"] = gn(c_out)
        p[name] = b
    last = cfg["stage_channels"][-1]
    p["Dense_0"] = {"kernel": kernel((last, cfg["num_classes"])),
                    "bias": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return p


def init_frozen(key, cfg):
    return None


def _apply(f, x, w, quant):
    """``f(x, w)``; the control routes it through its lower precision."""
    return f(x, w) if quant is None else quant(f)(x, w)


def _conv(x, w, stride, quant):
    return _apply(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST),
        x, w, quant)


def _group_norm(x, p):
    b, h, w, c = x.shape
    g = min(GROUPS, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + EPS)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def make_model(cfg):
    plan = _block_plan(cfg)

    def forward(p, x, quant):
        x = jax.nn.relu(_group_norm(
            _conv(x, p["Conv_0"]["kernel"], 1, quant), p["GroupNorm_0"]))
        for name, _, _, stride in plan:
            b = p[name]
            y = jax.nn.relu(_group_norm(
                _conv(x, b["Conv_0"]["kernel"], stride, quant),
                b["GroupNorm_0"]))
            y = _group_norm(_conv(y, b["Conv_1"]["kernel"], 1, quant),
                            b["GroupNorm_1"])
            if "Conv_2" in b:
                x = _group_norm(_conv(x, b["Conv_2"]["kernel"], stride, quant),
                                b["GroupNorm_2"])
            x = jax.nn.relu(x + y)
        x = jnp.mean(x, axis=(1, 2))
        d = p["Dense_0"]
        return _apply(lambda a, b: jnp.dot(a, b, precision=HIGHEST), x,
                      d["kernel"], quant) + d["bias"]

    def grad_fn(trainable, frozen, batch, quant):
        """Gradient of the mean cross-entropy over the batch's real rows."""
        mask = batch["mask"].astype(jnp.float32)

        def loss(p):
            logits = forward(p, batch["x"].astype(jnp.float32), quant)
            logp = jax.nn.log_softmax(logits, axis=-1)
            per_row = -jnp.take_along_axis(
                logp, batch["y"].astype(jnp.int32)[:, None], axis=-1)[:, 0]
            loss_sum = jnp.sum(per_row * mask)
            return loss_sum / jnp.maximum(jnp.sum(mask), 1.0), loss_sum

        (_, loss_sum), grads = jax.value_and_grad(loss, has_aux=True)(trainable)
        return grads, loss_sum, jnp.sum(mask)

    return grad_fn
