"""Parity tests for the fused conv->GroupNorm->residual->ReLU Pallas block
(``core/kernels/conv_block``, ISSUE 16 tentpole).

Tier-1 runs everything here interpreted on CPU (the ``pallas`` marker);
``tests/test_chip_compile.py`` AOT-compiles the kernel for the v5e and
``chip_smoke.py`` checks parity on the chip. The XLA
:func:`reference_block` is the numerical golden — it is itself pinned
bit-identical to the unfused flax ``BasicBlock``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.kernels.conv_block import (DEFAULT_BLOCK_N, GN_EPS,
                                               fused_block, reference_block)
from fedml_tpu.model.cv.resnet import BasicBlock, create_resnet

pytestmark = pytest.mark.pallas


def _make_params(rng, cin, cout, proj, dtype=jnp.float32):
    ks = jax.random.split(rng, 8)
    p = {"w1": (jax.random.normal(ks[0], (3, 3, cin, cout)) * 0.2),
         "g1_scale": 1.0 + 0.1 * jax.random.normal(ks[1], (cout,)),
         "g1_bias": 0.1 * jax.random.normal(ks[2], (cout,)),
         "w2": jax.random.normal(ks[3], (3, 3, cout, cout)) * 0.2,
         "g2_scale": 1.0 + 0.1 * jax.random.normal(ks[4], (cout,)),
         "g2_bias": 0.1 * jax.random.normal(ks[5], (cout,))}
    if proj:
        p["wp"] = jax.random.normal(ks[6], (1, 1, cin, cout)) * 0.2
        p["gp_scale"] = 1.0 + 0.1 * jax.random.normal(ks[7], (cout,))
        p["gp_bias"] = jnp.zeros((cout,))
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), p)


def _flax_to_dict(variables):
    v = variables["params"]
    p = {"w1": v["Conv_0"]["kernel"],
         "g1_scale": v["GroupNorm_0"]["scale"],
         "g1_bias": v["GroupNorm_0"]["bias"],
         "w2": v["Conv_1"]["kernel"],
         "g2_scale": v["GroupNorm_1"]["scale"],
         "g2_bias": v["GroupNorm_1"]["bias"]}
    if "Conv_2" in v:
        p["wp"] = v["Conv_2"]["kernel"]
        p["gp_scale"] = v["GroupNorm_2"]["scale"]
        p["gp_bias"] = v["GroupNorm_2"]["bias"]
    return p


@pytest.mark.parametrize("width", [16, 32, 64])
def test_parity_across_channel_widths(width):
    """Kernel vs XLA reference at each narrow-stage width the flagship
    model ships (identity residual, stride 1)."""
    p = _make_params(jax.random.PRNGKey(width), width, width, proj=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, width))
    ref = reference_block(x, p, strides=1, groups=8)
    fus = fused_block(x, p, strides=1, groups=8)
    np.testing.assert_allclose(np.asarray(fus), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h,w,strides", [(7, 9, 1), (7, 7, 2), (9, 8, 2)])
def test_odd_spatial_dims(h, w, strides):
    """Odd extents exercise the pad-then-subsample path (stride-2 samples
    EVEN positions for odd extents, ODD for even — parity-dependent)."""
    proj = strides != 1
    p = _make_params(jax.random.PRNGKey(7), 16, 32 if proj else 16, proj)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, h, w, 16))
    ref = reference_block(x, p, strides=strides, groups=8)
    fus = fused_block(x, p, strides=strides, groups=8)
    assert fus.shape == ref.shape == (2, -(-h // strides),
                                      -(-w // strides),
                                      32 if proj else 16)
    np.testing.assert_allclose(np.asarray(fus), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_projection_residual_branch():
    """Strided stage transition: 1x1-projection + GN residual branch."""
    p = _make_params(jax.random.PRNGKey(3), 16, 32, proj=True)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 8, 16))
    ref = reference_block(x, p, strides=2, groups=8)
    fus = fused_block(x, p, strides=2, groups=8)
    np.testing.assert_allclose(np.asarray(fus), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_channel_change_without_stride():
    """cin != cout at stride 1 also takes the projection branch."""
    p = _make_params(jax.random.PRNGKey(5), 16, 32, proj=True)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 8, 16))
    np.testing.assert_allclose(
        np.asarray(fused_block(x, p, strides=1, groups=8)),
        np.asarray(reference_block(x, p, strides=1, groups=8)),
        rtol=1e-5, atol=1e-5)


def test_batch_grid_padding():
    """A batch that is not a multiple of the block size pads the grid and
    slices the pad rows back off (and the zero pad rows must not NaN the
    GroupNorm: var 0 -> rsqrt(eps) stays finite)."""
    p = _make_params(jax.random.PRNGKey(8), 16, 16, proj=False)
    n = DEFAULT_BLOCK_N + 3
    x = jax.random.normal(jax.random.PRNGKey(9), (n, 8, 8, 16))
    fus = fused_block(x, p)
    assert fus.shape[0] == n
    assert np.isfinite(np.asarray(fus)).all()
    np.testing.assert_allclose(np.asarray(fus),
                               np.asarray(reference_block(x, p)),
                               rtol=1e-5, atol=1e-5)


def test_bf16_parity():
    p = _make_params(jax.random.PRNGKey(10), 16, 16, proj=False,
                     dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 8, 8, 16),
                          dtype=jnp.bfloat16)
    ref = reference_block(x, p)
    fus = fused_block(x, p)
    assert fus.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(fus, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.06, atol=0.06)


def test_mixed_dtype_output_and_grad_follow_reference():
    """bf16 activations against f32 params: the fused output takes the
    reference's promoted dtype, so the reference-recompute backward gets
    the cotangent dtype it expects."""
    p = _make_params(jax.random.PRNGKey(10), 16, 16, proj=False)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 8, 8, 16),
                          dtype=jnp.bfloat16)
    assert fused_block(x, p).dtype == reference_block(x, p).dtype
    gx = jax.grad(lambda x_: jnp.sum(fused_block(x_, p) ** 2))(x)
    assert gx.dtype == jnp.bfloat16 and np.isfinite(
        np.asarray(gx, np.float32)).all()


def test_grad_through_kernel():
    """``jax.grad`` through the fused block (custom_vjp with
    reference-recompute backward) matches the reference path's gradients
    for both the input and every parameter leaf."""
    p = _make_params(jax.random.PRNGKey(12), 16, 32, proj=True)
    x = jax.random.normal(jax.random.PRNGKey(13), (1, 8, 8, 16))

    def loss(fn):
        return lambda x_, p_: jnp.sum(
            fn(x_, p_, strides=2, groups=8) ** 2)

    gx_f, gp_f = jax.grad(loss(fused_block), argnums=(0, 1))(x, p)
    gx_r, gp_r = jax.grad(loss(reference_block), argnums=(0, 1))(x, p)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-4)
    for k in gp_r:
        np.testing.assert_allclose(np.asarray(gp_f[k]),
                                   np.asarray(gp_r[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


def test_grad_under_jit_and_scan():
    """The engine wraps the model in jit(scan(...)) — the custom_vjp must
    survive that composition."""
    p = _make_params(jax.random.PRNGKey(14), 16, 16, proj=False)
    x = jax.random.normal(jax.random.PRNGKey(15), (3, 2, 8, 8, 16))

    @jax.jit
    def total(p_):
        def body(c, xb):
            g = jax.grad(
                lambda pp: jnp.sum(fused_block(xb, pp) ** 2))(p_)
            return c + g["w1"].sum(), None
        out, _ = jax.lax.scan(body, jnp.float32(0), x)
        return out

    assert np.isfinite(float(total(p)))


def test_reference_block_matches_flax_bitwise():
    """The XLA reference path is the golden: on params extracted from the
    unfused flax module it must reproduce flax bit-for-bit (same conv
    primitive, same one-pass f32 GroupNorm formula, same op order)."""
    for filters, strides, cin in ((16, 1, 16), (32, 2, 16)):
        m = BasicBlock(filters, strides)
        x = jax.random.normal(jax.random.PRNGKey(16), (3, 8, 8, cin))
        variables = m.init(jax.random.PRNGKey(17), x)
        out_flax = m.apply(variables, x)
        out_ref = reference_block(x, _flax_to_dict(variables),
                                  strides=strides,
                                  groups=min(8, filters))
        assert np.array_equal(np.asarray(out_flax), np.asarray(out_ref))


def test_fused_module_init_tree_bit_identical():
    """``fused`` modes declare params through explicitly-named child
    scopes (Conv_0/GroupNorm_0/...), so the init tree — names AND values
    — is bit-identical to the unfused module's: checkpoints and the
    engine's flat-vector machinery are mode-agnostic."""
    x = jnp.zeros((1, 8, 8, 16))
    base = BasicBlock(32, strides=2).init(jax.random.PRNGKey(18), x)
    for mode in ("pallas", "reference"):
        fused = BasicBlock(32, strides=2, fused=mode).init(
            jax.random.PRNGKey(18), x)
        flat_b = jax.tree_util.tree_leaves_with_path(base)
        flat_f = jax.tree_util.tree_leaves_with_path(fused)
        assert [p for p, _ in flat_b] == [p for p, _ in flat_f]
        for (pb, lb), (_, lf) in zip(flat_b, flat_f):
            assert np.array_equal(np.asarray(lb), np.asarray(lf)), pb


@pytest.mark.parametrize(
    "mode",
    ["reference",
     # the pallas whole-model pass re-runs the interpret-mode kernel 9
     # blocks deep (~12 s on a 1-core CPU) and its numerics are already
     # tier-1-covered per block; keep whole-model wiring in tier-1 via
     # the reference mode and gate the pallas repeat behind slow
     pytest.param("pallas", marks=pytest.mark.slow)])
def test_resnet20_model_parity(mode):
    """Whole-model parity: resnet20 with every narrow block fused vs the
    unfused flax path, same init tree, same logits within f32 tolerance."""
    base = create_resnet("resnet20", 10)
    fused = create_resnet("resnet20", 10, fused=mode)
    x = jax.random.normal(jax.random.PRNGKey(19), (1, 8, 8, 3))
    vb = base.init(jax.random.PRNGKey(20), x, train=False)
    vf = fused.init(jax.random.PRNGKey(20), x, train=False)
    for (pb, lb), (_, lf) in zip(
            jax.tree_util.tree_leaves_with_path(vb),
            jax.tree_util.tree_leaves_with_path(vf)):
        assert np.array_equal(np.asarray(lb), np.asarray(lf)), pb
    out_b = base.apply(vb, x, train=False)
    out_f = fused.apply(vf, x, train=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_b),
                               rtol=1e-4, atol=1e-4)


def test_model_hub_knob_threading():
    """``fused_conv_block`` reaches the resnet factory through
    ``model.create`` and an off/absent knob keeps the original module."""
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.model import create

    def bundle(**kw):
        return create(Arguments(dataset="cifar10", model="resnet20",
                                allow_synthetic=True, **kw), 10)

    assert bundle().module.fused == ""
    assert bundle(fused_conv_block=False).module.fused == ""
    assert bundle(fused_conv_block=True).module.fused == "pallas"
    assert bundle(fused_conv_block="reference").module.fused == "reference"
    with pytest.raises(ValueError):
        bundle(fused_conv_block="mystery")


def test_wide_blocks_stay_unfused():
    """Blocks wider than MAX_FUSED_CHANNELS (ResNet-18's 128-512 channel
    stages) keep the flax path even with the knob on — the narrow-stage
    kernel must not be asked to hold ImageNet activations in VMEM. Since
    the width gate routes to the IDENTICAL flax code, the output must be
    bit-equal, not merely close."""
    from fedml_tpu.core.kernels.conv_block import MAX_FUSED_CHANNELS

    wide = MAX_FUSED_CHANNELS * 2
    x = jax.random.normal(jax.random.PRNGKey(21), (2, 4, 4, wide))
    base = BasicBlock(wide, strides=1)
    m = BasicBlock(wide, strides=1, fused="pallas")
    v = base.init(jax.random.PRNGKey(22), x)
    assert np.array_equal(np.asarray(m.apply(v, x)),
                          np.asarray(base.apply(v, x)))
