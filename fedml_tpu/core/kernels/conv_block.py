"""Fused conv -> GroupNorm -> residual-add -> ReLU block (Pallas TPU).

A static count of the ResNet-56 round (ISSUE 14/16, before the chip)
had the 16-channel stage memory-bound: every GroupNorm
and residual elementwise op round-trips the full activation through HBM
at AI ~ 0.55-0.60. This kernel keeps the whole ``BasicBlock`` chain —

    conv3x3(s) -> GN -> relu -> conv3x3 -> GN -> (+residual|proj) -> relu

— inside ONE VMEM-resident grid program per batch block, so the
intermediate activations never leave VMEM. Design notes:

* Convolutions are 9 shifted matmuls on the spatially pre-padded input
  (``acc += x_pad[:, dy:dy+H, dx:dx+W, :] @ w[dy, dx]``) — MXU dots with
  ``preferred_element_type=f32``, no conv primitive inside the kernel.
* Stride-2 blocks compute the stride-1 output and keep every second
  position. The halo is padded the way SAME pads a stride-2 window —
  (0, 2) on an even extent, (1, 1) on an odd one — so the kept positions
  are always the EVEN ones, for the 3x3 conv and the 1x1 projection
  alike: Mosaic refuses a reduction over a value whose layout carries the
  sublane offset an odd-position pick leaves behind. Only 2 of
  ResNet-56's 27 blocks are strided, so the extra full-res conv work is
  noise next to the saved elementwise HBM traffic.
* GroupNorm statistics are computed in f32 with the same one-pass
  ``max(0, E[x^2] - E[x]^2)`` formula as flax, per sample per group:
  per-channel sums, then a ``[c, groups]`` membership matmul, so no
  reshape splits the lane (channel) dimension — Mosaic has no layout for
  that shape cast.
* Interpreted off-TPU (``core.kernels.interpret``) so the tier-1 parity
  tests run on CPU; ``tests/test_chip_compile.py`` AOT-compiles every
  ResNet-56 stage geometry for the v5e.
* The backward pass is a ``custom_vjp`` that RECOMPUTES the block via
  ``jax.vjp`` of :func:`reference_block` — residual-recompute semantics:
  no intermediate activations are saved, and gradients are exactly the
  reference path's gradients.

Channel widths here are narrow (16-64 lanes of the 128-lane VPU);
``model/cv/resnet.py`` only routes blocks with <= 64 filters to this
kernel — wide ImageNet stages already saturate the MXU through XLA.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret, tpu_compiler_params

#: flax GroupNorm default epsilon — the unfused path's value
GN_EPS = 1e-6

#: largest channel width routed to the fused kernel (narrow stages only)
MAX_FUSED_CHANNELS = 64

#: batch rows per grid program; at the flagship 32x32x16 geometry this
#: keeps the f32 working set (padded input + two activations) ~1.5 MiB,
#: comfortably inside the ~16 MiB/core VMEM budget
DEFAULT_BLOCK_N = 8

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# XLA reference path — the numerical golden, and the backward recompute.


def _conv_same(x, w, strides: int):
    dt = jnp.promote_types(x.dtype, w.dtype)
    return jax.lax.conv_general_dilated(
        x.astype(dt), w.astype(dt), window_strides=(strides, strides),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, scale, bias, groups: int, eps: float):
    """flax GroupNorm semantics: f32 one-pass stats per (sample, group),
    normalized output scaled/shifted and cast back to the input dtype."""
    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, groups, c // groups).astype(jnp.float32)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    mean2 = jnp.mean(jax.lax.square(xg), axis=(1, 2, 4), keepdims=True)
    var = jnp.maximum(mean2 - jax.lax.square(mean), 0.0)
    y = (xg - mean) * jax.lax.rsqrt(var + eps)
    y = y.reshape(n, h, w, c)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    out_dt = jnp.promote_types(x.dtype, scale.dtype)
    return y.astype(out_dt)


def reference_block(x, params: Params, *, strides: int = 1, groups: int = 8,
                    eps: float = GN_EPS):
    """Pure-XLA BasicBlock math on an explicit param dict — mirrors
    ``model/cv/resnet.py:BasicBlock`` (and is parity-tested against it).

    ``params``: ``w1``/``w2`` [3,3,cin,c]/[3,3,c,c] conv kernels,
    ``g1_*``/``g2_*`` GroupNorm scale/bias [c]; a strided or
    channel-changing block adds the 1x1 projection ``wp`` + ``gp_*``.
    """
    y = _conv_same(x, params["w1"], strides)
    y = _group_norm(y, params["g1_scale"], params["g1_bias"], groups, eps)
    y = jax.nn.relu(y)
    y = _conv_same(y, params["w2"], 1)
    y = _group_norm(y, params["g2_scale"], params["g2_bias"], groups, eps)
    if "wp" in params:
        r = _conv_same(x, params["wp"], strides)
        r = _group_norm(r, params["gp_scale"], params["gp_bias"], groups,
                        eps)
    else:
        r = x
    return jax.nn.relu(r + y)


# ---------------------------------------------------------------------------
# Pallas kernel.


def _stride2_pad(extent: int, strides: int):
    """SAME halo of a 3-wide window along one axis: (0, 2) for a stride-2
    window over an even extent, (1, 1) otherwise."""
    return (0, 2) if strides == 2 and extent % 2 == 0 else (1, 1)


def _subsample2(y):
    """Keep the even positions along H and W, via pad+reshape
    (Mosaic-friendly: no strided slicing)."""
    for axis in (1, 2):
        shape = list(y.shape)
        if shape[axis] % 2:
            pads = [(0, 0)] * y.ndim
            pads[axis] = (0, 1)
            y = jnp.pad(y, pads)
            shape[axis] += 1
        new_shape = shape[:axis] + [shape[axis] // 2, 2] + shape[axis + 1:]
        idx = [slice(None)] * (y.ndim + 1)
        idx[axis + 1] = 0
        y = y.reshape(new_shape)[tuple(idx)]
    return y


def _block_kernel(*refs, strides: int, groups: int, eps: float, h: int,
                  w: int, has_proj: bool):
    if has_proj:
        (xp_ref, w1_ref, g1s_ref, g1b_ref, w2_ref, g2s_ref, g2b_ref,
         wp_ref, gps_ref, gpb_ref, o_ref) = refs
    else:
        (xp_ref, w1_ref, g1s_ref, g1b_ref, w2_ref, g2s_ref, g2b_ref,
         o_ref) = refs
    f32 = jnp.float32
    xp = xp_ref[...].astype(f32)                  # [bn, h+2, w+2, cin]
    bn = xp.shape[0]
    ho = -(-h // strides)
    wo = -(-w // strides)
    lo_h = _stride2_pad(h, strides)[0]
    lo_w = _stride2_pad(w, strides)[0]

    def conv3(xpad, w_ref, hh, ww):
        cin = xpad.shape[-1]
        cout = w_ref.shape[-1]
        wk = w_ref[...].astype(f32)
        acc = jnp.zeros((bn * hh * ww, cout), f32)
        for dy in range(3):
            for dx in range(3):
                xs = xpad[:, dy:dy + hh, dx:dx + ww, :]
                acc = acc + jnp.dot(xs.reshape(bn * hh * ww, cin),
                                    wk[dy, dx],
                                    preferred_element_type=f32)
        return acc.reshape(bn, hh, ww, cout)

    def gn(y, s_ref, b_ref):
        _, hh, ww, c = y.shape
        cg = c // groups
        y3 = y.reshape(bn, hh * ww, c)
        ch = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 0)
        gr = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 1)
        member = (ch // cg == gr).astype(f32)         # [c, groups]
        cnt = float(hh * ww * cg)

        def per_channel(stat):  # [bn, c] sums -> group mean, per channel
            g = jnp.dot(stat, member, preferred_element_type=f32) / cnt
            return jnp.dot(g, member.T, preferred_element_type=f32)

        mean = per_channel(jnp.sum(y3, axis=1))
        mean2 = per_channel(jnp.sum(y3 * y3, axis=1))
        var = jnp.maximum(mean2 - mean * mean, 0.0)
        yn = (y3 - mean[:, None, :]) * jax.lax.rsqrt(var + eps)[:, None, :]
        yn = yn * s_ref[...].astype(f32) + b_ref[...].astype(f32)
        return yn.reshape(bn, hh, ww, c)

    y = conv3(xp, w1_ref, h, w)
    if strides == 2:
        y = _subsample2(y)
    y = jnp.maximum(gn(y, g1s_ref, g1b_ref), 0.0)
    yp = jnp.pad(y, ((0, 0), (1, 1), (1, 1), (0, 0)))
    y2 = gn(conv3(yp, w2_ref, ho, wo), g2s_ref, g2b_ref)

    x_core = xp[:, lo_h:lo_h + h, lo_w:lo_w + w, :]
    if has_proj:
        if strides == 2:
            x_core = _subsample2(x_core)
        cin = x_core.shape[-1]
        cout = wp_ref.shape[-1]
        r = jnp.dot(x_core.reshape(bn * ho * wo, cin),
                    wp_ref[...].astype(f32)[0, 0],
                    preferred_element_type=f32).reshape(bn, ho, wo, cout)
        r = gn(r, gps_ref, gpb_ref)
    else:
        r = x_core
    o_ref[...] = jnp.maximum(r + y2, 0.0).astype(o_ref.dtype)


def _pallas_block(x, params: Params, strides: int, groups: int, eps: float,
                  block_n: int = DEFAULT_BLOCK_N):
    n, h, w, cin = x.shape
    cout = params["w1"].shape[-1]
    ho = -(-h // strides)
    wo = -(-w // strides)
    bn = max(1, min(int(block_n), n))
    n_pad = -(-n // bn) * bn
    # host-side spatial pre-pad (SAME halo) + batch pad to the grid
    xp = jnp.pad(x, ((0, n_pad - n), _stride2_pad(h, strides),
                     _stride2_pad(w, strides), (0, 0)))
    has_proj = "wp" in params

    def row2(a):  # [c] GN params as [1, c]: TPU refs want >= 2D
        return a.reshape(1, -1)

    const = lambda blk: pl.BlockSpec(blk, lambda i: (0,) * len(blk))
    inputs = [xp, params["w1"], row2(params["g1_scale"]),
              row2(params["g1_bias"]), params["w2"],
              row2(params["g2_scale"]), row2(params["g2_bias"])]
    in_specs = [pl.BlockSpec((bn, h + 2, w + 2, cin),
                             lambda i: (i, 0, 0, 0)),
                const((3, 3, cin, cout)), const((1, cout)),
                const((1, cout)), const((3, 3, cout, cout)),
                const((1, cout)), const((1, cout))]
    if has_proj:
        inputs += [params["wp"], row2(params["gp_scale"]),
                   row2(params["gp_bias"])]
        in_specs += [const((1, 1, cin, cout)), const((1, cout)),
                     const((1, cout))]
    kernel = functools.partial(
        _block_kernel, strides=strides, groups=groups, eps=eps, h=h, w=w,
        has_proj=has_proj)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, ho, wo, cout), lambda i: (i, 0, 0, 0)),
        # the reference's dtype: bf16 activations against f32 params
        # promote to f32, and the backward recompute expects that cotangent
        out_shape=jax.ShapeDtypeStruct(
            (n_pad, ho, wo, cout),
            jnp.result_type(x.dtype, *(v.dtype for v in params.values()))),
        interpret=interpret(),
        compiler_params=tpu_compiler_params(),
    )(*inputs)
    return out[:n] if n_pad != n else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused(x, params, strides, groups, eps):
    return _pallas_block(x, params, strides, groups, eps)


def _fused_fwd(x, params, strides, groups, eps):
    return _pallas_block(x, params, strides, groups, eps), (x, params)


def _fused_bwd(strides, groups, eps, res, g):
    # residual recompute: re-run the XLA reference forward under jax.vjp —
    # nothing from the kernel's VMEM-resident intermediates is saved, and
    # the gradient is exactly the reference path's gradient
    x, params = res
    _, vjp = jax.vjp(
        lambda xx, pp: reference_block(xx, pp, strides=strides,
                                       groups=groups, eps=eps), x, params)
    return vjp(g)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_block(x, params: Params, *, strides: int = 1, groups: int = 8,
                eps: float = GN_EPS):
    """The fused BasicBlock: Pallas forward (interpreted off-TPU),
    reference-recompute backward. Same signature/params as
    :func:`reference_block`; parity within f32 round-off."""
    return _fused(x, params, int(strides), int(groups), float(eps))
