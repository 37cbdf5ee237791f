"""CIFAR ResNets (ResNet-20/56, 6n+2 layout) and ResNet-18.

Parity targets: ``model/cv/resnet.py`` (resnet56 for the north-star CIFAR-10
benchmark) and ``model/cv/resnet_gn.py`` of the reference. GroupNorm is the
default normalization — the reference's own federated configs use GN because
BatchNorm statistics break under non-IID client data, and GN keeps the model
a pure function of (params, x), which is what lets a whole FL round jit.

``fused`` routes the narrow (<= 64 channel) BasicBlocks through the Pallas
fused conv->GN->residual->ReLU kernel (``core/kernels/conv_block``, ISSUE
16): ``"pallas"`` dispatches the VMEM-resident kernel (interpret mode off-
TPU), ``"reference"`` the XLA reference math, ``""`` (default) the original
flax path — bit-identical to before the knob existed. All three declare
byte-identical parameter trees (same scope paths, names, initializers), so
checkpoints and the engine's flat-vector defenses are mode-agnostic.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ...core.kernels.conv_block import (MAX_FUSED_CHANNELS, fused_block,
                                        reference_block)
from ...core.obs.scopes import scope


class _ConvKernel(nn.Module):
    """Parameter-only stand-in for ``nn.Conv(use_bias=False)``: declares
    the same ``kernel`` param (name, shape, lecun_normal init) under the
    same scope path, so the fused block's init tree is bit-identical to
    the unfused module's."""
    features: int
    ksize: Tuple[int, int] = (3, 3)

    @nn.compact
    def __call__(self, in_features: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.ksize + (int(in_features), self.features))


class _GroupNormParams(nn.Module):
    """Parameter-only stand-in for ``nn.GroupNorm``: scale (ones) then
    bias (zeros), flax declaration order."""
    features: int

    @nn.compact
    def __call__(self):
        scale = self.param("scale", nn.initializers.ones, (self.features,))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        return scale, bias


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    groups: int = 8
    fused: str = ""  # "" (flax path) | "pallas" | "reference"

    @nn.compact
    def __call__(self, x):
        # narrow stages only: wide ImageNet blocks already saturate the
        # MXU through XLA, and their activations dwarf the VMEM budget
        if self.fused and self.filters <= MAX_FUSED_CHANNELS:
            return self._fused_call(x)
        residual = x
        with scope("cv.conv"):
            y = nn.Conv(self.filters, (3, 3),
                        strides=(self.strides, self.strides),
                        use_bias=False)(x)
        with scope("cv.norm"):
            y = nn.GroupNorm(num_groups=min(self.groups, self.filters))(y)
            y = nn.relu(y)
        with scope("cv.conv"):
            y = nn.Conv(self.filters, (3, 3), use_bias=False)(y)
        with scope("cv.norm"):
            y = nn.GroupNorm(num_groups=min(self.groups, self.filters))(y)
        if residual.shape != y.shape:
            with scope("cv.conv"):
                residual = nn.Conv(self.filters, (1, 1),
                                   strides=(self.strides, self.strides),
                                   use_bias=False)(x)
            with scope("cv.norm"):
                residual = nn.GroupNorm(
                    num_groups=min(self.groups, self.filters))(residual)
        with scope("cv.norm"):
            return nn.relu(residual + y)

    def _fused_call(self, x):
        """One fused kernel per block. The explicit ``name=`` arguments pin
        the child scope paths to exactly what flax auto-naming gives the
        unfused path (Conv_0/GroupNorm_0/.../GroupNorm_2), which is what
        makes the two parameter trees — values included — bit-identical."""
        cin = int(x.shape[-1])
        f = self.filters
        p = {"w1": _ConvKernel(f, name="Conv_0")(cin)}
        p["g1_scale"], p["g1_bias"] = _GroupNormParams(
            f, name="GroupNorm_0")()
        p["w2"] = _ConvKernel(f, name="Conv_1")(f)
        p["g2_scale"], p["g2_bias"] = _GroupNormParams(
            f, name="GroupNorm_1")()
        if self.strides != 1 or cin != f:
            p["wp"] = _ConvKernel(f, ksize=(1, 1), name="Conv_2")(cin)
            p["gp_scale"], p["gp_bias"] = _GroupNormParams(
                f, name="GroupNorm_2")()
        impl = fused_block if self.fused == "pallas" else reference_block
        with scope("cv.conv"):   # one kernel: the convolutions lead it
            return impl(x, p, strides=self.strides,
                        groups=min(self.groups, f))


class CifarResNet(nn.Module):
    """6n+2 ResNet: stages of n blocks at widths 16/32/64."""
    num_classes: int
    blocks_per_stage: int  # n: 3 -> resnet20, 9 -> resnet56
    fused: str = ""

    @nn.compact
    def __call__(self, x, train: bool = False):
        with scope("cv.conv"):
            x = nn.Conv(16, (3, 3), use_bias=False)(x)
        with scope("cv.norm"):
            x = nn.GroupNorm(num_groups=8)(x)
            x = nn.relu(x)
        for stage, filters in enumerate((16, 32, 64)):
            for block in range(self.blocks_per_stage):
                strides = 2 if (stage > 0 and block == 0) else 1
                x = BasicBlock(filters, strides, fused=self.fused)(x)
        with scope("cv.head"):
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(self.num_classes)(x)


class ResNet18(nn.Module):
    """ImageNet-style ResNet-18 (reference ``model/cv/resnet.py`` resnet18)."""
    num_classes: int
    fused: str = ""

    @nn.compact
    def __call__(self, x, train: bool = False):
        small = x.shape[1] <= 64  # CIFAR-style stem for small images
        with scope("cv.conv"):
            if small:
                x = nn.Conv(64, (3, 3), use_bias=False)(x)
            else:
                x = nn.Conv(64, (7, 7), strides=(2, 2), use_bias=False)(x)
        with scope("cv.norm"):
            x = nn.GroupNorm(num_groups=8)(x)
            x = nn.relu(x)
            if not small:
                x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for stage, filters in enumerate((64, 128, 256, 512)):
            for block in range(2):
                strides = 2 if (stage > 0 and block == 0) else 1
                x = BasicBlock(filters, strides, fused=self.fused)(x)
        with scope("cv.head"):
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(self.num_classes)(x)


def create_resnet(name: str, num_classes: int, fused: str = "") -> nn.Module:
    name = name.lower()
    if name in ("resnet20", "resnet20_gn"):
        return CifarResNet(num_classes, blocks_per_stage=3, fused=fused)
    if name in ("resnet56", "resnet56_gn", "resnet"):
        return CifarResNet(num_classes, blocks_per_stage=9, fused=fused)
    if name in ("resnet18", "resnet18_gn"):
        return ResNet18(num_classes, fused=fused)
    raise ValueError(f"unknown resnet variant {name!r}")
