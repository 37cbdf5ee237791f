"""Model dispatch: ``fedml_tpu.model.create(args, output_dim)``.

Parity target: ``model/model_hub.py:19-88`` of the reference (dispatch on
``(model, dataset)``). Returns a :class:`ModelBundle` wrapping a flax module
with init/apply closures the algorithm frame consumes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass
class ModelBundle:
    module: nn.Module
    name: str
    _has_dropout: bool = False
    compute_dtype: Any = jnp.float32

    def init(self, rng: jax.Array, sample_input: jnp.ndarray) -> PyTree:
        # jit the init: run eagerly, a deep model's init (MobileNetV3:
        # hundreds of ops) is one small compile and one dispatch per op;
        # jitted it is one program. eval_shape-free — shapes come from
        # the sample input.
        variables = jax.jit(
            lambda r, x: self.module.init(r, x, train=False)
        )(rng, sample_input)
        return variables["params"]

    def apply(self, params: PyTree, x: jnp.ndarray, rng: Optional[jax.Array] = None,
              train: bool = False) -> jnp.ndarray:
        rngs = {"dropout": rng} if (rng is not None and self._has_dropout) else None
        if self.compute_dtype != jnp.float32:
            # Mixed precision, TPU-standard recipe: master params stay f32
            # (the optimizer and the FedAvg psum aggregate in f32); the
            # forward/backward compute path — where the MXU matmuls are —
            # runs in bf16 via a cast at the boundary. Gradients flow back
            # through the cast and land in f32 on the master leaves.
            dt = self.compute_dtype
            params = jax.tree_util.tree_map(
                lambda a: a.astype(dt) if a.dtype == jnp.float32 else a, params)
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(dt)
        out = self.module.apply({"params": params}, x, train=train, rngs=rngs)
        return out.astype(jnp.float32)


def _fused_conv_mode(args) -> str:
    """``fused_conv_block`` knob -> BasicBlock ``fused`` mode. Off (the
    default) keeps the original flax path, bit-compatible with every run
    before the knob existed; true/pallas dispatches the VMEM-resident
    Pallas kernel (interpret mode off-TPU); reference/xla runs the same
    fused math through plain XLA (the kernel's numerical golden)."""
    v = getattr(args, "fused_conv_block", None)
    if v is None or v is False:
        return ""
    s = str(v).lower()
    if s in ("", "false", "0", "no", "none", "off"):
        return ""
    if s in ("true", "1", "yes", "on", "pallas"):
        return "pallas"
    if s in ("reference", "xla"):
        return "reference"
    raise ValueError(
        f"unknown fused_conv_block mode {v!r} (false|true|pallas|reference)")


def _compute_dtype(args):
    p = str(getattr(args, "precision", "float32") or "float32").lower()
    if p in ("bf16", "bfloat16", "mixed", "mixed_bfloat16"):
        return jnp.bfloat16
    if p in ("fp16", "float16", "half"):
        return jnp.float16
    return jnp.float32


def create(args, output_dim: int):
    """Returns a ModelBundle, or a (generator, discriminator) bundle pair
    for model='gan' (consumed by custom FedGAN trainers). ``args.precision``
    (bfloat16/float32) selects the compute dtype of the bundle's apply path."""
    out = _create(args, output_dim)
    dt = _compute_dtype(args)
    if dt != jnp.float32:
        if isinstance(out, tuple):
            out = tuple(dataclasses.replace(b, compute_dtype=dt) for b in out)
        else:
            out = dataclasses.replace(out, compute_dtype=dt)
    return out


def _create(args, output_dim: int):
    name = str(getattr(args, "model", "lr")).lower()
    from .linear import LogisticRegression, MLP
    from .cv.cnn import CNNFemnist, SimpleCNN

    if name in ("lr", "logistic_regression"):
        return ModelBundle(LogisticRegression(output_dim), name)
    if name == "mlp":
        return ModelBundle(MLP(output_dim), name, _has_dropout=True)
    if name in ("cnn", "cnn_dropout", "femnist_cnn"):
        return ModelBundle(CNNFemnist(output_dim), name, _has_dropout=True)
    if name in ("device_cnn", "mobile_cnn"):
        from .cv.cnn import DeviceCNN
        return ModelBundle(DeviceCNN(num_classes=output_dim), name)
    if name in ("simple_cnn", "cifar_cnn"):
        return ModelBundle(SimpleCNN(output_dim), name)
    if name in ("lenet", "lenet5", "mnn_lenet"):
        from .cv.lenet import LeNet5
        return ModelBundle(LeNet5(output_dim), name)
    if name in ("vfl_feature_extractor", "local_model"):
        from .finance import VFLFeatureExtractor
        return ModelBundle(VFLFeatureExtractor(out_dim=output_dim), name)
    if name in ("vfl_classifier", "dense_model"):
        from .finance import VFLClassifier
        return ModelBundle(VFLClassifier(output_dim), name)
    if name in ("lending_club_mlp", "finance_mlp"):
        from .finance import LendingClubMLP
        return ModelBundle(LendingClubMLP(output_dim), name)
    if name.startswith("resnet"):
        from .cv.resnet import create_resnet
        return ModelBundle(
            create_resnet(name, output_dim, fused=_fused_conv_mode(args)),
            name)
    if name in ("rnn", "lstm", "rnn_shakespeare", "stacked_lstm"):
        dataset = str(getattr(args, "dataset", "")).lower()
        if "stackoverflow" in dataset:
            from .nlp.rnn import RNNStackOverflow
            return ModelBundle(RNNStackOverflow(vocab_size=output_dim), name)
        from .nlp.rnn import RNNShakespeare
        return ModelBundle(RNNShakespeare(vocab_size=output_dim), name)
    if name.startswith("mobilenet"):
        from .cv.mobilenet import MobileNetV3Small
        return ModelBundle(MobileNetV3Small(output_dim), name)
    if name.startswith("efficientnet"):
        from .cv.efficientnet import create_efficientnet
        return ModelBundle(create_efficientnet(name, output_dim), name,
                           _has_dropout=True)
    if name.startswith("vgg"):
        from .cv.vgg import create_vgg
        return ModelBundle(create_vgg(name, output_dim), name,
                           _has_dropout=True)
    if name in ("gan", "mnist_gan"):
        from .cv.gan import Discriminator, Generator
        # FedGAN trains (generator, discriminator) pairs; return both
        return (ModelBundle(Generator(), "generator"),
                ModelBundle(Discriminator(), "discriminator"))
    raise ValueError(f"unknown model {name!r}")
