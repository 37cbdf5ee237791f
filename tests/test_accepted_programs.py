"""The accepted cells' train steps lower to the text they had.

One hash a program: the StableHLO of ``value_and_grad`` of each accepted
language-model cell's LoRA train step at its rehearsal sizes (the
benchmark's reference draws the weights), and ResNet-20's classification
step. A refactor of the model, the loader or the trainer that means to
change no program is checked here on the CPU, hash for hash; a change that
means to alter one of these programs brings its new hash.
"""

from __future__ import annotations

import base64
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.model import CausalLM, LLMConfig
from fedml_tpu.llm.trainer import CausalLMTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(cell_name, dtype, impl, rehearse=True, seq=64):
    """``(cfg, traffic, llm_config, reference module)`` of a cell at its
    rehearsal sizes and sequence ``seq``, or at its own sizes and
    sequence, the published expert count given to the loader."""
    path = list(sys.path)
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        from harness import manifest
        cell = manifest.Cell(cell_name, rehearse=rehearse)
        cfg = dict(cell.config, compute_dtype=dtype)
        ref = manifest.load_module("reference", cell.entry["config"])
    finally:
        sys.path[:] = path
    if not rehearse:
        seq = cell.traffic["seq_len"]
    if cfg.get("model_type") is None:      # Mistral: the plain decoder
        lc = LLMConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"], max_seq_len=seq,
            dtype=dtype, rms_eps=cfg["rms_norm_eps"],
            rope_theta=cfg["rope_theta"], tie_embeddings=False,
            attention_impl=impl)
    else:
        held = "num_experts" if "num_experts" in cfg else "n_routed_experts"
        lc = llm_config_from_hf(
            dict(cfg, **{held: cfg["published"][held]}), max_seq_len=seq,
            dtype=dtype, attention_impl=impl,
            first_expert=cfg["first_expert"], experts_held=cfg[held])
    return cfg, cell.traffic, lc, ref


def _step(cfg, lc):
    """``value_and_grad`` of the cell's LoRA loss toward the adapters, the
    frozen base and the batch its other arguments."""
    def loss(lora, base, batch):
        bundle = LLMBundle(CausalLM(lc), lc, base, cfg["lora_rank"],
                           cfg["lora_alpha"])
        spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
        return spec.loss(lora, batch, None)
    return jax.value_and_grad(loss, has_aux=True)


# sha256 of the StableHLO of ``value_and_grad`` of the LoRA train step of
# the accepted language-model configurations at their rehearsal sizes on
# the ``dense`` path, as commit bbeebdd lowers them with jax 0.9.0; the
# two with an expert layer since their backward pass works from the
# forward's gate and up products (``llm/moe.py``), the Mistral pair
# unmoved by that; the MiMo pair as commit d4c1675 lowers it, and the Ling
# pair with its KDA layers' element-wise work in the fused passes of
# ``llm/linear_attention.py`` (and the backward pass reading each chunk's
# inverse and scores that the forward pass kept, which no other model
# has); the Nemotron pair as commit 882ce0d lowers it: this step takes the
# ``dense`` path, and only ``flash`` runs the fused passes of
# ``llm/state_space.py``; the Kimi pair as commit d86eeb7 lowers it; the
# Keye pair (sparse attention, softmax routing) as the commit that brought
# it lowers it.
_ACCEPTED = {
    ("mistral7b_lora_silo2", "float32"):
        "61f778a13edfff89801bb55b63d5146bd9377814d1580a6d2204001bc7971871",
    ("mistral7b_lora_silo2", "bfloat16"):
        "785c8460622b1c8d8b6f94bede26b1d714202f0d6a9b710e813e9e77f92cbb1c",
    ("axk1_lora_silo2_seq4096", "float32"):
        "8fba67e49ff52964af84c8c8f25e71ba262015141d00f73220a2918458100f6d",
    ("axk1_lora_silo2_seq4096", "bfloat16"):
        "1a7fdcdbcaf7a273b37515bbb23e732915e1e125d84456e6dd45ea225b155c63",
    ("ling3flash_lora_silo2_seq4096", "float32"):
        "6e40313ca5180ff3b167445e682fc3c46fc58479c43fd3c3945f7016fcc8dfb6",
    ("ling3flash_lora_silo2_seq4096", "bfloat16"):
        "57055d8d5ec04bc27c0e30d469392487a16d7a3d9b32c50b8352490d9fb68be2",
    ("mimo_v2_flash_lora_silo2_seq4096", "float32"):
        "1fb34b7547f28910dd41fde348fd94d9c3ff187d7eb14c3596279504ac9107a9",
    ("mimo_v2_flash_lora_silo2_seq4096", "bfloat16"):
        "a795e6669c019f88c0d5cccbc00a80a0aed09a8f20aa787b46b0eb1f0c1d297d",
    ("nemotron3_super_lora_silo2_seq4096", "float32"):
        "a5aee3d8e402b271c0a0fac11bf916cc86f67bd36787a5c13c3301f3f40bc2f4",
    ("nemotron3_super_lora_silo2_seq4096", "bfloat16"):
        "2f680c73b939f1825df338dbacd949b789fba76e285f15b4a862a5fc12198294",
    ("kimi_linear_lora_silo2_seq4096", "float32"):
        "0c30295415f7a69785e4588c76af2b28aae8da4fec9fade162602f8834a41385",
    ("kimi_linear_lora_silo2_seq4096", "bfloat16"):
        "25e3479b46c15960d918e16f7b2a7c3daa1c82c9bc43e306f061b8df3abc9379",
    ("keye_vl2_lora_silo2_seq16384", "float32"):
        "6859fc46f905e5e00537d9f1c2e039cbd5968b4cd64f449db67911fcd0983461",
    ("keye_vl2_lora_silo2_seq16384", "bfloat16"):
        "be4b7162f1e6981ac66869a6a0d461900aa41826b75cb01d7eeac682e25ba01e",
}


@pytest.mark.parametrize("cell_name,dtype", sorted(_ACCEPTED))
def test_the_accepted_small_train_steps_lower_to_the_parents_text(
        cell_name, dtype):
    """A window and a sink are static properties of a layer: a model
    without them traces exactly the program it did."""
    cfg, _, lc, ref = _cell(cell_name, dtype, "dense")
    key = jax.random.PRNGKey(3)
    frozen = ref.init_frozen(jax.random.fold_in(key, 2), cfg)
    lora = ref.init_trainable(jax.random.fold_in(key, 1), cfg)
    bundle = LLMBundle(CausalLM(lc), lc, frozen, cfg["lora_rank"],
                       cfg["lora_alpha"])
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    tok = jax.random.randint(key, (2, 65), 0, cfg["vocab_size"])
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}
    text = jax.jit(jax.value_and_grad(
        lambda p: spec.loss(p, batch, None), has_aux=True)).lower(
        lora).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _ACCEPTED[cell_name, dtype]


def _without_kernel_locations(text):
    """The lowered text with each Mosaic module's bytecode (which carries
    its source file's path and line numbers) printed without locations."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def plain(m):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            return ir.Module.parse(base64.b64decode(m.group(1))).operation \
                .get_asm(enable_debug_info=False)
    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, text)


# the step of each cell as the chip runs it: bfloat16 on the ``flash``
# path at the cell's own sizes, sequence and rows a step, lowered for a
# v5e with its Pallas kernels as Mosaic modules (printed without
# locations), as commit d86eeb7 lowers it (the Keye cell's as the commit
# that brought it lowers it)
_ACCEPTED_FLASH = {
    "mistral7b_lora_silo2":
        "2c908feb7d21f45805a31dba70e716deeaa65086f321b831aed0f99b46f53747",
    "axk1_lora_silo2_seq4096":
        "2b008c51b15d7e407505d65f329fac58febfd45fae374a20728783c24d2d6e04",
    "ling3flash_lora_silo2_seq4096":
        "ede01612288b8b2b1d8ad13345603b972a9453d7fd965082ce73056f7accbe55",
    "mimo_v2_flash_lora_silo2_seq4096":
        "59fdf58ee7653d9b7f345d31c18da119bcbc95b8a17406a0334d6c80f2105535",
    "nemotron3_super_lora_silo2_seq4096":
        "8c4f0426616b5c9afd010f88753fe30582e4f1d833738f119db43ba3f6421adb",
    "kimi_linear_lora_silo2_seq4096":
        "df4f2e9d782ca1e823483f722d6a148e3ca86feb6de3922d9514d2e5108a3ac4",
    "keye_vl2_lora_silo2_seq16384":
        "dd3aef224823149f3dd665a8e4f2027aee5ce1cc70cd1339af355c76e7caaf59",
}


def flash_text(cell_name):
    """The cell's bfloat16 train step on the ``flash`` path at its own
    sizes, lowered for one chip of a ``v5e:2x2`` topology from abstract
    weights and rows, kernel locations stripped."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from fedml_tpu.core import kernels

    cfg, traffic, lc, ref = _cell(cell_name, "bfloat16", "flash",
                                  rehearse=False)
    key = jax.random.PRNGKey(3)
    frozen = jax.eval_shape(lambda k: ref.init_frozen(k, cfg), key)
    lora = jax.eval_shape(lambda k: ref.init_trainable(k, cfg), key)
    rows = (traffic["batch_size"], traffic["seq_len"])
    batch = {"x": jax.ShapeDtypeStruct(rows, jnp.int32),
             "y": jax.ShapeDtypeStruct(rows, jnp.int32),
             "mask": jax.ShapeDtypeStruct(rows[:1], jnp.float32)}
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    s = SingleDeviceSharding(device)
    with kernels.compile_for_tpu():
        text = jax.jit(_step(cfg, lc), in_shardings=s,
                       out_shardings=s).lower(lora, frozen, batch).as_text()
    return _without_kernel_locations(text)


@pytest.mark.pallas
@pytest.mark.parametrize("cell_name", sorted(_ACCEPTED_FLASH))
def test_the_accepted_flash_train_steps_lower_to_the_parents_text(cell_name):
    """The chip's path: the kernels and everything around them as the
    program the chip runs, at a sequence the kernels take."""
    assert hashlib.sha256(flash_text(cell_name).encode()).hexdigest() == \
        _ACCEPTED_FLASH[cell_name]


@pytest.mark.parametrize("precision,want", [
    ("float32",
     "07c58a0749289bfb16ceb196180f22b6596a6f259e820549677be5e4578722e1"),
    ("bfloat16",
     "21967ba8f2e5b8fb6be19f1550d0f1d2eb7f414aa93dc8a6180aa9aac867504c")])
def test_the_small_resnet_train_step_lowers_to_the_parents_text(
        precision, want):
    """The other half of the accepted cells: ResNet-20's classification
    step at batch 8, as commit d4c1675 lowers it."""
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.model import create

    bundle = create(Arguments(model="resnet20", precision=precision), 10)
    x = jnp.zeros((8, 32, 32, 3), jnp.float32)
    params = bundle.init(jax.random.PRNGKey(0), x)
    spec = ClassificationTrainer(bundle.apply)
    batch = {"x": x, "y": jnp.zeros((8,), jnp.int32), "mask": jnp.ones((8,))}
    text = jax.jit(jax.value_and_grad(
        lambda p: spec.loss(p, batch, None), has_aux=True)).lower(
        params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want
