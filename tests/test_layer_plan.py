"""``LLMConfig.layers``: what each layer of a language model is, decided
once. Each benchmark configuration loads to the plan its family's published
keys give, and the field refuses what no layer module builds."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import pytest

from fedml_tpu.llm.federated import llm_config_from_hf
from fedml_tpu.llm.model import LLMConfig, init_llm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def published(name: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,want", [
    # Mistral: every layer grouped-query attention and the dense MLP
    ("mistral7b_v01_l4", ("full+mlp",) * 4),
    # DeepSeek-V3 keys: latent attention, first_k_dense_replace 1
    ("axk1_ep16_l5", ("latent+mlp",) + ("latent+moe",) * 4),
    # layer_group_size 6: KDA but for the sixth layer of each group
    ("ling3flash_ep8_l7", ("linear+mlp",) + ("linear+moe",) * 4
     + ("latent+moe", "linear+moe")),
    # hybrid_layer_pattern and moe_layer_freq
    ("mimo_v2_flash_ep16_l7", ("full+mlp",) + ("window+moe",) * 5
     + ("full+moe",)),
    # hybrid_override_pattern MEMEMEMEM*E: one mixer a layer
    ("nemotron3_super_ep8_l11", ("ssm", "moe") * 4 + ("ssm", "full", "moe")),
    # kda_layers 1-3, 5-7, 9; full_attn_layers 4, 8
    ("kimi_linear_ep8_l9", ("linear+mlp", "linear+moe", "linear+moe",
                            "latent+moe", "linear+moe", "linear+moe",
                            "linear+moe", "latent+moe", "linear+moe")),
])
def test_each_configuration_loads_to_its_published_layer_plan(name, want):
    assert llm_config_from_hf(published(name), max_seq_len=4096).layers == want


@pytest.mark.parametrize("layers", [
    ("full+mlp", "mamba"),          # no such mixer
    ("full+mlp", "full+ssm"),       # ssm is no feed-forward
    ("full+mlp", "full+"),          # a '+' without a feed-forward
    ("full+mlp",),                  # one entry for two layers
])
def test_the_layer_plan_refuses_what_is_not_built(layers):
    with pytest.raises(ValueError, match="one entry for each of the 2"):
        LLMConfig(num_layers=2, layers=layers)
    assert LLMConfig(num_layers=2).plan == ("full+mlp",) * 2


def test_an_empty_plan_follows_num_layers_through_a_replace():
    """``layers`` left empty stays empty, so a replace of ``num_layers``
    (as the four-chip ring phase of ``chip_smoke.py`` makes) builds that
    many default layers."""
    cfg = dataclasses.replace(LLMConfig(num_layers=4), num_layers=1,
                              attention_impl="ring")
    assert cfg.layers == () and cfg.plan == ("full+mlp",)
    _, params = init_llm(dataclasses.replace(cfg, attention_impl="dense"),
                         jax.random.PRNGKey(0))
    assert sorted(k for k in params if k.startswith("layer_")) == ["layer_0"]


@pytest.mark.parametrize("name,over,field,want", [
    # a flag present but null is off, as a missing one takes its default
    ("axk1_ep16_l5", {"norm_topk_prob": None}, "norm_topk_prob", False),
    ("kimi_linear_ep8_l9", {"moe_renormalize": None}, "norm_topk_prob",
     False),
    ("nemotron3_super_ep8_l11", {"use_conv_bias": None}, "ssm_conv_bias",
     False),
    # a size or scale of zero or null is unset: the field's default
    ("nemotron3_super_ep8_l11", {"chunk_size": 0}, "ssm_chunk", 128),
    ("nemotron3_super_ep8_l11", {"conv_kernel": 0}, "ssm_conv_kernel", 4),
    ("axk1_ep16_l5", {"routed_scaling_factor": None},
     "routed_scaling_factor", 1.0),
    # any family's norm epsilon by the first of the three names set
    ("mimo_v2_flash_ep16_l7", {"layer_norm_epsilon": 3e-5}, "rms_eps",
     1e-5),
    ("nemotron3_super_ep8_l11", {"rms_norm_eps": 2e-6}, "rms_eps", 2e-6),
])
def test_null_and_zero_keys_load_as_they_always_did(name, over, field,
                                                     want):
    cfg = llm_config_from_hf(dict(published(name), **over), max_seq_len=4096)
    assert getattr(cfg, field) == want


@pytest.mark.parametrize("name", ["kimi_linear_ep8_l9", "mistral7b_v01_l4",
                                  "nemotron3_super_ep8_l11"])
@pytest.mark.parametrize("over,match", [
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"scoring_func": "softmax", "n_routed_experts": 8}, "scoring_func"),
    ({"expert_swiglu_limit_list": [7.0] * 12}, "clamped SwiGLU"),
])
def test_every_family_passes_the_shared_refusals(name, over, match):
    with pytest.raises(NotImplementedError, match=match):
        llm_config_from_hf(dict(published(name), **over), max_seq_len=4096)
