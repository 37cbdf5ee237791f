"""Driver ``fed_rounds_nemotron_h``: ``fed_rounds_hf`` for a configuration of
the ``nemotron_h`` family: a stack of single-mixer layers (Mamba-2
state-space mixers, latent ``relu2`` experts beside a shared one, attention
without positions) chosen by ``hybrid_override_pattern``. The configuration
file keeps the family's published key names, which are the DeepSeek family's
where the two share a mechanism (``n_routed_experts``,
``num_experts_per_tok``, ``routed_scaling_factor``), so ``fed_rounds_hf``'s
builder takes it unchanged and ``llm_config_from_hf`` reads the rest
(``mamba_*``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
``chunk_size``, ``mlp_hidden_act``, ``moe_latent_size``, ...) itself.

Importing this file needs the program's state-space kernels; on a program
without them the import fails at once, before any data or weight is made.
"""

from __future__ import annotations

from harness import manifest

from fedml_tpu.llm.state_space import SSD_KERNEL_NAMES  # noqa: F401

hf = manifest.load_module("drivers", "fed_rounds_hf")


def build(cfg, traffic, program_seed, data, trainable, frozen):
    return hf.FedRoundsHF(cfg, traffic, program_seed, data, trainable, frozen)
