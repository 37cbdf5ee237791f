"""Driver ``fed_rounds_mimo``: ``fed_rounds_hf`` for a configuration whose
grouped-query layers are of two kinds, one behind a sliding window with a
learned sink. The configuration file keeps the ``mimo_v2_flash`` key names,
which are the DeepSeek family's where the two share a mechanism
(``n_routed_experts``, ``num_experts_per_tok``, ``topk_method``), so
``fed_rounds_hf``'s builder takes it unchanged and ``llm_config_from_hf``
reads the rest (``hybrid_layer_pattern``, ``sliding_window``, ``swa_*``,
``add_*_attention_sink_bias``, ``moe_layer_freq`` as a list, ...) itself.

Importing this file needs the program's window kernels; on a program
without them the import fails at once, before any data or weight is made.
"""

from __future__ import annotations

from harness import manifest

from fedml_tpu.llm.attention import WINDOW_KERNEL_NAMES  # noqa: F401

hf = manifest.load_module("drivers", "fed_rounds_hf")


def build(cfg, traffic, program_seed, data, trainable, frozen):
    return hf.FedRoundsHF(cfg, traffic, program_seed, data, trainable, frozen)
