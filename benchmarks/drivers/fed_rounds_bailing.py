"""Driver ``fed_rounds_bailing``: ``fed_rounds_hf`` for a configuration file
that keeps the Bailing hybrid family's published key names (``num_experts``
for the routed experts where the DeepSeek family says ``n_routed_experts``):
the file's ``num_experts`` is what this rank holds, ``published.num_experts``
what the router scores. The builder hands ``fed_rounds_hf``'s a view of the
configuration under the names it reads; the file's own keys stay as
published (``llm_config_from_hf`` reads ``num_shared_experts``,
``score_function`` and the rest itself).

Importing this file needs the program's linear-attention layer; on a
program without it the import fails at once, before any data or weight is
made.
"""

from __future__ import annotations

from harness import manifest

from fedml_tpu.llm.linear_attention import KDA_KERNEL_NAMES  # noqa: F401

hf = manifest.load_module("drivers", "fed_rounds_hf")


@hf.fed_rounds.builder("causal_lm_lora_bailing")
def build_causal_lm_lora_bailing(cfg, traffic, program_seed, data, frozen):
    view = dict(cfg, n_routed_experts=cfg["num_experts"],
                published={"n_routed_experts":
                           cfg["published"]["num_experts"]})
    return hf.build_causal_lm_lora_hf(view, traffic, program_seed, data,
                                      frozen)


def build(cfg, traffic, program_seed, data, trainable, frozen):
    return hf.FedRoundsHF(cfg, traffic, program_seed, data, trainable, frozen)
