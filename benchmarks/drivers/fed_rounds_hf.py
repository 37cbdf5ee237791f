"""Driver ``fed_rounds_hf``: ``fed_rounds`` for a language model whose
``LLMConfig`` is built from the configuration file's published
``config.json`` keys (``llm_config_from_hf``) rather than from ``llm_*``
arguments: latent attention, sparse experts of which this rank holds some,
a frozen base in the dtype the benchmark made it in.

Importing this file needs the program's part of that support; on a program
without it the import fails at once, before any data or weight is made.
"""

from __future__ import annotations

from harness import manifest

from fedml_tpu.llm.federated import llm_config_from_hf

fed_rounds = manifest.load_module("drivers", "fed_rounds")


@fed_rounds.builder("causal_lm_lora_hf")
def build_causal_lm_lora_hf(cfg, traffic, program_seed, data, frozen):
    """An imported checkpoint's wiring: ``config.json`` -> ``LLMConfig``,
    the given base -> ``LLMBundle`` -> ``CausalLMTrainer`` ->
    ``TPUSimulator``."""
    import jax

    from fedml_tpu.llm.federated import LLMBundle
    from fedml_tpu.llm.model import CausalLM
    from fedml_tpu.llm.trainer import CausalLMTrainer
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = fed_rounds._arguments(
        cfg, traffic, program_seed, dataset="llm", model="causal_lm",
        llm_max_seq_len=traffic["seq_len"], lora_rank=cfg["lora_rank"],
        lora_alpha=cfg["lora_alpha"])
    published = dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"])
    llm_cfg = llm_config_from_hf(
        published, max_seq_len=traffic["seq_len"], dtype=cfg["compute_dtype"],
        attention_impl=("flash" if jax.default_backend() == "tpu"
                        else "dense"),
        first_expert=cfg["first_expert"],
        experts_held=cfg["n_routed_experts"])
    bundle = LLMBundle(CausalLM(llm_cfg), llm_cfg, frozen, cfg["lora_rank"],
                       cfg["lora_alpha"])
    fed = fed_rounds._federated_dataset(data, cfg["vocab_size"], "llm")
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    return TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)


class FedRoundsHF(fed_rounds.FedRounds):
    def step(self, round_idx):
        """``FedRounds.step``; the round's loss is on the host when it
        returns, so the router-load sums of the same program are ready: the
        simulator records them now, as ``TPUSimulator.run()`` does after
        its own readback, and the last round's are not left waiting for a
        next dispatch."""
        out = super().step(round_idx)
        self.sim.flush_program_counters()
        return out


def build(cfg, traffic, program_seed, data, trainable, frozen):
    return FedRoundsHF(cfg, traffic, program_seed, data, trainable, frozen)
