"""FedLLM — the LLM fine-tuning pillar (reference ``train/llm/`` +
``spotlight_prj/unitedllm/``), rebuilt TPU-first:

- ``model``: flax decoder (RMSNorm/rotary/SwiGLU) with grouped-query,
  latent or linear (Kimi delta) attention, one kind a layer, and dense or
  sparse-expert layers, or a stack of single-mixer layers (Mamba-2
  state-space mixers, experts in a latent, attention without positions),
  bf16 compute, MXU-shaped matmuls.
- ``moe``: routing over all experts (plain or group-limited with a
  score-correction bias), the dropless plan for the experts a rank holds,
  the Pallas grouped product over them (SwiGLU or non-gated experts).
- ``linear_attention``: the chunked gated delta rule, forward and the
  backward of its scan, as Pallas kernels and as ``jax.numpy``.
- ``state_space``: Mamba-2's recurrence (SSD) in chunks, likewise.
- ``attention``: dense golden + Pallas flash kernels (``d_qk != d_v``
  too) + ring attention over the ``sp`` mesh axis for long context.
- ``lora``: adapters as a pure pytree transform; federated rounds ship
  adapters only.
- ``sharding``: FSDP/TP partition specs (XLA-FSDP, the DeepSpeed ZeRO
  analogue) + sequence-parallel forward.
- ``trainer``: completion-only causal-LM TrainerSpec that composes with the
  whole algorithm frame.
- ``federated``: ``build_llm`` / ``run_federated_llm`` — UnitedLLM parity.
- ``hf``: local HF/Llama torch-checkpoint import.
"""

from .model import CausalLM, LLMConfig, init_llm
from .lora import lora_init, lora_merge, lora_param_count
from .trainer import CausalLMTrainer
from .federated import (LLMBundle, build_llm, llm_config_from_args,
                        llm_config_from_hf, run_federated_llm)

__all__ = [
    "CausalLM", "LLMConfig", "init_llm",
    "lora_init", "lora_merge", "lora_param_count",
    "CausalLMTrainer",
    "LLMBundle", "build_llm", "llm_config_from_args", "llm_config_from_hf",
    "run_federated_llm",
]
