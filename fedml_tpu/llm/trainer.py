"""Causal-LM trainer spec — plugs the LLM into the algorithm frame.

Parity target: ``HFTrainer`` (reference ``train/llm/hf_trainer.py:28``) and
the completion-only collator (``modeling_utils.py:28``): per-token
cross-entropy where prompt/padding positions are excluded from the loss.
Here ignored positions are encoded as label ``-1`` inside the standard
``{"x", "y", "mask"}`` batch, so the spec composes with ``run_local_sgd``
and therefore with the whole federated-optimizer zoo, the defense/DP hook
chain, and both simulators — the LLM is not a special case of the runtime,
just another TrainerSpec.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax

from ..core.algframe.client_trainer import TrainerSpec
from ..core.obs.scopes import scope

PyTree = Any


class CausalLMTrainer(TrainerSpec):
    """Next-token CE. Batch: ``x`` [bs, L] int tokens, ``y`` [bs, L] labels
    with ``-1`` = ignore (prompt tokens under completion-only masking,
    right-padding), ``mask`` [bs] per-sample realness.

    ``extra_metrics``: names of further sums the model reports a step
    (``LLMBundle.extra_metrics``: router load of a model with experts,
    passes through linear-attention, window and state-space layers);
    ``apply_fn(..., with_stats=True)`` then returns them beside the logits,
    the training metrics carry them out of the round program, and
    :meth:`record_round_counters` turns a round's sums into ``fed_moe_*``
    ``fed_kda_*``, ``fed_attn_window_*`` and ``fed_ssm_*``."""

    def __init__(self, apply_fn, extra_metrics=()):
        super().__init__(apply_fn)
        self.extra_metrics = tuple(extra_metrics)

    def record_round_counters(self, sums):
        from ..core.obs import metrics as obs_metrics
        if "moe_slots_held" in sums:
            obs_metrics.record_moe_round(
                sums["moe_slots_held"], sums["moe_load_max"],
                sums["moe_layer_steps"], sums["moe_expert_steps"],
                sums["moe_dropped"], sums["moe_compact_steps"],
                sums.get("moe_tokens_here"),
                kept_steps=sums["moe_kept_steps"])
        if "kda_layer_steps" in sums:
            obs_metrics.record_kda_round(sums["kda_layer_steps"])
        if "kda_decays" in sums:
            obs_metrics.record_kda_decays(sums["kda_decays"],
                                          sums["kda_steep_decays"])
        if "attn_window_layer_steps" in sums:
            obs_metrics.record_window_round(sums["attn_window_layer_steps"])
        if "ssm_layer_steps" in sums:
            obs_metrics.record_ssm_round(sums["ssm_layer_steps"])

    def _stats(self, params, batch, rng, train):
        kwargs = {"train": train}
        if rng is not None:
            kwargs["rng"] = rng
        extra = {}
        if train and self.extra_metrics:
            logits, extra = self.apply_fn(params, batch["x"],
                                          with_stats=True, **kwargs)
        else:
            logits = self.apply_fn(params, batch["x"], **kwargs)
        labels = batch["y"].astype(jnp.int32)
        tok_w = ((labels >= 0).astype(jnp.float32)
                 * batch["mask"].astype(jnp.float32)[:, None])
        safe = jnp.maximum(labels, 0)
        with scope("head"):   # the loss over float32 logits is the head's
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits, safe)
            loss_sum = jnp.sum(per_tok * tok_w)
            correct = jnp.sum((jnp.argmax(logits, -1) == safe) * tok_w)
            count = jnp.sum(tok_w)
        return loss_sum, correct, count, extra

    def loss(self, params, batch, rng):
        loss_sum, correct, count, extra = self._stats(params, batch, rng, True)
        loss = loss_sum / jnp.maximum(count, 1.0)
        return loss, dict(extra, loss_sum=loss_sum, correct=correct,
                          count=count)

    def eval_stats(self, params, batch):
        loss_sum, correct, count, _ = self._stats(params, batch, None, False)
        return {"loss_sum": loss_sum, "correct": correct, "count": count}
