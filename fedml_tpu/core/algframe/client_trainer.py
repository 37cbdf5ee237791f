"""Client-side trainer specs as pure functions.

Parity target: ``ClientTrainer`` ABC (reference
``core/alg_frame/client_trainer.py:10`` — ``get/set_model_params``, ``train``,
``test``) and the default concrete trainers
(``ml/trainer/my_model_trainer_classification.py:14`` train loop :21-77).

A trainer here is a *spec*: ``loss(params, batch, rng) -> (loss, aux)`` and
``eval_stats(params, batch) -> dict of sums``. The local SGD loop itself lives
in ``local_training.py`` and is shared by every federated optimizer; get/set
of model params is replaced by pytrees flowing through function arguments.
The reference's before/after-training attack/DP hooks
(``client_trainer.py:61,80``) map to the engine-level defense -> aggregate ->
DP pipeline in ``simulation/tpu/engine.py`` (built from ``core/security`` and
``core/dp``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

PyTree = Any
Batch = Dict[str, jnp.ndarray]  # {"x", "y", "mask"}


class TrainerSpec:
    """Pure-function trainer: subclass or compose to customize the loss.

    ``apply_fn(params, x, rng=...)`` is the model forward (flax ``apply``).
    ``extra_metrics`` names further scalars a subclass's ``loss`` puts into
    its aux dict beside ``loss_sum`` / ``correct`` / ``count``; local
    training and the round sum them like those, and the TPU engine hands a
    round's sums, once they are on the host, to ``record_round_counters``.
    """

    extra_metrics: Tuple[str, ...] = ()

    def record_round_counters(self, sums: Dict[str, float]) -> None:
        """A finished round's ``extra_metrics`` sums (no-op by default)."""

    def __init__(self, apply_fn: Callable[..., jnp.ndarray]):
        self.apply_fn = apply_fn

    def loss(self, params: PyTree, batch: Batch, rng: jax.Array
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        raise NotImplementedError

    def eval_stats(self, params: PyTree, batch: Batch) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError


class ClassificationTrainer(TrainerSpec):
    """Cross-entropy classification (``ModelTrainerCLS``,
    ``my_model_trainer_classification.py:14``). Masked mean over real samples
    so padded slots contribute nothing."""

    def loss(self, params, batch, rng):
        logits = self.apply_fn(params, batch["x"], rng=rng, train=True)
        labels = batch["y"].astype(jnp.int32)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        mask = batch["mask"].astype(per_ex.dtype)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(per_ex * mask) / denom
        correct = jnp.sum((jnp.argmax(logits, -1) == labels) * mask)
        return loss, {"loss_sum": jnp.sum(per_ex * mask),
                      "correct": correct, "count": jnp.sum(mask)}

    def eval_stats(self, params, batch):
        logits = self.apply_fn(params, batch["x"], train=False)
        labels = batch["y"].astype(jnp.int32)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        mask = batch["mask"].astype(per_ex.dtype)
        correct = jnp.sum((jnp.argmax(logits, -1) == labels) * mask)
        return {"loss_sum": jnp.sum(per_ex * mask), "correct": correct,
                "count": jnp.sum(mask)}


class SequenceTrainer(TrainerSpec):
    """Per-token cross-entropy for next-word-prediction tasks (reference
    ``my_model_trainer_nwp.py``): labels [bs, L], logits [bs, L, V]; the
    per-sample mask broadcasts over tokens."""

    def loss(self, params, batch, rng):
        logits = self.apply_fn(params, batch["x"], rng=rng, train=True)
        labels = batch["y"].astype(jnp.int32)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        mask = batch["mask"].astype(per_tok.dtype)[:, None]  # [bs,1] over [bs,L]
        tok_count = jnp.sum(mask * jnp.ones_like(per_tok))
        denom = jnp.maximum(tok_count, 1.0)
        loss = jnp.sum(per_tok * mask) / denom
        correct = jnp.sum((jnp.argmax(logits, -1) == labels) * mask)
        return loss, {"loss_sum": jnp.sum(per_tok * mask),
                      "correct": correct, "count": tok_count}

    def eval_stats(self, params, batch):
        logits = self.apply_fn(params, batch["x"], train=False)
        labels = batch["y"].astype(jnp.int32)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        mask = batch["mask"].astype(per_tok.dtype)[:, None]
        tok_count = jnp.sum(mask * jnp.ones_like(per_tok))
        correct = jnp.sum((jnp.argmax(logits, -1) == labels) * mask)
        return {"loss_sum": jnp.sum(per_tok * mask), "correct": correct,
                "count": tok_count}


class MultiLabelTrainer(TrainerSpec):
    """Sigmoid-BCE tag prediction (reference
    ``my_model_trainer_tag_prediction.py`` — stackoverflow_lr). ``y`` is a
    multi-hot [bs, n_tags] matrix; accuracy is exact-match-free micro-F1-ish:
    we report per-tag correctness so curves stay informative."""

    def loss(self, params, batch, rng):
        logits = self.apply_fn(params, batch["x"], rng=rng, train=True)
        labels = batch["y"].astype(logits.dtype)
        per_tag = optax.sigmoid_binary_cross_entropy(logits, labels)
        per_ex = jnp.mean(per_tag, axis=-1)
        mask = batch["mask"].astype(per_ex.dtype)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(per_ex * mask) / denom
        pred = (logits > 0).astype(labels.dtype)
        correct = jnp.sum(jnp.mean((pred == labels).astype(jnp.float32), -1)
                          * mask)
        return loss, {"loss_sum": jnp.sum(per_ex * mask),
                      "correct": correct, "count": jnp.sum(mask)}

    def eval_stats(self, params, batch):
        logits = self.apply_fn(params, batch["x"], train=False)
        labels = batch["y"].astype(logits.dtype)
        per_tag = optax.sigmoid_binary_cross_entropy(logits, labels)
        per_ex = jnp.mean(per_tag, axis=-1)
        mask = batch["mask"].astype(per_ex.dtype)
        pred = (logits > 0).astype(labels.dtype)
        correct = jnp.sum(jnp.mean((pred == labels).astype(jnp.float32), -1)
                          * mask)
        return {"loss_sum": jnp.sum(per_ex * mask), "correct": correct,
                "count": jnp.sum(mask)}


class RegressionTrainer(TrainerSpec):
    """MSE regression (covers the reference's tag-prediction style trainers,
    ``my_model_trainer_tag_prediction.py``)."""

    def loss(self, params, batch, rng):
        preds = self.apply_fn(params, batch["x"], rng=rng, train=True)
        labels = batch["y"].astype(preds.dtype)
        if preds.ndim > labels.ndim:
            labels = labels[..., None]
        per_ex = jnp.mean((preds - labels) ** 2, axis=-1)
        mask = batch["mask"].astype(per_ex.dtype)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(per_ex * mask) / denom
        return loss, {"loss_sum": jnp.sum(per_ex * mask),
                      "correct": jnp.zeros(()), "count": jnp.sum(mask)}

    def eval_stats(self, params, batch):
        preds = self.apply_fn(params, batch["x"], train=False)
        labels = batch["y"].astype(preds.dtype)
        if preds.ndim > labels.ndim:
            labels = labels[..., None]
        per_ex = jnp.mean((preds - labels) ** 2, axis=-1)
        mask = batch["mask"].astype(per_ex.dtype)
        return {"loss_sum": jnp.sum(per_ex * mask),
                "correct": jnp.zeros(()), "count": jnp.sum(mask)}


def make_trainer_spec(fed, bundle) -> TrainerSpec:
    """Pick the TrainerSpec from the dataset's declared task (reference
    ``ml/trainer/trainer_creator.py`` chooses per-dataset trainers)."""
    task = getattr(fed, "task", "classification")
    if task == "classification" and fed.train.y.ndim >= 4:
        # caller built the dataset without declaring a task: a trailing axis
        # on y means per-token ints (sequence) or multi-hot floats
        import jax.numpy as _jnp
        task = ("multilabel" if _jnp.issubdtype(fed.train.y.dtype,
                                                _jnp.floating)
                else "sequence")
    if task in ("llm", "causal_lm"):
        from ...llm.trainer import CausalLMTrainer
        return CausalLMTrainer(bundle.apply)
    if task == "sequence":
        return SequenceTrainer(bundle.apply)
    if task == "multilabel":
        return MultiLabelTrainer(bundle.apply)
    if task == "regression":
        return RegressionTrainer(bundle.apply)
    return ClassificationTrainer(bundle.apply)


def make_inner_optimizer(name: str, learning_rate, momentum: float = 0.0,
                         weight_decay: float = 0.0) -> optax.GradientTransformation:
    """The client's inner optimizer (reference: torch SGD/Adam built in the
    trainer, ``my_model_trainer_classification.py:21-40``)."""
    name = (name or "sgd").lower()
    if name == "adamw":
        # adamw handles decoupled decay itself — do not also add_decayed_weights
        return optax.adamw(learning_rate, weight_decay=weight_decay)
    txs = []
    if weight_decay:
        txs.append(optax.add_decayed_weights(weight_decay))
    if name == "sgd":
        txs.append(optax.sgd(learning_rate, momentum=momentum or None))
    elif name == "adam":
        txs.append(optax.adam(learning_rate))
    else:
        raise ValueError(f"unknown client_optimizer {name!r}")
    return optax.chain(*txs)
