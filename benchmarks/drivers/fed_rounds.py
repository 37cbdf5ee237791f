"""Driver ``fed_rounds``: one federated round per step through
``TPUSimulator.run_round``, for every configuration that trains in rounds.

The harness makes the data and the weights from the seed and hands them in;
this file builds the simulator through the entry points a user calls and
exposes the three things the harness needs: ``step(r)`` (one round, ended by
the scalar readback ``TPUSimulator.run()`` itself does), ``trainable()`` (a
host copy of the tree the server aggregates) and ``close()``.

A configuration names its builder under ``"builder"``; a later PR adds a
builder by adding a driver file that registers one more, not by editing this.
"""

from __future__ import annotations

import dataclasses

import numpy as np

BUILDERS = {}


def builder(name):
    def register(fn):
        BUILDERS[name] = fn
        return fn
    return register


def _arguments(cfg, traffic, program_seed, **extra):
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    args = Arguments(
        backend="tpu", precision=cfg["compute_dtype"],
        client_num_in_total=traffic["clients_total"],
        client_num_per_round=traffic["clients_per_round"],
        batch_size=traffic["batch_size"], epochs=traffic["local_epochs"],
        learning_rate=traffic["learning_rate"],
        client_optimizer=traffic["client_optimizer"],
        federated_optimizer=traffic["federated_optimizer"],
        comm_round=1_000_000, frequency_of_the_test=0,
        random_seed=program_seed, **extra)
    return fedml_tpu.init(args)


def _federated_dataset(data, num_classes, task):
    """The harness's arrays in the system's own container. ``test`` is one
    batch of client 0: ``run_round`` never evaluates."""
    import jax.numpy as jnp

    from fedml_tpu.core.algframe.types import ClientData
    from fedml_tpu.data.containers import FederatedDataset

    counts = np.asarray(data["num_samples"], np.int64)
    train = ClientData(x=jnp.asarray(data["x"]), y=jnp.asarray(data["y"]),
                       mask=jnp.asarray(data["mask"]),
                       num_samples=jnp.asarray(counts, jnp.float32))
    test = {k: jnp.asarray(data[k][0, :1]) for k in ("x", "y", "mask")}
    return FederatedDataset(
        train=train, test=test, num_classes=num_classes,
        input_shape=tuple(data["x"].shape[3:]), num_clients=len(counts),
        client_num_samples=counts, task=task, provenance="synthetic")


@builder("cifar_resnet")
def build_cifar_resnet(cfg, traffic, program_seed, data, frozen):
    """What ``fedml_tpu.run_simulation`` does up to ``runner.run()``, with
    the harness's data in place of ``data.load``."""
    from fedml_tpu import model as model_mod
    from fedml_tpu.runner import FedMLRunner

    args = _arguments(cfg, traffic, program_seed, dataset="synthetic_cifar10",
                      model=cfg["program_model"])
    fed = _federated_dataset(data, cfg["num_classes"], "classification")
    bundle = model_mod.create(args, cfg["num_classes"])
    return FedMLRunner(args, dataset=fed, model=bundle).runner


@builder("causal_lm_lora")
def build_causal_lm_lora(cfg, traffic, program_seed, data, frozen):
    """``build_llm``'s wiring with a given base in place of ``init_llm``'s
    random one, as a user does who fine-tunes an imported checkpoint
    (``llm/hf.py`` -> ``LLMBundle``)."""
    from fedml_tpu.llm.federated import LLMBundle, llm_config_from_args
    from fedml_tpu.llm.model import CausalLM
    from fedml_tpu.llm.trainer import CausalLMTrainer
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = _arguments(
        cfg, traffic, program_seed, dataset="llm", model="causal_lm",
        llm_vocab_size=cfg["vocab_size"], llm_hidden_size=cfg["hidden_size"],
        llm_intermediate_size=cfg["intermediate_size"],
        llm_num_layers=cfg["num_hidden_layers"],
        llm_num_heads=cfg["num_attention_heads"],
        llm_num_kv_heads=cfg["num_key_value_heads"],
        llm_max_seq_len=traffic["seq_len"], lora_rank=cfg["lora_rank"],
        lora_alpha=cfg["lora_alpha"])
    # the two keys llm_config_from_args cannot carry (PERF.md, open questions)
    llm_cfg = dataclasses.replace(
        llm_config_from_args(args), rms_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])
    if llm_cfg.head_dim != cfg["head_dim"]:
        raise ValueError(f"head_dim {llm_cfg.head_dim} != {cfg['head_dim']}")
    bundle = LLMBundle(CausalLM(llm_cfg), llm_cfg, frozen, cfg["lora_rank"],
                       cfg["lora_alpha"])
    fed = _federated_dataset(data, cfg["vocab_size"], "llm")
    spec = CausalLMTrainer(bundle.apply)
    return TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)


class FedRounds:
    """The simulator with the harness's weights in it."""

    def __init__(self, cfg, traffic, program_seed, data, trainable, frozen):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.core.algframe.types import TrainHyper

        self.sim = BUILDERS[cfg["builder"]](cfg, traffic, program_seed, data,
                                            frozen)
        sim = self.sim
        want = jax.tree_util.tree_structure(sim.params)
        got = jax.tree_util.tree_structure(trainable)
        if want != got:
            raise ValueError(f"the system's trainable tree {want} is not the "
                             f"reference's {got}")
        for a, b in zip(jax.tree_util.tree_leaves(sim.params),
                        jax.tree_util.tree_leaves(trainable)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(f"leaf {a.shape} {a.dtype} is not the "
                                 f"reference's {b.shape} {b.dtype}")
        sim.params = jax.device_put(trainable, sim.repl_sharding)
        # as TPUSimulator.run() builds it
        self.hyper = TrainHyper(
            learning_rate=jnp.float32(sim.args.learning_rate),
            epochs=int(sim.args.epochs))
        self.attention_impl = getattr(getattr(sim.bundle, "cfg", None),
                                      "attention_impl", None)

    def step(self, round_idx):
        """One round -> (loss_sum, count), read back as ``run()`` reads them.
        The two host spans let a traced run say what the host was doing in a
        device-idle gap: scheduling and dispatching, or waiting and reading."""
        import jax
        with jax.profiler.TraceAnnotation("bench.run_round"):
            m = self.sim.run_round(round_idx, self.hyper)
        with jax.profiler.TraceAnnotation("bench.readback"):
            return float(m["loss_sum"]), float(m["count"])

    def trainable(self):
        import jax
        return jax.tree_util.tree_map(np.asarray, self.sim.params)

    def close(self):
        """Drop everything the simulator holds on the device."""
        import jax
        self.sim = None
        jax.clear_caches()


def build(cfg, traffic, program_seed, data, trainable, frozen):
    return FedRounds(cfg, traffic, program_seed, data, trainable, frozen)
