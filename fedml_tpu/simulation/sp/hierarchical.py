"""Hierarchical FL — two-level (edge -> cloud) aggregation.

Parity target: reference ``simulation/sp/hierarchical_fl/`` (``trainer.py:10``
global rounds over groups, ``group.py:7,43`` per-group FedAvg sub-rounds):
clients are partitioned into groups; each global round runs
``group_comm_round`` local FedAvg rounds *within* each group, then averages
the group models — the pattern of cross-silo hierarchical where a silo is a
group. The TPU mapping (SURVEY §2.8) is a two-level psum: ``client`` axis
then ``group`` axis; this engine-agnostic implementation reuses the jitted
per-client local step and keeps both aggregations as weighted tree averages.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.algframe.types import TrainHyper
from ...core.algframe.local_training import evaluate
from ...core.collectives import tree_weighted_average
from ..sampling import client_sampling, sampling_stream_from_args

logger = logging.getLogger(__name__)


# Module-level jitted helpers (NOT methods with a static self: jit's cache
# would strongly retain every simulator instance — dataset and all — for
# process lifetime, and share no compilations between instances).
@jax.jit
def _apply_updates(params, updates, weights):
    """Stack + weighted-average + apply as ONE compiled program: done
    eagerly this is 3 device ops per leaf, each first-seen one its own
    compile — ~450 of them for a deep model (MobileNet: ~150 leaves)."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *updates)
    agg = tree_weighted_average(stacked, jnp.stack(weights))
    return (jax.tree_util.tree_map(jnp.add, params, agg),
            jnp.sum(jnp.stack(weights)))


@jax.jit
def _average_groups(group_params, group_weights):
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *group_params)
    return tree_weighted_average(stacked, group_weights)


class HierarchicalSimulator:
    """``group_num`` edge aggregators, ``group_comm_round`` edge rounds per
    global round."""

    def __init__(self, args, fed_dataset, bundle, optimizer, spec):
        self.args = args
        self.fed = fed_dataset
        self.bundle = bundle
        self.opt = optimizer
        self.spec = spec
        self.group_num = int(getattr(args, "group_num", 2) or 2)
        self.group_comm_round = int(getattr(args, "group_comm_round", 1) or 1)
        self.rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
        init_rng, self.rng = jax.random.split(self.rng)
        self.params = bundle.init(init_rng, fed_dataset.train.x[0, 0])
        self._local_train = jax.jit(self.opt.local_train)
        self._evaluate = jax.jit(lambda p, x, y, m: evaluate(spec, p, x, y, m))
        # static partition of clients into groups (reference partitions by
        # index; group g owns clients g, g+G, g+2G, ...)
        self.groups: List[List[int]] = [
            [c for c in range(fed_dataset.num_clients)
             if c % self.group_num == g]
            for g in range(self.group_num)]
        self.history: List[Dict[str, Any]] = []

    def _train_clients(self, params, client_ids, round_key, hyper):
        updates, weights = [], []
        for cid in client_ids:
            key = jax.random.fold_in(round_key, cid)
            out = self._local_train(params, {}, {},  # stateless optimizers
                                    jax.tree_util.tree_map(
                                        lambda a: a[cid], self.fed.train),
                                    key, hyper)
            updates.append(out.update)
            weights.append(out.weight)
        new_params, total_w = _apply_updates(params, updates, weights)
        return new_params, float(total_w)

    def run(self, comm_round: Optional[int] = None) -> Dict[str, Any]:
        args = self.args
        rounds = comm_round if comm_round is not None else int(args.comm_round)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=int(args.epochs))
        per_round = int(args.client_num_per_round)
        t0 = time.time()
        for round_idx in range(rounds):
            sampled = set(client_sampling(
                round_idx, self.fed.num_clients, per_round,
                random_seed=int(getattr(args, "random_seed", 0) or 0),
                stream=sampling_stream_from_args(args)))
            group_params, group_weights = [], []
            for g, members in enumerate(self.groups):
                active = [c for c in members if c in sampled]
                if not active:
                    continue
                gp = self.params
                gw = 0.0
                for edge_round in range(self.group_comm_round):
                    key = jax.random.fold_in(
                        jax.random.fold_in(self.rng, round_idx),
                        g * 1000 + edge_round)
                    gp, gw = self._train_clients(
                        gp, active, key,
                        hyper.replace(round_idx=jnp.int32(round_idx)))
                group_params.append(gp)
                group_weights.append(gw)
            self.params = _average_groups(
                group_params, jnp.asarray(group_weights, jnp.float32))
            rec: Dict[str, Any] = {"round": round_idx}
            freq = int(getattr(args, "frequency_of_the_test", 5) or 5)
            # freq < 0: never evaluate in-loop (bench timing mode —
            # a per-round full-test eval would pollute round_s)
            if freq > 0 and (round_idx % freq == 0
                             or round_idx == rounds - 1):
                stats = self._evaluate(self.params, self.fed.test["x"],
                                       self.fed.test["y"], self.fed.test["mask"])
                n = max(float(stats["count"]), 1.0)
                rec["test_acc"] = float(stats["correct"]) / n
                logger.info("hierarchical round %d: acc=%.4f", round_idx,
                            rec["test_acc"])
            self.history.append(rec)
        last_eval = next((r for r in reversed(self.history)
                          if "test_acc" in r), {})
        return {"params": self.params, "history": self.history,
                "wall_time_s": time.time() - t0,
                "final_test_acc": last_eval.get("test_acc"),
                "rounds": rounds}
