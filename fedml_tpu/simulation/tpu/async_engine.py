"""Buffered-async federated rounds on the TPU mesh simulator.

``round_mode: async_buffered`` removes the round barrier: the server pours
a staleness-weighted buffer of K client updates whenever the K-th arrives
(FedBuff, Nguyen et al. AISTATS 2022; decay families from FedAsync, Xie et
al. 2019), so one slow or dead client caps nothing — it is down-weighted
when it finally lands and redeemed back into the rotation, never waited on.

How the async world maps onto a synchronous mesh:

* **Arrival time is simulated.** Clients get seeded heterogeneous base
  durations (``core/async_rounds/arrivals.py``); the chaos plan is the
  adversary — a straggler does full work slowly (duration / work fraction)
  and a dropped client never delivers, rejoining the idle pool after its
  duration (the redemption event). A virtual clock + event heap orders
  arrivals; everything is a pure function of the seeds, so runs (and
  crash-resumes) replay identical pours.

* **Device work stays one-dispatch-per-pour.** Each pour is ONE jitted
  ``shard_map`` program that simultaneously (a) aggregates the poured
  buffer — a ``[K, D]`` matrix of staleness-tagged update vectors, weights
  and staleness decay riding as DATA — through the staleness-corrected
  server transform (``FedOptimizer.server_update_async``), and (b) trains
  the re-dispatched cohort on the PRE-POUR params. The two subgraphs share
  only that stale input, so XLA overlaps training of cohort N+1 with
  aggregation of cohort N — the double-buffered dispatch: two model slots
  (the donated pre-pour params in, the post-pour params out), and the
  program compiles exactly once (schedules pad to one canonical width, all
  staleness math is data). The pour programs (``async_pour`` /
  ``async_pour_defended``) go through the inherited ``_traced`` seam,
  so a pour that recompiles names the shape that moved
  (``core/obs/recompile``).

* **A client trains on the model it was handed.** Its update is computed
  at dispatch (mathematically identical to computing it at arrival, since
  the base is fixed then) but enters the buffer only when the virtual
  clock says it arrived — staleness is the honest per-update count of
  pours that happened in between.

Buffered rows are replicated ``[K, D]`` f32 vectors (update ‖ extras), so
SCAFFOLD's control variates ride the buffer next to the model delta; for
LLM-scale models a feature-sharded buffer is the known follow-up.

**Defended pours** (ISSUE 7): attacks/defenses compose with the buffer.
A robust defense compares update vectors, but buffered updates were
trained from DIFFERENT model versions — their deltas are not comparable
until every row is re-based onto the current version. The engine keeps a
fixed-size per-version base-delta ring on device (slot ``v mod R`` holds
the server movement ``params_{v+1} − params_v``; the async cross-silo
server's base ring is the host-side template): at pour time each row is
corrected by the accumulated movement it missed (``Δ − (params_v −
params_{v−s})``, a DATA-driven masked sum over the ring — never a
recompile), the chaos model-attack injects on the re-based shards as the
in-program adversary, and the row flows through the same feature-sharded
defense kernels as the sync fused path with the staleness decay folded
into the defense's row weights and a ``[K]`` validity mask covering
partial pours. At staleness 0 the correction is exactly zero, so a
defended pour is bit-identical to the sync defended round — the parity
anchor the tests pin. Stateful defenses keep their device-resident state
pytree, which joins the async checkpoint so crash-resume replays
identical verdicts; verdicts feed the PR 5 reputation store, and the
arrival rotation stops re-dispatching benched byzantine clients.
"""

from __future__ import annotations

import heapq
import logging
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...constants import AXIS_CLIENT
from ...core import mlops
from ...core.obs import metrics as obs_metrics
from ...core.obs import trace as obs_trace
from ...core.async_rounds import (adaptive_staleness_cap, buffer_k_from_args,
                                  durations_from_args, faulted_duration,
                                  make_staleness_fn, merge_alpha_from_args,
                                  pour_weights, staleness_cap_from_args,
                                  UpdateBuffer, weighting_knobs_from_args)
from ...core.algframe.types import TrainHyper
from ...core.chaos import ChaosCrash
from ...core.collectives import psum_tree, vector_to_tree_like
from ...core.security.defense import sharded as sharded_defense
from ...core.selection import slot_placement
from ..sampling import build_schedule
from .engine import ATTACK_FOLD, DEFENSE_FOLD, TPUSimulator

logger = logging.getLogger(__name__)

_ARRIVE = 0
_REDEEM = 1

# domain-separation tag for the idle-pool rotation order (distinct from
# the chaos and duration tags)
_ROTATION_TAG = 1013


class AsyncBufferedSimulator(TPUSimulator):
    """TPU engine in ``round_mode: async_buffered``. ``comm_round`` counts
    POURS (global model versions), the async analog of rounds."""

    def __init__(self, args, fed_dataset, bundle, optimizer, spec,
                 mesh=None, server_aggregator=None):
        super().__init__(args, fed_dataset, bundle, optimizer, spec,
                         mesh=mesh, server_aggregator=server_aggregator)
        # --- config guards: fail loudly, never silently degrade ----------
        if self.contribution.enabled or self.server_aggregator is not None:
            raise ValueError(
                "round_mode: async_buffered composes with attacks/defenses "
                "(defended pours re-base the buffer onto the current "
                "version), but not yet with contribution assessment or "
                "user ServerAggregators — both consume a same-version "
                "host-ordered update matrix; use round_mode: sync")
        if self.dp.is_dp_enabled():
            raise ValueError(
                "round_mode: async_buffered does not yet compose with DP "
                "(per-pour accounting under stale mixed cohorts is an open "
                "design); use round_mode: sync with DP")
        # defended pours: attack/defense ride the compile-once pour
        # program (re-base -> in-program attack -> sharded defense)
        self._defended = (self.defender.is_defense_enabled()
                          or self.attacker.is_model_attack())
        if self.defender.is_defense_enabled():
            if self.defender.defense_type in ("weak_dp", "crfl"):
                raise ValueError(
                    "round_mode: async_buffered refuses defense_type "
                    f"{self.defender.defense_type!r}: noise-adding "
                    "defenses are DP by another name, and per-pour noise "
                    "accounting over a mixed-staleness buffer is the same "
                    "open design that keeps async+DP refused; use "
                    "round_mode: sync")
            if not self._use_sharded_defense():
                raise ValueError(
                    "round_mode: async_buffered runs the defense INSIDE "
                    "the compile-once pour program and needs the sharded "
                    "defense path; sharded_defense: false configs must "
                    "use round_mode: sync")
            pref = str(getattr(args, "robust_fused", "auto")
                       or "auto").lower()
            if pref in ("false", "0", "no", "host"):
                raise ValueError(
                    "robust_fused: host has no meaning under round_mode: "
                    "async_buffered — the defended pour is one fused "
                    "program by construction; use robust_fused: auto")
        if self.selection.adaptive:
            # no per-round cohort to over-sample: the in-flight
            # concurrency is fixed and dropped arrivals are redeemed by
            # the rotation — pin rather than refuse, loudly
            self.selection.pin_adaptive(
                "async_buffered has no per-round cohort to over-sample "
                "(fixed in-flight concurrency; drops redeem via the "
                "rotation)")
        self.concurrency = min(int(args.client_num_per_round),
                               int(fed_dataset.num_clients))
        self.k = buffer_k_from_args(args, self.concurrency)
        self.merge_alpha = merge_alpha_from_args(args)
        (self._weighting_kind, self._poly_a,
         self._hinge_b) = weighting_knobs_from_args(args)
        self._cap_adaptive = int(getattr(args, "async_staleness_cap", 16)
                                 or 0) == 0
        self.staleness_cap = staleness_cap_from_args(args)
        # validate the weighting knobs NOW, not at the first pour
        make_staleness_fn(self._weighting_kind, self._poly_a, self._hinge_b,
                          self.staleness_cap)
        self.buffer = UpdateBuffer(self.k)
        self.durations = durations_from_args(fed_dataset.num_clients, args)
        self._n_k = np.asarray(fed_dataset.train.num_samples, np.float64)

        # flattened-row geometry: update vector ‖ extras vector
        extras_zero = self.opt.server_extras_zero(self.params)
        self._extras_d = int(sum(int(np.prod(l.shape)) for l in
                                 jax.tree_util.tree_leaves(extras_zero)))
        self._row_d = self._true_d + self._extras_d

        if self._defended:
            # _check_extras_compat (base __init__) already refuses
            # extras-carrying optimizers in robust mode, so a defended
            # buffer row is exactly the [true_d] model delta
            # per-version base-delta ring: slot (v mod R) holds the
            # server movement params_{v+1} - params_v as a replicated
            # device row; R covers the staleness cap (the adaptive cap
            # can grow to its 64 ceiling, so adaptive runs size for it).
            # Staleness beyond the ring re-bases over the retained
            # movement only — the weight is saturated anyway (logged
            # once, mirroring the cross-silo base ring's fallback).
            self._ring_r = int(np.clip(
                64 if self._cap_adaptive else self.staleness_cap, 1, 64))
            self._ring = jax.device_put(
                jnp.zeros((self._ring_r, self._true_d), jnp.float32),
                self.repl_sharding)
            self._ring_fallback_logged = False

        # virtual clock + event heap: (t, seq, kind, cid, version, weight,
        # duration, vec) — vec is the client's device-resident [row_d]
        # update row for arrivals, None for redemption events; seq is
        # unique, so tuple ordering never compares the trailing array
        self.version = 0
        self.virtual_t = 0.0
        self.updates_aggregated = 0
        self._dispatch_seq = 0
        self._evseq = 0
        self._events: List[Any] = []
        self._pour_interval_ema: Optional[float] = None
        self._last_pour_t = 0.0
        # per-client observed arrival latency EMA (simulated seconds) —
        # the arrival-rate signal behind the adaptive staleness cap
        self._lat_ema = np.zeros(fed_dataset.num_clients, np.float64)
        self._lat_seen = np.zeros(fed_dataset.num_clients, np.float64)
        # running aggregates over the seen-clients' EMAs, maintained
        # incrementally so the per-arrival rate gauge costs O(1), not a
        # full-population mean in the event-heap hot loop
        self._lat_ema_sum = 0.0
        self._lat_seen_n = 0
        self._last_arrival_t = np.full(fed_dataset.num_clients, -1.0,
                                       np.float64)
        # idle rotation: seeded permutation so dispatch order respects
        # random_seed via the same (seed, tag) stream discipline
        order = np.random.default_rng(
            (int(getattr(args, "random_seed", 0) or 0),
             _ROTATION_TAG)).permutation(fed_dataset.num_clients)
        self._idle = deque(int(c) for c in order)
        self._bootstrapped = False

        self._async_width = min(self.cpd, self.concurrency)
        self._pour_fn = self._build_async_pour_fn()
        self._row_fn = jax.jit(lambda m, i: m[i])
        self._stack_fn = jax.jit(lambda vs: jnp.stack(vs))
        self._zero_row = jnp.zeros((self._row_d,), jnp.float32)

    # ------------------------------------------------------------------
    def _build_async_pour_fn(self):
        """The ONE async program: pour the buffer through the staleness-
        corrected server transform while training the freshly-dispatched
        cohort on the pre-pour params (independent subgraphs — XLA
        overlaps them; two donated model slots). In defended mode the
        pour half additionally re-bases every buffered row onto the
        current version (base-delta ring, DATA masks), injects the
        on-device model attack, and runs the feature-sharded defense —
        still one program, still compiled exactly once."""
        emit_extras = self._extras_d > 0
        collect = self._make_collect_core(emit_extras_stack=emit_extras)
        opt = self.opt
        true_d = self._true_d
        extras_zero = opt.server_extras_zero(self.params)
        n_total = float(max(self.fed.num_clients, 1))

        def train_rows(params, server_state, local_data, local_states,
                       sched_idx, sched_active, sched_work, round_key,
                       hyper):
            """The training half shared by both pour flavors: slot-scan
            the dispatched cohort, then gather the [S, ...] local stacks
            into the replicated [n_dev*S, row_d] dispatch matrix (row =
            d*S+s, the _robust_rows convention)."""
            sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            res = collect(params, server_state, sq(local_data),
                          sq(local_states), sched_idx[0], sched_active[0],
                          sched_work[0], round_key, hyper)
            (upd_stack, w_stack, states, acc_ex, acc_w, acc_m,
             slot_mets) = res[:7]
            leaves = jax.tree_util.tree_leaves(upd_stack)
            n_slots = leaves[0].shape[0]
            parts = [jnp.reshape(l, (n_slots, -1)).astype(jnp.float32)
                     for l in leaves]
            if emit_extras:
                parts += [jnp.reshape(l, (n_slots, -1)).astype(jnp.float32)
                          for l in jax.tree_util.tree_leaves(res[7])]
            rows_mat = jax.lax.all_gather(
                jnp.concatenate(parts, axis=1), AXIS_CLIENT, axis=0,
                tiled=True)
            metrics = psum_tree(acc_m)
            states = jax.tree_util.tree_map(lambda a: a[None], states)
            slot_mets = jax.tree_util.tree_map(lambda a: a[None], slot_mets)
            return rows_mat, states, metrics, slot_mets

        if self._defended:
            return self._build_defended_pour_fn(train_rows, opt, true_d,
                                                n_total)

        def pour_body(params, server_state, local_data, local_states,
                      sched_idx, sched_active, sched_work,
                      buf_mat, buf_nw, merge_scale, pour_n,
                      round_key, hyper):
            rows_mat, states, metrics, slot_mets = train_rows(
                params, server_state, local_data, local_states,
                sched_idx, sched_active, sched_work, round_key, hyper)
            # the pour: buf_nw is the padded [K] relative mix and
            # merge_scale the absolute damping, BOTH computed host-side by
            # core/async_rounds.pour_weights (the one staleness
            # implementation) and riding as DATA; pour_n (the actual
            # poured count — partial pours under heavy dropout pour fewer
            # than K) sizes the population fraction SCAFFOLD's control
            # variate advances by
            agg_vec = jnp.einsum("k,kd->d", buf_nw, buf_mat)
            agg_update = vector_to_tree_like(agg_vec[:true_d], params)
            agg_extras = (vector_to_tree_like(agg_vec[true_d:], extras_zero)
                          if emit_extras else {})
            upd_params, upd_sstate = opt.server_update_async(
                params, server_state, agg_update, agg_extras,
                hyper.round_idx, merge_scale, pour_n / n_total)
            # a no-op pour (bootstrap, drained-heap retry) must leave the
            # SERVER STATE untouched too: merge_scale=0 already pins the
            # params, but FedOpt's adam/yogi would still advance its step
            # count and decay its moments on a zero pseudo-gradient
            poured = pour_n > 0
            new_params = jax.tree_util.tree_map(
                lambda n, o: jnp.where(poured, n, o), upd_params, params)
            new_sstate = jax.tree_util.tree_map(
                lambda n, o: jnp.where(poured, n, o), upd_sstate,
                server_state)
            return (new_params, new_sstate, states, rows_mat, metrics,
                    slot_mets)

        shard_fn = jax.shard_map(
            pour_body,
            mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS_CLIENT), P(AXIS_CLIENT),
                      P(AXIS_CLIENT), P(AXIS_CLIENT), P(AXIS_CLIENT),
                      P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(AXIS_CLIENT), P(), P(), P(AXIS_CLIENT)),
            check_vma=False,
        )
        return jax.jit(shard_fn, donate_argnums=self._donate_args(0, 1, 3))

    def _build_defended_pour_fn(self, train_rows, opt, true_d,
                                n_total: float):
        """The defended pour flavor: re-base the buffer onto the current
        version via the base-delta ring (DATA masks — staleness never
        recompiles), inject the on-device model attack on the re-based
        feature shards, run the sharded defense with the staleness decay
        already folded into ``buf_nw`` and a [K] validity mask for
        partial pours, then apply the defended aggregate through the
        staleness-corrected server transform. Also maintains the ring
        (this pour's server movement lands in slot ``version mod R``) and
        emits the defense's [K] verdict for the reputation store."""
        defense_type = (self.defender.defense_type
                        if self.defender.is_defense_enabled() else "mean")
        hp = sharded_defense.DefenseHP.from_defender(self.defender)
        attack_type = (self.attacker.attack_type
                       if self.attacker.is_model_attack() else None)
        attack_scale = float(getattr(self.attacker, "attack_scale", 1.0))
        n_dev = self.n_devices
        d_pad = self._d_pad
        k_buf = self.k
        state_specs = self._defense_state_specs

        def flat32(tree):
            return jnp.concatenate(
                [jnp.reshape(l, (-1,)).astype(jnp.float32)
                 for l in jax.tree_util.tree_leaves(tree)])

        def pour_body(params, server_state, local_data, local_states,
                      sched_idx, sched_active, sched_work,
                      buf_mat, buf_nw, merge_scale, pour_n,
                      drift_mask, row_mask, pour_ids, byz_mask, ring,
                      dstate, ring_slot, round_key, hyper):
            rows_mat, states, metrics, slot_mets = train_rows(
                params, server_state, local_data, local_states,
                sched_idx, sched_active, sched_work, round_key, hyper)
            # RE-BASE: a row trained from version v-s proposed the target
            # model params_{v-s} + delta; comparable at version v it is
            # delta - (params_v - params_{v-s}) — the accumulated server
            # movement the client missed, summed from the ring by the
            # per-row DATA mask. At staleness 0 the mask is all-zero and
            # the row passes through untouched (the sync-parity anchor).
            drift = jnp.einsum("kr,rd->kd", drift_mask, ring)
            rebased = buf_mat - drift
            pad = d_pad - true_d
            mat_full = (jnp.pad(rebased, ((0, 0), (0, pad))) if pad
                        else rebased)
            shard_w = d_pad // n_dev
            dev = jax.lax.axis_index(AXIS_CLIENT)
            # replicated [K, D] -> this device's [K, D/n] feature shard:
            # same column blocks as the P(None, axis) layout the sync
            # sharded path lands via its all_to_all
            mat_s = jax.lax.dynamic_slice(
                mat_full, (jnp.int32(0), dev * shard_w), (k_buf, shard_w))
            if attack_type is not None:
                mat_s = sharded_defense._apply_attack_shard(
                    attack_type, mat_s, byz_mask,
                    jax.random.fold_in(round_key, ATTACK_FOLD),
                    attack_scale, AXIS_CLIENT)
            vec_s, new_dstate, verdict = \
                sharded_defense.defend_shard_stateful(
                    mat_s, buf_nw, AXIS_CLIENT, defense_type, hp,
                    state=dstate, ids=pour_ids,
                    key=jax.random.fold_in(round_key, DEFENSE_FOLD),
                    true_d=true_d, row_mask=row_mask)
            vec = jax.lax.all_gather(vec_s, AXIS_CLIENT,
                                     tiled=True)[:true_d]
            agg_update = vector_to_tree_like(vec, params)
            upd_params, upd_sstate = opt.server_update_async(
                params, server_state, agg_update, {}, hyper.round_idx,
                merge_scale, pour_n / n_total)
            # no-op pour (bootstrap, drained-heap retry): pin params,
            # server state AND defense state — the kernels just ran on
            # all-padding and must not advance cross-round history
            poured = pour_n > 0
            new_params = jax.tree_util.tree_map(
                lambda n, o: jnp.where(poured, n, o), upd_params, params)
            new_sstate = jax.tree_util.tree_map(
                lambda n, o: jnp.where(poured, n, o), upd_sstate,
                server_state)
            new_dstate = jax.tree_util.tree_map(
                lambda n, o: jnp.where(poured, n, o), new_dstate, dstate)
            # ring maintenance: this pour's server movement becomes the
            # base delta of the version it just created; a no-op pour
            # leaves the slot holding whatever version it still caches
            delta = flat32(new_params) - flat32(params)
            new_ring = ring.at[ring_slot].set(
                jnp.where(poured, delta, ring[ring_slot]))
            return (new_params, new_sstate, states, rows_mat, metrics,
                    slot_mets, new_dstate, verdict, new_ring)

        shard_fn = jax.shard_map(
            pour_body,
            mesh=self.mesh,
            in_specs=(P(), P(), P(AXIS_CLIENT), P(AXIS_CLIENT),
                      P(AXIS_CLIENT), P(AXIS_CLIENT), P(AXIS_CLIENT),
                      P(), P(), P(), P(),
                      P(), P(), P(), P(), P(),
                      state_specs, P(), P(), P()),
            out_specs=(P(), P(), P(AXIS_CLIENT), P(), P(), P(AXIS_CLIENT),
                       state_specs, P(), P()),
            check_vma=False,
        )
        # donate params / server_state / client_states / ring / defense
        # state: each is replaced 1:1 by an output of identical shape+spec
        return jax.jit(shard_fn,
                       donate_argnums=self._donate_args(0, 1, 3, 15, 16))

    # ------------------------------------------------------------------
    def _staleness_fn(self):
        if self._cap_adaptive:
            seen = self._lat_seen > 0
            self.staleness_cap = adaptive_staleness_cap(
                self._lat_ema[seen], self._pour_interval_ema or 0.0)
        return make_staleness_fn(self._weighting_kind, self._poly_a,
                                 self._hinge_b, self.staleness_cap)

    def _inflight(self) -> int:
        return len(self._events)

    def _rank_idle(self) -> None:
        """Async-aware dispatch (non-uniform ``client_selection``): there
        is no per-round cohort to strategize over, so the strategy instead
        decides WHO the freed capacity goes to next by reordering the idle
        rotation before the draw.

        * ``oort`` / ``power_of_choice``: rank by statistical utility ×
          arrival-rate posterior — a high-loss client that also delivers
          updates quickly buys the most model movement per unit of
          simulated time. Clients with no observed arrivals score the
          observed-mean rate (neutral), so exploration still happens.
        ``reputation`` benches by EXCLUSION instead (see
        :meth:`_benched_now`): with the buffer in steady state every
        freed client is re-dispatched immediately, so reordering alone
        could never keep a byzantine client out of the rotation.

        ``uniform`` (the default) never calls this — the rotation is
        bit-identical to the pre-defense engine."""
        idle = list(self._idle)
        if len(idle) <= 1:
            return
        self.selection.flush()
        st = self.selection.store
        name = self.selection.strategy_name
        if name == "power_of_choice":
            util = st.last_loss()  # +inf for unobserved: explore first
        else:  # oort
            util = self.selection.strategy._utility(self.version)
        rate = st.arrival_rate()
        # rate == 0 IFF never observed (both store backends); the sparse
        # store's arr_obs is row-space, so never read it as [n] here
        seen = rate > 0
        fill = (float(np.mean(rate[seen])) if bool(np.any(seen)) else 1.0)
        rate = np.where(seen, rate, max(fill, 1e-9))
        score = np.asarray([float(util[c]) * float(rate[c])
                            if np.isfinite(util[c]) else np.inf
                            for c in idle])
        order = np.argsort(-score, kind="stable")
        self._idle = deque(idle[i] for i in order)

    def _benched_now(self) -> set:
        """The ``reputation`` strategy's benched set: clients whose
        defense-verdict reputation fell below the threshold are excluded
        from dispatch entirely — they sit idle (burning no compute,
        poisoning no pour) until the relative posterior heals. The shared
        ``cap_bench`` floor guarantees at least ``max(K, min_keep_frac ×
        population)`` clients stay dispatchable, so a poisoned score
        stream can neither empty the rotation nor starve the pour
        trigger below its K."""
        if self.selection.strategy_name != "reputation":
            return set()
        from ...core.selection.strategies import cap_bench, rep_bench_knobs
        self.selection.flush()
        rep = self.selection.store.reputation
        thresh, keep_frac = rep_bench_knobs(self.args)
        flagged = [c for c in range(self.fed.num_clients)
                   if rep[c] < thresh]
        return set(cap_bench(
            self.fed.num_clients, flagged, badness=lambda c: -rep[c],
            keep_frac=keep_frac, quorum=self.k))

    def _draw_cohort(self, target: int) -> List[int]:
        """Pop up to ``target`` idle clients, deferring any whose device
        already filled its canonical slot width this dispatch (the [D, S]
        schedule shape must never grow, or the program recompiles).
        Reputation-benched clients are skipped (they stay idle);
        non-uniform strategies rank the pool first."""
        benched = self._benched_now()
        if self.selection.strategy_name not in ("uniform", "reputation"):
            self._rank_idle()
        counts = [0] * self.n_devices
        cohort: List[int] = []
        deferred: List[int] = []
        while self._idle and len(cohort) < target:
            cid = self._idle.popleft()
            d = cid // self.cpd
            if cid in benched or counts[d] >= self._async_width:
                deferred.append(cid)
                continue
            counts[d] += 1
            cohort.append(cid)
        self._idle.extendleft(reversed(deferred))
        return cohort

    def _defended_pour_data(self, entries):
        """Host-side DATA for one defended pour: per-update drift masks
        over the base-delta ring, the [K] partial-pour validity mask,
        pour client ids (padded with ids DISJOINT from the poured clients
        so the stateful defenses' masked scatters are exact no-ops), and
        the byzantine mask driving the in-program model attack."""
        k, r, v = self.k, self._ring_r, self.version
        dmask = np.zeros((k, r), np.float32)
        row_mask = np.zeros((k,), np.float32)
        for i, e in enumerate(entries):
            row_mask[i] = 1.0
            u = int(e.version)
            if u < v - r and not self._ring_fallback_logged:
                self._ring_fallback_logged = True
                logger.warning(
                    "defended pour: staleness %d exceeds the base-delta "
                    "ring (%d slots) — re-basing over the retained server "
                    "movement only; the update's staleness weight is "
                    "saturated anyway", v - u, r)
            for j in range(max(u, v - r), v):
                dmask[i, j % r] = 1.0
        poured = {int(e.client_id) for e in entries}
        ids = [int(e.client_id) for e in entries]
        ids += [c for c in range(self.fed.num_clients)
                if c not in poured][:k - len(ids)]
        ids = np.asarray(ids, np.int32)
        if self.attacker.is_model_attack():
            byz = np.asarray(self.attacker.byzantine_mask(ids),
                             np.float32) * row_mask
        else:
            byz = np.zeros(k, np.float32)
        return dmask, row_mask, ids, byz

    def _dispatch_plan(self, cohort: List[int]):
        """Chaos verdicts + schedule arrays for one dispatch. Returns
        (idx, active, work, per-client plan rows) — work is 0 only for
        dropped clients (stragglers do FULL work slowly in async; the
        fault is their arrival time)."""
        self._dispatch_seq += 1
        width = self._async_width
        idx, active = build_schedule(cohort, self.n_devices, self.cpd,
                                     max_slots=width)
        if idx.shape[1] < width:
            extra = width - idx.shape[1]
            idx = np.pad(idx, ((0, 0), (0, extra)))
            active = np.pad(active, ((0, 0), (0, extra)))
        work = np.ones_like(active)
        plan = []  # (cid, row, work_scale, duration)
        inj = self.chaos.injects_availability
        for cid, d, s in slot_placement(cohort, self.n_devices, self.cpd):
            ws = self.chaos.work_scale(self._dispatch_seq, cid) if inj \
                else 1.0
            if ws <= 0.0:
                work[d, s] = 0.0  # dropped: no compute, no arrival
            plan.append((cid, d * width + s, ws,
                         faulted_duration(self.durations[cid], ws)))
        return idx, active, work, plan

    def _push_events(self, plan, rows_mat, ctx=None) -> None:
        """Turn a dispatch plan into future events: arrivals carry the
        client's update row (extracted as a device slice — computed at
        dispatch, delivered at arrival); drops become redemption events.
        ``ctx`` is the dispatching pour span's trace context: it rides
        the event to the buffer entry, so the pour that eventually
        consumes the update can LINK back to the dispatch that produced
        it (staleness per link). Never compared by the heap — ``seq`` is
        unique before it."""
        t0 = self.virtual_t
        dropped = []
        for cid, row, ws, dur in plan:
            if ws <= 0.0:
                kind, vec = _REDEEM, None
                dropped.append(cid)
            else:
                kind, vec = _ARRIVE, self._row_fn(rows_mat,
                                                  jnp.int32(row))
            heapq.heappush(self._events,
                           (t0 + dur, self._evseq, kind, cid, self.version,
                            float(self._n_k[cid]), dur, vec, ctx))
            self._evseq += 1
        if dropped:
            mlops.log_chaos(round_idx=self._dispatch_seq,
                            injected={"dropped": dropped})

    def _absorb_until(self, n: int) -> bool:
        """Advance the virtual clock until ``n`` updates are buffered.
        False when the event heap drains first (everything idle)."""
        while len(self.buffer) < n:
            if not self._events:
                return False
            (t, _, kind, cid, ver, w, dur, vec,
             ctx) = heapq.heappop(self._events)
            self.virtual_t = max(self.virtual_t, t)
            if kind == _ARRIVE:
                self.buffer.add(cid, vec, weight=w, version=ver,
                                arrival_t=t, trace=ctx)
                # observed arrival latency = the FAULTED duration (a
                # straggler's slowness is the signal, not its base speed)
                self._note_arrival(cid, dur)
                if self._last_arrival_t[cid] >= 0:
                    self.selection.note_arrival(
                        cid, t - self._last_arrival_t[cid])
                self._last_arrival_t[cid] = t
            self._idle.append(cid)
        return True

    def _note_arrival(self, cid: int, latency_s: float) -> None:
        a = 0.2
        old = float(self._lat_ema[cid])
        if self._lat_seen[cid] > 0:
            self._lat_ema[cid] = (1 - a) * old + a * float(latency_s)
            self._lat_ema_sum += float(self._lat_ema[cid]) - old
        else:
            self._lat_ema[cid] = float(latency_s)
            self._lat_seen[cid] = 1.0
            self._lat_ema_sum += float(latency_s)
            self._lat_seen_n += 1
        self.selection.note_latency(int(cid), float(latency_s))
        # arrival-rate plane: latency histogram + the population-mean
        # rate gauge the adaptive staleness cap effectively tracks
        # (running sum/count — O(1) per arrival)
        mean_lat = (self._lat_ema_sum / self._lat_seen_n
                    if self._lat_seen_n else 0.0)
        obs_metrics.record_arrival(
            float(latency_s),
            rate_mean=(1.0 / mean_lat) if mean_lat > 0 else None)

    # ------------------------------------------------------------------
    def _pour_step(self, hyper: TrainHyper) -> Dict[str, Any]:
        """One pour: absorb arrivals to K, aggregate them, re-dispatch the
        freed clients — all device work in ONE program call. The pour is
        its own trace, LINKING each consumed update back to the pour span
        of the dispatch that produced it, staleness per link — the async
        fan-in a parent/child tree cannot express."""
        with obs_trace.tracer.span(
                "pour", root=True,
                attrs={"role": "engine", "version": self.version}) as psp:
            with obs_trace.span("wait.arrivals",
                                attrs={"version": self.version}):
                # the absorb loop advances the virtual clock to the K-th
                # arrival; wall-wise it is the host draining the event
                # heap (device row slices included) — the async analog
                # of the sync server's wait.uploads
                self._absorb_until(self.k)
                entries = self.buffer.pour(self.version)
            psp.set_attr("poured", len(entries))
            for e in entries:
                if e.trace is not None:
                    psp.add_link(e.trace, client=int(e.client_id),
                                 staleness=int(e.staleness(self.version)),
                                 dispatch_version=int(e.version))
            return self._pour_step_traced(hyper, entries, psp)

    def _pour_step_traced(self, hyper: TrainHyper, entries,
                          psp) -> Dict[str, Any]:
        # host-side pour prep (staleness weights, buffer stack, cohort
        # draw, schedule device_put) — its own span so trace_report can
        # attribute the pour's host half, not just the dispatch
        # the with-form ends the span even when prep raises (device_put
        # OOM, shape errors) — a failed pour still flushes its host half
        with obs_trace.span("host.input", attrs={"version": self.version}):
            fn = self._staleness_fn()
            stal = np.asarray([e.staleness(self.version) for e in entries],
                              np.float64)
            pad = self.k - len(entries)
            if entries:
                # the ONE staleness implementation: relative mix + absolute
                # merge scale from core/async_rounds.pour_weights, fed to
                # the program as data (padded rows carry weight 0)
                norm_w, merge_scale = pour_weights(
                    [e.weight for e in entries], stal, fn, self.merge_alpha)
                buf_nw = np.concatenate([norm_w, np.zeros(pad, np.float32)])
            else:  # bootstrap / drained heap: a no-op pour
                buf_nw = np.zeros(self.k, np.float32)
                merge_scale = 0.0
            vecs = [e.update for e in entries] + [self._zero_row] * pad
            # pin the stacked buffer to the replicated sharding: the
            # bootstrap rows (fresh zeros, single-device sharding) and
            # steady-state rows (slices of the shard_map output, named
            # sharding) must present the SAME input sharding or pjit
            # recompiles the pour program on the bootstrap->steady-state
            # transition
            buf_mat = jax.device_put(self._stack_fn(vecs),
                                     self.repl_sharding)

            target = max(0, self.concurrency - self._inflight()
                         - len(self.buffer))
            cohort = self._draw_cohort(target)
            idx, active, work, plan = self._dispatch_plan(cohort)
            idx = jax.device_put(jnp.asarray(idx), self.client_sharding)
            active = jax.device_put(jnp.asarray(active),
                                    self.client_sharding)
            work = jax.device_put(jnp.asarray(work), self.client_sharding)
            round_key = jax.random.fold_in(self.rng, self._dispatch_seq)
            hyper_r = hyper.replace(round_idx=jnp.int32(self.version))
        if self._defended:
            dmask, row_mask, pour_ids, byz = self._defended_pour_data(
                entries)
            dstate = (self._defense_state
                      if self._defense_state is not None else {})
            (self.params, self.server_state, self.client_states, rows_mat,
             metrics, slot_mets, new_dstate, verdict,
             self._ring) = self._traced(
                "async_pour_defended", 1, self._pour_fn,
                self.params, self.server_state, self.train_data,
                self.client_states, idx, active, work, buf_mat,
                jnp.asarray(buf_nw), jnp.float32(merge_scale),
                jnp.float32(len(entries)), jnp.asarray(dmask),
                jnp.asarray(row_mask), jnp.asarray(pour_ids),
                jnp.asarray(byz), self._ring, dstate,
                jnp.int32(self.version % self._ring_r), round_key, hyper_r)
            if self._defense_state is not None:
                self._defense_state = new_dstate
            if self.selection.track and entries:
                # the defense's verdict is about the POURED clients (not
                # the freshly-dispatched cohort): reputation evidence, so
                # the arrival rotation stops re-dispatching benched
                # byzantine clients
                self.selection.note_results(
                    self.version, [e.client_id for e in entries], [],
                    verdict=verdict[:len(entries)])
        else:
            (self.params, self.server_state, self.client_states, rows_mat,
             metrics, slot_mets) = self._traced(
                "async_pour", 1, self._pour_fn,
                self.params, self.server_state, self.train_data,
                self.client_states, idx, active, work, buf_mat,
                jnp.asarray(buf_nw), jnp.float32(merge_scale),
                jnp.float32(len(entries)), round_key, hyper_r)
        with obs_trace.span("host.close", attrs={"version": self.version}):
            self._push_events(plan, rows_mat, ctx=psp.context)
            if self.selection.track:
                self.selection.note_results(
                    self.version, cohort,
                    slot_placement(cohort, self.n_devices, self.cpd),
                    slot_metrics=slot_mets)

            poured = len(entries)
            self.updates_aggregated += poured
            if poured:
                # pour-interval EMA: the clock the adaptive staleness cap
                # converts arrival latencies into version lag with
                dt = self.virtual_t - self._last_pour_t
                self._last_pour_t = self.virtual_t
                self._pour_interval_ema = (dt
                                           if self._pour_interval_ema is None
                                           else 0.8 * self._pour_interval_ema
                                           + 0.2 * dt)
                self.chaos_ledger.record_pour(
                    self.version,
                    arrivals=[{"client": e.client_id,
                               "staleness": e.staleness(self.version),
                               "arrival_t": e.arrival_t,
                               "dispatch_version": e.version}
                              for e in entries],
                    observed={"poured": poured,
                              "buffered": len(self.buffer),
                              "staleness_cap": self.staleness_cap,
                              "virtual_t": self.virtual_t})
                self.version += 1
        return {"metrics": metrics, "poured": poured,
                "staleness_mean": float(np.mean(stal)) if poured else 0.0,
                "staleness_max": int(np.max(stal)) if poured else 0}

    def _bootstrap(self, hyper: TrainHyper) -> None:
        """Dispatch the initial in-flight cohort (empty buffer — the
        program's zero-masked pour is a no-op on the model)."""
        if self._bootstrapped:
            return
        self._bootstrapped = True
        self._pour_step(hyper)  # buffer empty: trains, pours nothing

    # ------------------------------------------------------------------
    # sync-engine entry points that make no sense without a barrier
    def run_round(self, round_idx, hyper):  # pragma: no cover - guard
        raise NotImplementedError(
            "async_buffered has no per-round barrier; use run()")

    def run_rounds_fused(self, start_round, n_rounds, hyper):
        raise NotImplementedError(
            "async_buffered has no per-round barrier; use run()")

    def run(self, comm_round: Optional[int] = None) -> Dict[str, Any]:
        args = self.args
        pours = comm_round if comm_round is not None \
            else int(args.comm_round)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=int(args.epochs))
        t0 = time.time()
        restored = self._ckpt_latest()
        if restored is not None:
            step, st = restored
            self._load_ckpt_state(st)
            logger.info("resumed async state from checkpoint at pour %d "
                        "(version %d)", step, self.version)
        freq = int(getattr(args, "frequency_of_the_test", 5) or 5)
        self._bootstrap(hyper)
        stalls = 0
        while self.version < pours:
            rec_in = self._pour_step(hyper)
            if rec_in["poured"] == 0:
                # nothing buffered AND nothing in flight produced an
                # arrival — one redispatch retry, then refuse to spin
                stalls += 1
                if stalls > 2:
                    raise RuntimeError(
                        "async pour stalled: no updates in flight "
                        f"(concurrency={self.concurrency}, k={self.k})")
                continue
            stalls = 0
            v = self.version - 1  # the pour that just completed
            metrics = jax.device_get(rec_in["metrics"])
            rec: Dict[str, Any] = {"round": v,
                                   "virtual_t": self.virtual_t,
                                   "poured": rec_in["poured"],
                                   "staleness_mean": rec_in["staleness_mean"],
                                   "staleness_max": rec_in["staleness_max"]}
            cnt = max(float(metrics["count"]), 1.0)
            rec["train_loss"] = float(metrics["loss_sum"]) / cnt
            rec["train_acc"] = float(metrics["correct"]) / cnt
            if freq > 0 and (v % freq == 0 or v == pours - 1):
                stats = self._evaluate(self.params, self.fed.test["x"],
                                       self.fed.test["y"],
                                       self.fed.test["mask"])
                n = max(float(stats["count"]), 1.0)
                rec["test_acc"] = float(stats["correct"]) / n
                rec["test_loss"] = float(stats["loss_sum"]) / n
                logger.info("pour %d (staleness mean %.2f): test_acc=%.4f",
                            v, rec["staleness_mean"], rec["test_acc"])
            self.history.append(rec)
            if self.ckpt.enabled:
                self.ckpt.maybe_save(v, self._ckpt_state())
            mlops.log_round_info(pours, v)
            mlops.log({k: val for k, val in rec.items() if k != "round"},
                      step=v)
            if self.chaos.crash_due(v):
                self.ckpt.flush()
                raise ChaosCrash(v)
        self.ckpt.flush()
        # final metrics snapshot (see the sync engine's run())
        obs_metrics.flush_final(step=self.version - 1)
        wall = time.time() - t0
        last_eval = next((r for r in reversed(self.history)
                          if "test_acc" in r), None)
        if last_eval is None:
            if freq <= 0:
                last_eval = {"test_acc": None}
            else:
                stats = self._evaluate(self.params, self.fed.test["x"],
                                       self.fed.test["y"],
                                       self.fed.test["mask"])
                n = max(float(stats["count"]), 1.0)
                last_eval = {"test_acc": float(stats["correct"]) / n,
                             "test_loss": float(stats["loss_sum"]) / n}
        return {"params": self.params, "history": self.history,
                "wall_time_s": wall,
                "final_test_acc": last_eval["test_acc"],
                "final_test_loss": last_eval.get("test_loss"),
                "rounds": self.version,
                "virtual_time_s": self.virtual_t,
                "updates_aggregated": self.updates_aggregated}

    # ------------------------------------------------------------------
    # checkpointing: the async control state rides RoundCheckpointer next
    # to params/server_state/client_states — fixed shapes (buffer padded
    # to its hard bound, events to the concurrency) so the orbax template
    # never depends on how full the buffer was at the save
    _OPTIONAL_CKPT_KEYS = TPUSimulator._OPTIONAL_CKPT_KEYS + (
        "async_rounds",)

    def _ckpt_state(self):
        st = super()._ckpt_state()
        st["async_rounds"] = self._async_state_dict()
        return st

    def _load_ckpt_state(self, st):
        super()._load_ckpt_state(st)
        if "async_rounds" in st:
            self._async_load_state(st["async_rounds"])
        else:
            logger.warning(
                "checkpoint has no async_rounds leaf — async control "
                "state (buffer, in-flight cohort, virtual clock) resumes "
                "cold from the restored model")

    def _async_state_dict(self) -> Dict[str, np.ndarray]:
        n = self.fed.num_clients
        ev = sorted(self._events, key=lambda e: e[:2])
        e_rows = self.concurrency
        if len(ev) > e_rows:  # cannot happen by construction; be loud
            raise RuntimeError(f"{len(ev)} in-flight events > concurrency")
        ev_meta = np.zeros((e_rows, 7), np.float64)  # t,seq,kind,cid,ver,w,dur
        ev_vecs = np.zeros((e_rows, self._row_d), np.float32)
        ev_mask = np.zeros((e_rows,), np.float32)
        # the trailing trace context (observability only) is NOT
        # persisted: a resumed run replays identical pours, just without
        # links to spans from before the crash
        for i, (t, seq, kind, cid, ver, w, dur, vec, _ctx) in enumerate(ev):
            ev_meta[i] = (t, seq, kind, cid, ver, w, dur)
            if vec is not None:
                ev_vecs[i] = np.asarray(vec, np.float32)
            ev_mask[i] = 1.0
        idle = np.full((n,), -1, np.int64)
        for i, cid in enumerate(self._idle):
            idle[i] = cid
        out = {
            "scalars": np.asarray(
                [self.version, self.virtual_t, self._dispatch_seq,
                 self._evseq,
                 -1.0 if self._pour_interval_ema is None
                 else self._pour_interval_ema,
                 self._last_pour_t, self.updates_aggregated,
                 1.0 if self._bootstrapped else 0.0,
                 self.staleness_cap], np.float64),
            "buffer": self.buffer.state_dict(
                encode=lambda v: np.asarray(v, np.float32),
                pad_rows=2 * self.k, vec_dim=self._row_d),
            "ev_meta": ev_meta, "ev_vecs": ev_vecs, "ev_mask": ev_mask,
            "idle": idle,
            "lat_ema": self._lat_ema.copy(),
            "lat_seen": self._lat_seen.copy(),
            "last_arrival_t": self._last_arrival_t.copy(),
        }
        if self._defended:
            # the base-delta ring must survive a crash, or a resumed run
            # would re-base the restored buffer's stale rows against a
            # zeroed movement history and diverge from the uninterrupted
            # pour trajectory (fixed [R, D] shape — template-stable)
            out["ring"] = np.asarray(jax.device_get(self._ring), np.float32)
        return out

    def _async_load_state(self, st: Dict[str, np.ndarray]) -> None:
        sc = np.asarray(st["scalars"], np.float64)
        (self.version, self.virtual_t, self._dispatch_seq, self._evseq,
         pie, self._last_pour_t, self.updates_aggregated) = (
            int(sc[0]), float(sc[1]), int(sc[2]), int(sc[3]), float(sc[4]),
            float(sc[5]), int(sc[6]))
        self._bootstrapped = sc[7] > 0.0
        self.staleness_cap = int(sc[8])
        self._pour_interval_ema = None if pie < 0 else pie
        self.buffer.load_state_dict(dict(st["buffer"]),
                                    decode=lambda a: jnp.asarray(a))
        self._events = []
        mask = np.asarray(st["ev_mask"], np.float32)
        meta = np.asarray(st["ev_meta"], np.float64)
        vecs = np.asarray(st["ev_vecs"], np.float32)
        for i in range(mask.shape[0]):
            if mask[i] <= 0.0:
                continue
            t, seq, kind, cid, ver, w, dur = meta[i]
            vec = jnp.asarray(vecs[i]) if int(kind) == _ARRIVE else None
            heapq.heappush(self._events, (float(t), int(seq), int(kind),
                                          int(cid), int(ver), float(w),
                                          float(dur), vec, None))
        self._idle = deque(int(c) for c in np.asarray(st["idle"], np.int64)
                           if c >= 0)
        self._lat_ema = np.asarray(st["lat_ema"], np.float64).copy()
        self._lat_seen = np.asarray(st["lat_seen"], np.float64).copy()
        # rebuild the O(1) running aggregates from the restored arrays
        seen = self._lat_seen > 0
        self._lat_ema_sum = float(np.sum(self._lat_ema[seen]))
        self._lat_seen_n = int(np.sum(seen))
        self._last_arrival_t = np.asarray(st["last_arrival_t"],
                                          np.float64).copy()
        if self._defended and "ring" in st:
            self._ring = jax.device_put(
                jnp.asarray(np.asarray(st["ring"], np.float32)),
                self.repl_sharding)
