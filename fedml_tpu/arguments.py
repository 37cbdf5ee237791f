"""Typed flat configuration.

Reproduces the load-bearing semantics of the reference's config system
(``python/fedml/arguments.py:36,75,187,193``): a YAML file with sections
(``common_args``, ``data_args``, ``model_args``, ``train_args``, ...) is
flattened into ONE attribute namespace so every component reads ``args.X``.
Differences from the reference, by design:

* a dataclass-backed schema with defaults + type coercion instead of a
  free-form attribute bag (unknown keys are still kept, so user extensions
  and reference YAMLs work unchanged);
* per-silo override files (``data_silo_config``) are resolved here, mirroring
  ``__init__.py:188-212`` of the reference.
"""

from __future__ import annotations

import argparse
import os
from typing import Any, Dict, List, Optional

import yaml

from .constants import (
    FEDML_SIMULATION_BACKEND_ALIASES,
    FEDML_TRAINING_PLATFORM_SIMULATION,
)

# Schema of known fields: (default, type). Everything else is passed through
# untyped. Types are used for coercion when values arrive as strings (CLI).
_SCHEMA: Dict[str, Any] = {
    # common_args
    "training_type": FEDML_TRAINING_PLATFORM_SIMULATION,
    "random_seed": 0,
    "scenario": "horizontal",
    "config_version": "release",
    "run_id": "0",
    "using_mlops": False,
    # data_args
    "dataset": "synthetic_mnist",
    "data_cache_dir": "~/.cache/fedml_tpu/data",
    "partition_method": "hetero",
    "partition_alpha": 0.5,
    "allow_synthetic": False,    # opt-in gate for synthetic stand-ins
    # model_args
    "model": "lr",
    # train_args
    "federated_optimizer": "FedAvg",
    "client_id_list": None,
    "client_num_in_total": 8,
    "client_num_per_round": 8,
    "comm_round": 10,
    "epochs": 1,
    "batch_size": 32,
    "client_optimizer": "sgd",
    "learning_rate": 0.03,
    "weight_decay": 0.0,
    "momentum": 0.0,
    "server_optimizer": "sgd",
    "server_lr": 1.0,
    "server_momentum": 0.9,
    "fedprox_mu": 0.1,
    "feddyn_alpha": 0.01,
    # validation_args
    "frequency_of_the_test": 5,
    # device_args / tpu_args
    "worker_num": None,          # devices used; defaults to local device count
    "using_gpu": True,
    "device_type": "tpu",
    "mesh_shape": None,          # e.g. {"client": 8} or {"client": 4, "fsdp": 2}
    "clients_per_device": None,  # schedule width; derived if None
    "precision": "float32",      # or "bfloat16" for the compute path
    "rounds_per_dispatch": 8,    # fused-block length (rounds per dispatch)
    # auto: defended rounds fuse train->attack->defense->CDP->server into
    # ONE dispatch whenever the sharded defense path applies; host forces
    # the 3-dispatch host-orchestrated pipeline; fused refuses configs
    # that cannot fuse instead of silently degrading
    "robust_fused": "auto",
    # auto: feature-sharded (no host materialization) defense whenever the
    # configured defense supports it; false/host forces the host kernels
    "sharded_defense": "auto",
    # perf knobs (ISSUE 16) — all off by default, off = bit-identical to
    # the pre-knob programs:
    # fused conv->GroupNorm->residual->ReLU Pallas kernel for the narrow
    # (<= 64 channel) ResNet stages; true/pallas = the VMEM-resident
    # kernel (interpret mode off-TPU), reference = same math via XLA.
    # A mode STRING (bool coercion would eat "reference"); bools work too
    "fused_conv_block": "",
    # fold the [S] client-slot axis into the conv batch axis (FedSGD-style
    # optimizers that evaluate shared params only); refuses configs that
    # need per-client updates (robust/DP/tracking selection)
    "client_slot_fold": False,
    # quantize the fused robust path's all_to_all re-layout rows across
    # the mesh: int8 (per-row scales, ~4x fewer re-layout wire bytes) or
    # bf16 (~2x); None keeps the dense f32 re-layout byte-identical
    "robust_relayout_quant": None,
    # donate params/server_state/client_states buffers to the round
    # programs (outputs replace them 1:1) — halves model-state HBM peak;
    # off-switch for debugging aliasing suspicions only
    "donate_buffers": True,
    # comm_args
    "backend": "tpu",
    "grpc_ipconfig_path": None,
    "mqtt_config_path": None,
    # wire-efficiency for cross-silo updates (utils/compression.py). Off by
    # default: the wire stays byte-identical to the dense float32 path.
    "comm_compression": None,            # topk|randk|qsgd|topk_qsgd|randk_qsgd
    "comm_compression_ratio": 0.1,       # sparsifier keep-ratio in (0, 1]
    "comm_quantize_levels": 127,         # QSGD levels (int8 wire, <= 127)
    "comm_compression_broadcast": "full",  # server->client: full|bf16|compress
    # unified wire pipeline (core/wire, ISSUE 19). ALL off by default:
    # every transport's wire stays byte-identical.
    "comm_compression_adaptive": False,  # stats-driven per-round keep-ratio
    "comm_compression_ratio_min": None,  # adaptive bounds (None -> ratio/4)
    "comm_compression_ratio_max": None,  # adaptive bounds (None -> ratio)
    "comm_compression_latency_budget_s": None,  # uplink s == full pressure
    "secagg_compress_bits": 0,           # 0=dense field; 4|8|16-bit lanes
    "secagg_compress_clip": 4.0,         # round-0 clip (auto-scaled after)
    "gossip_compression": None,          # decentralized neighbor deltas
    "device_wire_compression": None,     # cross-device uplink artifacts
    # chaos_args — deterministic fault injection (core/chaos). ALL off by
    # default: a default run injects nothing, the simulator programs and
    # the cross-silo wire stay byte/bit-identical.
    "chaos_seed": None,              # falls back to random_seed
    "chaos_dropout_prob": 0.0,       # per-(round, client) dropout
    "chaos_straggler_prob": 0.0,     # per-(round, client) straggler
    "chaos_straggler_work": 0.5,     # fraction of local work a straggler runs
    "chaos_link_loss_prob": 0.0,     # per-message loss at the send seam
    "chaos_link_dup_prob": 0.0,      # per-message duplication
    "chaos_link_delay_prob": 0.0,    # per-message delay probability
    "chaos_link_delay_s": 0.0,       # delay applied when it fires
    "chaos_crash_at_round": None,    # raise ChaosCrash after this round
    # fault TOLERANCE (on by default — it is the correct behavior; the
    # off-switch exists so the bench can demonstrate what dropout does to
    # an intolerant aggregator): dropped clients are renormalized out of
    # the weighted average instead of diluting it with zero updates
    "chaos_tolerance": True,
    # sample ceil(client_num_per_round * (1 + frac)) clients so that after
    # expected dropout the surviving cohort still hits the target size
    "chaos_over_sample": 0.0,
    # selection_args — adaptive participant selection & client reputation
    # (core/selection). Defaults are a strict no-op: uniform selection on
    # the legacy sampling stream produces bit-identical schedules.
    "client_selection": "uniform",   # uniform|power_of_choice|oort|reputation
    # legacy: reference-parity per-round stream (ignores random_seed, like
    # the reference's np.random.seed(round_idx) — but via a private
    # RandomState, no longer clobbering the process-global RNG);
    # seeded: default_rng((random_seed, round_idx)) — the fixed stream
    "sampling_stream": "legacy",
    # size the sampled cohort from the OBSERVED Beta-posterior dropout
    # rate (ceil(k / (1 - p))) instead of the static chaos_over_sample
    # factor; capped by selection_max_over_sample so the canonical
    # schedule width (and the compile-once invariant) never moves
    "selection_adaptive_oversample": False,
    "selection_max_over_sample": 1.0,
    "selection_loss_window": 8,      # last-K training losses per client
    "selection_ema_alpha": 0.2,      # latency / work-fraction EMA weight
    # reputation: a client's normalized inclusion posterior over defense
    # verdicts (its Beta-posterior keep-rate relative to the cohort mean,
    # in [0, 1]); clients below rep_threshold are benched as renormalized
    # in-program dropout, never benching past min_keep_frac of the cohort
    "selection_rep_threshold": 0.3,
    "selection_min_keep_frac": 0.5,
    "poc_d_factor": 2.0,             # power-of-choice candidate multiplier
    "oort_explore_frac": 0.1,        # cohort fraction exploring new clients
    "oort_alpha": 2.0,               # system-utility latency exponent
    "oort_pref_latency_s": 0.0,      # 0 = observed median latency
    # fleet_args — durable multi-tenant fleet plane (core/fleet; ISSUE
    # 18). ALL off by default: no registry file is opened and the
    # single-tenant cohort path stays bit-identical.
    # sqlite registry path (None = in-memory only, the amnesiac PR 15
    # behavior); servers sharing one path are tenants of one fleet
    "fleet_registry": None,
    # this server's task name in the registry (None = "train" for the
    # FL server, "fa" for the analytics server)
    "fleet_task_id": None,
    # per-device fairness: at most this many participations (any task)
    # in the trailing window (0 = uncapped); one-task-per-round is
    # always enforced by the registry's claims table
    "fleet_max_rounds_per_window": 0,
    "fleet_fairness_window_s": 3600.0,
    # pacer-driven cohort sizing (Oort: grow k when the cohort's
    # aggregate statistical utility saturates; off = k never moves)
    "pacer_adapt_cohort": False,
    "pacer_util_window": 4,          # rounds per utility comparison window
    "pacer_util_saturation": 0.05,   # relative improvement below = plateau
    "pacer_min_cohort_scale": 1.0,   # k multiplier bounds
    "pacer_max_cohort_scale": 4.0,
    # cross-silo: a timed-out round aggregates only if at least
    # ceil(frac * expected) silos reported; below quorum the server keeps
    # waiting (another timeout interval) instead of averaging a sliver
    "round_quorum_frac": 0.0,
    # cross-silo DATA-index assignment: legacy = the reference's
    # round-robin (rank i gets sampled index i mod k, bit-identical);
    # scored = the stats store ranks silos by availability/latency and
    # the first-sampled indices go to the most deliverable silos
    "silo_index_assignment": "legacy",
    # async_args — buffered-async rounds (core/async_rounds, FedBuff +
    # FedAsync staleness decay). Default `sync` keeps every path
    # bit-identical: the round barrier, FSM, and engine programs are
    # untouched until the knob flips.
    "round_mode": "sync",            # sync | async_buffered
    "async_buffer_k": 0,             # pour trigger; 0 = half the cohort
    "async_alpha": 0.6,              # FedAsync mixing rate for each pour
    "async_staleness_weighting": "polynomial",  # constant|polynomial|hinge
    "async_staleness_poly": 0.5,     # poly decay exponent / hinge slope
    "async_hinge_b": 4,              # hinge: free staleness up to b versions
    # staleness clamp before weighting (stale uploads are DOWN-WEIGHTED,
    # never dropped); 0 = adaptive from observed arrival-rate posteriors
    "async_staleness_cap": 16,
    # cross-silo: pour whatever is buffered (>= 1 update) after this many
    # seconds without reaching K; 0 falls back to round_timeout_s, then
    # to a 30 s default — the liveness valve is never OFF in async mode
    # (a decimated fleet must not stall the pour forever)
    "async_pour_timeout_s": 0.0,
    # simulated-arrival heterogeneity (async engine + SP toy durations)
    "async_duration_sigma": 0.6,
    # comm retry policy (exponential backoff + jitter at the transport
    # send seam; 0 attempts = fail fast like the pre-chaos transports).
    # deadline_s caps the TOTAL retry budget in wall seconds — without it
    # a long per-try timeout times max_attempts can stall an async pour
    # far past usefulness; 0 = attempt-count bound only (legacy)
    "comm_retry_max_attempts": 4,
    "comm_retry_base_s": 0.2,
    "comm_retry_max_s": 2.0,
    "comm_retry_deadline_s": 0.0,
    # serving_args — LLM serving (serving/llm_template + serving/batch).
    # Default `single` keeps the original one-request-at-a-time compiled
    # full-forward loop; `batch` turns on continuous batching (paged KV
    # cache, fixed [serving_slots] slot matrix, per-request multi-LoRA
    # adapter selection from llm_adapter_dir).
    "llm_serving_mode": "single",      # single | batch
    "serving_slots": 8,                # in-flight decode slots [S]
    "serving_kv_block_size": 16,       # KV-cache block (must divide
                                       # llm_max_seq_len)
    "serving_prefill_chunk": 32,       # chunked-prefill program width
    "serving_max_adapters": 64,        # adapter-bank capacity [A]
    "serving_deadline_s": 0.0,         # per-request decode deadline;
                                       # past it the request is evicted
                                       # with finish_reason: length (0=off)
    "serving_request_timeout_s": 120.0,
    # serving-plane observability: the engine's stall/NaN watchdog (0 =
    # off) and the black-box flight recorder (ring of the last N
    # request-lifecycle + engine-step records, dumped as JSONL on crash,
    # SIGTERM, or watchdog trip; dir None = next to the run logs)
    "serving_watchdog_s": 30.0,
    "serving_flight_records": 256,
    "serving_flight_dir": None,
    # serving fault tolerance (crash-only recovery; ISSUE 11). A watchdog
    # trip (decode stall / NaN logits) triggers a controlled reset:
    # in-flight requests are snapshotted, the slot matrix + paged KV pool
    # rebuilt (same geometry — zero recompiles), and the snapshots
    # requeued for deterministic recompute-from-prompt. The reset budget
    # is serving_max_resets per serving_reset_window_s; exhausted, the
    # engine stays unhealthy (/healthz 503) and dumps its flight ring.
    "serving_max_resets": 3,
    "serving_reset_window_s": 300.0,
    # per-request requeue cap: past it the request resolves with
    # finish_reason "preempted" (partial output) instead of looping
    "serving_max_requeues": 2,
    # graceful degradation: preempt-and-requeue the YOUNGEST slot when
    # the queue head has starved this long without admission (0 = off)
    "serving_preempt_after_s": 0.0,
    # load shedding: submit fails fast with 503 + Retry-After once the
    # queue is this deep (0 = off — the pre-ISSUE-11 unbounded queue)
    "serving_shed_queue_depth": 0,
    # chaos_serving_* — seeded serving-plane fault injection (core/chaos
    # serving kinds; all OFF by default). *_prob knobs draw per-index
    # from the (chaos_seed, kind, index) stream; *_at_step/_at_request
    # are the deterministic single-shot variants tests pin.
    "chaos_serving_stall_prob": 0.0,     # per-decode-step stall draw
    "chaos_serving_stall_s": 0.0,        # injected stall length
    "chaos_serving_stall_at_step": None,  # stall exactly at this step
    "chaos_serving_nan_prob": 0.0,       # per-step NaN-logit poison draw
    "chaos_serving_nan_at_step": None,   # poison exactly at this step
    "chaos_serving_conn_drop_prob": 0.0,  # gateway->replica connect drop
    "chaos_serving_crash_at_request": None,  # replica dies on request N
    # serving perf levers (ISSUE 13) — ALL off by default: wire bytes and
    # decode tokens stay bit-identical to the pre-ISSUE-13 path.
    # shared-prefix KV cache: refcounted copy-on-write aliasing of
    # fully-matched read-only prompt blocks — a system-prompt-heavy chat
    # workload prefills only its novel suffix (aliasing changes where KV
    # lives, never its values: greedy decode stays bit-identical)
    "llm_prefix_cache": False,
    # piggybacked prefill: batch an admission wave's chunks through one
    # [B, C] program (B = this width; 0/1 = serial) so K admits cost
    # ~one pass over the longest novel suffix instead of K serial passes
    "llm_prefill_batch": 0,
    # SSE token streaming on /v1/chat/completions for requests carrying
    # "stream": true (off = the flag is ignored, byte-identical wire)
    "llm_stream": False,
    # adapter hot-swap: poll llm_adapter_dir every this-many seconds and
    # swap changed/new exports live (0 = off); in-flight requests keep
    # the adapter version they started with
    "llm_adapter_watch_s": 0.0,
    "llm_adapter_dir": None,           # adapter-bank manifest dir to serve
    # fleet-serving levers (ISSUE 17) — ALL off by default: wire bytes
    # and decode tokens stay bit-identical to the pre-ISSUE-17 path.
    # generated-token suffix caching (RadixAttention-style): index full
    # decode blocks into the prefix index at slot release under the same
    # refcount/COW discipline as prompt blocks, so a requeued or
    # follow-up request (prior prompt + generated reply + new user turn)
    # aliases the whole conversation prefix instead of re-prefilling
    # tokens the engine itself produced. Implies the prefix index.
    "llm_suffix_cache": False,
    # cache-aware gateway routing: hash each request's leading prompt
    # bytes (~ leading token blocks under the byte tokenizer) into a
    # routing digest and stick same-digest traffic to the replica whose
    # prefix cache is warm, with KV-headroom-aware spill to round-robin
    # when the warm replica is saturated
    "serving_cache_aware_routing": False,
    # serving_slo_* — SLO-driven autoscaling (SLOPolicy): close the loop
    # from the serving SLO instruments (TTFT/ITL percentiles, queue
    # depth, KV headroom scraped from each replica's /healthz) to
    # ReplicaSet scaling. Targets of 0 disable that signal; with both
    # latency targets 0 the policy never scales on latency.
    "serving_slo_ttft_p99_s": 0.0,     # scale up while p99 TTFT exceeds
    "serving_slo_itl_p99_s": 0.0,      # scale up while p99 ITL exceeds
    "serving_slo_queue_per_replica": 4.0,  # queue-depth bound per replica
    "serving_slo_kv_headroom_min": 1,  # min KV admission headroom (reqs)
    "serving_slo_cooldown_s": 5.0,     # min seconds between scale moves
    # drain-before-kill on scale-down: give the victim replica this long
    # to finish in-flight streams before stop (0 = legacy immediate stop)
    "serving_drain_grace_s": 0.0,
    # federated-LoRA adapter export: after run_federated_llm, write the
    # global + per-silo personalized adapters as named artifacts the
    # serving adapter bank loads (None = off)
    "llm_adapter_export_dir": None,
    "llm_adapter_personalize_steps": 4,
    # tracking_args
    "enable_wandb": False,
    "enable_tracking": True,     # master switch for the JSONL sink
    "log_server_url": None,      # remote log shipper endpoint (log_daemon)
    "sys_perf_profiling": False,  # host/device sampler thread (mlops)
    # observability (core/obs): tracing + metrics are default-on-cheap
    # (spans are dicts + one JSONL line; metric hooks are dict lookups)
    "obs_tracing": True,          # spans + traceparent wire propagation
    "obs_metrics": True,          # typed counter/gauge/histogram registry
    "obs_metrics_flush_rounds": 10,  # metrics_snapshot JSONL cadence
    # wall-clock metrics_snapshot cadence (seconds; 0 = off) for
    # workloads that never cross a round boundary — serving, the
    # cross-device handshake, agents; skips when nothing changed
    "obs_metrics_flush_s": 60.0,
    "log_file_dir": "~/.cache/fedml_tpu/logs",
    "save_model_path": None,     # persist final params (serving artifact)
    "checkpoint_dir": None,
    "checkpoint_every_rounds": 0,  # 0 = off
    # security/privacy (consulted by hook chain; parity with L4 singletons)
    "enable_attack": False,
    "attack_type": None,
    "enable_defense": False,
    "defense_type": None,
    "rfa_iters": 8,              # Weiszfeld iterations for the RFA defense
    # rfa_tol > 0: convergence-based early exit — rfa_iters becomes a
    # budget, the loop stops once the estimate moves < tol. 0 (default)
    # keeps the exact fixed trip count, bit-parity-tested host vs sharded
    "rfa_tol": 0.0,
    "enable_dp": False,
    "dp_mechanism": "gaussian",
    "enable_dp_ldp": False,
    "enable_secure_agg": False,
    "enable_fhe": False,
}


class Arguments:
    """Flat config namespace. Known keys get defaults from ``_SCHEMA``;
    unknown keys from the YAML are attached as-is (reference
    ``set_attr_from_config`` ``arguments.py:187-190``)."""

    def __init__(self, config: Optional[Dict[str, Any]] = None, **overrides: Any):
        for key, default in _SCHEMA.items():
            setattr(self, key, default)
        merged: Dict[str, Any] = {}
        if config:
            merged.update(_flatten_sections(config))
        merged.update(overrides)
        for key, value in merged.items():
            setattr(self, key, _coerce(key, value))
        self._finalize()

    def _finalize(self) -> None:
        backend = str(getattr(self, "backend", "tpu")).lower()
        self.backend = FEDML_SIMULATION_BACKEND_ALIASES.get(backend, backend)
        if self.client_num_per_round > self.client_num_in_total:
            self.client_num_per_round = self.client_num_in_total
        for key in ("data_cache_dir", "log_file_dir", "checkpoint_dir"):
            val = getattr(self, key, None)
            if isinstance(val, str):
                setattr(self, key, os.path.expanduser(val))

    # dict-style helpers used across the framework
    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)

    def to_dict(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items() if not k.startswith("_")}

    def __repr__(self) -> str:  # keep logs readable
        keys = sorted(self.to_dict())
        return "Arguments(" + ", ".join(f"{k}={getattr(self, k)!r}" for k in keys) + ")"


_SECTION_SUFFIX = "_args"


def _flatten_sections(config: Dict[str, Any]) -> Dict[str, Any]:
    """Flatten ``{section_args: {k: v}}`` into ``{k: v}``; non-section keys
    pass through. Later sections win on duplicate keys, matching the
    reference's setattr order."""
    flat: Dict[str, Any] = {}
    for key, value in config.items():
        if key.endswith(_SECTION_SUFFIX) and isinstance(value, dict):
            flat.update(value)
        else:
            flat[key] = value
    return flat


def _coerce(key: str, value: Any) -> Any:
    default = _SCHEMA.get(key)
    if default is None or value is None:
        return value
    ty = type(default)
    if isinstance(value, ty):
        return value
    try:
        if ty is bool and isinstance(value, str):
            return value.strip().lower() in ("1", "true", "yes", "on")
        return ty(value)
    except (TypeError, ValueError):
        return value


def load_arguments(
    config_path: Optional[str] = None,
    rank: int = 0,
    role: Optional[str] = None,
    **overrides: Any,
) -> Arguments:
    """Load YAML config (if given) → flat ``Arguments``.

    Mirrors ``load_arguments`` (reference ``arguments.py:193``) including the
    per-silo override files: if the YAML names ``data_silo_config`` (a list of
    YAML paths) and ``rank >= 1``, the rank-specific file is merged on top
    (reference ``__init__.py:188-212``).
    """
    config: Dict[str, Any] = {}
    if config_path:
        with open(config_path, "r") as f:
            config = yaml.safe_load(f) or {}
    args = Arguments(config, **overrides)
    args.rank = rank
    if role is not None:
        args.role = role
    silo_configs: Optional[List[str]] = getattr(args, "data_silo_config", None)
    if silo_configs and rank >= 1 and rank - 1 < len(silo_configs):
        base = os.path.dirname(os.path.abspath(config_path)) if config_path else "."
        silo_path = os.path.join(base, silo_configs[rank - 1])
        with open(silo_path, "r") as f:
            silo_cfg = yaml.safe_load(f) or {}
        for key, value in _flatten_sections(silo_cfg).items():
            setattr(args, key, _coerce(key, value))
        args._finalize()
    return args


def add_args() -> argparse.Namespace:
    """Bootstrap CLI flags (reference ``arguments.py:36-72``)."""
    parser = argparse.ArgumentParser(description="fedml_tpu")
    parser.add_argument("--cf", "--config_file", dest="yaml_config_file",
                        type=str, default=None, help="yaml configuration file")
    parser.add_argument("--rank", type=int, default=0)
    parser.add_argument("--role", type=str, default="client")
    parser.add_argument("--run_id", type=str, default="0")
    parser.add_argument("--run_device_id", type=str, default="0")
    known, _ = parser.parse_known_args()
    return known
