"""Benchmark: FL round throughput + time-to-accuracy + LLM-step MFU.

Prints one JSON line per metric (flagship first):

1. ``fedavg_resnet56_cifar10_rounds_per_hour`` — the BASELINE.md north-star
   shape: FedAvg ResNet-56, 64 clients/round on the mesh engine, bf16.
   ``vs_baseline`` = mesh rounds/hour ÷ the reference-architecture golden
   loop (per-sample normalized). Real CIFAR-10 when cached/downloadable,
   loud synthetic stand-in otherwise (throughput is shape-determined).
   MFU counts only REAL local steps (padded hetero batches are skipped by
   the dynamic local loop — see engine.round_cost_flops).
2. ``fedavg_digits_time_to_90pct_s`` — real data (sklearn-bundled digits),
   FedAvg+LR: wall-clock to 90% test accuracy and final accuracy.
   BASELINE.json names time-to-target-accuracy a primary metric; this line
   keeps an accuracy axis on real data in every bench run.
3. ``llm_train_step_mfu`` — single-chip causal-LM train step (the FedLLM
   hot loop: Llama-style block, bf16, bs x seq = 8 x 1024). Shows the MFU
   the engine reaches when the workload has MXU-sized operands — the
   flagship's low MFU is a property of CIFAR ResNet's 16..64-wide channels,
   not of the runtime (see BASELINE.md "Roofline").
"""

from __future__ import annotations

import json
import time


# The peak table and the MFU formula live in core/obs/profiler. The FLOPs
# model is unchanged (engine.round_cost_flops), so the BENCH trajectory
# stays comparable.
from fedml_tpu.core.obs import profiler as _obs_profiler


def _peak_tflops(device):
    return _obs_profiler.peak_tflops(device)


def _force(tree):
    """End a timed region: wait until the device has produced ``tree``.
    (Once a scalar readback; chip_smoke.py measured that the two agree on
    the v5e — PERF.md, PR 21.)"""
    import jax
    jax.block_until_ready(tree)


def _hbm_peak_gb():
    """Per-device peak HBM (GiB) from memory_stats, or None off-TPU.
    NOTE: the counter is monotonic per process — deltas between snapshots
    attribute only what ran in between."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return round(peak / 2**30, 4) if peak else None
    except Exception:
        return None


def bench_flagship():
    import jax
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.sp.simulator import SPSimulator
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    n_clients = 64
    args = Arguments(
        dataset="cifar10", model="resnet56", precision="bfloat16",
        client_num_in_total=n_clients, client_num_per_round=n_clients,
        comm_round=1, epochs=1, batch_size=32, learning_rate=0.1,
        frequency_of_the_test=10_000, random_seed=0,
        allow_synthetic=True,  # loud, labeled fallback when no net/cache
        synthetic_size=50_000,  # stand-in matches real CIFAR-10's workload
    )
    fed, output_dim = load(args)
    provenance = getattr(fed, "provenance", "real")
    bundle = create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate), epochs=1)

    def time_rounds(run_one, params_of, warmup=1, iters=3):
        """Min-of-iters, with the raw trials disclosed in the JSON. No
        spread is computed; ROADMAP S0 replaces this with a median and
        quartiles."""
        for _ in range(warmup):
            run_one()
        _force(params_of())
        trials = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run_one()
            _force(params_of())
            trials.append(time.perf_counter() - t0)
        return min(trials), trials

    # --- mesh engine (ours): rounds run in fused blocks of 8 — ONE
    # dispatch per block, exactly what engine.run() does in production
    # (a dispatch round trip is ~0.6 ms on the v5e — chip_smoke.py,
    # PR 21 — so what the block saves is the host work between rounds)
    opt = create_optimizer(args, spec)
    tpu_sim = TPUSimulator(args, fed, bundle, opt, spec)
    r = [0]
    BLOCK = 8

    def tpu_block():
        tpu_sim.run_rounds_fused(r[0], BLOCK, hyper)
        r[0] += BLOCK

    tpu_block_s, tpu_trials = time_rounds(tpu_block,
                                          lambda: tpu_sim.params)
    tpu_round_s = tpu_block_s / BLOCK

    # HBM-peak delta of buffer donation (params/server_state/client_states
    # alias their outputs when donate_buffers is on — the default): peak
    # after the donating run vs after one extra block with donation OFF.
    # The counter is monotonic, so the delta is a LOWER bound on the
    # double-residency donation removes; off-TPU both read null.
    hbm_peak_on = _hbm_peak_gb()
    hbm_peak_off = None
    try:
        # same simulator, same data buffers — only the round program is
        # rebuilt without donation, so the delta attributes the program's
        # in/out double-residency and nothing else
        tpu_sim._donate = False
        tpu_sim._fused_fn = tpu_sim._build_fused_fn()
        tpu_block()
        _force(tpu_sim.params)
        hbm_peak_off = _hbm_peak_gb()
    except Exception as e:
        # the donation-OFF leg is the one that can OOM (it deliberately
        # needs more HBM) — a null column must say why, not swallow it
        print(json.dumps({"metric": "hbm_peak_donation_off_gb",
                          "error": f"{type(e).__name__}: {e}"}),
              flush=True)
    finally:
        tpu_sim._donate = True
        tpu_sim._fused_fn = tpu_sim._build_fused_fn()

    # FLOPs of the real (non-padded) work per round, for MFU
    flops = tpu_sim.round_cost_flops(hyper)
    n_dev = tpu_sim.n_devices
    achieved_tflops = (flops / tpu_round_s) / 1e12 if flops else 0.0
    mfu = _obs_profiler.mfu_value(flops, tpu_round_s, n_dev,
                                  device=jax.devices()[0])

    # --- baseline: golden per-client loop (reference SP architecture),
    # scaled down (8 of 64 clients) then per-sample normalized
    base_clients = 8
    bargs = Arguments(
        dataset="cifar10", model="resnet56", precision="bfloat16",
        client_num_in_total=base_clients, client_num_per_round=base_clients,
        comm_round=1, epochs=1, batch_size=32, learning_rate=0.1,
        frequency_of_the_test=-1,  # timing: no eval inside the timed call
        random_seed=0, allow_synthetic=True,
        synthetic_size=6_250, max_total_samples=6_250,
    )
    bfed, _ = load(bargs)
    sp_sim = SPSimulator(bargs, bfed, bundle, create_optimizer(bargs, spec),
                         spec)

    def sp_round():
        sp_sim.run(comm_round=1)

    # iters=4: the SP loop is 8 small dispatches/round, so host latency
    # weighs on it far more than on the mesh engine's single dispatch;
    # sp_round_s is disclosed in the JSON so vs_baseline is auditable
    # against the raw legs
    sp_round_s, sp_trials = time_rounds(sp_round, lambda: sp_sim.params,
                                        warmup=1, iters=4)
    tpu_samples = float(fed.total_train_samples)
    sp_samples = float(bfed.total_train_samples)
    rounds_per_hour = 3600.0 / tpu_round_s
    vs_baseline = (sp_round_s / sp_samples) / (tpu_round_s / tpu_samples)
    print(json.dumps({
        "metric": "fedavg_resnet56_cifar10_rounds_per_hour",
        "value": round(rounds_per_hour, 1),
        "unit": f"rounds/hour (64 clients/round, 1 local epoch, bf16, "
                f"{provenance} data)",
        "vs_baseline": round(vs_baseline, 3),
        "sp_baseline_round_s": round(sp_round_s, 4),
        "sp_baseline_trials": [round(t, 3) for t in sp_trials],
        "sp_baseline_samples": int(sp_samples),
        "step_time_s": round(tpu_round_s, 4),
        "block_trials": [round(t, 3) for t in tpu_trials],
        "tflops": round(achieved_tflops, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "n_devices": n_dev,
        # donation HBM accounting (peak counter is monotonic: the delta is
        # a lower bound on the double-residency donation removes)
        "hbm_peak_donation_on_gb": hbm_peak_on,
        "hbm_peak_donation_off_gb": hbm_peak_off,
        "hbm_peak_delta_gb": (round(hbm_peak_off - hbm_peak_on, 4)
                              if hbm_peak_on and hbm_peak_off else None),
        "data_provenance": provenance,
        # honesty note: the SP baseline deliberately runs a 1/8-size
        # workload (per-sample normalized); disclose any train-set caps
        "baseline_train_capped_to": getattr(bargs, "_train_capped_to",
                                            None),
    }), flush=True)


def bench_time_to_acc(target_acc=0.90, max_rounds=80):
    """Real-data accuracy axis: FedAvg + logistic regression on the
    sklearn-bundled digits set (no network needed — provenance 'real')."""
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = Arguments(
        dataset="digits", model="lr", client_num_in_total=10,
        client_num_per_round=10, comm_round=max_rounds, epochs=1,
        batch_size=32, learning_rate=0.3, frequency_of_the_test=10_000,
        random_seed=0)  # eval below, once per round — not also in-engine
    fed, output_dim = load(args)
    provenance = getattr(fed, "provenance", "real")
    bundle = create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                       epochs=1)

    t0 = time.perf_counter()
    t_hit, acc, hit_round = None, 0.0, None
    for round_idx in range(max_rounds):
        sim.run_round(round_idx, hyper)
        stats = sim._evaluate(sim.params, sim.fed.test["x"],
                              sim.fed.test["y"], sim.fed.test["mask"])
        acc = float(stats["correct"]) / max(float(stats["count"]), 1.0)
        if t_hit is None and acc >= target_acc:
            t_hit = time.perf_counter() - t0
            hit_round = round_idx
    total_s = time.perf_counter() - t0
    print(json.dumps({
        "metric": "fedavg_digits_time_to_90pct_s",
        "value": round(t_hit, 3) if t_hit is not None else None,
        "unit": f"s wall-clock to {target_acc:.0%} test acc "
                f"(10 clients, FedAvg+LR, incl. compile)",
        "vs_baseline": None,
        "final_acc": round(acc, 4),
        "rounds_to_target": hit_round,
        "total_rounds": max_rounds,
        "total_s": round(total_s, 2),
        "data_provenance": provenance,
    }), flush=True)


def _secagg_wire_leg(target_acc=0.90, rounds=40, bits=4):
    """SecAgg-compatible lane compression column (ISSUE 19): the digits
    FedAvg trajectory driven through the REAL secure-uplink wire math —
    ``core/wire.field_encode`` (EF + stochastic lane quantization),
    pairwise ``core/mpc.expand_mask`` masks, mod-p summation, and
    ``lane_dequantize_sum`` — once over dense field vectors (the
    frac_bits=16 layout, 4 B/coord) and once over ``bits``-bit lanes
    (k_max=4 silos -> 5 lanes/word, 0.8 B/coord). The Bonawitz FSM
    itself needs the ``cryptography`` package (absent here); this
    harness is the same per-round algebra with the key agreement
    elided, so the masked bytes and the mask-cancellation bit-exactness
    it reports are exactly what the FSM would put on the wire.
    Every round asserts masked-sum == unmasked quantized sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import make_trainer_spec
    from fedml_tpu.core.algframe.local_training import run_local_sgd
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.core.collectives import (tree_flatten_to_vector,
                                            vector_to_tree_like)
    from fedml_tpu.core.mpc import P, dequantize, expand_mask, quantize
    from fedml_tpu.core.wire import (field_encode, lane_dequantize_sum,
                                     plan_for, suggest_scale)
    from fedml_tpu.cross_silo.horizontal.runner import _make_eval_fn
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer

    K = 4
    args = Arguments(
        dataset="digits", model="lr", client_num_in_total=K,
        client_num_per_round=K, comm_round=rounds, epochs=1,
        batch_size=32, learning_rate=0.3, frequency_of_the_test=1,
        random_seed=0, training_type="cross_silo")
    fed, output_dim = load(args)
    bundle = create(args, output_dim)
    spec = make_trainer_spec(fed, bundle)
    opt = create_optimizer(args, spec)
    eval_fn = _make_eval_fn(spec, fed)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                       epochs=1)
    init_rng, _ = jax.random.split(jax.random.PRNGKey(0))
    params0 = jax.device_get(bundle.init(init_rng, fed.train.x[0, 0]))
    d = int(np.asarray(tree_flatten_to_vector(params0)).shape[0])

    def impl(params, cdata, rng, hyper):
        inner = opt.make_inner_opt(hyper)
        new_params, _, _ = run_local_sgd(
            spec, inner, params, cdata, rng, hyper,
            grad_transform=opt.grad_transform,
            ctx={"global_params": params, "server_state": {},
                 "client_state": {}, "hyper": hyper})
        return new_params

    train_jit = jax.jit(impl)

    def local_vec(global_p, cidx, rnd):
        cdata = jax.tree_util.tree_map(lambda a: a[cidx], fed.train)
        key = jax.random.fold_in(jax.random.PRNGKey(17 + cidx), rnd)
        new_p = train_jit(jax.tree_util.tree_map(jnp.asarray, global_p),
                          cdata, key, hyper)
        return np.asarray(tree_flatten_to_vector(jax.device_get(new_p)),
                          np.float32)

    def leg(use_lanes: bool):
        plan = plan_for(bits, K) if use_lanes else None
        scale = suggest_scale(4.0, plan) if plan else None
        residuals = [None] * K
        global_p = params0
        plen = plan.packed_len(d) if plan else d
        hit, acc, exact = None, 0.0, True
        for rnd in range(rounds):
            qs = []
            for k in range(K):
                vec = local_vec(global_p, k, rnd)
                if plan:
                    packed, residuals[k] = field_encode(
                        vec, scale, plan, residuals[k],
                        np.random.default_rng((k + 1) * 1000003 + rnd))
                    qs.append(packed.astype(np.uint64))
                else:
                    qs.append(np.asarray(quantize(jnp.asarray(vec)),
                                         np.uint64))
            # pairwise mask algebra over the packed length: +s_ij for
            # i<j, -s_ij for i>j — sums cancel bit-for-bit mod p
            masked, plain = np.zeros(plen, np.uint64), np.zeros(plen,
                                                                np.uint64)
            for i in range(K):
                m = qs[i] % P
                for j in range(K):
                    if i == j:
                        continue
                    seed = (rnd << 16) ^ (min(i, j) << 8) ^ max(i, j)
                    s = expand_mask(seed, plen).astype(np.uint64)
                    m = (m + s) % P if i < j else (m + P - s) % P
                masked = (masked + m) % P
                plain = (plain + qs[i]) % P
            exact = exact and bool(np.array_equal(masked, plain))
            if plan:
                ssum = lane_dequantize_sum(masked.astype(np.uint32), K,
                                           scale, plan, d)
                avg = ssum / K
                # auto-scale EMA, mirroring SecAggServerManager
                per_client = float(np.abs(ssum).max()) / K
                scale = 0.5 * scale + 0.5 * suggest_scale(
                    max(2.0 * per_client, 1e-8), plan)
            else:
                avg = np.asarray(dequantize(jnp.asarray(
                    masked.astype(np.uint32))), np.float32)[:d] / K
            global_p = jax.tree_util.tree_map(
                np.asarray, vector_to_tree_like(np.asarray(avg, np.float32),
                                                params0))
            stats = eval_fn(global_p) or {}
            acc = float(stats.get("test_acc", 0.0))
            if hit is None and acc >= target_acc:
                hit = rnd
        return {"bytes_per_round": float(plen * 4 * K),
                "rounds_to_target": hit, "final_acc": round(acc, 4),
                "mask_sum_bit_exact": exact}

    dense = leg(use_lanes=False)
    lanes = leg(use_lanes=True)
    return {
        "bytes_per_round": lanes["bytes_per_round"],
        "dense_field_bytes_per_round": dense["bytes_per_round"],
        "reduction_vs_dense_field": round(
            dense["bytes_per_round"] / lanes["bytes_per_round"], 2),
        "rounds_to_target": lanes["rounds_to_target"],
        "dense_field_rounds_to_target": dense["rounds_to_target"],
        "final_acc": lanes["final_acc"],
        "dense_field_final_acc": dense["final_acc"],
        "mask_sum_bit_exact": bool(lanes["mask_sum_bit_exact"]
                                   and dense["mask_sum_bit_exact"]),
        "bits": bits, "k_max": K,
    }


def _gossip_wire_leg(rounds=8):
    """Gossip delta-chain compression column (ISSUE 19): the synthetic
    gossip session dense vs ``gossip_compression: topk_qsgd`` — N2N
    model-bearing bytes per round off the same ``WireStats`` ledger."""
    from fedml_tpu import data as data_mod
    from fedml_tpu import model as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.distributed.communication.message import WIRE_STATS
    from fedml_tpu.cross_silo.decentralized import GossipMsg,\
        run_gossip_inproc

    def session(**kw):
        args = Arguments(
            dataset="digits", model="lr", client_num_in_total=4,
            client_num_per_round=4, comm_round=rounds, epochs=1,
            batch_size=32, learning_rate=0.3, random_seed=0,
            training_type="cross_silo", **kw)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        WIRE_STATS.reset()
        result = run_gossip_inproc(args, fed, bundle)
        by_type = WIRE_STATS.snapshot()["by_type"]
        rec = by_type.get(str(GossipMsg.N2N_PARAMS),
                          by_type.get(GossipMsg.N2N_PARAMS, {"bytes": 0}))
        return {"bytes_per_round": rec["bytes"] / rounds,
                "final_acc": result.get("final_test_acc"),
                "consensus_dist": result.get("consensus_dist")}

    off = session()
    on = session(gossip_compression="topk_qsgd", comm_compression_ratio=0.1)
    return {
        "bytes_per_round": round(on["bytes_per_round"], 1),
        "dense_bytes_per_round": round(off["bytes_per_round"], 1),
        "reduction_vs_dense": round(
            off["bytes_per_round"] / on["bytes_per_round"], 2)
        if on["bytes_per_round"] else None,
        "final_acc": on["final_acc"],
        "dense_final_acc": off["final_acc"],
        "consensus_dist": round(on["consensus_dist"], 4)
        if on["consensus_dist"] is not None else None,
    }


def bench_cross_silo_wire(target_acc=0.90, rounds=40):
    """Wire-efficiency axis (QSGD + error-feedback top-k, ISSUE 1): the
    digits FedAvg session runs twice over the in-proc WAN FSM — dense
    float32 vs ``comm_compression: topk_qsgd`` with compressed broadcast —
    and reports model-bearing bytes-on-wire per round (types INIT/SYNC/
    C2S_MODEL from the ``WireStats`` ledger at the ``Message.encode``
    seam; the in-proc broker encode/decodes every message exactly like
    TCP/gRPC). The compressed session must still reach the accuracy
    target — wire savings that cost convergence are not savings."""
    from fedml_tpu import data as data_mod
    from fedml_tpu import model as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.distributed.communication.message import WIRE_STATS
    from fedml_tpu.cross_silo.horizontal.runner import run_cross_silo_inproc
    from fedml_tpu.cross_silo.message_define import MyMessage

    model_types = (str(MyMessage.MSG_TYPE_S2C_INIT_CONFIG),
                   str(MyMessage.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT),
                   str(MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER))

    def session(**cc):
        args = Arguments(
            dataset="digits", model="lr", client_num_in_total=10,
            client_num_per_round=10, comm_round=rounds, epochs=1,
            batch_size=32, learning_rate=0.3, frequency_of_the_test=1,
            random_seed=0, training_type="cross_silo", **cc)
        fed, output_dim = data_mod.load(args)
        bundle = model_mod.create(args, output_dim)
        WIRE_STATS.reset()
        t0 = time.perf_counter()
        result = run_cross_silo_inproc(args, fed, bundle)
        wall = time.perf_counter() - t0
        by_type = WIRE_STATS.snapshot()["by_type"]
        model_bytes = sum(by_type.get(t, {"bytes": 0})["bytes"]
                          for t in model_types)
        accs = [h.get("test_acc", 0.0) for h in result["history"]]
        hit = next((i for i, a in enumerate(accs) if a >= target_acc), None)
        return {"bytes_per_round": model_bytes / rounds,
                "final_acc": accs[-1] if accs else 0.0,
                "rounds_to_target": hit, "wall_s": wall}

    off = session()
    on = session(comm_compression="topk_qsgd", comm_compression_ratio=0.05,
                 comm_compression_broadcast="compress")
    reduction = (off["bytes_per_round"] / on["bytes_per_round"]
                 if on["bytes_per_round"] else None)
    print(json.dumps({
        "metric": "fedavg_cross_silo_wire_bytes_per_round",
        "value": round(on["bytes_per_round"], 1),
        "unit": f"model-bearing wire bytes/round (10 silos, FedAvg+LR "
                f"digits, topk_qsgd 5% + EF, compressed broadcast, "
                f"{rounds} rounds incl. dense init)",
        "vs_baseline": round(reduction, 2) if reduction else None,
        "dense_bytes_per_round": round(off["bytes_per_round"], 1),
        "compressed_final_acc": round(on["final_acc"], 4),
        "dense_final_acc": round(off["final_acc"], 4),
        "target_acc": target_acc,
        "compressed_rounds_to_target": on["rounds_to_target"],
        "dense_rounds_to_target": off["rounds_to_target"],
        "compressed_wall_s": round(on["wall_s"], 2),
        "dense_wall_s": round(off["wall_s"], 2),
        # ISSUE 19 columns: SecAgg-compatible lane compression (masked
        # uplink bytes vs the dense field layout, same trajectory gate)
        # and the gossip delta-chain (N2N bytes dense vs compressed).
        # Under `legs` so scripts/bench_diff.py flattens + gates them.
        "legs": {
            "secagg_compressed": _secagg_wire_leg(target_acc=target_acc),
            "gossip_compressed": _gossip_wire_leg(),
        },
    }), flush=True)


def bench_chaos_dropout(target_acc=0.90, max_rounds=80):
    """Fault-tolerance axis (chaos subsystem, ISSUE 3): digits FedAvg+LR
    under a seeded 20% client dropout + 10% stragglers (half local work),
    tolerance ON (dropped clients renormalized out of the weighted
    average, the chaos default) vs OFF (their scheduled weight stays in
    the denominator, diluting every round's aggregate with zeros — what a
    fault-oblivious aggregator does). Same 90% digits target as
    ``fedavg_digits_time_to_90pct_s``: tolerance must reach it; the
    intolerant leg degrades (more rounds) or stalls (None). lr 0.1 (not
    the time-to-acc leg's 0.3): the smoother trajectory is where dilution
    shows — at 0.3 the first rounds overshoot past 90% regardless."""
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    def leg(tolerance: bool):
        args = Arguments(
            dataset="digits", model="lr", client_num_in_total=10,
            client_num_per_round=10, comm_round=max_rounds, epochs=1,
            batch_size=32, learning_rate=0.1, frequency_of_the_test=10_000,
            random_seed=0, chaos_dropout_prob=0.2,
            chaos_straggler_prob=0.1, chaos_straggler_work=0.5,
            chaos_seed=7, chaos_tolerance=tolerance)
        fed, output_dim = load(args)
        bundle = create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        opt = create_optimizer(args, spec)
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=1)
        t0 = time.perf_counter()
        hit_round, acc = None, 0.0
        for round_idx in range(max_rounds):
            sim.run_round(round_idx, hyper)
            stats = sim._evaluate(sim.params, sim.fed.test["x"],
                                  sim.fed.test["y"], sim.fed.test["mask"])
            acc = float(stats["correct"]) / max(float(stats["count"]), 1.0)
            if hit_round is None and acc >= target_acc:
                hit_round = round_idx
        injected = sum(len(r["injected"]["dropped"])
                       for r in sim.chaos_ledger.rounds())
        return {"rounds_to_target": hit_round, "final_acc": acc,
                "wall_s": time.perf_counter() - t0,
                "injected_dropouts": injected,
                "provenance": getattr(fed, "provenance", "real")}

    on = leg(tolerance=True)
    off = leg(tolerance=False)
    print(json.dumps({
        "metric": "fedavg_chaos_dropout_rounds_to_target",
        "value": on["rounds_to_target"],
        "unit": f"rounds to {target_acc:.0%} digits test acc under seeded "
                f"20% dropout + 10% stragglers (10 clients, FedAvg+LR, "
                f"tolerance on; max {max_rounds})",
        "vs_baseline": (off["rounds_to_target"] / max(
                            on["rounds_to_target"], 1)
                        if on["rounds_to_target"] is not None
                        and off["rounds_to_target"] is not None else None),
        "tolerance_on_rounds_to_target": on["rounds_to_target"],
        "tolerance_off_rounds_to_target": off["rounds_to_target"],
        "tolerance_on_final_acc": round(on["final_acc"], 4),
        "tolerance_off_final_acc": round(off["final_acc"], 4),
        "injected_dropouts": on["injected_dropouts"],
        "tolerance_on_wall_s": round(on["wall_s"], 2),
        "tolerance_off_wall_s": round(off["wall_s"], 2),
        "data_provenance": on["provenance"],
    }), flush=True)


def bench_async_chaos(straggler_probs=(0.2, 0.4), sync_rounds=60,
                      async_pours=100):
    """Buffered-async axis (core/async_rounds, ISSUE 6): digits FedAvg+LR,
    10 clients, seeded 10% dropout + straggler faults — the sync round
    barrier vs ``round_mode: async_buffered`` (K=5 staleness-weighted
    pours), measured as CLIENT UPDATES INCORPORATED PER SIMULATED HOUR on
    the shared seeded arrival model (``core/async_rounds/arrivals.py``;
    both legs train for real — the clock is simulated because one machine
    serializes what a fleet runs in parallel).

    Time semantics, per leg:

    * sync (the PR 3 barrier): the round closes at a deadline T = 1.35x
      the slowest client's healthy duration (a tuned ``round_timeout_s``);
      stragglers (2.5x slowdown) miss it and their uploads are DROPPED
      (the cross-silo stale-tag behavior), dropped clients stall the round
      to T. The engine leg runs ``chaos_straggler_work: 0`` so training
      matches the clock verdict exactly: a straggler contributes nothing.
    * async: nobody waits — a straggler's update arrives 2.5x late and is
      staleness-DOWN-WEIGHTED, never dropped; a dropped client's dispatch
      is lost and the client redeems into the rotation after its duration.

    The win must GROW with fault rate (4th acceptance criterion): sync
    throughput falls as (1 - p_straggler) — every straggler is wasted
    work plus a stalled barrier — while async only pays the (mild) extra
    time the straggler spends training."""
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.core.async_rounds import client_durations
    from fedml_tpu.core.chaos import FaultPlan
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.async_engine import AsyncBufferedSimulator
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    n_clients, k, p_drop, seed = 10, 5, 0.1, 7
    durations = client_durations(n_clients, random_seed=0)
    deadline = 1.35 * float(np.max(durations))

    def build(extra):
        args = Arguments(
            dataset="digits", model="lr", client_num_in_total=n_clients,
            client_num_per_round=n_clients, epochs=1, batch_size=32,
            learning_rate=0.1, frequency_of_the_test=10_000, random_seed=0,
            chaos_dropout_prob=p_drop, chaos_seed=seed, **extra)
        fed, output_dim = load(args)
        bundle = create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        opt = create_optimizer(args, spec)
        return args, fed, bundle, opt, spec

    def eval_acc(sim):
        stats = sim._evaluate(sim.params, sim.fed.test["x"],
                              sim.fed.test["y"], sim.fed.test["mask"])
        return float(stats["correct"]) / max(float(stats["count"]), 1.0)

    def sync_leg(p_strag):
        # straggler_work 0: a barrier-missed upload contributes nothing —
        # training and the clock read the SAME plan verdicts
        args, fed, bundle, opt, spec = build(dict(
            comm_round=sync_rounds, chaos_straggler_prob=p_strag,
            chaos_straggler_work=0.0))
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=1)
        plan = FaultPlan.from_args(args)
        sim_t, updates = 0.0, 0
        wall0 = time.perf_counter()
        for r in range(sync_rounds):
            sim.run_round(r, hyper)
            healthy = [c for c in range(n_clients)
                       if plan.work_scale(r, c) >= 1.0]
            # any fault stalls the barrier to its deadline; an all-healthy
            # round closes when its slowest member reports
            sim_t += (deadline if len(healthy) < n_clients
                      else float(np.max(durations[healthy])))
            updates += len(healthy)
        return {"updates_per_h": updates / sim_t * 3600.0,
                "versions_per_h": sync_rounds / sim_t * 3600.0,
                "final_acc": eval_acc(sim), "sim_t": sim_t,
                "wall_s": time.perf_counter() - wall0,
                "provenance": getattr(fed, "provenance", "real")}

    def async_leg(p_strag):
        args, fed, bundle, opt, spec = build(dict(
            comm_round=async_pours, round_mode="async_buffered",
            async_buffer_k=k, chaos_straggler_prob=p_strag,
            chaos_straggler_work=0.4))  # 2.5x slowdown, full work
        sim = AsyncBufferedSimulator(args, fed, bundle, opt, spec)
        wall0 = time.perf_counter()
        r = sim.run()
        stal = [h["staleness_mean"] for h in sim.history]
        return {"updates_per_h": (r["updates_aggregated"]
                                  / r["virtual_time_s"] * 3600.0),
                "versions_per_h": r["rounds"] / r["virtual_time_s"] * 3600.0,
                "final_acc": r["final_test_acc"], "sim_t": r["virtual_time_s"],
                "wall_s": time.perf_counter() - wall0,
                "staleness_mean": float(np.mean(stal))}

    legs = {}
    for p in straggler_probs:
        legs[p] = {"sync": sync_leg(p), "async": async_leg(p)}
    p0 = straggler_probs[0]
    ratios = {p: legs[p]["async"]["updates_per_h"]
              / max(legs[p]["sync"]["updates_per_h"], 1e-9)
              for p in straggler_probs}
    rec = {
        "metric": "fedavg_async_chaos_updates_per_hour",
        "value": round(legs[p0]["async"]["updates_per_h"], 1),
        "unit": (f"client updates incorporated per SIMULATED hour (digits "
                 f"FedAvg+LR, 10 clients, K={k} buffered-async pours, "
                 f"seeded {int(p_drop*100)}% dropout + "
                 f"{int(p0*100)}% stragglers at 2.5x slowdown; sync "
                 f"barrier deadline {deadline:.2f}s drops late uploads)"),
        "vs_baseline": round(ratios[p0], 3),
        "data_provenance": legs[p0]["sync"]["provenance"],
    }
    for p in straggler_probs:
        tag = f"straggler_{int(p*100)}pct"
        rec[f"{tag}_sync_updates_per_h"] = round(
            legs[p]["sync"]["updates_per_h"], 1)
        rec[f"{tag}_async_updates_per_h"] = round(
            legs[p]["async"]["updates_per_h"], 1)
        rec[f"{tag}_async_vs_sync"] = round(ratios[p], 3)
        rec[f"{tag}_sync_final_acc"] = round(legs[p]["sync"]["final_acc"], 4)
        rec[f"{tag}_async_final_acc"] = round(
            legs[p]["async"]["final_acc"], 4)
        rec[f"{tag}_async_staleness_mean"] = round(
            legs[p]["async"]["staleness_mean"], 2)
    rec["win_grows_with_fault_rate"] = bool(
        ratios[straggler_probs[-1]] > ratios[p0])
    print(json.dumps(rec), flush=True)


def bench_async_robust(p_strag=0.2, n_byz=2, sync_rounds=50,
                       async_pours=80):
    """Byzantine-robust async axis (ISSUE 7): digits FedAvg+LR, 10
    clients, 2 byzantine clients injecting ``byzantine_random`` at 10x
    scale, seeded 20% stragglers (2.5x slowdown) + 10% dropout — the sync
    DEFENDED barrier (robust_fused engine) vs DEFENDED buffered-async
    pours (async+krum and async+foolsgold), measured as client updates
    incorporated per simulated hour on the shared arrival model (the
    ISSUE 6 clock semantics, unchanged: sync stragglers miss the barrier
    deadline and are dropped; async stragglers arrive late, re-based and
    staleness-down-weighted).

    Byzantine containment is the second column: each async defended
    attacked run is compared against its attack-free twin (same seed,
    same defense) as a relative params distance. Krum must keep the
    10x-scaled rows out — the distance stays in the attack-free run's
    neighborhood while an UNDEFENDED attacked async run lands far away
    (reported for contrast); ``byzantine_kept_out`` pins that. FoolsGold
    faces colluding sign-flipped rows (its sybil signature — random
    byzantine noise is exactly what it cannot see) and its containment
    of a 2-strong collusion on this workload is WEAK in sync and async
    alike — the column the foolsgold leg is honest about is parity of
    behavior (async acc tracks the sync defended acc under the same
    attack) plus the stateful defended-pour throughput."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.core.async_rounds import client_durations
    from fedml_tpu.core.chaos import FaultPlan
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.async_engine import AsyncBufferedSimulator
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    n_clients, k, p_drop, seed = 10, 5, 0.1, 7
    durations = client_durations(n_clients, random_seed=0)
    deadline = 1.35 * float(np.max(durations))

    def build(extra):
        args = Arguments(
            dataset="digits", model="lr", client_num_in_total=n_clients,
            client_num_per_round=n_clients, epochs=1, batch_size=32,
            learning_rate=0.1, frequency_of_the_test=10_000, random_seed=0,
            chaos_dropout_prob=p_drop, chaos_seed=seed, **extra)
        fed, output_dim = load(args)
        bundle = create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        return args, fed, bundle, create_optimizer(args, spec), spec

    def eval_acc(sim):
        stats = sim._evaluate(sim.params, sim.fed.test["x"],
                              sim.fed.test["y"], sim.fed.test["mask"])
        return float(stats["correct"]) / max(float(stats["count"]), 1.0)

    def pvec(params):
        return np.concatenate([np.asarray(jax.device_get(l)).ravel()
                               for l in jax.tree_util.tree_leaves(params)])

    def rel_dist(a, b):
        va, vb = pvec(a), pvec(b)
        return float(np.linalg.norm(va - vb)
                     / max(np.linalg.norm(va), 1e-12))

    # byzantine_client_num rides defense_kw (both the attacker and the
    # defender read it from args; passing it twice would collide).
    # Per-defense attack: krum faces 10x random byzantine rows (the
    # distance outlier it is built to exclude); foolsgold faces COLLUDING
    # 5x flipped rows (the sybil similarity signature it is built to
    # down-weight — random noise is exactly what it cannot see).
    ATK = {"krum": dict(enable_attack=True,
                        attack_type="byzantine_random", attack_scale=10.0),
           "foolsgold": dict(enable_attack=True,
                             attack_type="byzantine_flip",
                             attack_scale=1.0)}

    def defense_kw(d):
        return dict(enable_defense=True, defense_type=d,
                    byzantine_client_num=n_byz,
                    **({"krum_param_m": 3} if d == "multi_krum" else {}))

    def sync_defended_leg(defense):
        args, fed, bundle, opt, spec = build(dict(
            comm_round=sync_rounds, chaos_straggler_prob=p_strag,
            chaos_straggler_work=0.0, **defense_kw(defense),
            **ATK[defense]))
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=1)
        plan = FaultPlan.from_args(args)
        sim_t, updates = 0.0, 0
        wall0 = time.perf_counter()
        for r in range(sync_rounds):
            sim.run_round(r, hyper)
            healthy = [c for c in range(n_clients)
                       if plan.work_scale(r, c) >= 1.0]
            sim_t += (deadline if len(healthy) < n_clients
                      else float(np.max(durations[healthy])))
            updates += len(healthy)
        return {"updates_per_h": updates / sim_t * 3600.0,
                "final_acc": eval_acc(sim),
                "wall_s": time.perf_counter() - wall0,
                "provenance": getattr(fed, "provenance", "real")}

    def async_leg(defense, attacked=True):
        extra = dict(comm_round=async_pours, round_mode="async_buffered",
                     async_buffer_k=k, chaos_straggler_prob=p_strag,
                     chaos_straggler_work=0.4)
        if defense is not None:
            extra.update(defense_kw(defense))
        else:
            extra["byzantine_client_num"] = n_byz
        if attacked:
            extra.update(ATK[defense] if defense is not None
                         else ATK["krum"])
        args, fed, bundle, opt, spec = build(extra)
        sim = AsyncBufferedSimulator(args, fed, bundle, opt, spec)
        wall0 = time.perf_counter()
        r = sim.run()
        return {"updates_per_h": (r["updates_aggregated"]
                                  / r["virtual_time_s"] * 3600.0),
                "final_acc": r["final_test_acc"],
                "params": r["params"],
                "wall_s": time.perf_counter() - wall0}

    legs = {}
    for d in ("krum", "foolsgold"):
        legs[d] = {
            "sync": sync_defended_leg(d),
            "async": async_leg(d),
            "async_clean": async_leg(d, attacked=False),
        }
    # the undefended contrast needs its OWN clean twin: measuring the
    # undefended attacked run against a DEFENDED clean run would inflate
    # the denominator with defense-vs-mean aggregation divergence and let
    # the containment gate pass even when the defense failed
    undefended = async_leg(None)
    undefended_clean = async_leg(None, attacked=False)

    rec = {
        "metric": "fedavg_async_robust_updates_per_hour",
        "value": round(legs["krum"]["async"]["updates_per_h"], 1),
        "unit": (f"client updates incorporated per SIMULATED hour (digits "
                 f"FedAvg+LR, {n_clients} clients, {n_byz} byzantine at "
                 f"10x byzantine_random, K={k} DEFENDED async pours with "
                 f"base-ring re-basing; seeded {int(p_drop*100)}% dropout "
                 f"+ {int(p_strag*100)}% stragglers at 2.5x; sync "
                 f"defended barrier deadline {deadline:.2f}s drops late "
                 "uploads)"),
        "vs_baseline": round(legs["krum"]["async"]["updates_per_h"]
                             / max(legs["krum"]["sync"]["updates_per_h"],
                                   1e-9), 3),
        "data_provenance": legs["krum"]["sync"]["provenance"],
    }
    for d in ("krum", "foolsgold"):
        L = legs[d]
        rec[f"{d}_sync_updates_per_h"] = round(L["sync"]["updates_per_h"],
                                               1)
        rec[f"{d}_async_updates_per_h"] = round(
            L["async"]["updates_per_h"], 1)
        rec[f"{d}_async_vs_sync"] = round(
            L["async"]["updates_per_h"]
            / max(L["sync"]["updates_per_h"], 1e-9), 3)
        rec[f"{d}_sync_final_acc"] = round(L["sync"]["final_acc"], 4)
        rec[f"{d}_async_final_acc"] = round(L["async"]["final_acc"], 4)
        # byzantine containment: attacked-defended vs attack-free-defended
        rec[f"{d}_params_dist_vs_attack_free"] = round(
            rel_dist(L["async_clean"]["params"], L["async"]["params"]), 4)
    rec["undefended_attacked_final_acc"] = round(
        undefended["final_acc"], 4)
    rec["undefended_params_dist_vs_attack_free"] = round(
        rel_dist(undefended_clean["params"], undefended["params"]), 4)
    rec["byzantine_kept_out"] = bool(
        rec["krum_params_dist_vs_attack_free"]
        < 0.1 * rec["undefended_params_dist_vs_attack_free"])
    rec["foolsgold_containment_note"] = (
        "weak vs a 2-strong flip collusion in sync AND async alike — "
        "the leg pins async/sync behavior parity + stateful defended-"
        "pour throughput, not containment")
    print(json.dumps(rec), flush=True)


def bench_chaos_selection(target_acc=0.90, max_rounds=80):
    """Participant-selection axis (core/selection, ISSUE 5): digits
    FedAvg+LR with PARTIAL participation (5 of 10 clients per round)
    under the chaos bench's seeded 20% dropout + 10% stragglers —
    ``uniform`` (the static default: fixed cohort size, blind draw) vs
    ``oort`` (loss-utility cohorts) and ``reputation``, both with
    adaptive over-sampling from the OBSERVED Beta-posterior dropout rate
    in place of the static ``chaos_over_sample`` knob. Same 90% digits
    target as the other chaos leg; a selection strategy must strictly
    beat uniform rounds-to-target for the subsystem to earn its keep."""
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    def leg(strategy: str):
        extra = {}
        if strategy != "uniform":
            extra = dict(client_selection=strategy,
                         selection_adaptive_oversample=True,
                         selection_max_over_sample=1.0)
        args = Arguments(
            dataset="digits", model="lr", client_num_in_total=10,
            client_num_per_round=5, comm_round=max_rounds, epochs=1,
            batch_size=32, learning_rate=0.1, frequency_of_the_test=10_000,
            random_seed=0, chaos_dropout_prob=0.2,
            chaos_straggler_prob=0.1, chaos_straggler_work=0.5,
            chaos_seed=7, chaos_tolerance=True, **extra)
        fed, output_dim = load(args)
        bundle = create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        opt = create_optimizer(args, spec)
        sim = TPUSimulator(args, fed, bundle, opt, spec)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=1)
        t0 = time.perf_counter()
        hit_round, acc = None, 0.0
        for round_idx in range(max_rounds):
            sim.run_round(round_idx, hyper)
            stats = sim._evaluate(sim.params, sim.fed.test["x"],
                                  sim.fed.test["y"], sim.fed.test["mask"])
            acc = float(stats["correct"]) / max(float(stats["count"]), 1.0)
            if hit_round is None and acc >= target_acc:
                hit_round = round_idx
                break
        return {"rounds_to_target": hit_round, "final_acc": acc,
                "wall_s": time.perf_counter() - t0,
                "provenance": getattr(fed, "provenance", "real")}

    uni = leg("uniform")
    oort = leg("oort")
    rep = leg("reputation")
    best = min((l for l in (oort, rep)
                if l["rounds_to_target"] is not None),
               key=lambda l: l["rounds_to_target"], default=oort)
    print(json.dumps({
        "metric": "fedavg_chaos_selection_rounds_to_target",
        "value": best["rounds_to_target"],
        "unit": f"rounds to {target_acc:.0%} digits test acc under seeded "
                f"20% dropout + 10% stragglers (5 of 10 clients/round, "
                f"FedAvg+LR, best selection strategy; max {max_rounds})",
        "vs_baseline": (uni["rounds_to_target"] / max(
                            best["rounds_to_target"], 1)
                        if best["rounds_to_target"] is not None
                        and uni["rounds_to_target"] is not None else None),
        "uniform_rounds_to_target": uni["rounds_to_target"],
        "oort_rounds_to_target": oort["rounds_to_target"],
        "reputation_rounds_to_target": rep["rounds_to_target"],
        "uniform_final_acc": round(uni["final_acc"], 4),
        "oort_final_acc": round(oort["final_acc"], 4),
        "reputation_final_acc": round(rep["final_acc"], 4),
        "uniform_wall_s": round(uni["wall_s"], 2),
        "oort_wall_s": round(oort["wall_s"], 2),
        "data_provenance": uni["provenance"],
    }), flush=True)


def bench_engine_mfu_resnet18():
    """Engine MFU on an MXU-friendly federated CV workload (VERDICT r4
    item 2): FedAvg ResNet-18 (64..512-wide channels), 64 clients/round,
    bf16, fused 8-round dispatch — the proof that the ENGINE feeds the
    MXU once operand shapes allow it, completing the flagship roofline
    story (the ResNet-56 line's 6.9% is the workload's 16..64-wide
    channels, BASELINE.md §3b). Reference counterpart: the NCCL
    simulator's raison d'être
    (``/root/reference/python/fedml/simulation/nccl/README.md:5``).
    vs_baseline = per-sample-normalized speedup over the golden SP loop
    on the same model."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.sp.simulator import SPSimulator
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    n_clients = 64
    args = Arguments(
        dataset="cifar10", model="resnet18", precision="bfloat16",
        client_num_in_total=n_clients, client_num_per_round=n_clients,
        comm_round=1, epochs=1, batch_size=32, learning_rate=0.1,
        frequency_of_the_test=10_000, random_seed=0,
        allow_synthetic=True, synthetic_size=50_000)
    fed, output_dim = load(args)
    provenance = getattr(fed, "provenance", "real")
    bundle = create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                       epochs=1)
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    r = [0]
    BLOCK = 8

    def block():
        sim.run_rounds_fused(r[0], BLOCK, hyper)
        r[0] += BLOCK

    block()
    _force(sim.params)
    # min-of-3 with the trials disclosed (no spread; see time_rounds)
    trials = []
    for _ in range(3):
        t0 = time.perf_counter()
        block()
        _force(sim.params)
        trials.append((time.perf_counter() - t0) / BLOCK)
    round_s = min(trials)
    flops = sim.round_cost_flops(hyper)
    achieved_tflops = flops / round_s / 1e12
    peak = _peak_tflops(jax.devices()[0])
    mfu = (achieved_tflops / (peak * sim.n_devices)) if peak else None

    # SP golden baseline at 1/8 workload, per-sample normalized (same
    # honesty protocol as the flagship line)
    bargs = Arguments(
        dataset="cifar10", model="resnet18", precision="bfloat16",
        client_num_in_total=8, client_num_per_round=8, comm_round=1,
        epochs=1, batch_size=32, learning_rate=0.1,
        frequency_of_the_test=-1,  # timing: no eval inside the timed call
        random_seed=0, allow_synthetic=True, synthetic_size=6_250,
        max_total_samples=6_250)
    bfed, _ = load(bargs)
    sp_sim = SPSimulator(bargs, bfed, bundle,
                         create_optimizer(bargs, spec), spec)
    sp_sim.run(comm_round=1)
    _force(sp_sim.params)
    # same protocol as the engine leg: min over DISCLOSED trials
    sp_trials = []
    for _ in range(3):
        t0 = time.perf_counter()
        sp_sim.run(comm_round=1)
        _force(sp_sim.params)
        sp_trials.append(time.perf_counter() - t0)
    sp_round_s = min(sp_trials)
    vs_baseline = ((sp_round_s / float(bfed.total_train_samples))
                   / (round_s / float(fed.total_train_samples)))
    print(json.dumps({
        "metric": "fedavg_resnet18_engine_mfu",
        "value": round(mfu, 4) if mfu is not None else None,
        "unit": f"MFU (FedAvg ResNet-18, 64 clients/round, bf16, fused "
                f"8-round dispatch, {provenance} data)",
        "vs_baseline": round(vs_baseline, 3),
        "rounds_per_hour": round(3600.0 / round_s, 1),
        "step_time_s": round(round_s, 4),
        "tflops": round(achieved_tflops, 2),
        "round_s_trials": [round(t, 4) for t in trials],
        "sp_baseline_round_s": round(sp_round_s, 4),
        "sp_baseline_trials": [round(t, 4) for t in sp_trials],
        "n_devices": sim.n_devices,
        "data_provenance": provenance,
        "mfu_vs_resnet56_line": "see fedavg_resnet56 line: same engine, "
                                "workload-bound channels",
    }), flush=True)


def bench_robust_defended(metric, unit_note, config_kw, rounds_per_leg=16,
                          block=8, host_kw=None):
    """Defended-round throughput (ISSUEs 2/4): run the SAME robust config
    twice — ``robust_fused: host`` (train dispatch -> host-ordered update
    matrix -> defense dispatch -> server-update dispatch, the pre-fusion
    pipeline; ``host_kw`` can force it further back, e.g.
    ``sharded_defense: false`` for the contribution leg's pre-ISSUE-4
    behavior) vs ``robust_fused: auto`` (the whole robust round as ONE
    jitted SPMD program, fused ``block`` rounds per dispatch). The two
    paths must agree client-for-client — identical defense verdicts imply
    identical final params, which is what ``params_max_abs_diff`` audits;
    a speedup that changes verdicts would be a bug, not a win."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    def build(mode, extra):
        args = Arguments(
            dataset="synthetic_mnist", model="lr",
            client_num_in_total=16, client_num_per_round=16,
            comm_round=rounds_per_leg, epochs=1, batch_size=32,
            learning_rate=0.1, frequency_of_the_test=10_000,
            random_seed=0, robust_fused=mode, **config_kw, **extra)
        fed, output_dim = load(args)
        bundle = create(args, output_dim)
        spec = ClassificationTrainer(bundle.apply)
        sim = TPUSimulator(args, fed, bundle,
                           create_optimizer(args, spec), spec)
        hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                           epochs=1)
        return sim, hyper

    def timed_leg(mode, extra):
        sim, hyper = build(mode, extra)
        r = [0]

        def leg_block():
            sim.run_rounds_fused(r[0], block, hyper)
            r[0] += block

        leg_block()  # compile warmup
        _force(sim.params)
        trials = []
        for _ in range(max(rounds_per_leg // block, 2)):
            t0 = time.perf_counter()
            leg_block()
            _force(sim.params)
            trials.append((time.perf_counter() - t0) / block)
        return min(trials), trials, sim

    fused_s, fused_trials, sim_f = timed_leg("auto", {})
    host_s, host_trials, sim_h = timed_leg("host", host_kw or {})
    assert sim_f.robust_fused and not sim_h.robust_fused
    # verdict audit: both engines ran the identical round sequence above —
    # identical params <=> identical defense verdicts client-for-client
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree_util.tree_leaves(sim_f.params),
                               jax.tree_util.tree_leaves(sim_h.params)))
    speedup = host_s / fused_s if fused_s else None
    print(json.dumps({
        "metric": metric,
        "value": round(3600.0 / fused_s, 1),
        "unit": f"defended rounds/hour ({unit_note}, fused {block}-round "
                f"dispatch)",
        "vs_baseline": round(speedup, 3) if speedup else None,
        "host_path_rounds_per_hour": round(3600.0 / host_s, 1),
        "step_time_s": round(fused_s, 4),
        "host_path_step_time_s": round(host_s, 4),
        "fused_trials": [round(t, 4) for t in fused_trials],
        "host_trials": [round(t, 4) for t in host_trials],
        "params_max_abs_diff": diff,
        "verdicts_identical": bool(diff < 1e-5),
        "n_devices": sim_f.n_devices,
    }), flush=True)


def bench_robust_krum(rounds_per_leg=16, block=8):
    """ISSUE 2 leg: byzantine-flip x3 + multi-krum m=5."""
    bench_robust_defended(
        "fedavg_robust_krum_rounds_per_hour",
        "16 clients, byzantine-flip x3 + multi-krum m=5",
        dict(enable_attack=True, attack_type="byzantine_flip",
             byzantine_client_num=3, attack_scale=5.0, enable_defense=True,
             defense_type="multi_krum", krum_param_m=5),
        rounds_per_leg=rounds_per_leg, block=block)


def bench_robust_rfa(rounds_per_leg=16, block=8):
    """ISSUE 4 leg: RFA (smoothed Weiszfeld geometric median) — the
    strongest defense we ship, host-only before this issue. The fused
    program runs the whole Weiszfeld loop on feature shards (psum'd
    distance fragments per iteration), so the ~3x dispatch tax is gone."""
    bench_robust_defended(
        "fedavg_robust_rfa_rounds_per_hour",
        "16 clients, byzantine-flip x3 + RFA geometric median",
        dict(enable_attack=True, attack_type="byzantine_flip",
             byzantine_client_num=3, attack_scale=5.0, enable_defense=True,
             defense_type="rfa"),
        rounds_per_leg=rounds_per_leg, block=block)


def bench_contribution_fused(rounds_per_leg=16, block=8):
    """ISSUE 4 leg: contribution assessment (LOO) + multi-krum. Before
    this issue ``contribution.enabled`` forced the full host fallback
    (collect dispatch + host defense + host Shapley/LOO); now the round
    stays ONE fused dispatch and the K+1 coalition evaluations run on the
    sharded matrix. The host leg pins the pre-ISSUE-4 behavior
    (``sharded_defense: false`` so the defense AND assessor are
    host-side)."""
    bench_robust_defended(
        "fedavg_contribution_loo_rounds_per_hour",
        "16 clients, multi-krum m=5 + LOO contribution",
        dict(enable_defense=True, defense_type="multi_krum",
             krum_param_m=5, contribution_method="loo"),
        rounds_per_leg=rounds_per_leg, block=block,
        host_kw=dict(sharded_defense="false"))


def bench_hierarchical_femnist(global_rounds=2):
    """BASELINE config 5: cross-device hierarchical FL, FEMNIST shapes
    (28x28x1, 62 classes), MobileNetV3-Small — groups run
    ``group_comm_round`` edge FedAvg rounds per global round, then the
    edge models average (reference ``sp_hierarchicalfl_mnist_lr_example``
    + ``data/FederatedEMNIST`` + ``model/cv/mobilenet.py``). Real FEMNIST
    is a LEAF download (no egress here), so the stand-in is loudly
    synthetic with the real shapes; throughput is shape-determined."""
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.runner import FedMLRunner

    args = Arguments(
        dataset="femnist", model="mobilenet", precision="bfloat16",
        client_num_in_total=24, client_num_per_round=24,
        comm_round=1, epochs=1, batch_size=16, learning_rate=0.05,
        group_num=4, group_comm_round=2,
        federated_optimizer="hierarchicalfl",
        frequency_of_the_test=-1,  # timing: no eval inside the timed call
        random_seed=0, allow_synthetic=True)
    fed, output_dim = load(args)
    provenance = getattr(fed, "provenance", "real")
    bundle = create(args, output_dim)
    runner = FedMLRunner(args, dataset=fed, model=bundle)
    sim = runner.runner
    sim.run(comm_round=1)  # warmup: compile (persistent-cached) + 1 round
    _force(sim.params)
    t0 = time.perf_counter()
    for _ in range(global_rounds):
        sim.run(comm_round=1)
    _force(sim.params)
    round_s = (time.perf_counter() - t0) / global_rounds
    print(json.dumps({
        "metric": "hierarchical_femnist_mobilenet_rounds_per_hour",
        "value": round(3600.0 / round_s, 1),
        "unit": f"global rounds/hour (24 clients, 4 groups x 2 edge "
                f"rounds, MobileNetV3-Small, bf16, {provenance} data)",
        "vs_baseline": None,
        "step_time_s": round(round_s, 4),
        "data_provenance": provenance,
    }), flush=True)


def bench_shakespeare_fedopt(rounds=12, target_acc=0.21):
    """BASELINE.json config 3: FedOpt + LSTM next-character prediction on
    REAL text — the bundled role-partitioned Shakespeare shard (public
    domain, client = speaking role, same natural partition as LEAF
    fed_shakespeare). Reports round throughput and accuracy vs the
    majority-character baseline (~0.19)."""
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.core.algframe.client_trainer import make_trainer_spec
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = Arguments(
        dataset="shakespeare", model="rnn", client_num_in_total=10,
        client_num_per_round=10, comm_round=rounds, epochs=2,
        batch_size=16, learning_rate=0.4, federated_optimizer="fedopt",
        server_optimizer="sgd", server_lr=1.0, server_momentum=0.9,
        frequency_of_the_test=10_000, random_seed=0)
    fed, output_dim = load(args)
    provenance = getattr(fed, "provenance", "real")
    bundle = create(args, output_dim)
    spec = make_trainer_spec(fed, bundle)
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                       epochs=int(args.epochs))

    sim.run_round(0, hyper)  # compile warmup
    _force(sim.params)
    # rounds/hour times run_round ALONE; time-to-target runs on its own
    # wall clock that legitimately includes the per-round eval cost
    # (mirrors bench_time_to_acc) — mixing them would let the eval passes
    # before the target hit contaminate the throughput headline
    train_s = 0.0
    t0 = time.perf_counter()
    t_hit, hit_round = None, None
    for round_idx in range(1, rounds):
        r0 = time.perf_counter()
        sim.run_round(round_idx, hyper)
        _force(sim.params)
        train_s += time.perf_counter() - r0
        if t_hit is None:
            stats = sim._evaluate(sim.params, sim.fed.test["x"],
                                  sim.fed.test["y"], sim.fed.test["mask"])
            acc = float(stats["correct"]) / max(float(stats["count"]), 1.0)
            if acc >= target_acc:
                t_hit, hit_round = time.perf_counter() - t0, round_idx
    dt = train_s / (rounds - 1)
    stats = sim._evaluate(sim.params, sim.fed.test["x"],
                          sim.fed.test["y"], sim.fed.test["mask"])
    acc = float(stats["correct"]) / max(float(stats["count"]), 1.0)
    print(json.dumps({
        "metric": "fedopt_shakespeare_rnn_rounds_per_hour",
        "value": round(3600.0 / dt, 1),
        "unit": "rounds/hour (10 roles, LSTM NWP, FedOpt momentum server)",
        "vs_baseline": None,
        "round_s": round(dt, 4),
        "final_acc": round(acc, 4),
        "target_acc": target_acc,
        "time_to_target_s": round(t_hit, 2) if t_hit else None,
        "rounds_to_target": hit_round,
        "data_provenance": provenance,
    }), flush=True)


def bench_federated_lora(rounds=4):
    """BASELINE.json config 4 as a *federated round* (not just one train
    step): two silos LoRA-fine-tune a causal LM on REAL bundled text; each
    round ships only the adapter tree. Reports federated round latency."""
    import jax.numpy as jnp

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.llm.lora import lora_param_count
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = Arguments(
        dataset="llm", model="causal_lm", precision="bfloat16",
        client_num_in_total=2, client_num_per_round=2, comm_round=rounds,
        epochs=1, batch_size=8, learning_rate=1e-3,
        federated_optimizer="fedavg", frequency_of_the_test=10_000,
        random_seed=0, llm_corpus_fallback="shakespeare",
        llm_hidden_size=512, llm_intermediate_size=1408, llm_num_layers=4,
        llm_num_heads=8, llm_max_seq_len=256, lora_rank=8)
    fed, bundle, spec, _ = build_llm(args)
    provenance = getattr(fed, "provenance", "synthetic")
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                       epochs=1)
    sim.run_round(0, hyper)  # compile warmup
    _force(sim.params)
    t0 = time.perf_counter()
    for round_idx in range(1, rounds):
        sim.run_round(round_idx, hyper)
        _force(sim.params)
    dt = (time.perf_counter() - t0) / (rounds - 1)
    adapters = lora_param_count(sim.params)
    print(json.dumps({
        "metric": "fedllm_lora_federated_round_s",
        "value": round(dt, 4),
        "unit": "s/round (2 silos, LoRA r8 adapters only on the wire, "
                "bf16 causal LM, seq 256)",
        "vs_baseline": None,
        "rounds_per_hour": round(3600.0 / dt, 1),
        "adapter_params": int(adapters),
        "data_provenance": provenance,
    }), flush=True)


def _llm_train_step_timing(seq_len: int, bs: int, steps: int, iters: int,
                           attention_impl: str):
    """Shared harness for the LLM train-step metrics: one causal-LM
    (Llama-style block, bf16) scan-of-steps under jit, timed after a
    compile warmup. ``attention_impl`` is EXPLICIT — the production
    default on TPU is the Pallas flash kernels (llm/federated.py), and a
    bench must name the code path it ran. Returns (s_per_step, n_params,
    flops_per_step)."""
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.llm.model import LLMConfig, init_llm, count_params
    from fedml_tpu.llm.trainer import CausalLMTrainer

    cfg = LLMConfig(vocab_size=8192, hidden_size=1024,
                    intermediate_size=2816, num_layers=8, num_heads=8,
                    max_seq_len=seq_len, dtype="bfloat16",
                    attention_impl=attention_impl)
    rng = jax.random.PRNGKey(0)
    model, params = init_llm(cfg, rng)
    spec = CausalLMTrainer(
        lambda p, x, rng=None, train=False: model.apply(
            {"params": p}, x, train=train))
    batch = {
        "x": jax.random.randint(rng, (bs, seq_len), 0, cfg.vocab_size),
        "y": jax.random.randint(rng, (bs, seq_len), 0, cfg.vocab_size),
        "mask": jnp.ones((bs,), jnp.float32),
    }
    tx = optax.sgd(1e-3)

    def many_steps(params, batch, rng):
        opt_state = tx.init(params)

        def one(carry, i):
            params, opt_state = carry
            (_, aux), grads = jax.value_and_grad(
                spec.loss, has_aux=True)(params, batch,
                                         jax.random.fold_in(rng, i))
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), None

        (params, _), _ = jax.lax.scan(one, (params, opt_state),
                                      jnp.arange(steps))
        return params

    jfn = jax.jit(many_steps)
    _force(jfn(params, batch, rng))
    t0 = time.perf_counter()
    for _ in range(iters):
        _force(jfn(params, batch, rng))
    dt = (time.perf_counter() - t0) / iters / steps
    return dt, count_params(params), cfg.flops_per_token() * bs * seq_len


def bench_llm_mfu(steps=16):
    """Single-chip causal-LM train-step MFU: the FedLLM hot loop with
    MXU-sized matmuls (d_model 1024), through the PRODUCTION attention
    path (Pallas flash on TPU). Demonstrates the runtime's ceiling when
    operand shapes fit the hardware."""
    import jax

    bs, L = 8, 1024
    impl = "flash" if jax.default_backend() == "tpu" else "dense"
    dt, n_params, flops = _llm_train_step_timing(L, bs, steps, iters=2,
                                                 attention_impl=impl)
    achieved = flops / dt / 1e12
    peak = _peak_tflops(jax.devices()[0])
    mfu = achieved / peak if peak else None
    print(json.dumps({
        "metric": "llm_train_step_mfu",
        "value": round(mfu, 4) if mfu is not None else None,
        "unit": f"MFU (bf16, {n_params/1e6:.0f}M params, "
                f"bs{bs} x seq{L}, {impl} attention, single chip)",
        "vs_baseline": None,
        "step_time_s": round(dt, 4),
        "tflops": round(achieved, 2),
        "tokens_per_s": round(bs * L / dt, 0),
        "attention_impl": impl,
    }), flush=True)


def bench_long_context(seq_len=4096, steps=8, metric_suffix=""):
    """Long-context training throughput through the Pallas flash fwd+bwd
    kernels (a dense backward at s=4096 would materialize 64 MiB of
    scores per head per layer; flash trains in O(s·block) memory — the
    property test_flash_bwd_never_materializes_scores asserts on-chip;
    ring attention extends the same contract across chips,
    test_ring_bwd_residuals_stay_linear_in_s). Off-TPU falls back to
    dense and says so in the unit string."""
    import jax

    impl = "flash" if jax.default_backend() == "tpu" else "dense"
    dt, _, flops = _llm_train_step_timing(seq_len, 1, steps, iters=2,
                                          attention_impl=impl)
    peak = _peak_tflops(jax.devices()[0])
    mfu = (flops / dt / 1e12 / peak) if peak else None
    print(json.dumps({
        "metric": "llm_long_context_train_tokens_per_s" + metric_suffix,
        "value": round(seq_len / dt, 0),
        "unit": f"tokens/s (bf16, seq {seq_len}, bs 1, {impl} fwd+bwd, "
                "single chip)",
        "vs_baseline": None,
        "step_time_s": round(dt, 4),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "attention_impl": impl,
    }), flush=True)


def bench_llm_serving(concurrencies=(1, 8, 64), max_new=24):
    """Continuous-batching serving throughput (ISSUE 9): tokens/s and p99
    request latency at concurrency 1/8/64 through the paged-KV batched
    decode engine vs the original one-request-at-a-time full-forward
    loop, single-adapter vs a 64-adapter LoRA bank (every request routed
    to a different silo's personalization). The decode step must compile
    exactly once across the whole sweep — occupancy and adapter mix are
    data."""
    import concurrent.futures as cf

    import jax
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import mlops
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.serving.llm_template import CausalLMPredictor

    args = Arguments(
        dataset="llm_synthetic", model="causal_lm",
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        epochs=1, batch_size=4, learning_rate=1e-3, random_seed=0,
        llm_hidden_size=128, llm_num_layers=2, llm_num_heads=4,
        llm_intermediate_size=352, llm_max_seq_len=128, lora_rank=8)
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    prompts = [f"request {i}: summarize federated round {i * 7}"
               for i in range(max(concurrencies))]

    def sweep(gen, conc):
        """gen(i) -> result dict; returns (tokens_per_s, p99_latency_s)
        with per-request latency measured from sweep start (what a queued
        user experiences)."""
        t0 = time.perf_counter()
        lats = [0.0] * conc
        toks = [0] * conc

        def one(i):
            out = gen(i)
            lats[i] = time.perf_counter() - t0
            toks[i] = out["completion_tokens"]

        with cf.ThreadPoolExecutor(conc) as ex:
            list(ex.map(one, range(conc)))
        wall = time.perf_counter() - t0
        p99 = sorted(lats)[min(conc - 1, int(0.99 * (conc - 1) + 0.5))]
        return sum(toks) / wall, p99

    legs = {}
    # --- sequential baseline: the original single-request path ----------
    seq_pred = CausalLMPredictor(bundle, params, tokenizer=tok)
    seq_pred.generate("warm", max_new_tokens=2)
    seq_lock = __import__("threading").Lock()

    def seq_gen(i):
        with seq_lock:  # the old loop serves one request at a time
            return seq_pred.generate(prompts[i], max_new_tokens=max_new)

    for c in concurrencies:
        tps, p99 = sweep(seq_gen, c)
        legs[f"sequential_c{c}"] = {"tokens_per_s": round(tps, 1),
                                    "p99_latency_s": round(p99, 3)}

    # --- batched: single-adapter bank, then 64-adapter bank -------------
    mlops.install_compile_counter()
    for bank_size, tag in ((1, "bank1"), (64, "bank64")):
        pred = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": max(concurrencies), "block_size": 16,
                        "prefill_chunk": 32, "max_adapters": 66})
        names = [None]
        if bank_size > 1:
            rng = jax.random.PRNGKey(1)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            for a in range(bank_size):
                k = jax.random.fold_in(rng, a)
                tree = jax.tree_util.tree_unflatten(
                    treedef, [0.1 * jax.random.normal(
                        jax.random.fold_in(k, j), l.shape)
                        for j, l in enumerate(leaves)])
                pred.adapter_bank.add(f"silo_{a}", tree)
            names = [f"silo_{a}" for a in range(bank_size)]
        try:
            pred.generate("warm", max_new_tokens=2,
                          adapter=names[0])   # compile warmup
            compiles0 = mlops.compile_count()
            for c in concurrencies:
                tps, p99 = sweep(
                    lambda i: pred.generate(
                        prompts[i], max_new_tokens=max_new,
                        adapter=names[i % len(names)]), c)
                legs[f"batched_{tag}_c{c}"] = {
                    "tokens_per_s": round(tps, 1),
                    "p99_latency_s": round(p99, 3)}
            legs[f"batched_{tag}_recompiles"] = (mlops.compile_count()
                                                 - compiles0)
        finally:
            pred.close()

    top = max(concurrencies)
    speedup = (legs[f"batched_bank1_c{top}"]["tokens_per_s"]
               / max(legs[f"sequential_c{top}"]["tokens_per_s"], 1e-9))
    print(json.dumps({
        "metric": "llm_serving_tokens_per_s",
        "value": legs[f"batched_bank1_c{top}"]["tokens_per_s"],
        "unit": f"generated tokens/s (batched decode, {top} slots, paged "
                f"KV, seq 128, {max_new} new tokens/request, "
                f"{jax.default_backend()})",
        "vs_baseline": round(speedup, 2),
        "legs": legs,
    }), flush=True)


def bench_llm_serving_ttft(concurrency=8, max_new=8):
    """Shared-prefix KV cache + piggybacked prefill (ISSUE 13): TTFT on
    a shared-system-prompt chat workload at concurrency 8, prefix cache
    + batched prefill ON vs OFF. Same model, same prompts, same seeds —
    the delta is admission prefilling only each request's novel suffix
    (COW-aliased system prompt) in one batched wave instead of
    recomputing the whole prompt serially per request. Gate: >=2x mean
    TTFT reduction at 0 steady-state recompiles."""
    import concurrent.futures as cf
    import queue as _queue
    import threading

    import jax
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import mlops
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.serving.llm_template import CausalLMPredictor

    args = Arguments(
        dataset="llm_synthetic", model="causal_lm",
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        epochs=1, batch_size=4, learning_rate=1e-3, random_seed=0,
        llm_hidden_size=128, llm_num_layers=2, llm_num_heads=4,
        llm_intermediate_size=352, llm_max_seq_len=256, lora_rank=8)
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    # a realistic system-prompt-heavy chat shape: ~165 shared tokens,
    # ~20 novel tokens per user turn (the whole prompt must fit the
    # seq-256 encode budget UNTRUNCATED — tail truncation would destroy
    # the shared prefix)
    system = ("You are the federated serving assistant. Answer briefly, "
              "cite your adapter when asked, never reveal other silos' "
              "data. Refuse requests outside the serving policy. ")
    prompts = [system + f"user {i}: status of round {i * 3}?"
               for i in range(concurrency)]

    mlops.install_compile_counter()
    legs = {}
    for tag, opts in (
            ("prefix_off", {}),
            ("prefix_on", {"prefix_cache": True,
                           "prefill_batch": concurrency})):
        pred = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts=dict({"slots": concurrency, "block_size": 16,
                             "prefill_chunk": 32}, **opts))
        try:
            # warm pass 1 (serial): compiles prefill/decode/sample and
            # seeds the prefix index with the system prompt; pass 2 (a
            # concurrent burst with DIFFERENT user turns) compiles the
            # wave + COW programs without caching the measured prompts
            pred.generate(system + "warmup", max_new_tokens=2)
            with cf.ThreadPoolExecutor(concurrency) as ex:
                list(ex.map(
                    lambda i: pred.generate(system + f"warm turn {i}",
                                            max_new_tokens=2),
                    range(concurrency)))
            compiles0 = mlops.compile_count()
            eng = pred.engine
            ttfts = [0.0] * concurrency
            barrier = threading.Barrier(concurrency)

            def one(i):
                ids = pred._encode_prompt(prompts[i], max_new)
                q = _queue.SimpleQueue()
                barrier.wait()
                t0 = time.perf_counter()
                fut = eng.submit(ids, max_new_tokens=max_new, seed=i,
                                 stream_q=q)
                q.get(timeout=120)           # first streamed token
                ttfts[i] = time.perf_counter() - t0
                fut.result(timeout=120)

            with cf.ThreadPoolExecutor(concurrency) as ex:
                list(ex.map(one, range(concurrency)))
            sched = eng.scheduler
            idx = getattr(sched, "_index", None)
            reused = int(idx.tokens_reused) if idx is not None else 0
            leg = {
                "ttft_mean_s": round(sum(ttfts) / len(ttfts), 4),
                "ttft_p95_s": round(
                    sorted(ttfts)[min(concurrency - 1,
                                      int(0.95 * (concurrency - 1)
                                          + 0.5))], 4),
                "steady_state_recompiles": mlops.compile_count()
                - compiles0,
                "kv_fragmentation":
                    sched.kv_pool_stats()["fragmentation"],
                "cached_tokens_reused": reused,
            }
            if idx is not None:
                lookups = idx.hits + idx.misses
                leg["prefix_hit_rate"] = round(
                    idx.hits / max(lookups, 1), 3)
            legs[tag] = leg
        finally:
            pred.close()

    on, off = legs["prefix_on"], legs["prefix_off"]
    speedup = off["ttft_mean_s"] / max(on["ttft_mean_s"], 1e-9)
    print(json.dumps({
        "metric": "llm_serving_ttft",
        "value": on["ttft_mean_s"],
        "unit": f"mean TTFT seconds (c{concurrency}, ~{len(system)} "
                f"shared system-prompt chars, seq 256, prefix cache + "
                f"prefill wave on, {jax.default_backend()})",
        "vs_baseline": round(speedup, 2),
        "legs": legs,
    }), flush=True)


def bench_llm_serving_chaos(concurrency=8, requests=24, max_new=12):
    """Serving-plane fault tolerance (ISSUE 11): tokens/s GOODPUT (tokens
    from successfully finished requests only) and request success rate
    under a seeded crash+stall+NaN serving fault plan, recovery ON
    (watchdog-driven engine resets + requeue) vs recovery OFF (the
    PR-10 behavior: first trip parks the engine unhealthy). Same plan,
    same seed, same requests — the delta is the recovery layer."""
    import concurrent.futures as cf

    import jax
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import mlops
    from fedml_tpu.core.chaos import (FaultLedger, FaultPlan,
                                      ServingChaosInjector)
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.serving.llm_template import CausalLMPredictor

    args = Arguments(
        dataset="llm_synthetic", model="causal_lm",
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        epochs=1, batch_size=4, learning_rate=1e-3, random_seed=0,
        llm_hidden_size=128, llm_num_layers=2, llm_num_heads=4,
        llm_intermediate_size=352, llm_max_seq_len=128, lora_rank=8)
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    # deterministic at-step faults keep both legs time-bounded: the
    # recovery-off leg must not sit out a 30s stall, and the NaN must
    # land inside the session's step window on any machine
    plan_kw = dict(seed=13, serving_stall_at_step=12, serving_stall_s=5.0,
                   serving_nan_at_step=25)

    mlops.install_compile_counter()
    legs = {}
    for tag, max_resets in (("recovery_on", 64), ("recovery_off", 0)):
        ledger = FaultLedger()
        inj = ServingChaosInjector(FaultPlan(**plan_kw), ledger=ledger)
        pred = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": concurrency, "block_size": 16,
                        "prefill_chunk": 32, "watchdog_s": 0.3,
                        "max_resets": max_resets, "max_requeues": 8,
                        "chaos": inj})
        pred._request_timeout_s = 30.0
        try:
            pred.generate("warm", max_new_tokens=2)
            compiles0 = mlops.compile_count()
            t0 = time.perf_counter()
            good_tokens = [0] * requests
            ok = [False] * requests

            def one(i):
                try:
                    out = pred.generate(
                        f"chaos bench req {i}", max_new_tokens=max_new,
                        temperature=(0.0 if i % 2 else 1.1), seed=i)
                except Exception:
                    return   # recovery-off: parked engine rejects
                if out["finish_reason"] in ("stop", "length"):
                    ok[i] = True
                    good_tokens[i] = out["completion_tokens"]

            with cf.ThreadPoolExecutor(concurrency) as ex:
                list(ex.map(one, range(requests)))
            wall = time.perf_counter() - t0
            eng = pred.engine
            legs[tag] = {
                "goodput_tokens_per_s": round(sum(good_tokens) / wall, 1),
                "success_rate": round(sum(ok) / requests, 3),
                "injected_faults": len(ledger.serving_events()),
                "engine_resets": int(eng.resets_total),
                "watchdog_trips": int(eng.watchdog.trips),
                "steady_state_recompiles": mlops.compile_count()
                - compiles0,
            }
        finally:
            pred.close()

    on, off = legs["recovery_on"], legs["recovery_off"]
    ratio = (on["goodput_tokens_per_s"]
             / max(off["goodput_tokens_per_s"], 1e-9))
    print(json.dumps({
        "metric": "llm_serving_chaos_goodput",
        "value": on["goodput_tokens_per_s"],
        "unit": f"goodput tokens/s (c{concurrency}, {requests} requests, "
                f"{max_new} new tokens each, seeded stall+NaN plan, "
                f"watchdog 0.3s, {jax.default_backend()})",
        "vs_baseline": round(ratio, 2),
        "legs": legs,
    }), flush=True)


def bench_llm_serving_fleet(replicas=3, tenants=8, sessions=32, turns=3,
                            max_new=16, concurrency=256):
    """Fleet serving soak (ISSUE 17): aggregate tokens/s on a sustained
    mixed-tenant multi-turn workload (the seeded ``scripts/serving_load``
    generator, c256) across 3+ in-process replicas behind the Gateway,
    with seeded chaos connection drops on the gateway wire and one
    deliberate replica loss mid-soak. ON = cache-aware routing +
    generated-token suffix caching + SLO autoscaler; OFF = the PR-16
    fleet (round-robin routing, prompt-only prefix cache, same chaos,
    same loss). Same model, same seeded workload — the delta is the
    fleet layer. Gate: >=1.3x aggregate tokens/s or >=1.5x mean-TTFT
    reduction, nonzero suffix hits, 0 steady-state recompiles during the
    fixed-fleet window (the post-loss replacement/scale-up is a cold
    start by definition and is reported separately)."""
    import threading

    import jax
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import mlops
    from fedml_tpu.core.chaos import (FaultLedger, FaultPlan,
                                      ServingChaosInjector)
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.serving.autoscale import (Autoscaler, EWMPolicy,
                                             Gateway, ReplicaSet, SLOPolicy)
    from fedml_tpu.serving.llm_template import (CausalLMPredictor,
                                                ChatCompletionRunner)
    from scripts.serving_load import LoadSpec, run_load

    args = Arguments(
        dataset="llm_synthetic", model="causal_lm",
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        epochs=1, batch_size=4, learning_rate=1e-3, random_seed=0,
        llm_hidden_size=128, llm_num_layers=2, llm_num_heads=4,
        llm_intermediate_size=352, llm_max_seq_len=1024, lora_rank=8)
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    # turn_chars=200: every user turn carries ~200 chars of
    # per-session-unique text (pasted-log traffic), so beyond the shared
    # per-tenant system prompt nothing aliases ACROSS sessions — turn-2/3
    # prefill is paid in full unless the follow-up lands on the replica
    # that served turn 1 (cache-aware routing) and the reply blocks were
    # indexed at release (suffix cache)
    spec = LoadSpec(tenants=tenants, sessions_per_tenant=sessions,
                    turns_per_session=turns, seed=0, mean_gap_s=0.002,
                    max_tokens=max_new, turn_chars=200)
    total_requests = spec.total_requests

    mlops.install_compile_counter()
    legs = {}
    for tag, fleet_on in (("fleet_off", False), ("fleet_on", True)):
        ledger = FaultLedger()
        chaos = ServingChaosInjector(
            FaultPlan(seed=17, serving_conn_drop_prob=0.04), ledger=ledger)
        # num_blocks: grow the KV pool past the slot default (slots x
        # max_blocks_per_slot = 1024) so per-session conversation chains
        # survive cascade eviction across 256 concurrent sessions; same
        # pool both legs — the delta stays the fleet layer, not memory
        opts = {"slots": 16, "block_size": 16, "prefill_chunk": 64,
                "prefix_cache": True, "prefill_batch": 8,
                "request_timeout_s": 600.0, "num_blocks": 8192,
                "suffix_cache": fleet_on}

        def factory(opts=opts):
            return CausalLMPredictor(bundle, params, tokenizer=tok,
                                     mode="batch", stream=True,
                                     batch_opts=dict(opts))

        rs = ReplicaSet(predictor_factory=factory, min_replicas=replicas,
                        max_replicas=replicas + 1,
                        runner_cls=ChatCompletionRunner,
                        drain_grace_s=2.0 if fleet_on else 0.0)
        gw = Gateway(rs, unhealthy_ttl_s=0.75, max_failovers=4,
                     backoff_seed=0, chaos=chaos,
                     cache_aware=fleet_on, heal_probe=fleet_on)
        # ON: the SLO policy may add the +1 burst replica under queue /
        # headroom breach. OFF: the PR-16 loop — health_check still
        # replaces the lost replica (both legs heal), but the legacy
        # policy never scales past min_replicas under this traffic.
        policy = (SLOPolicy(queue_depth_per_replica=32.0,
                            kv_headroom_min=1, cooldown_s=3.0)
                  if fleet_on
                  else EWMPolicy(target_qps_per_replica=1e9))
        asc = Autoscaler(gw, policy, interval_s=0.25)
        lock = threading.Lock()
        ttfts, tokens, oks = [], [], []
        post_loss_mark = [None]     # index into oks at the loss instant
        steady_recompiles = [None]
        done = threading.Event()

        def send(messages, meta):
            req = {"messages": messages, "stream": True,
                   "max_tokens": int(meta["max_tokens"]),
                   "temperature": float(meta["temperature"]),
                   "seed": int(meta["seed"])}
            t0 = time.perf_counter()
            first, parts, usage = None, [], None
            try:
                for data in gw.stream(req, timeout=600.0):
                    evt = json.loads(data)
                    ch = evt["choices"][0]
                    delta = ch.get("delta") or {}
                    if delta.get("content"):
                        if first is None:
                            first = time.perf_counter() - t0
                        parts.append(delta["content"])
                    if ch.get("finish_reason"):
                        usage = ch.get("usage") or {}
            except Exception:
                with lock:
                    oks.append(False)
                raise
            with lock:
                oks.append(True)
                if first is not None:
                    ttfts.append(first)
                tokens.append(int((usage or {}).get(
                    "completion_tokens", len(parts))))
            return "".join(parts)

        def disrupt():
            # wait out the fixed-fleet (steady-state) window, snapshot
            # the recompile count, then lose a replica and hand the
            # fleet to the SLO autoscaler for the rest of the soak
            while not done.is_set():
                with lock:
                    n = len(oks)
                if n >= int(0.4 * total_requests):
                    break
                time.sleep(0.05)
            if done.is_set():
                return
            steady_recompiles[0] = mlops.compile_count() - compiles0
            with rs._lock:
                victim = rs.replicas[-1] if rs.replicas else None
            if victim is not None:
                victim.stop()           # replica loss, mid-soak
            with lock:
                post_loss_mark[0] = len(oks)
            while not done.is_set():
                try:
                    asc.step()   # heal + replace + SLO scale
                except Exception:
                    pass
                done.wait(0.3)

        try:
            # warm every replica: compiles prefill/wave/COW/decode/sample
            # and seeds each prefix index with nothing the soak measures
            with rs._lock:
                runners = list(rs.replicas)
            import concurrent.futures as cf
            for r in runners:
                r.predictor.generate("fleet warmup", max_new_tokens=2)
                with cf.ThreadPoolExecutor(8) as ex:
                    list(ex.map(
                        lambda i, p=r.predictor: p.generate(
                            f"fleet warm turn {i}", max_new_tokens=2),
                        range(8)))
            compiles0 = mlops.compile_count()
            watcher = threading.Thread(target=disrupt, daemon=True)
            watcher.start()
            t0 = time.perf_counter()
            run_load(send, spec, concurrency=concurrency)
            wall = time.perf_counter() - t0
            done.set()
            watcher.join(timeout=10.0)

            with rs._lock:
                engines = [r.predictor.engine for r in rs.replicas
                           if getattr(r, "predictor", None) is not None
                           and r.predictor.engine is not None]
            sfx_hits = sfx_tokens = hits = misses = 0
            for eng in engines:
                idx = getattr(eng.scheduler, "_index", None)
                if idx is None:
                    continue
                sfx_hits += idx.suffix_hits
                sfx_tokens += idx.suffix_tokens_reused
                hits += idx.hits
                misses += idx.misses
            mark = post_loss_mark[0]
            with lock:
                n_ok = sum(oks)
                post = oks[mark:] if mark is not None else []
                ttft_sorted = sorted(ttfts)
                total_tokens = sum(tokens)
            leg = {
                "tokens_per_s": round(total_tokens / wall, 1),
                "ttft_mean_s": round(
                    sum(ttft_sorted) / max(len(ttft_sorted), 1), 4),
                "ttft_p99_s": round(
                    ttft_sorted[min(len(ttft_sorted) - 1,
                                    int(0.99 * (len(ttft_sorted) - 1)
                                        + 0.5))], 4) if ttft_sorted
                else 0.0,
                "success_rate": round(n_ok / max(len(oks), 1), 3),
                "post_loss_success_rate": round(
                    sum(post) / max(len(post), 1), 3),
                "suffix_hits": int(sfx_hits),
                "suffix_tokens_reused": int(sfx_tokens),
                "prefix_hit_rate": round(
                    hits / max(hits + misses, 1), 3),
                "steady_state_recompiles": steady_recompiles[0],
                "cold_start_compiles": mlops.compile_count() - compiles0
                - (steady_recompiles[0] or 0),
                "scale_events": int(asc.scale_events),
                "injected_conn_drops": len(ledger.serving_events()),
                "replicas_end": len(rs),
                "routes": dict(gw.route_counts),
            }
            legs[tag] = leg
        finally:
            done.set()
            rs.stop()

    on, off = legs["fleet_on"], legs["fleet_off"]
    ratio = on["tokens_per_s"] / max(off["tokens_per_s"], 1e-9)
    ttft_ratio = off["ttft_mean_s"] / max(on["ttft_mean_s"], 1e-9)
    print(json.dumps({
        "metric": "llm_serving_fleet_tokens_per_s",
        "value": on["tokens_per_s"],
        "unit": f"aggregate tokens/s (c{concurrency}, {tenants} tenants x "
                f"{sessions} sessions x {turns} turns, {replicas} "
                f"replicas, chaos conn-drops + mid-soak replica loss, "
                f"{jax.default_backend()})",
        "vs_baseline": round(ratio, 2),
        "ttft_reduction": round(ttft_ratio, 2),
        "legs": legs,
    }), flush=True)


def bench_llm_serving_adapter_churn(concurrency=64, rounds=4, max_new=12,
                                    bank_size=8):
    """Sustained adapter churn (ISSUE 14 satellite, the ROADMAP's
    in-but-unmeasured leg): c64 traffic flows through the batched engine
    while ONE adapter per round is re-exported into the watched dir and
    hot-swapped live through the PR 12 watcher/pin machinery. The
    numbers that matter: tokens/s under churn vs a churn-free round on
    the same engine (the swap is a host→device stack refresh, so the
    gap should be noise) and ZERO recompiles across the whole run."""
    import concurrent.futures as cf
    import os
    import tempfile

    import jax
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import mlops
    from fedml_tpu.llm.federated import build_llm, save_adapter_artifacts
    from fedml_tpu.serving.batch import AdapterBank
    from fedml_tpu.serving.llm_template import CausalLMPredictor

    args = Arguments(
        dataset="llm_synthetic", model="causal_lm",
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        epochs=1, batch_size=4, learning_rate=1e-3, random_seed=0,
        llm_hidden_size=128, llm_num_layers=2, llm_num_heads=4,
        llm_intermediate_size=352, llm_max_seq_len=128, lora_rank=8)
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))

    def rand_adapter(seed):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = jax.random.PRNGKey(seed)
        return jax.tree_util.tree_unflatten(
            treedef, [0.1 * jax.random.normal(jax.random.fold_in(key, j),
                                              l.shape)
                      for j, l in enumerate(leaves)])

    export_dir = tempfile.mkdtemp(prefix="churn_adapters_")
    names = [f"silo_{a}" for a in range(bank_size)]
    save_adapter_artifacts({n: rand_adapter(a)
                            for a, n in enumerate(names)}, export_dir)
    # capacity: bank rows + a fresh row per swap (retired rows rejoin
    # the pool once their last in-flight pin drops)
    bank = AdapterBank.from_artifacts(export_dir,
                                      capacity=bank_size + rounds + 4)
    pred = CausalLMPredictor(
        bundle, params, tokenizer=tok, mode="batch",
        batch_opts={"slots": concurrency, "block_size": 16,
                    "prefill_chunk": 32},
        adapter_bank=bank)
    prompts = [f"request {i}: summarize federated round {i * 7}"
               for i in range(concurrency)]

    def sweep():
        t0 = time.perf_counter()
        lats = [0.0] * concurrency
        toks = [0] * concurrency

        def one(i):
            out = pred.generate(prompts[i], max_new_tokens=max_new,
                                adapter=names[i % len(names)])
            lats[i] = time.perf_counter() - t0
            toks[i] = out["completion_tokens"]

        with cf.ThreadPoolExecutor(concurrency) as ex:
            list(ex.map(one, range(concurrency)))
        wall = time.perf_counter() - t0
        p99 = sorted(lats)[min(concurrency - 1,
                               int(0.99 * (concurrency - 1) + 0.5))]
        return sum(toks) / wall, p99

    legs = {}
    try:
        mlops.install_compile_counter()
        pred.generate("warm", max_new_tokens=2, adapter=names[0])
        sweep()                                    # warm the sweep path
        tps0, p99_0 = sweep()                      # churn-free reference
        legs["no_churn"] = {"tokens_per_s": round(tps0, 1),
                            "p99_latency_s": round(p99_0, 3)}
        bank.watch_dir(export_dir, poll_s=0.1)
        time.sleep(0.15)                           # initial scan settles
        compiles0 = mlops.compile_count()
        churn_tps, churn_p99 = [], []
        for r in range(rounds):
            victim = names[r % len(names)]
            with cf.ThreadPoolExecutor(1) as swapper:
                # one hot-swap per round, landing MID-TRAFFIC: the
                # exporter thread re-writes the artifact while the c64
                # sweep decodes against the bank
                fut = swapper.submit(
                    save_adapter_artifacts,
                    {victim: rand_adapter(1000 + r)}, export_dir)
                tps, p99 = sweep()
                fut.result()
            churn_tps.append(tps)
            churn_p99.append(p99)
        deadline = time.time() + 10                # let the last swap land
        while time.time() < deadline and bank.swaps < rounds:
            time.sleep(0.05)
        recompiles = mlops.compile_count() - compiles0
        legs["churn"] = {
            "tokens_per_s": round(sum(churn_tps) / len(churn_tps), 1),
            "tokens_per_s_best": round(max(churn_tps), 1),
            "p99_latency_s": round(max(churn_p99), 3),
            "swaps": int(bank.swaps),
            "recompiles": int(recompiles)}
    finally:
        pred.close()
    ratio = legs["churn"]["tokens_per_s"] / max(
        legs["no_churn"]["tokens_per_s"], 1e-9)
    print(json.dumps({
        "metric": "llm_serving_adapter_churn_tokens_per_s",
        "value": legs["churn"]["tokens_per_s"],
        "unit": f"generated tokens/s (c{concurrency}, {bank_size}-adapter "
                f"bank, one watched hot-swap per round x{rounds}, "
                f"{max_new} new tokens/request, "
                f"{jax.default_backend()})",
        "vs_baseline": round(ratio, 3),
        "legs": legs,
    }), flush=True)


def bench_cohort_assembly(populations=(10_000, 100_000, 1_000_000),
                          rounds=8, k=128):
    """Million-client control plane (core/selection, ISSUE 15): per-round
    cohort-assembly cost over synthetic populations of 10k/100k/1M
    devices — streaming eligibility scan (hash-derived charging/idle/
    unmetered flags, ~51% eligible) + Oort-utility scoring over the
    SPARSE stats store + chunked partial top-k + the deadline pacer —
    and, on the same populations, the selection strategies' per-round
    ``select()`` cost with seeded candidate pools (``oort``) vs the
    uniform stream. The headline is the 1M-client assembly wall; the leg
    table carries the selection-overhead-vs-population column and the
    sublinearity ratio (1M ÷ 10k — linear scaling would read ~100x)."""
    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.selection import (DeadlinePacer, SelectionManager,
                                          StreamingCohortAssembler,
                                          make_stats_store,
                                          population_chunks)
    from fedml_tpu.core.selection.cohort import _seeded_jitter

    def leg(n: int):
        args = Arguments(
            dataset="synthetic_mnist", model="lr", client_num_in_total=n,
            client_num_per_round=k, random_seed=7,
            sampling_stream="seeded", selection_store="sparse",
            cohort_require_charging=True, allow_synthetic=True)
        store = make_stats_store(args, n)
        # realistic warm history: a few thousand previously-seen devices
        rng = np.random.default_rng(0)
        touched = rng.choice(n, size=min(4096, n // 2), replace=False)
        for i, cid in enumerate(touched):
            store.record_selected(i % 64, [int(cid)])
            store.record_loss(int(cid), float(rng.gamma(2.0, 1.0)))
            store.record_latency(int(cid), float(rng.gamma(2.0, 5.0)))
            store.record_availability(int(cid),
                                      participated=bool(i % 5),
                                      work=1.0)
        asm = StreamingCohortAssembler(args, store, n)
        pacer = DeadlinePacer.from_args(args)

        def elig(ids):  # ~51% "charging" via the seeded hash
            return _seeded_jitter(ids, 99, 0) < 0.51

        walls = []
        for r in range(rounds):
            t0 = time.perf_counter()
            res = asm.assemble(r, pacer.target_cohort(k),
                               population_chunks(n, asm.chunk),
                               eligible_fn=elig)
            walls.append((time.perf_counter() - t0) * 1e3)
            pacer.observe_round(completed=int(0.9 * len(res.cohort)),
                                expected=len(res.cohort),
                                wall_s=pacer.deadline_s * 0.4)
        # strategy select() overhead on the same population (oort rides
        # a seeded candidate pool above the threshold; uniform rides the
        # streaming sampler)
        sel = {}
        for strat in ("uniform", "oort"):
            mgr = SelectionManager(
                Arguments(dataset="synthetic_mnist", model="lr",
                          client_num_in_total=n, client_num_per_round=k,
                          random_seed=7, sampling_stream="seeded",
                          selection_store="sparse",
                          client_selection=strat, allow_synthetic=True),
                n)
            t0 = time.perf_counter()
            for r in range(rounds):
                mgr.select(r, k)
            sel[strat] = (time.perf_counter() - t0) * 1e3 / rounds
        return {"assembly_ms": round(float(np.median(walls)), 3),
                "select_oort_ms": round(sel["oort"], 3),
                "select_uniform_ms": round(sel["uniform"], 3),
                "touched_rows": store.num_touched()}

    legs = {f"pop_{n//1000}k" if n < 1_000_000 else "pop_1m": leg(n)
            for n in populations}
    lo = legs[next(iter(legs))]
    hi = legs[list(legs)[-1]]
    ratio = hi["assembly_ms"] / max(lo["assembly_ms"], 1e-9)
    sel_ratio = hi["select_oort_ms"] / max(lo["select_oort_ms"], 1e-9)
    print(json.dumps({
        "metric": "cross_device_cohort_assembly_ms",
        "value": hi["assembly_ms"],
        "unit": f"median ms to assemble a {k}-cohort from 1M synthetic "
                f"devices (streaming eligibility + oort utility + "
                f"partial top-k, sparse store; legs: per-population "
                f"assembly and strategy-select overhead)",
        # ratios ride legs so bench_diff gates them (probe "overhead"
        # reads lower-is-better: selection must stay sublinear)
        "legs": dict(legs, scaling={
            "overhead_ratio_1m_vs_10k": round(ratio, 2),
            "select_overhead_ratio_1m_vs_10k": round(sel_ratio, 2)}),
        "population_scaling": f"{populations[-1] // populations[0]}x "
                              f"population -> {ratio:.1f}x assembly cost",
    }), flush=True)


def bench_cross_device_multitenant(n=100_000, rounds=6):
    """Durable multi-tenant fleet plane (core/fleet, ISSUE 18): 100k
    devices in a sqlite ``DeviceRegistry``, a ``TaskPlane`` running 3
    concurrent tasks (train k=256, federated analytics k=128, LLM-LoRA
    k=64) against that one population under a per-device fairness cap.
    Each timed round is a full plane step — per-task streaming assembly
    over the registry's id pages, atomic claims, release + participation
    records — under a logical clock. The headline is control-plane
    rounds/hour; the legs pin the ISOLATION and FAIRNESS columns
    (``overlap_devices`` and ``fairness_violations`` must read 0) plus
    the per-task cohort sizes and the assign wall."""
    import tempfile

    import numpy as np

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.fleet import DeviceRegistry, TaskPlane

    tasks = (("train", 256, "training"), ("fa", 128, "analytics"),
             ("lora", 64, "llm"))
    cap, window_s = 3, 3600.0
    with tempfile.TemporaryDirectory() as td:
        reg = DeviceRegistry(f"{td}/fleet.db")
        t0 = time.perf_counter()
        ids = np.arange(n)
        for lo in range(0, n, 10_000):
            reg.register_many(ids[lo:lo + 10_000], now=0.0)
        register_s = time.perf_counter() - t0
        args = Arguments(dataset="synthetic_mnist", model="lr",
                         client_num_in_total=n, random_seed=7,
                         selection_store="sparse", oort_alpha=0.0,
                         pacer_over_sample=1.0,
                         fleet_max_rounds_per_window=cap,
                         fleet_fairness_window_s=window_s,
                         allow_synthetic=True)
        plane = TaskPlane(args, reg, population=n)
        for tid, k, kind in tasks:
            plane.add_task(tid, cohort_k=k, kind=kind)
        walls, assign_ms, sizes = [], [], {t[0]: [] for t in tasks}
        for r in range(rounds):
            now = 60.0 * (r + 1)
            t0 = time.perf_counter()
            cohorts = plane.assign_round(now=now)
            t_assign = time.perf_counter() - t0
            for tid, cohort in cohorts.items():
                plane.observe_round(tid, cohort, wall_s=30.0,
                                    now=now + 30.0)
                sizes[tid].append(len(cohort))
            walls.append(time.perf_counter() - t0)
            assign_ms.append(t_assign * 1e3)
        audit = reg.audit(cap=cap, window_s=window_s)
        round_s = float(np.median(walls))
        print(json.dumps({
            "metric": "cross_device_multitenant_rounds_per_hour",
            "value": round(3600.0 / round_s, 1),
            "unit": f"full fleet-plane rounds/hour (3 concurrent tasks, "
                    f"{n // 1000}k-device sqlite registry, fairness cap "
                    f"{cap}/{window_s:.0f}s; isolation and fairness "
                    f"columns must read 0)",
            "legs": {
                "assign_ms": round(float(np.median(assign_ms)), 1),
                "round_s": round(round_s, 3),
                "register_100k_s": round(register_s, 2),
                "cohort_train": int(np.median(sizes["train"])),
                "cohort_fa": int(np.median(sizes["fa"])),
                "cohort_lora": int(np.median(sizes["lora"])),
                "overlap_devices": audit["overlap"],
                "fairness_violations": audit["cap_violations"],
                "denied_busy": plane.denied_busy,
                "denied_cap": plane.denied_cap,
            },
        }), flush=True)


def _sum_collective_kinds(colls, block):
    """Per-(op, group) wire bytes per round — SUMMED across distinct
    operand shapes (the roofline rows key on shape too; collapsing by
    overwrite would understate any kind with >1 payload shape)."""
    out = {}
    for c in colls:
        key = f"{c['op']}_g{c['group']}"
        out[key] = round(out.get(key, 0.0) + c["wire_bytes"] / block, 1)
    return out


def bench_robust_rfa_weak_scaling(device_counts=(1, 4, 8),
                                  rounds_per_leg=16, block=8,
                                  clients_per_device=2):
    """Weak scaling of the fused defended round (ISSUE 14 satellite —
    the missing BASELINE leg): `fedavg_robust_rfa_rounds_per_hour` at
    1/4/8 devices with CONSTANT per-device work (2 clients/device), so
    ideal scaling is a flat rounds/hour line. Each leg also captures the
    program's roofline (obs_roofline) and reports the predicted
    per-device collective wire bytes per round — the column that tells
    the multi-chip item whether a scaling cliff is the defense's
    psum/all_to_all traffic or something else. On the CPU host mesh the
    times are shape-comparable, the collective bytes exact, and a TPU
    re-run is the real verdict (BASELINE.md measurement-honesty note)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.constants import AXIS_CLIENT
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.core.obs import roofline as obs_roofline
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    devs = jax.devices()
    counts = [k for k in device_counts if k <= len(devs)]
    legs = {}
    # ISSUE 16: a second leg family with the int8-quantized all_to_all
    # re-layout (robust_relayout_quant) — same schedule, 4x fewer
    # re-layout wire bytes; its efficiency column is measured against its
    # OWN single-device base so the two families stay comparable
    for quant, suffix in ((None, ""), ("int8", "_int8")):
        base_rph = None
        for k in counts:
            n_clients = clients_per_device * k
            n_byz = max(1, n_clients // 8)
            args = Arguments(
                dataset="synthetic_mnist", model="lr",
                client_num_in_total=n_clients,
                client_num_per_round=n_clients,
                comm_round=rounds_per_leg, epochs=1, batch_size=32,
                learning_rate=0.1, frequency_of_the_test=10_000,
                random_seed=0, enable_attack=True,
                attack_type="byzantine_flip", byzantine_client_num=n_byz,
                attack_scale=5.0, enable_defense=True, defense_type="rfa",
                robust_relayout_quant=quant, obs_roofline=True)
            fed, output_dim = load(args)
            bundle = create(args, output_dim)
            spec = ClassificationTrainer(bundle.apply)
            mesh = Mesh(np.asarray(devs[:k]), (AXIS_CLIENT,))
            sim = TPUSimulator(args, fed, bundle,
                               create_optimizer(args, spec), spec,
                               mesh=mesh)
            hyper = TrainHyper(
                learning_rate=jnp.float32(args.learning_rate), epochs=1)
            r = [0]

            def leg_block():
                sim.run_rounds_fused(r[0], block, hyper)
                r[0] += block

            leg_block()                       # compile warmup + capture
            _force(sim.params)
            trials = []
            for _ in range(max(rounds_per_leg // block, 2)):
                t0 = time.perf_counter()
                leg_block()
                _force(sim.params)
                trials.append((time.perf_counter() - t0) / block)
            step_s = min(trials)
            rph = 3600.0 / step_s
            if base_rph is None:
                base_rph = rph
            rep = obs_roofline.report("robust_rounds_fused") or {}
            coll = rep.get("collective_wire_bytes")
            legs[f"d{k}{suffix}"] = {
                "rounds_per_hour": round(rph, 1),
                "step_time_s": round(step_s, 4),
                "clients": n_clients,
                "weak_scaling_efficiency": round(rph / base_rph, 3),
                "collective_wire_bytes_per_round": (
                    round(coll / block, 1) if coll is not None else None),
                "collective_kinds": _sum_collective_kinds(
                    rep.get("collectives", []), block),
            }
    top = f"d{counts[-1]}"
    print(json.dumps({
        "metric": "fedavg_robust_rfa_weak_scaling_efficiency",
        "value": legs[top]["weak_scaling_efficiency"],
        "unit": f"rounds/hour at {counts[-1]} devices ÷ at 1 device, "
                f"{clients_per_device} clients/device, byzantine-flip + "
                f"RFA fused {block}-round dispatch "
                f"({jax.default_backend()})",
        "vs_baseline": None,
        "legs": legs,
    }), flush=True)


def bench_fused_block(iters=12, batch=32):
    """Fused conv->GroupNorm->residual->ReLU block step (ISSUE 16
    tentpole): one resnet56 narrow-stage BasicBlock fwd+bwd at the
    flagship 32x32x16 geometry, Pallas kernel vs the unfused flax path.
    CPU-honest: off-TPU the kernel runs in Pallas INTERPRET mode, so the
    CPU ``fused_ms`` measures plumbing, not the kernel — the speedup leg
    is only a perf verdict on a TPU capture (BASELINE.md
    measurement-honesty note). The headline is the fused step time
    (lower is better); ``speedup`` = reference_ms / fused_ms."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.model.cv.resnet import BasicBlock

    x = jax.random.normal(jax.random.PRNGKey(0), (batch, 32, 32, 16))

    def leg(fused):
        m = BasicBlock(16, 1, fused=fused)
        variables = m.init(jax.random.PRNGKey(1), x)
        step = jax.jit(jax.grad(
            lambda v: jnp.sum(m.apply(v, x) ** 2)))
        _force(step(variables))           # compile warmup
        trials = []
        for _ in range(iters):
            t0 = time.perf_counter()
            _force(step(variables))
            trials.append(time.perf_counter() - t0)
        return min(trials) * 1e3

    reference_ms = leg("")
    fused_ms = leg("pallas")
    print(json.dumps({
        "metric": "fedavg_resnet56_fused_block_step_ms",
        "value": round(fused_ms, 3),
        "unit": f"ms/step, BasicBlock(16) fwd+bwd batch {batch} at "
                f"32x32x16, fused pallas"
                f"{'-interpret' if jax.default_backend() != 'tpu' else ''}"
                f" vs flax ({jax.default_backend()})",
        "vs_baseline": None,
        "legs": {
            "reference_ms": round(reference_ms, 3),
            "fused_ms": round(fused_ms, 3),
            "speedup": round(reference_ms / fused_ms, 3),
        },
    }), flush=True)


def run() -> int:
    """Run every leg; a leg that raises prints an error line and the
    others still run, but the process then exits non-zero."""
    failed = []
    for name, fn in (
            ("fedavg_resnet56_cifar10_rounds_per_hour", bench_flagship),
            ("fedavg_resnet56_fused_block_step_ms", bench_fused_block),
            ("fedavg_resnet18_engine_mfu", bench_engine_mfu_resnet18),
            ("fedavg_robust_krum_rounds_per_hour", bench_robust_krum),
            ("fedavg_robust_rfa_rounds_per_hour", bench_robust_rfa),
            ("fedavg_robust_rfa_weak_scaling_efficiency",
             bench_robust_rfa_weak_scaling),
            ("fedavg_contribution_loo_rounds_per_hour",
             bench_contribution_fused),
            ("hierarchical_femnist_mobilenet_rounds_per_hour",
             bench_hierarchical_femnist),
            ("fedavg_digits_time_to_90pct_s", bench_time_to_acc),
            ("fedavg_cross_silo_wire_bytes_per_round",
             bench_cross_silo_wire),
            ("fedavg_chaos_dropout_rounds_to_target", bench_chaos_dropout),
            ("fedavg_async_chaos_updates_per_hour", bench_async_chaos),
            ("fedavg_async_robust_updates_per_hour", bench_async_robust),
            ("fedavg_chaos_selection_rounds_to_target",
             bench_chaos_selection),
            ("cross_device_cohort_assembly_ms", bench_cohort_assembly),
            ("cross_device_multitenant_rounds_per_hour",
             bench_cross_device_multitenant),
            ("fedopt_shakespeare_rnn_rounds_per_hour",
             bench_shakespeare_fedopt),
            ("fedllm_lora_federated_round_s", bench_federated_lora),
            ("llm_serving_tokens_per_s", bench_llm_serving),
            ("llm_serving_adapter_churn_tokens_per_s",
             bench_llm_serving_adapter_churn),
            ("llm_serving_ttft", bench_llm_serving_ttft),
            ("llm_serving_chaos_goodput", bench_llm_serving_chaos),
            ("llm_serving_fleet_tokens_per_s", bench_llm_serving_fleet),
            ("llm_train_step_mfu", bench_llm_mfu),
            ("llm_long_context_train_tokens_per_s", bench_long_context),
            ("llm_long_context_train_tokens_per_s_seq8192",
             lambda: bench_long_context(seq_len=8192, steps=4,
                                        metric_suffix="_seq8192"))):
        try:  # a broken line must never mask the others
            fn()
        except Exception as e:
            failed.append(name)
            print(json.dumps({"metric": name,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    import sys
    sys.exit(run())
