"""Per-stage profile of the flagship FedAvg ResNet-56/CIFAR round.

VERDICT r3 item 1: name where every microsecond of the ~2.7 s round goes.
Strategy: stage ablation on the chip — time progressively simpler programs that share the flagship's
hot loop, so each delta isolates one stage:

  A. dispatch          — empty jitted fn + scalar readback (dispatch constant)
  B. sgd_stream bs=32  — shared-weight SGD scan, same total step count:
                         the per-step floor with ZERO federated machinery
  C. sgd_stream bs=256 — same at the roofline's perfect-batching size
                         (names the fixed per-op overhead amortization)
  D. local_loop        — scan over clients of run_local_sgd (dynamic-trip
                         while_loop + per-step batch gather + shuffle),
                         no schedule/accumulate/aggregate
  E. full_round        — the bench round (engine.run_round)

  B-A        = conv compute at the workload's real batch size
  D-B        = while_loop + gather + shuffle bookkeeping
  E-D        = schedule + update-accumulate + psum + server transform
               + per-round host work

Prints one JSON line per stage plus a summary split of the full round.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def _force(x):
    return float(jax.tree_util.tree_leaves(x)[0].sum())


def _time(fn, iters=3, warmup=1):
    for _ in range(warmup):
        _force(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        _force(fn())
    return (time.perf_counter() - t0) / iters


def main():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import ClassificationTrainer
    from fedml_tpu.core.algframe.local_training import run_local_sgd
    from fedml_tpu.core.algframe.types import ClientData, TrainHyper
    from fedml_tpu.data import load
    from fedml_tpu.model import create
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    n_clients = 64
    args = Arguments(
        dataset="cifar10", model="resnet56", precision="bfloat16",
        client_num_in_total=n_clients, client_num_per_round=n_clients,
        comm_round=1, epochs=1, batch_size=32, learning_rate=0.1,
        frequency_of_the_test=10_000, random_seed=0,
        allow_synthetic=True, synthetic_size=50_000)
    fed, output_dim = load(args)
    bundle = create(args, output_dim)
    spec = ClassificationTrainer(bundle.apply)
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate), epochs=1)

    mask = np.asarray(fed.train.mask)
    real_b = np.sum(np.any(mask.reshape(mask.shape[0], mask.shape[1], -1) > 0,
                           axis=-1), axis=-1)
    mean_real = float(real_b.mean())
    total_steps = int(round(n_clients * mean_real))
    print(json.dumps({"stage": "workload", "clients": n_clients,
                      "mean_real_batches": mean_real,
                      "total_steps": total_steps}), flush=True)

    rng = jax.random.PRNGKey(0)
    params = jax.device_put(bundle.init(rng, fed.train.x[0, 0]))
    tx = optax.sgd(0.1)

    # A. dispatch constant
    empty = jax.jit(lambda x: x + 1.0)
    t_disp = _time(lambda: empty(jnp.float32(0)), iters=5)
    print(json.dumps({"stage": "A_dispatch", "s": round(t_disp, 4)}),
          flush=True)

    # B/C. shared-weight SGD stream at bs 32 and 256
    def stream(bs, steps):
        x = jnp.zeros((bs, 32, 32, 3), jnp.float32)
        y = jnp.zeros((bs,), jnp.int32)
        m = jnp.ones((bs,), jnp.float32)
        batch = {"x": x, "y": y, "mask": m}

        def many(params, rng):
            opt_state = tx.init(params)

            def one(carry, i):
                p, s = carry
                (_, aux), g = jax.value_and_grad(spec.loss, has_aux=True)(
                    p, batch, jax.random.fold_in(rng, i))
                u, s = tx.update(g, s, p)
                return (optax.apply_updates(p, u), s), None

            (p, _), _ = jax.lax.scan(one, (params, opt_state),
                                     jnp.arange(steps))
            return p

        jf = jax.jit(many)
        return _time(lambda: jf(params, rng), iters=2)

    t_b32 = stream(32, total_steps)
    print(json.dumps({"stage": "B_sgd_stream_bs32", "s": round(t_b32, 4),
                      "per_step_ms": round(1e3 * (t_b32 - t_disp)
                                           / total_steps, 4)}), flush=True)
    steps256 = max(total_steps // 8, 1)
    t_b256 = stream(256, steps256)
    print(json.dumps({"stage": "C_sgd_stream_bs256", "s": round(t_b256, 4),
                      "per_step_ms_bs32equiv": round(
                          1e3 * (t_b256 - t_disp) / (steps256 * 8), 4)}),
          flush=True)

    # D. local loop over clients (while_loop + gather + shuffle), no engine.
    # Data is device_put OUTSIDE the timed region (a closure constant would
    # be baked into the program: ~600 MB re-uploaded at compile time).
    dx = jax.device_put(fed.train.x)
    dy = jax.device_put(fed.train.y)
    dm = jax.device_put(fed.train.mask)

    def local_all(params, rng, dx, dy, dm):
        def per_client(carry, c):
            p0 = carry
            cdata = ClientData(x=dx[c], y=dy[c], mask=dm[c],
                               num_samples=jnp.float32(1.0))
            newp, _, mets = run_local_sgd(
                spec, tx, p0, cdata, jax.random.fold_in(rng, c), hyper)
            # FedAvg accumulate, same math as the engine
            return p0, jax.tree_util.tree_map(lambda a, b: b - a, p0, newp)

        _, deltas = jax.lax.scan(per_client, params,
                                 jnp.arange(n_clients))
        return jax.tree_util.tree_map(lambda d: d.mean(0), deltas)

    jl = jax.jit(local_all)
    t_local = _time(lambda: jl(params, rng, dx, dy, dm), iters=2)
    print(json.dumps({"stage": "D_local_loop", "s": round(t_local, 4),
                      "per_step_ms": round(1e3 * (t_local - t_disp)
                                           / total_steps, 4)}), flush=True)

    # E. full engine round
    opt = create_optimizer(args, spec)
    sim = TPUSimulator(args, fed, bundle, opt, spec)
    r = [0]

    def round_once():
        sim.run_round(r[0], hyper)
        r[0] += 1
        return sim.params

    t_round = _time(round_once, iters=3)
    print(json.dumps({"stage": "E_full_round", "s": round(t_round, 4),
                      "per_step_ms": round(1e3 * (t_round - t_disp)
                                           / total_steps, 4)}), flush=True)

    print(json.dumps({
        "stage": "SPLIT",
        "dispatch_s": round(t_disp, 4),
        "conv_compute_s(B-A)": round(t_b32 - t_disp, 4),
        "loop_bookkeeping_s(D-B)": round(t_local - t_b32, 4),
        "engine_overhead_s(E-D)": round(t_round - t_local, 4),
        "bs256_amortization_x(B/Cequiv)": round(
            (t_b32 - t_disp) / max(t_b256 - t_disp, 1e-9) / 8 * 8
            / (total_steps / (steps256 * 8)), 3),
    }), flush=True)


if __name__ == "__main__":
    main()
