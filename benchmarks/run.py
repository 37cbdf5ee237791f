#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: make the data and the weights from the seed,
build the system through its public entry points, drive its first rounds (they
compile or load from the cache, and they are the rounds the reference later
follows), measure rounds for ``--seconds``, read the device's peak memory, free
the system, run the plain reference over the same first rounds and compare.
The last line of standard output is the result; everything else a reader may
want (per-round times, losses, the device stamp, compile counts) is on earlier
lines. ``--rehearse`` runs the cell's ``rehearsal`` sizes on whatever backend
JAX finds, prints no result line and exits 3: a CPU run never carries a device
metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from harness import compare, manifest, traffic as traffic_mod  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(**rec):
    print(json.dumps(rec), flush=True)


def parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; never prints a result")
    return ap.parse_args()


def device_stamp(jax):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax):
    """Peak bytes in use on the fullest chip (None where the backend keeps
    no statistics, as the CPU's does not)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def frozen_weights(jax, ref, cfg, seed32):
    """The frozen tree (None where nothing is frozen) on the device, from the
    seed, in one jitted call; the same seed gives the same tree again."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed32), 2)
    return jax.jit(lambda k: ref.init_frozen(k, cfg))(key)


def make_weights(jax, ref, cfg, seed32):
    """Trainable and frozen trees on the device, one jitted call each."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed32), 1)
    trainable = jax.jit(lambda k: ref.init_trainable(k, cfg))(key)
    return trainable, frozen_weights(jax, ref, cfg, seed32)


def first_rounds(driver, p0, rounds):
    """Drive the system's first rounds and keep what the comparison needs:
    -> ({"losses", "p0", "p1", "pK"}, seconds of each round)."""
    system, times = {"losses": [], "p0": p0}, []
    for r in range(rounds):
        t = time.perf_counter()
        loss_sum, count = driver.step(r)
        times.append(time.perf_counter() - t)
        system["losses"].append(loss_sum / max(count, 1.0))
        if r == 0:
            system["p1"] = driver.trainable()
    system["pK"] = driver.trainable()
    return system, times


def run_reference(jax, cell, ref, fedavg, data, p0, seed32, rounds,
                  quant=None):
    """The plain reference over the first ``rounds`` rounds from ``p0``."""
    import jax.numpy as jnp

    cfg, tr = cell.config, cell.traffic
    frozen = frozen_weights(jax, ref, cfg, seed32)
    round_fn = fedavg.make_round(ref.make_model(cfg), quant)
    dev = {k: jnp.asarray(v) for k, v in data.items()}
    p = jax.tree_util.tree_map(jnp.asarray, p0)
    out = {"losses": [], "p0": p0}
    nb = data["x"].shape[1]
    for r in range(rounds):
        orders = traffic_mod.program_batch_orders(
            seed32, r, tr["clients_total"], nb, tr["local_epochs"])
        p, loss_sum, count = round_fn(p, frozen, dev, orders,
                                      jnp.float32(tr["learning_rate"]))
        out["losses"].append(float(loss_sum) / max(float(count), 1.0))
        if r == 0:
            out["p1"] = jax.tree_util.tree_map(lambda a: jax.device_get(a), p)
    out["pK"] = jax.tree_util.tree_map(lambda a: jax.device_get(a), p)
    return out


def main():
    opts = parse()
    cell = manifest.Cell(opts.workload, rehearse=opts.rehearse)
    cfg, tr = cell.config, cell.traffic
    for k, v in cfg.get("env", {}).items():
        os.environ.setdefault(k, v)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    seconds = (opts.seconds if opts.seconds is not None
               else cell.bench["run_seconds"])

    import jax

    stamp = device_stamp(jax)
    on_chip = stamp["platform"] == "tpu"
    if not on_chip and not opts.rehearse:
        print(f"benchmark: no accelerator: JAX reports {stamp}",
              file=sys.stderr)
        return 1
    if on_chip and stamp["count"] < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, JAX reports "
              f"{stamp}", file=sys.stderr)
        return 1
    peaks = manifest.load_json("harness", "peaks.json").get(stamp["kind"])
    if on_chip and peaks is None:
        print(f"benchmark: no peaks for device kind {stamp['kind']!r}",
              file=sys.stderr)
        return 1

    counts = {"n": 0, "s": 0.0, "hits": 0}

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            counts["n"] += 1
            counts["s"] += secs

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            counts["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    import fedml_tpu  # noqa: F401  (places the compile cache at import)

    say(info="device", device=stamp, rehearsal=opts.rehearse, cell=cell.name,
        seed=opts.seed, compile_cache_dir=jax.config.jax_compilation_cache_dir)

    # ---- set-up: data, weights, the system, its first rounds --------------
    seed32 = traffic_mod.program_seed(opts.seed)
    ref = manifest.load_module("reference", cell.entry["config"])
    fedavg = manifest.load_module("reference", "fedavg")
    driver_mod = manifest.load_module("drivers", cfg["driver"])
    t = time.perf_counter()
    data = traffic_mod.generate(cfg, tr, opts.seed)
    t_data = time.perf_counter() - t
    t = time.perf_counter()
    trainable, frozen = make_weights(jax, ref, cfg, seed32)
    p0 = jax.tree_util.tree_map(lambda a: jax.device_get(a), trainable)
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    driver = driver_mod.build(cfg, tr, seed32, data, trainable, frozen)
    del trainable, frozen
    t_build = time.perf_counter() - t
    check_rounds = int(cell.cell["check_rounds"])
    system, first_s = first_rounds(driver, p0, check_rounds)
    setup_s = time.perf_counter() - T_START
    setup_compile_s, setup_compiles = counts["s"], counts["n"]
    say(info="setup", setup_s=setup_s, data_s=t_data, weights_s=t_weights,
        build_s=t_build,
        first_rounds_s=first_s, compiles=setup_compiles,
        compile_s=setup_compile_s, cache_hits=counts["hits"],
        attention_impl=driver.attention_impl, losses=system["losses"])

    # ---- the window --------------------------------------------------------
    attempted = failed = 0
    per_round, losses = [], []
    next_round = check_rounds
    trace_dir = os.path.join(REPO, ".bench_trace", cell.name)
    limit = int(cell.cell["trace_rounds"]) if opts.trace else None
    if opts.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    t_open = time.perf_counter()
    while True:
        t = time.perf_counter()
        attempted += 1
        try:
            with jax.profiler.TraceAnnotation("bench.round"):
                loss_sum, count = driver.step(next_round)
            loss = loss_sum / max(count, 1.0)
            if not (loss == loss and abs(loss) != float("inf")):
                failed += 1
            losses.append(loss)
        except Exception as e:  # a round that raises is a failed round
            failed += 1
            print(f"benchmark: round {next_round} raised {e!r}",
                  file=sys.stderr)
        next_round += 1
        now = time.perf_counter()
        per_round.append(now - t)
        if now - t_open >= seconds or (limit and attempted >= limit):
            break
    window_s = time.perf_counter() - t_open
    if opts.trace:
        jax.profiler.stop_trace()
    in_window_compiles = counts["n"] - setup_compiles
    completed = attempted - failed
    round_s = window_s / max(completed, 1)
    peak = memory_peak(jax)
    say(info="window", window_s=window_s, rounds=attempted, failed=failed,
        round_s=round_s, per_round_median_s=statistics.median(per_round),
        per_round_min_s=min(per_round), per_round_max_s=max(per_round),
        compiles_in_window=in_window_compiles, memory_peak_bytes=peak,
        memory_stats=jax.local_devices()[0].memory_stats(),
        loss_first_last=[losses[0], losses[-1]] if losses else None)

    # ---- per-layer metrics from the trace ----------------------------------
    metrics, breakdown, device_extra = {}, None, {}
    if opts.trace:
        from harness import trace_reduce
        t = time.perf_counter()
        raw = trace_reduce.load(trace_dir)
        summary = trace_reduce.reduce(raw)
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(trace_dir, ignore_errors=True)
        say(info="trace", reduce_s=time.perf_counter() - t,
            lines=raw["lines"],
            modules=summary and summary["modules"],
            program=summary and summary["program"],
            program_runs=summary and summary["program_runs"])
        flops_mod = manifest.load_module("flops", cell.entry["config"])
        ctx = {"trace": summary, "cell": cell, "peaks": peaks,
               "chips": cell.chips, "traced_rounds": completed,
               "traced_seconds": window_s,
               "flops_per_round": flops_mod.flops_per_round(cfg, tr),
               "flops_module": flops_mod,
               "counters": {"setup_compile_s": setup_compile_s,
                            "setup_compiles": setup_compiles,
                            "compiles_in_window": in_window_compiles}}
        for m in cell.per_layer:
            value = manifest.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary:
            device_extra = {"busy_s": summary["busy_s"],
                            "window_s": summary["window_s"]}
            top = sorted(((k, t) for k, (_, t) in summary["op_calls"].items()),
                         key=lambda t: -t[1])
            breakdown = {"device_ops": [[k, v] for k, v in top[:10]],
                         "idle_gaps": [[k, v] for k, v
                                       in summary["idle_gaps"][:10]]}
    else:
        values = {"round_s": round_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # ---- free the system, then the reference and the comparison -----------
    driver.close()
    del driver
    gc.collect()
    t = time.perf_counter()
    reference = run_reference(jax, cell, ref, fedavg, data, p0, seed32,
                              check_rounds)
    numbers, where = compare.compare(system, reference)
    ok, table = compare.verdict(numbers, cell.cell["limits"])
    correct = bool(ok and failed == 0 and in_window_compiles == 0)
    say(info="reference", seconds=time.perf_counter() - t,
        losses=reference["losses"], read_on=where, numbers=numbers)

    if opts.rehearse:
        say(info="rehearsal", correct=correct, compared=table,
            metrics=metrics, breakdown=breakdown)
        print(f"benchmark: rehearsal of {cell.name} on {stamp}; not a run",
              file=sys.stderr)
        return 3
    device = dict(stamp, memory_peak_bytes=peak, **device_extra)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    table["compiles_in_window"] = {"value": in_window_compiles, "limit": 0}
    result["compared"] = table
    for k, v in table.items():
        print(f"compared {k}: value {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
