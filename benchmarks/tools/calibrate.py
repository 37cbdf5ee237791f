#!/usr/bin/env python3
"""Read the two ends a cell's limits are set from, at the cell's own size.

    python3 benchmarks/tools/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --first-seed 1000

One process (set-up is long, the compiled programs are shared): for each seed
the system's first rounds against the reference (the lower readings); for the
first ``--control-seeds`` of them also the control (the reference with float8
matmul operands, put in the system's place) and the planted fault "half of
each batch left out, the mean taken over the rest" (the upper readings). A
state left unchanged reads 1 by construction and needs no run. Prints one
JSON line per reading and a summary; it is not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from harness import compare, manifest, traffic as traffic_mod  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()
    cell = manifest.Cell(opts.workload, rehearse=opts.rehearse)
    cfg, tr = cell.config, cell.traffic
    for k, v in cfg.get("env", {}).items():
        os.environ.setdefault(k, v)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np

    import fedml_tpu  # noqa: F401

    stamp = bench_run.device_stamp(jax)
    print(json.dumps({"device": stamp}), flush=True)
    if stamp["platform"] != "tpu" and not opts.rehearse:
        return 1
    ref = manifest.load_module("reference", cell.entry["config"])
    fedavg = manifest.load_module("reference", "fedavg")
    driver_mod = manifest.load_module("drivers", cfg["driver"])
    rounds = int(cell.cell["check_rounds"])
    readings = {"system": [], "control": [], "half_batch": []}
    for i in range(opts.seeds):
        seed = opts.first_seed + i * 7919
        seed32 = traffic_mod.program_seed(seed)
        data = traffic_mod.generate(cfg, tr, seed)
        trainable, frozen = bench_run.make_weights(jax, ref, cfg, seed32)
        p0 = jax.tree_util.tree_map(lambda a: jax.device_get(a), trainable)
        driver = driver_mod.build(cfg, tr, seed32, data, trainable, frozen)
        del trainable, frozen
        system, _ = bench_run.first_rounds(driver, p0, rounds)
        driver.close()
        del driver
        gc.collect()
        reference = bench_run.run_reference(jax, cell, ref, fedavg, data, p0,
                                            seed32, rounds)
        numbers, where = compare.compare(system, reference)
        readings["system"].append(numbers)
        print(json.dumps({"seed": seed, "kind": "system", "numbers": numbers,
                          "read_on": where, "losses": system["losses"],
                          "ref_losses": reference["losses"]}), flush=True)
        if i < opts.control_seeds:
            control = bench_run.run_reference(
                jax, cell, ref, fedavg, data, p0, seed32, rounds,
                quant=fedavg.fp8_quant)
            numbers, where = compare.compare(control, reference)
            readings["control"].append(numbers)
            print(json.dumps({"seed": seed, "kind": "control",
                              "numbers": numbers, "read_on": where}),
                  flush=True)
            half = dict(data)
            half["mask"] = data["mask"].copy()
            half["mask"][:, :, data["mask"].shape[2] // 2:] = 0
            fault = bench_run.run_reference(jax, cell, ref, fedavg, half, p0,
                                            seed32, rounds)
            numbers, where = compare.compare(fault, reference)
            readings["half_batch"].append(numbers)
            print(json.dumps({"seed": seed, "kind": "half_batch",
                              "numbers": numbers, "read_on": where}),
                  flush=True)
        del data, system, reference
        gc.collect()
    summary = {}
    for kind, rows in readings.items():
        if rows:
            summary[kind] = {k: {"min": float(np.min([r[k] for r in rows])),
                                 "max": float(np.max([r[k] for r in rows]))}
                             for k in rows[0]}
    print(json.dumps({"summary": summary, "seeds": opts.seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
