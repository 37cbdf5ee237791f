#!/usr/bin/env python3
"""Read planted faults that ``tools/calibrate.py`` does not have, each put in
the system's place and judged by the cell's own comparison and limits.

    python3 benchmarks/tools/calibrate_fault.py --workload <cell> \
        --faults num_experts_per_tok=7 half_steps --seeds 3 --first-seed 1000

For each seed the plain reference runs once as configured and once a fault,
from the same weights, data and batch order:

``KEY=VALUE``    ONE key of the configuration changed (for ``axk1_ep16_l5``
                 top-7 routing where the model routes top-8): a fault that
                 only one model has.
``half_steps``   the second half of every client's batches left out (their
                 local steps train nothing): the partial-training fault for
                 a cell at batch 1, where ``calibrate.py``'s half batch is
                 every row.
``no_exchange``  the aggregate taken over one chip's clients alone (the
                 first ``clients_total / chips``), as a round on ``chips``
                 chips would leave it if the exchange between chips were
                 left out: a fault only a cell across chips can have. The
                 reference runs on one chip.

Every limit of the cell is printed beside the reading; a fault must fail at
least one. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from harness import compare, manifest, traffic as traffic_mod  # noqa: E402


def with_key(cell, data, spec):
    """``KEY=VALUE``: a copy of ``cell`` whose configuration has the key set."""
    key, value = spec.split("=", 1)
    twin = copy.copy(cell)
    twin.config = dict(cell.config)
    if key not in twin.config:
        raise SystemExit(f"{key!r} is no key of {cell.entry['config']}")
    twin.config[key] = type(twin.config[key])(value)
    return twin, data


def half_steps(cell, data, _spec):
    out = dict(data)
    out["mask"] = data["mask"].copy()
    out["mask"][:, data["mask"].shape[1] // 2:] = 0
    return cell, out


def no_exchange(cell, data, _spec):
    if cell.chips < 2:
        raise SystemExit(f"{cell.name} runs on one chip: nothing to exchange")
    share = cell.traffic["clients_total"] // cell.chips
    twin = copy.copy(cell)
    twin.traffic = dict(cell.traffic, clients_total=share)
    return twin, {k: v[:share] for k, v in data.items()}


FAULTS = {"half_steps": half_steps, "no_exchange": no_exchange}


def read_faults(jax, cell, specs, seed):
    """-> {spec: (numbers, verdict table, correct)} on one seed."""
    ref = manifest.load_module("reference", cell.entry["config"])
    fedavg = manifest.load_module("reference", "fedavg")
    rounds = int(cell.cell["check_rounds"])
    seed32 = traffic_mod.program_seed(seed)
    data = traffic_mod.generate(cell.config, cell.traffic, seed)
    # the trainable tree alone, as run.make_weights draws it: the frozen
    # base is made inside each reference run and must not be held twice
    draw = jax.random.fold_in(jax.random.PRNGKey(seed32), 1)
    p0 = jax.tree_util.tree_map(
        lambda a: jax.device_get(a),
        jax.jit(lambda k: ref.init_trainable(k, cell.config))(draw))
    sound = bench_run.run_reference(jax, cell, ref, fedavg, data, p0, seed32,
                                    rounds)
    out = {}
    for spec in specs:
        twin, faulty = FAULTS.get(spec, with_key)(cell, data, spec)
        fault = bench_run.run_reference(jax, twin, ref, fedavg, faulty, p0,
                                        seed32, rounds)
        numbers, _ = compare.compare(fault, sound)
        ok, table = compare.verdict(numbers, cell.cell["limits"])
        out[spec] = (numbers, table, ok)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults", required=True, nargs="+",
                    metavar="KEY=VALUE|" + "|".join(FAULTS))
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()
    for spec in opts.faults:
        if spec not in FAULTS and "=" not in spec:
            ap.error(f"unknown fault {spec!r}")
    cell = manifest.Cell(opts.workload, rehearse=opts.rehearse)
    for k, v in cell.config.get("env", {}).items():
        os.environ.setdefault(k, v)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    stamp = bench_run.device_stamp(jax)
    print(json.dumps({"device": stamp}), flush=True)
    if stamp["platform"] != "tpu" and not opts.rehearse:
        return 1
    caught = dict.fromkeys(opts.faults, 0)
    for i in range(opts.seeds):
        seed = opts.first_seed + i * 7919
        for spec, (numbers, table, ok) in read_faults(
                jax, cell, opts.faults, seed).items():
            caught[spec] += not ok
            print(json.dumps({"seed": seed, "kind": f"fault {spec}",
                              "numbers": numbers, "compared": table,
                              "fails_a_limit": not ok}), flush=True)
    print(json.dumps({"seeds": opts.seeds, "caught": caught}), flush=True)
    return 0 if all(n == opts.seeds for n in caught.values()) else 4


if __name__ == "__main__":
    sys.exit(main())
