#!/usr/bin/env python3
"""``tools/calibrate_fault.py`` with the four faults that are Keye-VL-2.0's
own, each the reference with the fault put in the system's place:

``dense``           no selection: every causal key of every query (the
                    top-k set to the row's length).
``topk_1024``       1,024 keys a query where the model keeps 2,048.
``no_relu``         the index scores without their ReLU: ``sum_j w_j qi_j .
                    ki``.
``sigmoid_router``  the router's scores by a sigmoid of each logit where the
                    model takes a softmax over all 128.

    python3 benchmarks/tools/calibrate_fault_keye.py \\
        --workload keye_vl2_lora_silo2_seq16384 \\
        --faults dense topk_1024 no_relu sigmoid_router --seeds 1

The last two are keys the reference reads and no configuration file sets
(``fault_no_relu``, ``fault_sigmoid_router``); the weights are the same.
Everything else (``KEY=VALUE``, ``half_steps``, the printing, the exit
code) is that file's.

``--with-system`` reads, on each seed and before the faults, what
``tools/calibrate.py`` reads, in the same process and from the same sound
reference: the system's first rounds (the sound reading), the control (the
reference with float8 matmul operands) and, once, the (query, key)
selections of the system's first step in which the system and the
reference disagree, layer by layer (index scores that differ by rounding
swap keys at the 2,048th place; ``--gap none`` leaves that out, ``--gap
only`` reads it alone, on ``--first-seed``). Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import calibrate_fault  # noqa: E402

bench_run = calibrate_fault.bench_run
compare, manifest = calibrate_fault.compare, calibrate_fault.manifest
traffic_mod = calibrate_fault.traffic_mod


def _with(cell, **keys):
    twin = copy.copy(cell)
    twin.config = dict(cell.config, **keys)
    return twin


def _topk(cell, topk):
    return _with(cell, sa_config=dict(cell.config["sa_config"], topk=topk))


def dense(cell, data, _spec):
    return _topk(cell, cell.traffic["seq_len"]), data


def topk_1024(cell, data, _spec):
    return _topk(cell, 1024), data


def no_relu(cell, data, _spec):
    return _with(cell, fault_no_relu=True), data


def sigmoid_router(cell, data, _spec):
    return _with(cell, fault_sigmoid_router=True), data


calibrate_fault.FAULTS.update(dense=dense, topk_1024=topk_1024,
                              no_relu=no_relu, sigmoid_router=sigmoid_router)


def selection_gap(jax, cell, ref, data, trainable, frozen, seed32):
    """{layer: (query, key) pairs kept by one side alone, over those the
    reference keeps} on the first batch client 0 trains in round 0: the
    system's model on the path the driver builds (its bfloat16 compute,
    its kernels), the reference's float32 forward."""
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.llm.federated import llm_config_from_hf
    from fedml_tpu.llm.model import CausalLM
    from fedml_tpu.llm.sparse_attention import unpack

    cfg, tr = cell.config, cell.traffic
    order = traffic_mod.program_batch_orders(
        seed32, 0, tr["clients_total"], data["x"].shape[1],
        tr["local_epochs"])
    tokens = jnp.asarray(data["x"][0, int(order[0, 0])])
    lc = llm_config_from_hf(
        dict(cfg, n_routed_experts=cfg["published"]["num_experts"]),
        max_seq_len=tr["seq_len"], dtype=cfg["compute_dtype"],
        attention_impl=("flash" if jax.default_backend() == "tpu"
                        else "dense"),
        first_expert=cfg["first_expert"], experts_held=cfg["num_experts"])
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    _, state = jax.jit(lambda p, f: CausalLM(lc).apply(
        {"params": f}, tokens, adapters=p, lora_scale=scale,
        mutable=["intermediates"]))(trainable, frozen)
    s = tokens.shape[1]
    grad_fn = ref.make_model(cfg)
    with jax.default_matmul_precision("highest"):
        keeps = jax.jit(grad_fn.selections)(
            jax.tree_util.tree_map(jnp.asarray, trainable), frozen, tokens)
    out = {}
    for i, want in enumerate(keeps):
        words = state["intermediates"][f"layer_{i}"]["attn"]["selection"][0]
        got = unpack(words[:, :s], s)
        apart = int(jnp.sum(got != want))
        out[f"layer_{i}"] = {"apart": apart, "kept": int(jnp.sum(want)),
                             "share": apart / max(int(jnp.sum(want)), 1),
                             "rows_apart": int(jnp.sum(jnp.any(
                                 got != want, -1)))}
        del got, want
    out["max_share"] = float(np.max([v["share"] for v in out.values()]))
    return out


def print_gap(jax, cell, seed):
    """Prints the selection gap of one seed."""
    ref = manifest.load_module("reference", cell.entry["config"])
    seed32 = traffic_mod.program_seed(seed)
    data = traffic_mod.generate(cell.config, cell.traffic, seed)
    trainable, frozen = bench_run.make_weights(jax, ref, cell.config, seed32)
    p0 = jax.tree_util.tree_map(lambda a: jax.device_get(a), trainable)
    print(json.dumps({"seed": seed, "kind": "selection_gap",
                      "layers": selection_gap(jax, cell, ref, data, p0,
                                              frozen, seed32)}), flush=True)


def with_system(jax, cell, faults, seed, gap):
    """The sound reading, the control, the selection gap (where ``gap``)
    and every fault on one seed, against one sound reference."""
    if gap:
        print_gap(jax, cell, seed)
    ref = manifest.load_module("reference", cell.entry["config"])
    fedavg = manifest.load_module("reference", "fedavg")
    driver_mod = manifest.load_module("drivers", cell.config["driver"])
    rounds = int(cell.cell["check_rounds"])
    seed32 = traffic_mod.program_seed(seed)
    data = traffic_mod.generate(cell.config, cell.traffic, seed)
    trainable, frozen = bench_run.make_weights(jax, ref, cell.config, seed32)
    p0 = jax.tree_util.tree_map(lambda a: jax.device_get(a), trainable)
    driver = driver_mod.build(cell.config, cell.traffic, seed32, data,
                              trainable, frozen)
    del trainable, frozen
    system, _ = bench_run.first_rounds(driver, p0, rounds)
    driver.close()
    del driver
    gc.collect()
    sound = bench_run.run_reference(jax, cell, ref, fedavg, data, p0, seed32,
                                    rounds)
    limits = cell.cell["limits"]
    runs = [("system", system)]
    runs.append(("control", bench_run.run_reference(
        jax, cell, ref, fedavg, data, p0, seed32, rounds,
        quant=fedavg.fp8_quant)))
    for kind, got in runs:
        numbers, where = compare.compare(got, sound)
        ok, table = compare.verdict(numbers, limits)
        print(json.dumps({"seed": seed, "kind": kind, "numbers": numbers,
                          "read_on": where, "compared": table,
                          "fails_a_limit": not ok,
                          "losses": got["losses"],
                          "ref_losses": sound["losses"]}), flush=True)
    caught = {}
    for spec in faults:
        twin, faulty = calibrate_fault.FAULTS.get(
            spec, calibrate_fault.with_key)(cell, data, spec)
        fault = bench_run.run_reference(jax, twin, ref, fedavg, faulty, p0,
                                        seed32, rounds)
        numbers, _ = compare.compare(fault, sound)
        ok, table = compare.verdict(numbers, limits)
        caught[spec] = not ok
        print(json.dumps({"seed": seed, "kind": f"fault {spec}",
                          "numbers": numbers, "compared": table,
                          "fails_a_limit": not ok}), flush=True)
    return caught


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults", nargs="*", default=[],
                    metavar="KEY=VALUE|" + "|".join(calibrate_fault.FAULTS))
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--with-system", action="store_true")
    ap.add_argument("--gap", choices=("first", "none", "only"),
                    default="first", help="the selection gap on the first "
                    "seed (the default), on none, or on it alone")
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()
    if not opts.with_system:
        return calibrate_fault.main()
    cell = manifest.Cell(opts.workload, rehearse=opts.rehearse)
    for k, v in cell.config.get("env", {}).items():
        os.environ.setdefault(k, v)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    import fedml_tpu  # noqa: F401  (places the compile cache at import)

    stamp = bench_run.device_stamp(jax)
    print(json.dumps({"device": stamp}), flush=True)
    if stamp["platform"] != "tpu" and not opts.rehearse:
        return 1
    if opts.gap == "only":
        print_gap(jax, cell, opts.first_seed)
        return 0
    caught = dict.fromkeys(opts.faults, 0)
    for i in range(opts.seeds):
        seed = opts.first_seed + i * 7919
        for spec, hit in with_system(jax, cell, opts.faults, seed,
                                     gap=i == 0 and opts.gap == "first"
                                     ).items():
            caught[spec] += hit
    print(json.dumps({"seeds": opts.seeds, "caught": caught}), flush=True)
    return 0 if all(n == opts.seeds for n in caught.values()) else 4


if __name__ == "__main__":
    sys.exit(main())
