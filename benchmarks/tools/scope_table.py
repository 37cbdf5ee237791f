#!/usr/bin/env python3
"""Where a round's device time goes, by the program's own scopes.

    BENCH_KEEP_TRACE=1 python3 benchmarks/run.py --workload <cell> \\
        --seed <n> --trace 1
    python3 benchmarks/tools/scope_table.py .bench_trace/<cell>

A traced run with ``BENCH_KEEP_TRACE`` set keeps its profile and writes the
round program's scope table beside it (``scopes.json``: HLO instruction name
-> scope, made by ``fedml_tpu.core.obs.scopes.table()``). This prints, for
each scope of the program's vocabulary, device milliseconds a round, device
operations a round, the share of the device's busy time, and the named
kernels (Pallas ``custom-call``s such as ``flash_fwd``, ``kda_bwd``,
``moe_grouped_dx``) inside it with their milliseconds: a scope's time less
its kernels' is the glue XLA compiles around them. ``unscoped`` is an
instruction of the round program under no scope, ``unjoined`` an event of
another program. An operation counts under its innermost scope only, so the
rows add up to the device's operation time. ``--ops N`` lists each scope's N
longest operations as well, ``--kinds`` its time by HLO opcode (how much of
a scope is copies, how much fusions). The rounds are those the run wrote into
``scope_ms.json`` (a profile cut short by the profiler's cap on events holds
fewer than were traced), else the round program's executions in the profile.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import scope_time, trace_reduce  # noqa: E402


def kernel_of(key):
    """``flash_fwd`` for ``flash_fwd.2 custom-call ...``; None for anything
    but a custom call that carries a name of its own."""
    parts = key.split(" ")
    if len(parts) < 2 or parts[1] != "custom-call":
        return None
    base = parts[0].rsplit(".", 1)[0] if "." in parts[0] else parts[0]
    return None if base == "custom-call" else base


def rows(op_calls, table, rounds):
    """[(scope, ms a round, operations a round, {kernel: ms}, [(op, ms)],
    {opcode: ms})], longest first."""
    by = {}
    for key, (count, seconds) in op_calls.items():
        name = key.split(" ", 1)[0]
        scope = ((table[name] or scope_time.UNSCOPED) if name in table
                 else scope_time.UNJOINED)
        row = by.setdefault(scope, [0.0, 0, {}, [], {}])
        row[0] += seconds
        row[1] += count
        kernel = kernel_of(key)
        if kernel:
            row[2][kernel] = row[2].get(kernel, 0.0) + seconds
        row[3].append((key, seconds))
        kind = (key.split(" ") + ["?"])[1]
        row[4][kind] = row[4].get(kind, 0.0) + seconds
    scale = 1e3 / rounds
    return sorted(
        ((scope, s * scale, n / rounds,
          {k: v * scale for k, v in sorted(kern.items())},
          [(k, v * scale) for k, v in sorted(ops, key=lambda t: -t[1])],
          {k: v * scale for k, v in sorted(kinds.items(),
                                           key=lambda t: -t[1])})
         for scope, (s, n, kern, ops, kinds) in by.items()),
        key=lambda r: -r[1])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--kinds", action="store_true")
    opts = ap.parse_args()
    with open(os.path.join(opts.trace_dir, "scopes.json")) as f:
        table = json.load(f)
    summary = trace_reduce.reduce(trace_reduce.load(opts.trace_dir))
    rounds = max(summary["program_runs"], 1)
    sums = os.path.join(opts.trace_dir, "scope_ms.json")
    if os.path.exists(sums):
        with open(sums) as f:
            rounds = json.load(f)["rounds_kept"]
    busy_ms = 1e3 * summary["busy_s"] / rounds
    print(f"{summary['program']}: {rounds:.3f} rounds, busy {busy_ms:.1f} "
          f"ms a round")
    print(f"{'scope':<18}{'ms a round':>12}{'ops a round':>13}"
          f"{'% of busy':>11}  kernels (ms a round)")
    total = 0.0
    for scope, ms, n, kernels, ops, kinds in rows(summary["op_calls"],
                                                  table, rounds):
        total += ms
        inside = ", ".join(f"{k} {v:.1f}" for k, v in kernels.items())
        print(f"{scope:<18}{ms:>12.2f}{n:>13.0f}{100 * ms / busy_ms:>11.2f}"
              f"  {inside}")
        if opts.kinds:
            print("    by opcode: " + ", ".join(
                f"{k} {v:.1f}" for k, v in kinds.items() if v >= 0.05))
        for key, op_ms in ops[:opts.ops]:
            print(f"    {op_ms:>10.3f}  {key}")
    print(f"{'all operations':<18}{total:>12.2f}{'':>13}"
          f"{100 * total / busy_ms:>11.2f}")


if __name__ == "__main__":
    main()
