#!/usr/bin/env python3
"""Which of the program's own spans each device-idle gap falls under.

    BENCH_KEEP_TRACE=1 python3 benchmarks/run.py --workload <cell> ... --trace 1
    python3 benchmarks/tools/program_gaps.py .bench_trace/<cell>

A span the program opens as a context manager (``fedml_tpu/core/obs/trace.py``)
is also a ``jax.profiler.TraceAnnotation("fed.<name>")``, so it sits in the
trace's host plane on the device's clock. This lists device 0's idle pieces
between consecutive executions of the round program (the module that takes
most device time) with, for each, the innermost ``fed.*`` span over its middle
beside the innermost ``bench.*`` one, and how the piece divides among the
innermost ``fed.*`` spans it overlaps (a piece of several milliseconds runs
through more than one host phase); then the means by span. An optional second
argument hides pieces shorter than so many microseconds from the list. It
loads the trace itself: ``trace_reduce.load`` keeps the ``bench.`` names only.
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.trace_reduce import (MODULES_LINE, OPS_LINE, attribute,  # noqa: E402
                                  clip, gaps, union)

PREFIXES = ("fed.", "bench.")


def load(log_dir):
    """-> {"ops": [(start_ns, end_ns)], "modules": [...], "host": [...]} of
    the first device plane and the host planes; modules and host events as
    (name, start_ns, duration_ns), of the host's only those named ``fed.*``
    or ``bench.*``. The operations (millions in a ResNet round) keep their
    intervals only."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"ops": [], "modules": [], "host": []}
    device = min((p.name for p in data.planes
                  if p.name.startswith("/device:TPU:")), default=None)
    for plane in data.planes:
        for line in plane.lines:
            if plane.name == device and line.name == OPS_LINE:
                out["ops"] = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                              for e in line.events]
            elif plane.name == device and line.name == MODULES_LINE:
                out["modules"] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                  for e in line.events]
            elif plane.name.startswith("/host:"):
                out["host"].extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith(PREFIXES))
    return out


def split(a, b, spans):
    """{span: ns} of [a, b] by the innermost of ``spans`` over each part."""
    cuts = {a, b}
    for _, s, d in spans:
        cuts.update(t for t in (s, s + d) if a < t < b)
    cuts, out = sorted(cuts), {}
    for piece in zip(cuts, cuts[1:]):
        name = attribute(piece, spans)
        out[name] = out.get(name, 0) + (piece[1] - piece[0])
    return out


def round_gaps(trace, program=None):
    """-> (program, [{"round", "start_ns", "ns", "fed", "bench", "split"}]):
    every idle piece of the device between two consecutive executions of
    ``program``."""
    by_mod = {}
    for name, _, d in trace["modules"]:
        by_mod[name] = by_mod.get(name, 0) + d
    if not by_mod:
        return None, []
    if program is None:
        program = max(by_mod, key=by_mod.get)
    runs = sorted((s, s + d) for name, s, d in trace["modules"]
                  if name == program)
    busy = union(trace["ops"])
    fed = [e for e in trace["host"] if e[0].startswith("fed.")]
    bench = [e for e in trace["host"] if e[0].startswith("bench.")]
    out = []
    for i, ((_, e0), (s1, _)) in enumerate(zip(runs, runs[1:])):
        for a, b in gaps(clip(busy, e0, s1), e0, s1):
            out.append({"round": i, "start_ns": a, "ns": b - a,
                        "fed": attribute((a, b), fed),
                        "bench": attribute((a, b), bench),
                        "split": split(a, b, fed)})
    return program, out


def by_span(pieces, n_gaps):
    """{fed span: mean ms a between-rounds gap spends idle under it}."""
    out = {}
    for p in pieces:
        for name, ns in p["split"].items():
            out[name] = out.get(name, 0.0) + ns * 1e-6
    return {k: v / max(n_gaps, 1) for k, v in
            sorted(out.items(), key=lambda t: -t[1])}


def main(argv):
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    min_us = float(argv[2]) if len(argv) > 2 else 0.0
    program, pieces = round_gaps(load(argv[1]))
    if program is None:
        print("no device plane in the trace", file=sys.stderr)
        return 1
    n_gaps = len({p["round"] for p in pieces})
    print(f"round program {program}: {n_gaps} gaps between its executions")
    print(f"{'gap':>3} {'idle_us':>10}  {'program span':<18} "
          f"{'benchmark span':<16} of it under (us)")
    for p in pieces:
        if p["ns"] * 1e-3 >= min_us:
            parts = ", ".join(f"{k} {v * 1e-3:.0f}" for k, v in sorted(
                p["split"].items(), key=lambda t: -t[1]))
            print(f"{p['round']:>3} {p['ns'] * 1e-3:>10.1f}  {p['fed']:<18} "
                  f"{p['bench']:<16} {parts}")
    total = sum(p["ns"] for p in pieces) * 1e-6 / max(n_gaps, 1)
    print(json.dumps({"program": program, "gaps": n_gaps,
                      "idle_ms_per_gap": total,
                      "idle_ms_per_gap_by_span": by_span(pieces, n_gaps)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
