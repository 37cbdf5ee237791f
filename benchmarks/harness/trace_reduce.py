"""From a profiler trace to numbers: busy union, idle gaps, per-op time.

``load(dir)`` reads the newest ``.xplane.pb`` under a ``jax.profiler`` log
directory with ``jax.profiler.ProfileData`` into plain tuples; everything
after that works on the tuples, so the tests drive it with a hand-built
trace. Times are nanoseconds on the profiler's clock.

A device plane is one whose name starts with ``/device:TPU:``. Of its lines,
``XLA Ops`` holds one event per executed HLO operation (events nest: a
``while`` spans its body's operations) and ``XLA Modules`` one per executed
program. The host plane's events are the host's ``TraceAnnotation`` spans.
"""

from __future__ import annotations

import functools
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an event that only wraps other events on the same line
CONTAINERS = ("while", "conditional", "call")


def load(log_dir):
    """-> {"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]} with events as (name, start_ns, duration_ns)."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            events = [(short_name(e.name), int(e.start_ns),
                       int(e.duration_ns)) for e in line.events]
            out["lines"][f"{plane.name}|{line.name}"] = len(events)
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out["devices"].setdefault(
                    plane.name, {"ops": [], "modules": []})[key] = events
            elif plane.name.startswith("/host:"):
                out["host"].extend(e for e in events if e[0].startswith("bench."))
    return out


_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")


@functools.lru_cache(maxsize=None)
def short_name(name):
    """The profiler names a TPU operation by its whole HLO text; keep the
    instruction's name, its opcode, the shapes it produces (layouts dropped)
    and the fusion kind: ``fusion.12 fusion bf16[32,32,32,16] kOutput``."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    if rest.startswith("(") and ") " in rest:
        cut = rest.index(") ") + 1
    else:
        cut = rest.find(" ") if " " in rest else len(rest)
    produced, after = rest[:cut], rest[cut:].strip()
    opcode = after.split("(", 1)[0].strip()
    shapes = ",".join(_SHAPE.findall(produced)[:4])
    kind = ""
    if "kind=" in rest:
        kind = " " + rest.split("kind=", 1)[1].split(",", 1)[0].split(" ")[0]
    return f"{head.lstrip('%')} {opcode} {shapes}{kind}"[:160]


def union(intervals):
    """Merged, sorted [(start, end)] of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def is_container(name):
    parts = name.lstrip("%").split(" ")
    opcode = parts[1] if len(parts) > 1 else parts[0].split(".")[0]
    return opcode in CONTAINERS


def op_calls(ops, lo, hi):
    """{op name: (events, seconds)} inside [lo, hi], wrappers left out so
    that time is charged once, to the operation that ran."""
    out = {}
    for name, s, d in ops:
        if is_container(name):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            n, t = out.get(name, (0, 0.0))
            out[name] = (n + 1, t + (b - a) * 1e-9)
    return out


def attribute(gap, host):
    """What the host was doing in the middle of an idle gap: the innermost
    ``bench.*`` span that covers it, else ``unattributed``."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, d in host:
        if s <= mid < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "unattributed"


def reduce(trace, lo=None, hi=None, program=None):
    """The numbers the metric readers take, for the window [lo, hi] (default:
    from the first device operation to the end of the last)."""
    devs = trace["devices"]
    if not devs:
        return None
    per_dev, summary = [], {}
    for plane, lines in sorted(devs.items()):
        ops = lines["ops"]
        if not ops:
            continue
        d_lo = min(s for _, s, _ in ops) if lo is None else lo
        d_hi = max(s + d for _, s, d in ops) if hi is None else hi
        busy = clip(union((s, s + d) for _, s, d in ops), d_lo, d_hi)
        per_dev.append({"plane": plane, "lo": d_lo, "hi": d_hi, "busy": busy,
                        "busy_ns": sum(e - s for s, e in busy),
                        "ops": op_calls(ops, d_lo, d_hi),
                        "modules": lines["modules"]})
    if not per_dev:
        return None
    first = per_dev[0]
    window_ns = first["hi"] - first["lo"]
    summary["window_s"] = window_ns * 1e-9
    summary["busy_s"] = sum(d["busy_ns"] for d in per_dev) / len(per_dev) * 1e-9
    summary["op_calls"] = first["ops"]
    idle = gaps(first["busy"], first["lo"], first["hi"])
    summary["idle_gaps"] = sorted(
        ((attribute(g, trace["host"]), (g[1] - g[0]) * 1e-9) for g in idle),
        key=lambda t: -t[1])
    # the round program: the module that takes most of the device's time
    by_mod = {}
    for name, s, d in first["modules"]:
        by_mod[name] = by_mod.get(name, 0) + d
    summary["modules"] = {k: v * 1e-9 for k, v in by_mod.items()}
    if program is None and by_mod:
        program = max(by_mod, key=by_mod.get)
    runs = sorted((s, s + d) for name, s, d in first["modules"]
                  if name == program)
    between = []
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        if e0 >= first["lo"] and s1 <= first["hi"] and s1 > e0:
            inner = clip(first["busy"], e0, s1)
            between.append((s1 - e0) - sum(e - s for s, e in inner))
    summary["program"] = program
    summary["program_runs"] = len(runs)
    summary["round_gaps_ns"] = between
    return summary
