#!/usr/bin/env python
"""Round-trace flight-recorder report: per-round critical path from JSONL.

Reads one or more run logs (the mlops sink's ``run_<id>.jsonl`` files —
pass every process's file for a multi-process session; spans carry
trace/span IDs, so the trees reassemble regardless of which file a span
landed in), rebuilds the trace trees, and prints where each round's wall
time went: straggler wait vs compute vs wire vs host.

    python scripts/trace_report.py ~/.cache/fedml_tpu/logs/run_0.jsonl
    python scripts/trace_report.py server.jsonl silo1.jsonl silo2.jsonl
    python scripts/trace_report.py run.jsonl --trace 4f2a...   # one tree

For every ROOT span (``round`` / ``pour`` / ``block``, the engine's
post-block per-round ``eval`` / ``checkpoint`` / ``host.readback`` roots,
the once-a-start ``setup.simulator`` / ``setup.init``, plus orphans whose
parent lives in a file you didn't pass) the report shows the duration,
the per-category time, the attributed fraction (the ≥95% acceptance bar:
unattributed time is wall time no span explains), the descendants with
the most self time, and — for pours — the linked contributing uploads
with their per-link staleness.

A span's time is its SELF time: its interval minus what its own children
cover. So ``host.input``, whose children ``host.schedule`` and
``host.stage`` cover nearly all of it, is not counted twice, and a
category's figure is the union of its spans' self intervals clipped to
the root window (overlapping spans never double-count).

Span-name → category map (keep in sync with the instrumentation):
  compute: train, dispatch, aggregate, eval
  wire:    comm.send, broadcast, upload, async.sync
  wait:    wait.uploads, wait.arrivals
  host:    host.input (> host.schedule: the client schedule and the fault
           ledger; > host.stage: the schedule's device_puts), host.keys
           (round key, hyper-parameters, slot placement), host.post
           (selection and privacy bookkeeping), host.readback (the scalar
           reads that end a round: the wait for its program), host.close,
           checkpoint, setup.init, setup.simulator (> setup.place_data,
           setup.init_state, setup.build_programs)
A ``dispatch`` span is the jitted call's enqueue, not the program's run;
when the call traced, lowered or compiled, its attrs say for how long
(``trace_s``, ``lower_s``, ``compile_s``, ``cache_load_s``, ``cache_hits``).
Container spans (round, pour, block, silo.round) attribute through their
children, not themselves.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

CATEGORY = {
    "train": "compute", "dispatch": "compute", "aggregate": "compute",
    "eval": "compute",
    "comm.send": "wire", "broadcast": "wire", "upload": "wire",
    "async.sync": "wire",
    "wait.uploads": "wait", "wait.arrivals": "wait",
    "host.input": "host", "host.schedule": "host", "host.stage": "host",
    "host.keys": "host", "host.post": "host", "host.readback": "host",
    "host.close": "host", "checkpoint": "host",
    "setup.init": "host", "setup.simulator": "host",
    "setup.place_data": "host", "setup.init_state": "host",
    "setup.build_programs": "host",
}
CONTAINERS = {"round", "pour", "block", "silo.round"}
# eval/checkpoint/host.readback are the engine's post-block per-round
# roots (the fused block span is closed by the time they run, so they
# cannot be children); the setup.* roots come once a start
ROOT_NAMES = ("round", "pour", "block", "eval", "checkpoint",
              "host.readback", "setup.init", "setup.simulator")


def load_spans(paths: List[str]) -> List[Dict[str, Any]]:
    spans = []
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "span":
                    spans.append(rec)
    return spans


def union_len(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end] intervals."""
    total = 0.0
    end = -float("inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


class Tree:
    def __init__(self, spans: List[Dict[str, Any]]):
        self.by_id = {s["span_id"]: s for s in spans}
        self.children = defaultdict(list)
        for s in spans:
            self.children[s.get("parent_id")].append(s)
        # a root is parentless OR references a parent we never saw (its
        # file was not passed) — report it anyway rather than dropping
        # the whole subtree silently
        self.roots = [s for s in spans
                      if s.get("parent_id") is None
                      or s["parent_id"] not in self.by_id]

    def descendants(self, span: Dict[str, Any]) -> List[Dict[str, Any]]:
        out, stack = [], [span["span_id"]]
        while stack:
            for c in self.children.get(stack.pop(), []):
                out.append(c)
                stack.append(c["span_id"])
        return out


def clip(span: Dict[str, Any], lo: float,
         hi: float) -> Optional[Tuple[float, float]]:
    s = max(float(span["start_ts"]), lo)
    e = min(float(span["end_ts"]), hi)
    return (s, e) if e > s else None


def subtract(iv: Tuple[float, float],
             holes: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The pieces of ``iv`` that no interval of ``holes`` covers."""
    out, at = [], iv[0]
    for s, e in sorted(holes):
        if s > at:
            out.append((at, min(s, iv[1])))
        at = max(at, e)
        if at >= iv[1]:
            break
    if at < iv[1]:
        out.append((at, iv[1]))
    return [(s, e) for s, e in out if e > s]


def self_intervals(tree: Tree, span: Dict[str, Any], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """``span``'s interval inside [lo, hi] minus what its children cover."""
    iv = clip(span, lo, hi)
    if iv is None:
        return []
    kids = [clip(c, lo, hi) for c in tree.children.get(span["span_id"], [])]
    return subtract(iv, [k for k in kids if k is not None])


def analyze_root(tree: Tree, root: Dict[str, Any]) -> Dict[str, Any]:
    lo, hi = float(root["start_ts"]), float(root["end_ts"])
    dur = max(hi - lo, 1e-12)
    per_cat: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    covered: List[Tuple[float, float]] = []
    leaves: List[Tuple[float, Dict[str, Any]]] = []
    if root["name"] not in CONTAINERS:
        # a leaf root (engine eval/checkpoint, an orphaned worker span)
        # IS its own attribution — containers attribute through children
        covered.append((lo, hi))
        per_cat[CATEGORY.get(root["name"]) or "other"].extend(
            self_intervals(tree, root, lo, hi))
    for d in tree.descendants(root):
        iv = clip(d, lo, hi)
        if iv is None:
            continue
        cat = CATEGORY.get(d["name"])
        if d["name"] in CONTAINERS:
            # containers attribute through their children — but still
            # count toward coverage, so a remote silo.round whose inner
            # spans landed in an unpassed file is not "unattributed"
            covered.append(iv)
            continue
        covered.append(iv)
        own = self_intervals(tree, d, lo, hi)
        per_cat[cat or "other"].extend(own)
        leaves.append((sum(e - s for s, e in own), d))
    cats = {c: union_len(v) for c, v in per_cat.items()}
    leaves.sort(key=lambda t: t[0], reverse=True)
    return {
        "root": root,
        "duration_s": dur,
        "categories": cats,
        "attributed_s": union_len(covered),
        "attributed_frac": min(union_len(covered) / dur, 1.0),
        "top": leaves[:3],
        "links": root.get("links", []),
        "events": root.get("events", []),
    }


def _label(span: Dict[str, Any]) -> str:
    attrs = span.get("attrs", {}) or {}
    for key in ("round_idx", "version", "start_round"):
        if key in attrs:
            return f"{span['name']}[{key}={attrs[key]}]"
    return span["name"]


def print_report(spans: List[Dict[str, Any]], only_trace: Optional[str],
                 min_attr: float, out=sys.stdout) -> int:
    by_trace: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    for s in spans:
        if only_trace is None or s["trace_id"].startswith(only_trace):
            by_trace[s["trace_id"]].append(s)
    if not by_trace:
        print("no span records found", file=out)
        return 1
    rows = []
    for trace_id in sorted(by_trace,
                           key=lambda t: min(s["start_ts"]
                                             for s in by_trace[t])):
        tree = Tree(by_trace[trace_id])
        for root in sorted(tree.roots, key=lambda s: s["start_ts"]):
            # genuinely-parentless non-round spans (a stray comm.send
            # outside any session span) stay out of the report, but an
            # ORPHAN — a subtree whose parent lives in a file that was
            # not passed (e.g. a silo log without the server's) — is
            # reported as its own root rather than dropped silently
            orphan = root.get("parent_id") is not None
            if (root["name"] not in ROOT_NAMES and not orphan
                    and only_trace is None):
                continue
            rows.append((trace_id, analyze_root(tree, root)))
    if not rows:
        print("no round/pour/block root spans found", file=out)
        return 1
    hdr = (f"{'root':<26} {'wall_s':>9} {'compute':>9} {'wire':>8} "
           f"{'wait':>8} {'host':>8} {'attr%':>6}  trace")
    print(hdr, file=out)
    print("-" * len(hdr), file=out)
    worst = 1.0
    for trace_id, a in rows:
        c = a["categories"]
        worst = min(worst, a["attributed_frac"])
        print(f"{_label(a['root']):<26} {a['duration_s']:>9.4f} "
              f"{c.get('compute', 0.0):>9.4f} {c.get('wire', 0.0):>8.4f} "
              f"{c.get('wait', 0.0):>8.4f} {c.get('host', 0.0):>8.4f} "
              f"{100.0 * a['attributed_frac']:>5.1f}%  {trace_id[:12]}",
              file=out)
        for self_s, t in a["top"]:
            print(f"    └ {_label(t):<24} {self_s:.4f}s self of "
                  f"{t['end_ts'] - t['start_ts']:.4f}s", file=out)
        links = a["links"]
        if links:
            parts = []
            for ln in links:
                at = ln.get("attrs", {}) or {}
                parts.append(f"c{at.get('client', '?')}"
                             f"@s{at.get('staleness', '?')}")
            print(f"    ↳ links ({len(links)} uploads): "
                  + " ".join(parts), file=out)
        for ev in a["events"]:
            if ev["name"].startswith("chaos"):
                print(f"    ⚡ {ev['name']} {ev.get('attrs', {})}", file=out)
    n = len(rows)
    mean_attr = sum(a["attributed_frac"] for _, a in rows) / n
    print(f"\n{n} roots; attribution mean {100 * mean_attr:.1f}%, "
          f"min {100 * worst:.1f}%", file=out)
    if min_attr > 0 and worst < min_attr:
        print(f"FAIL: minimum attribution {100 * worst:.1f}% < "
              f"{100 * min_attr:.0f}% — wall time no span explains",
              file=out)
        return 2
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("logs", nargs="+",
                    help="run JSONL file(s) — pass every process's log")
    ap.add_argument("--trace", default=None,
                    help="only this trace id (prefix match)")
    ap.add_argument("--min-attr", type=float, default=0.0,
                    help="exit 2 if any root's attributed fraction is "
                         "below this (e.g. 0.95)")
    args = ap.parse_args(argv)
    spans = load_spans(args.logs)
    return print_report(spans, args.trace, args.min_attr)


if __name__ == "__main__":
    sys.exit(main())
