"""BENCHMARK.json and the files it names: one loader, one set of name rules.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``cells/<cell>.json``, ``drivers/<driver>.py``,
``reference/<config>.py``, ``flops/<config>.py``, ``metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/
REPO = os.path.dirname(ROOT)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module, found by its name."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(ROOT, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def check_names(bench):
    """The contract's character rules, for the manifest's own test."""
    problems = []

    def name(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            problems.append(f"{what}: bad name {n!r}")

    for c in bench["configs"]:
        name(c["name"], "config")
        for k in c["reduced"]:
            name(k, f"reduced of {c['name']}")
    for w in bench["workloads"]:
        for k in ("name", "config", "traffic"):
            name(w[k], f"workload {k}")
        if w["chips"] not in (1, 4):
            problems.append(f"{w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            problems.append(f"{w['name']}: why is {len(w['why'])} characters")
    for m in bench["end_to_end"] + bench["per_layer"]:
        name(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            problems.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            problems.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            problems.append(f"{m['name']}: source {m['source']!r}")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for group in (names, [w["name"] for w in bench["workloads"]],
                  [c["name"] for c in bench["configs"]]):
        if len(set(group)) != len(group):
            problems.append(f"duplicate names in {group}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            problems.append(f"{m['name']} moves unknown {m['moves']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                problems.append(f"{m['name']} lists unknown cell {w!r}")
    return problems


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and limits,
    at the full sizes or, for a rehearsal, with each file's ``rehearsal``
    overrides laid over them."""

    def __init__(self, workload, rehearse=False):
        bench = benchmark()
        entry = [w for w in bench["workloads"] if w["name"] == workload]
        if not entry:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                             f"known: {[w['name'] for w in bench['workloads']]}")
        self.entry = entry[0]
        self.name = workload
        self.chips = self.entry["chips"]
        self.bench = bench
        self.config = load_json("configs", self.entry["config"] + ".json")
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.cell = load_json("cells", workload + ".json")
        if rehearse:
            for d in (self.config, self.traffic, self.cell):
                d.update(d.get("rehearsal", {}))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]
