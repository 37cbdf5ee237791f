"""BENCHMARK.json loads, every name and unit passes the character rules, and
every file a cell is found by exists."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest  # noqa: E402


def test_names_and_units():
    assert manifest.check_names(manifest.benchmark()) == []


def test_every_named_file_exists():
    bench = manifest.benchmark()
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(manifest.REPO, c["file"]))
        cfg = manifest.load_json("configs", c["name"] + ".json")
        assert cfg["reduced"] == c["reduced"] and cfg["source"]
        for kind in ("reference", "flops"):
            assert os.path.exists(os.path.join(manifest.ROOT, kind,
                                               c["name"] + ".py"))
        assert os.path.exists(os.path.join(manifest.ROOT, "drivers",
                                           cfg["driver"] + ".py"))
    for w in bench["workloads"]:
        cell = manifest.Cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert {"check_rounds", "trace_rounds", "limits"} <= set(cell.cell)
    for m in bench["per_layer"]:
        assert hasattr(manifest.load_module("metrics", m["name"]), "read")


def test_peaks_have_a_source():
    for kind, row in manifest.load_json("harness", "peaks.json").items():
        assert row["bf16_flops_per_s"] > 0 and row["source"], kind
