"""A toy-size CPU rehearsal of each cell: its earlier lines name the platform
and it prints no result line (exit code 3)."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import manifest  # noqa: E402

CELLS = [w["name"] for w in manifest.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_names_the_platform_and_prints_no_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "4000000007", "--seconds", "1", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0]["device"]["platform"] == "cpu" and lines[0]["rehearsal"]
    assert lines[-1]["info"] == "rehearsal"
    assert lines[-1]["correct"] is True, lines[-1]["compared"]
    assert not any("attempted" in ln for ln in lines)


def test_without_a_chip_a_run_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 1 and p.stdout.strip() == ""
