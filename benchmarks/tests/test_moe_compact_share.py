"""``moe_compact_share`` on hand-set counters: nothing where the program
counted no pass through an expert layer (never 0), else the share."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest  # noqa: E402

from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics  # noqa: E402


def test_compact_share_reads_the_counters_or_nothing():
    reader = manifest.load_module("metrics", "moe_compact_share")
    REGISTRY.reset()
    assert reader.read({}) is None
    # a program that records router load but counts no compact passes (the
    # parent's ``record_moe_round`` takes five sums) still reads nothing
    REGISTRY.counter("fed_moe_compact_steps_total").inc(0.0)
    assert reader.read({}) is None
    obs_metrics.record_moe_round(8192.0, 4 * 260.0, 4.0, 48.0, 0.0,
                                 compact_steps=3.0)
    assert reader.read({}) == 75.0
    obs_metrics.record_moe_round(8192.0, 4 * 260.0, 4.0, 48.0, 0.0,
                                 compact_steps=4.0)
    assert reader.read({}) == 87.5
    REGISTRY.reset()


def test_manifest_names_the_reader_for_the_axk1_cell_alone():
    entry = [m for m in manifest.benchmark()["per_layer"]
             if m["name"] == "moe_compact_share"]
    assert entry == [{
        "name": "moe_compact_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "expert layer",
        "moves": "round_s", "workloads": ["axk1_lora_silo2_seq4096"]}]
