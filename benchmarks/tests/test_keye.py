"""``keye_vl2_ep8_l12``: its manifest entries, hand counts for its work
functions, the two readers this configuration brought on a made-up trace
and registry, and (by hand, as the README says: whole rounds) its cell's
CPU rehearsal and the four planted faults that are this model's own."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness import manifest  # noqa: E402

CONFIG = "keye_vl2_ep8_l12"
CELL = "keye_vl2_lora_silo2_seq16384"
# the model catalog (architectures.jsonl) where one is at hand
CATALOG = os.environ.get("MODEL_CATALOG", "")


def test_rehearsal_is_correct_and_reports_the_counters():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000007", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 3, p.stderr[-2000:]
    last = [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith("{")][-1]
    assert last["info"] == "rehearsal" and last["correct"] is True, last
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1
    # the rehearsal takes the dense path and traces no kernel: no gauge,
    # and no device trace on the CPU
    for name in ("sparse_computed_over_selected", "sparse_kernels_roofline",
                 "scope_attn_sparse_ms", "scope_attn_index_ms"):
        assert name not in last["metrics"]


@pytest.mark.parametrize("seed", [5, 4000000007])
def test_the_four_faults_each_fail_a_limit(seed):
    import jax

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import calibrate_fault_keye  # noqa: F401  (registers the four faults)
    import calibrate_fault

    cell = manifest.Cell(CELL, rehearse=True)
    got = calibrate_fault.read_faults(
        jax, cell, ["dense", "topk_1024", "no_relu", "sigmoid_router"], seed)
    for spec, (numbers, table, ok) in got.items():
        assert not ok, (spec, table)


def test_manifest_entries_are_as_stated():
    bench = manifest.benchmark()
    assert not manifest.check_names(bench)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    cfg = manifest.load_json("configs", CONFIG + ".json")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert "Keye-VL-2.0-30B-A3B" in cfg["source"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    sa = cfg["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["indexer_num_kv_heads"], sa["topk"]) == (16, 64, 1, 2048)
    assert (cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_local_experts"]) == (768, 8, 128)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 128, 151936)
    assert cfg["num_hidden_layers"] * 4 == pub["num_hidden_layers"]
    assert cfg["num_experts"] * 8 == pub["num_experts"]
    assert cfg["first_expert"] == 3 * cfg["num_experts"]
    assert cfg["gradient_checkpointing"] is True
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "lora_silo2_seq16384", 1)
    traffic = manifest.load_json("traffic", "lora_silo2_seq16384.json")
    assert (traffic["clients_total"], traffic["rows_per_client"],
            traffic["seq_len"], traffic["batch_size"]) == (2, 2, 16384, 1)
    new = {"scope_attn_sparse_ms": ("ms", "lower", "device_trace",
                                    "local training / LLM train step"),
           "scope_attn_index_ms": ("ms", "lower", "device_trace",
                                   "local training / LLM train step"),
           "sparse_kernels_roofline": ("%", "higher", "device_trace",
                                       "sparse attention kernels"),
           "sparse_computed_over_selected": ("x", "lower", "program_counter",
                                             "sparse attention kernels")}
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(new)
    for m in bench["per_layer"][-4:]:
        assert m == {"name": m["name"], "unit": new[m["name"]][0],
                     "better": new[m["name"]][1],
                     "source": new[m["name"]][2],
                     "layer": new[m["name"]][3], "moves": "round_s",
                     "workloads": [CELL]}
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in ("setup_compile_s", "round_gap_ms", "round_mfu",
                 "device_idle_share", "round_host_ms", "host_input_ms",
                 "setup_build_s", "setup_trace_lower_s", "peak_hbm_gib",
                 "scope_engine_ms", "scope_unscoped_share",
                 "moe_grouped_roofline", "moe_load_max_over_mean",
                 "moe_compact_share", "scope_moe_route_ms",
                 "scope_moe_experts_ms", "scope_head_ms", "scope_lora_ms"):
        assert lists[name][-1] == CELL, name
    for name, cells in lists.items():
        if name not in new and cells and cells[-1] != CELL:
            assert CELL not in cells, name


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog")
def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The file holds every key of the catalog's copy of the published
    config under the same name with the same value, but the three it lists
    as reduced, whose published values it keeps under ``published``."""
    cfg = manifest.load_json("configs", CONFIG + ".json")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["source_url"] == cfg["source"]][0]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_work_functions_against_hand_counts():
    cell = manifest.Cell(CELL)
    flops = manifest.load_module("flops", CONFIG)
    s = 16384
    # per position, forward multiply-adds of the frozen weights, by hand
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert round(attn / 1e6, 2) == 18.87
    index = 2048 * (16 * 64 + 64 + 16)
    assert round(index / 1e6, 2) == 2.26
    router = 2048 * 128
    routed = 8 * 16 / 128 * 3 * 2048 * 768          # one expert, 1/8
    head = 18992 * 2048
    r = 8
    adapters = r * ((2048 + 4096) * 2 + (2048 + 512) * 2)
    selected = 2048 * 2049 // 2 + (s - 2048) * 2048
    assert round(selected / s, 2) == 1920.06
    by_hand = (12 * (4 * attn + 6 * adapters + 2 * index
                    + 2 * 16 * 64 * (s + 1) / 2
                    + 6 * 32 * 256 * selected / s
                    + 4 * (router + routed))
               + 4 * head) * 65536
    got = flops.flops_per_round(cell.config, cell.traffic)
    assert abs(got - by_hand) / by_hand < 1e-9
    assert 170e12 < got < 185e12
    assert flops.expert_layer_steps(cell.config, cell.traffic) == 48
    f, b = flops.grouped_expert_work(cell.config, 1024 * 16, 16)
    assert f == 1024 * 16 * 6 * 2 * 2048 * 768
    assert b > 16 * 2 * 16 * 3 * 2048 * 768 * 2        # the kernels alone
    work = flops.sparse_kernel_work(cell.config, cell.traffic)
    causal = s * (s + 1) // 2
    assert work["index"][0] == 2 * 16 * 64 * causal
    assert work["fwd"][0] == 2 * 32 * selected * 2 * 128
    assert work["dq"][0] == 2 * 32 * selected * 3 * 128
    assert work["dkv"][0] == 2 * 32 * selected * 4 * 128
    q, kv, bits = 32 * s * 128 * 2, 4 * s * 128 * 2, causal / 8
    assert work["fwd"][1] == 2 * q + 2 * kv + bits
    assert work["dkv"][1] == 2 * q + 4 * kv + bits
    assert work["index"][1] == s * (16 * 64 * 2 + 64 * 2 + 16 * 4) + bits


OP_CALLS = {
    "sparse_index.1 sparse_index s32[1,16384,512]": (4, 0.040),
    "jvp_sparse_fwd_.2 custom-call bf16[4,8,16384,128]": (4, 0.080),
    "transpose_jvp_sparse_dq_.3 custom-call": (4, 0.120),
    "transpose_jvp_sparse_dkv_.4 custom-call": (4, 0.160),
    "jvp_flash_fwd_.5 custom-call": (8, 0.500),
    "fusion.12 fusion f32[16384,18992]": (8, 1.000),
}


def _ctx(trace=True):
    cell = manifest.Cell(CELL)
    return {"trace": {"op_calls": OP_CALLS} if trace else None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "cell": cell, "flops_module": manifest.load_module("flops",
                                                               CONFIG)}


def test_sparse_reader_finds_its_kernels_by_name_only():
    reader = manifest.load_module("metrics", "sparse_kernels_roofline")
    flash = manifest.load_module("metrics", "flash_kernels_roofline")
    kinds = {name: reader.kind_of(name) for name in OP_CALLS}
    assert sorted(filter(None, kinds.values())) == ["dkv", "dq", "fwd",
                                                    "index"]
    assert [n for n in OP_CALLS if flash.kind_of(n)] == [
        "jvp_flash_fwd_.5 custom-call"]
    ctx = _ctx()
    work = ctx["flops_module"].sparse_kernel_work(ctx["cell"].config,
                                                  ctx["cell"].traffic)
    least = sum(4 * max(f / 197e12, b / 819e9) for f, b in work.values())
    assert reader.read(ctx) == pytest.approx(100 * least / 0.4)
    assert reader.read(_ctx(trace=False)) is None
    ctx["trace"] = {"op_calls": {k: v for k, v in OP_CALLS.items()
                                 if not reader.kind_of(k)}}
    assert reader.read(ctx) is None


def test_ratio_reader_reads_the_gauge_or_nothing():
    from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics
    reader = manifest.load_module("metrics", "sparse_computed_over_selected")
    REGISTRY.reset()
    assert reader.read({}) is None          # a program without the gauge
    obs_metrics.record_sparse_plan(4.3)
    assert reader.read({}) == pytest.approx(4.3)
    REGISTRY.reset()
