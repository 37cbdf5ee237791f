"""``mimo_v2_flash_ep16_l7``: its manifest entries, hand counts for its work
functions, the two readers this configuration brought on a made-up trace and
registry, and (by hand, as the README says: whole rounds) its cell's CPU
rehearsal and the three planted faults."""

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

CONFIG = "mimo_v2_flash_ep16_l7"
CELL = "mimo_v2_flash_lora_silo2_seq4096"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
    assert last["metrics"]["moe_compact_share"]["value"] == 100.0
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    # the CPU runs the dense path: no window call is traced, no device trace
    for name in ("flash_window_block_share", "window_kernels_roofline",
                 "flash_kernels_roofline", "moe_grouped_roofline"):
        assert name not in last["metrics"]


@pytest.mark.parametrize("seed", [5, 4000000007])
def test_no_window_no_sink_and_half_the_steps_each_fail_a_limit(seed):
    import jax

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import calibrate_fault_mimo  # noqa: F401  (registers the two faults)
    import calibrate_fault

    cell = manifest.Cell(CELL, rehearse=True)
    got = calibrate_fault.read_faults(
        jax, cell, ["no_window", "no_sink", "half_steps"], seed)
    for spec, (numbers, table, ok) in got.items():
        assert not ok, (spec, table)


def test_manifest_entries_are_the_issues():
    bench = manifest.benchmark()
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    cfg = manifest.load_json("configs", CONFIG + ".json")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and "MiMo-V2-Flash" in cfg["source"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["v_head_dim"]) == (4096, 64, 192, 128)
    assert (cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["sliding_window"]) == (4, 8, 128)
    assert int(cfg["head_dim"] * cfg["partial_rotary_factor"]) == 64
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (16384, 2048, 8)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 256, 152576)
    assert len(pub["hybrid_layer_pattern"]) == len(pub["moe_layer_freq"]) == 48
    # layer 0 and one whole period, the published layers 6-11
    assert cfg["hybrid_layer_pattern"] == [0] + pub["hybrid_layer_pattern"][6:12]
    assert cfg["moe_layer_freq"] == [0] + pub["moe_layer_freq"][6:12]
    assert cfg["n_routed_experts"] * 16 == pub["n_routed_experts"]
    assert cfg["first_expert"] == 5 * cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["n_shared_experts"] is None
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "lora_silo2_seq4096", 1)
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in ("window_kernels_roofline", "flash_window_block_share"):
        assert lists[name] == [CELL]
    for name in ("flash_roofline", "kda_kernels_roofline",
                 "moe_tokens_here_share"):
        assert CELL not in lists[name]
    assert CELL in lists["flash_kernels_roofline"]


def test_work_functions_against_hand_counts():
    cell = manifest.Cell(CELL)
    flops = manifest.load_module("flops", CONFIG)
    s, w = 4096, 128
    # per position, forward multiply-adds of the frozen weights, by hand
    full = 4096 * 64 * 192 + 4096 * 4 * 192 + 4096 * 4 * 128 + 64 * 128 * 4096
    window = full + 4096 * 4 * 192 + 4096 * 4 * 128       # 8 kv heads
    assert round(full / 1e6, 1) == 89.1 and round(window / 1e6, 1) == 94.4
    dense = 3 * 4096 * 16384
    expert = 3 * 4096 * 2048
    router = 4096 * 256
    head = 19072 * 4096
    frozen = (2 * full + 5 * window + dense + 6 * (router + 8 * 16 / 256
                                                  * expert) + head)
    r = 8
    lora = lambda kv: r * (4096 + 64 * 192 + 4096 + kv * 192  # noqa: E731
                           + 4096 + kv * 128 + 64 * 128 + 4096)
    adapters = 2 * lora(4) + 5 * lora(8) + r * 3 * (4096 + 16384)
    band = w * (w + 1) // 2 + (s - w) * w           # a head's live scores
    assert flops.band_scores(s, w) == band == 516_160
    scores = 64 * (192 + 128) * (2 * s * s / 2 + 5 * band) / s
    by_hand = (4 * frozen + 6 * adapters + 6 * scores) * 32768
    got = flops.flops_per_round(cell.config, cell.traffic)
    assert abs(got - by_hand) / by_hand < 1e-9
    assert 150e12 < got < 155e12
    assert flops.expert_layer_steps(cell.config, cell.traffic) == 48
    f, b = flops.grouped_expert_work(cell.config, 2048 * 48, 48)
    assert f == 2048 * 48 * 6 * 2 * 4096 * 2048
    assert b > 48 * 2 * 16 * 3 * 4096 * 2048 * 2        # the kernels alone
    flash = flops.flash_kernel_work(cell.config, cell.traffic)
    # the FLOPs the axk1 file counts at this shape; keys and values at the
    # 4 heads the model has
    assert flash["fwd"][0] == 64 * s * s * (192 + 128)
    assert flash["dkv"][0] == 64 * s * s * (2 * 192 + 2 * 128)
    assert flash["fwd"][1] == s * 2 * (64 * 192 + 4 * 192 + 4 * 128 + 64 * 128)
    win = flops.window_kernel_work(cell.config, cell.traffic)
    assert win["fwd"][0] == 2 * 64 * band * (192 + 128)
    assert win["dq"][0] == 2 * 64 * band * (2 * 192 + 128)
    assert win["dkv"][1] == s * 2 * (64 * 192 + 2 * 8 * 192 + 2 * 8 * 128
                                     + 64 * 128)
    # the band is 6% of the half-square
    assert 0.06 < win["fwd"][0] / flash["fwd"][0] < 0.065
    # memory-bound by shapes alone at this window
    assert win["fwd"][1] / 819e9 > win["fwd"][0] / 197e12 / 4


def _ctx(op_calls, workload=CELL):
    cell = manifest.Cell(workload)
    return {"trace": {"op_calls": op_calls} if op_calls is not None else None,
            "peaks": PEAKS, "cell": cell, "traced_rounds": 4,
            "flops_module": manifest.load_module("flops",
                                                 cell.entry["config"])}


def test_window_reader_finds_its_kernels_by_name_and_the_flash_reader_not():
    reader = manifest.load_module("metrics", "window_kernels_roofline")
    flash_reader = manifest.load_module("metrics", "flash_kernels_roofline")
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx({
        "custom-call.7 custom-call bf16[64,4096,128]": (100, 0.0),
        "jvp_flash_fwd_.5 custom-call bf16[64,4096,128]": (64, 0.2)})) is None
    calls = {"jvp_flash_win_fwd_.5 custom-call bf16[64,4096,128]": (160, 0.26),
             "transpose_jvp_flash_win_dq__.7 custom-call bf16[64,4096,192]":
             (160, 0.26),
             "transpose_jvp_flash_win_dkv__.3 custom-call bf16[64,4096,192]":
             (160, 0.32),
             "jvp_flash_fwd_.5 custom-call bf16[64,4096,128]": (64, 0.2)}
    share = reader.read(_ctx(calls))
    work = manifest.load_module("flops", CONFIG).window_kernel_work(
        manifest.Cell(CELL).config, manifest.Cell(CELL).traffic)
    least = sum(160 * max(f / 197e12, b / 819e9) for f, b in work.values())
    assert abs(share - 100 * least / 0.84) < 1e-9
    assert 0 < share < 100
    assert reader.kind_of("transpose_jvp_flash_win_dkv__.3 custom-call") == "dkv"
    assert reader.kind_of("jvp_flash_fwd_.5 custom-call") is None
    # the accepted reader takes the causal kernels and none of the window's
    for name in calls:
        assert (flash_reader.kind_of(name) is None) == ("_win_" in name)
    # a configuration without the layer has no work function: nothing
    assert reader.read(_ctx(calls, "axk1_lora_silo2_seq4096")) is None


def test_block_share_reader_reads_the_gauge_or_nothing():
    from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics
    reader = manifest.load_module("metrics", "flash_window_block_share")
    REGISTRY.reset()
    assert reader.read(_ctx(None)) is None
    obs_metrics.record_flash_plan(0.778, False)      # a causal call alone
    assert reader.read(_ctx(None)) is None
    obs_metrics.record_flash_window(128, 31 / 136, True)
    assert abs(reader.read(_ctx(None)) - 100 * 31 / 136) < 1e-9
    REGISTRY.reset()
