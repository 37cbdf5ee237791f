"""``kimi_linear_ep8_l9``: its manifest entries, hand counts for its work
functions, the reader this configuration brought on a made-up registry,
and (by hand, as the README says: whole rounds) its cell's CPU rehearsal and
the two planted faults that are this model's own."""

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

CONFIG = "kimi_linear_ep8_l9"
CELL = "kimi_linear_lora_silo2_seq4096"
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
    assert 1 < last["metrics"]["kda_steep_decay_share"]["value"] < 50
    assert last["metrics"]["moe_compact_share"]["value"] == 100.0
    # no device trace on the CPU: the roofline shares are left out
    for name in ("kda_kernels_roofline", "flash_kernels_roofline",
                 "scope_attn_linear_ms"):
        assert name not in last["metrics"]


@pytest.mark.parametrize("seed", [5, 4000000007])
def test_clamped_decay_rotary_and_half_the_steps_each_fail_a_limit(seed):
    import jax

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import calibrate_fault_kimi  # noqa: F401  (registers the two faults)
    import calibrate_fault

    cell = manifest.Cell(CELL, rehearse=True)
    got = calibrate_fault.read_faults(
        jax, cell, ["clamp_decay", "rotary", "half_steps"], seed)
    for spec, (numbers, table, ok) in got.items():
        assert not ok, (spec, table)


def test_manifest_entries_are_as_stated():
    bench = manifest.benchmark()
    assert not manifest.check_names(bench)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    cfg = manifest.load_json("configs", CONFIG + ".json")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "linear_attn_config"]
    assert entry["source"] == cfg["source"]
    assert "Kimi-Linear-48B-A3B-Instruct" in cfg["source"]
    # every width as published
    linear = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (2304, 32, 128, 4)
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (512, 128, 64, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_token"], cfg["num_shared_experts"],
            cfg["routed_scaling_factor"]) == (9216, 1024, 8, 1, 2.446)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (27, 256, 163840)
    # the published layers 1-9: the dense one and two whole 3:1 periods
    assert cfg["num_hidden_layers"] == 9
    assert linear["kda_layers"] == [i for i in pub["linear_attn_config"][
        "kda_layers"] if i <= 9]
    assert linear["full_attn_layers"] == [4, 8]
    assert {k: v for k, v in linear.items() if not k.endswith("_layers")} \
        == {k: v for k, v in pub["linear_attn_config"].items()
            if not k.endswith("_layers")}
    assert cfg["num_experts"] * 8 == pub["num_experts"]
    assert cfg["first_expert"] == 3 * cfg["num_experts"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "lora_silo2_seq4096", 1)
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    assert bench["per_layer"][-1] == {
        "name": "kda_steep_decay_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "linear attention kernels",
        "moves": "round_s", "workloads": [CELL]}
    for name in ("flash_roofline", "moe_tokens_here_share",
                 "window_kernels_roofline", "flash_window_block_share",
                 "ssd_kernels_roofline", "scope_attn_full_ms",
                 "scope_attn_window_ms", "scope_attn_ssm_ms",
                 "scope_moe_latent_ms", "scope_conv_ms", "scope_norm_ms"):
        assert CELL not in lists[name], name
    for name in ("setup_compile_s", "round_gap_ms", "round_mfu",
                 "device_idle_share", "round_host_ms", "host_input_ms",
                 "setup_build_s", "setup_trace_lower_s", "peak_hbm_gib",
                 "flash_kernels_roofline", "kda_kernels_roofline",
                 "moe_grouped_roofline", "moe_load_max_over_mean",
                 "moe_compact_share", "scope_engine_ms",
                 "scope_attn_latent_ms", "scope_attn_linear_ms",
                 "scope_mlp_ms", "scope_head_ms", "scope_lora_ms",
                 "scope_moe_route_ms", "scope_moe_experts_ms",
                 "scope_unscoped_share"):
        assert lists[name][-1] == CELL, name


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog")
def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The file holds every key of the catalog's copy of the published
    config under the same name with the same value, but the four it lists
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
    s = 4096
    # per position, forward multiply-adds of the frozen weights, by hand
    kda = (4 * 2304 * 4096 + 2304 * 32 + 2 * 2304 * 128 + 2 * 128 * 4096
           + 3 * 4096 * 4)
    assert round(kda / 1e6, 2) == 39.51
    latent = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert round(latent / 1e6, 2) == 29.11
    shared = 3 * 2304 * 1024                                  # 7.08M
    routed = 8 * 32 / 256 * shared                            # one expert
    router = 2304 * 256
    dense = 3 * 2304 * 9216
    head = 20480 * 2304
    frozen = (7 * kda + 2 * latent + 8 * (shared + routed + router) + dense
              + head)
    r = 8
    lora_kda = r * 4 * (2304 + 4096)
    lora_latent = r * (2304 + 6144 + 2304 + 576 + 512 + 8192 + 4096 + 2304)
    lora_ffn = lambda w: r * 3 * (2304 + w)  # noqa: E731
    adapters = (7 * lora_kda + 2 * lora_latent + lora_ffn(9216)
                + 8 * lora_ffn(1024))
    recurrence = 7 * 3 * 7 * 32 * 128 * 128
    core = 2 * 3 * 32 * (192 + 128) * s
    by_hand = (4 * frozen + 6 * adapters + recurrence + core) * 32768
    got = flops.flops_per_round(cell.config, cell.traffic)
    assert abs(got - by_hand) / by_hand < 1e-9
    assert 80e12 < got < 90e12
    assert flops.expert_layer_steps(cell.config, cell.traffic) == 64
    f, b = flops.grouped_expert_work(cell.config, 4096 * 64, 64)
    assert f == 4096 * 64 * 6 * 2 * 2304 * 1024
    assert b > 64 * 2 * 32 * 3 * 2304 * 1024 * 2        # the kernels alone
    flash = flops.flash_kernel_work(cell.config, cell.traffic)
    assert flash["fwd"][0] == 32 * s * s * (192 + 128)
    assert flash["dkv"][1] == 32 * s * 2 * (3 * 192 + 3 * 128)
    # the KDA kernels' work is the Ling cell's at the same heads and size:
    # the two cells' kda_kernels_roofline compare
    ling = manifest.Cell("ling3flash_lora_silo2_seq4096")
    assert flops.kda_kernel_work(cell.config, cell.traffic) == \
        manifest.load_module("flops", "ling3flash_ep8_l7").kda_kernel_work(
            ling.config, ling.traffic)
    tokens = s * 32
    kda_work = flops.kda_kernel_work(cell.config, cell.traffic)
    assert kda_work["fwd"][0] == 7 * tokens * 128 * 128
    assert kda_work["fwd"][1] == tokens * (4 * 128 * 2 + 128 * 4 + 4)


def test_steep_share_reader_reads_the_counters_or_nothing():
    from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics
    reader = manifest.load_module("metrics", "kda_steep_decay_share")
    REGISTRY.reset()
    assert reader.read({}) is None          # a program without the counters
    obs_metrics.record_kda_round(56.0)      # the bounded gate counts nothing
    assert reader.read({}) is None
    obs_metrics.record_kda_decays(7 * 8 * 4096 * 4096.0, 1e8)
    obs_metrics.record_kda_decays(7 * 8 * 4096 * 4096.0, 3e8)
    assert reader.read({}) == pytest.approx(
        100 * 4e8 / (2 * 7 * 8 * 4096 * 4096))
    REGISTRY.reset()
