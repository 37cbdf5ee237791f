"""``nemotron3_super_ep8_l11``: its manifest entries, hand counts for its work
functions, the three readers this configuration brought on a made-up trace
and scope table, and (by hand, as the README says: whole rounds) its cell's
CPU rehearsal and the three planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness import manifest, scope_time  # noqa: E402

CONFIG = "nemotron3_super_ep8_l11"
CELL = "nemotron3_super_lora_silo2_seq4096"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    # the CPU runs the dense path and has no device trace
    for name in ("ssd_kernels_roofline", "scope_attn_ssm_ms",
                 "scope_moe_latent_ms", "flash_kernels_roofline",
                 "moe_grouped_roofline"):
        assert name not in last["metrics"]


@pytest.mark.parametrize("seed", [5, 4000000007])
def test_no_decay_plain_relu_and_half_the_steps_each_fail_a_limit(seed):
    import jax

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import calibrate_fault_nemotron  # noqa: F401  (registers the two faults)
    import calibrate_fault

    cell = manifest.Cell(CELL, rehearse=True)
    got = calibrate_fault.read_faults(
        jax, cell, ["no_decay", "plain_relu", "half_steps"], seed)
    for spec, (numbers, table, ok) in got.items():
        assert not ok, (spec, table)


def test_manifest_entries_are_the_issues():
    bench = manifest.benchmark()
    assert not manifest.check_names(bench)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    cfg = manifest.load_json("configs", CONFIG + ".json")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"] == cfg["source"]
    assert "NVIDIA-Nemotron-3-Super-120B-A12B-BF16" in cfg["source"]
    # every width as published
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["expand"]) == (4096, 128, 64, 128, 8, 4,
                                                  128, 2)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (32, 2, 128)
    assert (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["mlp_hidden_act"]) == (1024, 2688, 5376, 22, 5, "relu2")
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"], pub["num_nextn_predict_layers"]) == (
        88, 512, 131072, 1)
    # one whole period: the published layers 27-37
    assert len(pub["hybrid_override_pattern"]) == 88
    assert cfg["hybrid_override_pattern"] == \
        pub["hybrid_override_pattern"][27:38] == "MEMEMEMEM*E"
    assert cfg["num_hidden_layers"] == 11
    assert cfg["n_routed_experts"] * 8 == pub["n_routed_experts"]
    assert cfg["first_expert"] == 3 * cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0
    for key in ("deployment", "assumed", "departures"):
        assert cfg[key]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "lora_silo2_seq4096", 1)
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in ("ssd_kernels_roofline", "scope_attn_ssm_ms",
                 "scope_moe_latent_ms"):
        assert lists[name] == [CELL]
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "ssd_kernels_roofline", "scope_attn_ssm_ms", "scope_moe_latent_ms"]
    for name in ("flash_roofline", "kda_kernels_roofline",
                 "moe_tokens_here_share", "window_kernels_roofline",
                 "scope_attn_latent_ms", "scope_attn_linear_ms",
                 "scope_attn_window_ms", "scope_conv_ms"):
        assert CELL not in lists[name]
    for name in ("flash_kernels_roofline", "moe_grouped_roofline",
                 "moe_load_max_over_mean", "moe_compact_share", "round_mfu",
                 "scope_attn_full_ms", "scope_mlp_ms", "scope_moe_experts_ms",
                 "scope_unscoped_share", "peak_hbm_gib"):
        assert lists[name][-1] == CELL


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The file holds every key of the catalog's copy of the published
    config under the same name with the same value, but the five it lists
    as reduced."""
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
    mamba = 4096 * 18560 + 8192 * 4096 + 10240 * 4
    assert round(mamba / 1e6, 2) == 109.62
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    assert round(attn / 1e6, 2) == 35.65
    expert = 2 * 1024 * 2688
    experts = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 22 * 64 / 512 * expert)
    head = 16384 * 4096
    frozen = 5 * mamba + attn + 5 * experts + head
    assert 0.99e9 < frozen < 1.01e9
    r = 8
    lora_mamba = r * (4096 + 18560 + 8192 + 4096)
    lora_attn = r * (2 * (4096 + 4096) + 2 * (4096 + 256))
    lora_experts = r * (2 * (4096 + 1024) + 2 * (4096 + 5376))
    adapters = 5 * lora_mamba + lora_attn + 5 * lora_experts
    recurrence = 5 * 3 * 5 * 128 * 64 * 128
    scores = 3 * 32 * 2 * 128 * s
    by_hand = (4 * frozen + 6 * adapters + recurrence + scores) * 32768
    got = flops.flops_per_round(cell.config, cell.traffic)
    assert abs(got - by_hand) / by_hand < 1e-9
    assert 135e12 < got < 140e12
    assert flops.expert_layer_steps(cell.config, cell.traffic) == 40
    f, b = flops.grouped_expert_work(cell.config, 11264 * 40, 40)
    assert f == 11264 * 40 * 4 * 2 * 1024 * 2688
    assert b > 40 * 2 * 64 * 2 * 1024 * 2688 * 2       # the kernels alone
    flash = flops.flash_kernel_work(cell.config, cell.traffic)
    assert flash["fwd"][0] == 32 * s * s * 2 * 128
    assert flash["dkv"][0] == 2 * flash["fwd"][0]
    # keys and values at the 2 heads the model has
    assert flash["fwd"][1] == s * 2 * 128 * (2 * 32 + 2 * 2)
    assert flash["dkv"][1] == s * 2 * 128 * (2 * 32 + 4 * 2)
    ssd = flops.ssd_kernel_work(cell.config, cell.traffic)
    assert ssd["fwd"][0] == s * (128 * 3 * 2 * 128 * 64 + 8 * 2 * 128 * 128)
    assert ssd["bwd"][0] == 2 * ssd["fwd"][0]
    x, bc, small = s * 8192 * 2, 2 * s * 1024 * 2, 2 * s * 128 * 4
    assert ssd["fwd"][1] == 2 * x + bc + small
    states = 32 * 128 * 64 * 128 * 4
    assert states == 134_217_728
    assert ssd["bwd"][1] == 3 * x + 2 * (bc + small) + states
    # bytes bind both by shapes alone
    for flops_, bytes_ in ssd.values():
        assert bytes_ / 819e9 > flops_ / 197e12


def _ctx(op_calls, workload=CELL):
    cell = manifest.Cell(workload)
    return {"trace": {"op_calls": op_calls} if op_calls is not None else None,
            "peaks": PEAKS, "cell": cell, "traced_rounds": 4,
            "flops_module": manifest.load_module("flops",
                                                 cell.entry["config"])}


def test_ssd_reader_finds_kernels_by_name_only():
    reader = manifest.load_module("metrics", "ssd_kernels_roofline")
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx({
        "custom-call.7 custom-call bf16[1,4096,8192]": (100, 0.0),
        "kda_fwd.5 custom-call bf16[1,4096,4096]": (5, 0.1),
        "fusion.3 fusion bf16[1,4096,8192] kLoop": (5, 0.1)})) is None
    calls = {"ssd_fwd.5 custom-call bf16[1,4096,8192],f32[1,64,32,128,128]":
             (160, 0.2),
             "ssd_bwd.7 custom-call bf16[1,4096,8192],f32[1,8,4096,16]":
             (160, 0.5),
             "jvp_flash_fwd_.5 custom-call bf16[32,4096,128]": (32, 0.1)}
    share = reader.read(_ctx(calls))
    work = manifest.load_module("flops", CONFIG).ssd_kernel_work(
        manifest.Cell(CELL).config, manifest.Cell(CELL).traffic)
    least = sum(160 * b / 819e9 for _, b in work.values())
    assert abs(share - 100 * least / 0.7) < 1e-9
    assert 0 < share < 100
    assert reader.kind_of("ssd_bwd.7 custom-call f32[8]") == "bwd"
    assert reader.kind_of("ssd_fwd custom-call f32[8]") == "fwd"
    # the accepted readers take none of these kernels, and this one none
    # of theirs
    for other in ("kda_kernels_roofline", "flash_kernels_roofline"):
        kind_of = manifest.load_module("metrics", other).kind_of
        assert kind_of("ssd_fwd.5 custom-call") is None
        assert kind_of("ssd_bwd.7 custom-call") is None
    assert reader.kind_of("kda_bwd.7 custom-call") is None
    # a configuration without the layer has no work function: nothing
    assert reader.read(_ctx(calls, "ling3flash_lora_silo2_seq4096")) is None


@pytest.mark.parametrize("name,scope,want", [
    ("scope_attn_ssm_ms", "attn.ssm", 55.0),
    ("scope_moe_latent_ms", "moe.latent", 7.0)])
def test_the_two_scope_readers_read_their_scope_or_nothing(
        name, scope, want, monkeypatch):
    table = {"fusion.1": "attn.ssm", "ssd_fwd.2": "attn.ssm",
             "fusion.3": "lora", "fusion.4": "moe.latent",
             "fusion.5": "moe.experts"}
    ms = {"fusion.1 fusion bf16[4096,18560] kOutput": 60,
          "ssd_fwd.2 custom-call bf16[1,4096,8192]": 50,
          "fusion.3 fusion f32[4096,8] kLoop": 10,
          "fusion.4 fusion bf16[4096,1024] kOutput": 14,
          "fusion.5 fusion bf16[38912,2688] kLoop": 30}
    ctx = {"trace": {"op_calls": {k: (4, v * 1e-3) for k, v in ms.items()},
                     "window_s": 3.99},
           "traced_rounds": 2, "traced_seconds": 4.0,
           "cell": types.SimpleNamespace(name="a_cell")}
    read = manifest.load_module("metrics", name).read
    monkeypatch.setattr(scope_time, "program_table",
                        lambda: (table, {"seconds": 0.1, "stale": False}))
    assert read(dict(ctx)) == pytest.approx(want)
    # a program without the scope (the parent's table has no such value)
    monkeypatch.setattr(
        scope_time, "program_table",
        lambda: ({k: v for k, v in table.items() if v != scope},
                 {"seconds": 0.1, "stale": False}))
    assert read(dict(ctx)) is None
    monkeypatch.setattr(scope_time, "program_table", lambda: (None, None))
    assert read(dict(ctx)) is None
    assert read(dict(ctx, trace=None)) is None
