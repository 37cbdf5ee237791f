"""``axk1_ep16_l5``: the CPU rehearsal of its cell is ``correct``, the fault
that is this model's own (top-7 routing) and half the steps left out are
not, nor is the four-chip cell's aggregate over one chip's clients, ``flops_per_round`` against
a hand count, and the three readers this configuration brought on a synthetic
summary. Run by hand, as the README says."""

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

CELL = "axk1_lora_silo2_seq4096"


def test_rehearsal_is_correct_and_reports_the_router_load():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000007", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 3, p.stderr[-2000:]
    last = [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith("{")][-1]
    assert last["info"] == "rehearsal" and last["correct"] is True, last
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    # no device trace on the CPU: the two roofline shares are left out
    assert "flash_kernels_roofline" not in last["metrics"]
    assert "moe_grouped_roofline" not in last["metrics"]


def _faults(workload, specs, seed):
    import jax

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import calibrate_fault

    cell = manifest.Cell(workload, rehearse=True)
    return cell, calibrate_fault.read_faults(jax, cell, specs(cell), seed)


@pytest.mark.parametrize("seed", [5, 4000000007])
def test_top7_routing_and_half_the_steps_each_fail_a_limit(seed):
    # the rehearsal routes top-4 of 16: the fault is one expert fewer
    _, got = _faults(
        CELL, lambda c: [f"num_experts_per_tok="
                         f"{c.config['num_experts_per_tok'] - 1}",
                         "half_steps"], seed)
    for spec, (numbers, table, ok) in got.items():
        assert not ok, (spec, table)


def test_an_aggregate_over_one_chips_clients_fails_a_limit():
    _, got = _faults("resnet56_fedavg_c64_x4", lambda c: ["no_exchange"], 5)
    numbers, table, ok = got["no_exchange"]
    assert not ok, table


def test_flops_per_round_against_a_hand_count():
    cell = manifest.Cell(CELL)
    flops = manifest.load_module("flops", "axk1_ep16_l5")
    # per position, forward multiply-adds of the frozen weights, by hand:
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
            + 8192 * 7168)                                   # 101.1M
    shared = 3 * 7168 * 2048                                 # 44.0M
    routed = 8 * 12 / 192 * shared                           # 0.5 expert
    router = 7168 * 192
    dense = 3 * 7168 * 18432
    head = 20480 * 7168
    frozen = 5 * attn + 4 * (shared + routed + router) + dense + head
    r = 8
    lora_attn = r * (7168 + 1536 + 1536 + 12288 + 7168 + 576 + 512 + 16384
                     + 8192 + 7168)
    lora_ffn = lambda w: r * 3 * (7168 + w)  # noqa: E731
    adapters = 5 * lora_attn + lora_ffn(18432) + 4 * lora_ffn(2048)
    core = 5 * 3 * 64 * (192 + 128) * 4096
    by_hand = (4 * frozen + 6 * adapters + core) * 32768
    got = flops.flops_per_round(cell.config, cell.traffic)
    assert abs(got - by_hand) / by_hand < 1e-9
    assert 210e12 < got < 220e12
    assert flops.expert_layer_steps(cell.config, cell.traffic) == 32
    f, b = flops.grouped_expert_work(cell.config, 2048 * 32, 32)
    assert f == 2048 * 32 * 6 * 2 * 7168 * 2048
    assert b > 32 * 2 * 12 * 3 * 7168 * 2048 * 2       # the kernels alone
    work = flops.flash_kernel_work(cell.config, cell.traffic)
    assert work["fwd"][0] == 64 * 4096 * 4096 * (192 + 128)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(op_calls, workload=CELL):
    cell = manifest.Cell(workload)
    return {"trace": {"op_calls": op_calls} if op_calls is not None else None,
            "peaks": PEAKS, "cell": cell, "traced_rounds": 4,
            "flops_module": manifest.load_module("flops",
                                                 cell.entry["config"])}


def test_flash_reader_finds_kernels_by_name_only():
    reader = manifest.load_module("metrics", "flash_kernels_roofline")
    assert reader.read(_ctx(None)) is None
    # XLA's own zero-time custom-calls and unnamed kernels are not counted
    assert reader.read(_ctx({
        "custom-call.7 custom-call bf16[64,4096,192]": (100, 0.0),
        "attn.3 custom-call bf16[64,4096,128],f32[64,4096,1]": (5, 0.1),
    })) is None
    calls = {"jvp_flash_fwd_.5 custom-call bf16[64,4096,128],f32[64,4096,1]":
             (2, 0.02),
             "transpose_jvp_flash_dq__.5 custom-call bf16[64,4096,192]":
             (2, 0.03),
             "transpose_jvp_flash_dkv__.5 custom-call bf16[64,4096,192],"
             "bf16[64,4096,128]": (2, 0.04),
             "custom-call.9 custom-call bf16[64,4096,192]": (50, 0.0)}
    share = reader.read(_ctx(calls))
    total = 2 * (343597383680 + 549755813888 + 687194767360) / 197e12
    assert abs(share - 100 * total / 0.09) < 1e-9 and 0 < share < 100
    assert reader.kind_of("fusion.12 fusion bf16[1,4096,7168] kOutput") is None
    # the dense model's cell is on the same reader, its work at d = 128
    mistral = {"jvp_flash_fwd_.2 custom-call bf16[256,1024,128],"
               "f32[256,1024,1]": (16, 0.0226),
               "custom-call.4 custom-call bf16[256,1024,128]": (64, 0.0)}
    share = reader.read(_ctx(mistral, "mistral7b_lora_silo2"))
    fwd = 2.0 * 8 * 1024 * 1024 * 128 * 32
    assert abs(share - 100 * 16 * fwd / 197e12 / 0.0226) < 1e-9


def test_grouped_reader_needs_the_counter_and_the_named_kernels(monkeypatch):
    reader = manifest.load_module("metrics", "moe_grouped_roofline")
    calls = {"moe_grouped_fwd.12 custom-call bf16[35840,2048]": (96, 0.3),
             "moe_grouped_dx.13 custom-call bf16[35840,7168]": (96, 0.3),
             "fusion.1 fusion bf16[35840,7168] kLoop": (32, 0.05)}
    monkeypatch.setattr(reader, "slots_per_round", lambda: None)
    assert reader.read(_ctx(calls)) is None           # no counter: nothing
    monkeypatch.setattr(reader, "slots_per_round", lambda: 65536.0)
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx({"fusion.1 fusion bf16[8] kLoop": (1, 1.0)})) is None
    share = reader.read(_ctx(calls))
    assert share is not None and 0 < share < 100


def test_load_reader_reads_the_gauges_or_nothing():
    from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics
    reader = manifest.load_module("metrics", "moe_load_max_over_mean")
    REGISTRY.reset()
    assert reader.read({}) is None
    obs_metrics.record_moe_round(65536.0, 32 * 260.0, 32.0, 384.0, 0.0)
    assert abs(reader.read({}) - 260.0 / (65536.0 / 384.0)) < 1e-9
    assert REGISTRY.counter("fed_moe_dropped").value() == 0.0
    REGISTRY.reset()
