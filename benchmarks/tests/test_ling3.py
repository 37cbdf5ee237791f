"""``ling3flash_ep8_l7``: hand counts for its four work functions, the two
readers this configuration brought on a made-up trace, and (by hand, as the
README says: whole rounds) its cell's CPU rehearsal and the two faults that
are this model's own."""

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

CELL = "ling3flash_lora_silo2_seq4096"
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
    assert 10 < last["metrics"]["moe_tokens_here_share"]["value"] < 90
    assert last["metrics"]["moe_compact_share"]["value"] == 100.0
    # no device trace on the CPU: the roofline shares are left out
    assert "kda_kernels_roofline" not in last["metrics"]
    assert "flash_kernels_roofline" not in last["metrics"]


@pytest.mark.parametrize("seed", [5, 4000000007])
def test_plain_routing_no_decay_and_half_the_steps_each_fail_a_limit(seed):
    import jax

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import calibrate_fault

    cell = manifest.Cell(CELL, rehearse=True)
    got = calibrate_fault.read_faults(
        jax, cell, ["topk_method=none", "kda_lower_bound=0", "half_steps"],
        seed)
    for spec, (numbers, table, ok) in got.items():
        assert not ok, (spec, table)


def test_work_functions_against_hand_counts():
    cell = manifest.Cell(CELL)
    flops = manifest.load_module("flops", "ling3flash_ep8_l7")
    # per position, forward multiply-adds of the frozen weights, by hand
    kda = 5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4096 * 4     # 52.6M
    latent = (2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560
              + 2560 * 32)                                    # 32.0M
    shared = 3 * 2560 * 768                                   # 5.90M
    routed = 8 * 64 / 512 * shared                            # one expert
    router = 2560 * 512
    dense = 3 * 2560 * 6144
    head = 19648 * 2560
    frozen = (6 * kda + latent + 6 * (shared + routed + router) + dense
              + head)
    r = 8
    lora_kda = r * (4 * (2560 + 4096) + 4096 + 2560)
    lora_latent = r * (2560 + 6144 + 2560 + 576 + 512 + 8192 + 4096 + 2560)
    lora_ffn = lambda w: r * 3 * (2560 + w)  # noqa: E731
    adapters = 6 * lora_kda + lora_latent + lora_ffn(6144) + 6 * lora_ffn(768)
    recurrence = 6 * 3 * 7 * 32 * 128 * 128
    core = 3 * 32 * (192 + 128) * 4096
    by_hand = (4 * frozen + 6 * adapters + recurrence + core) * 32768
    got = flops.flops_per_round(cell.config, cell.traffic)
    assert abs(got - by_hand) / by_hand < 1e-9
    assert 70e12 < got < 80e12
    assert flops.expert_layer_steps(cell.config, cell.traffic) == 48
    f, b = flops.grouped_expert_work(cell.config, 4096 * 48, 48)
    assert f == 4096 * 48 * 6 * 2 * 2560 * 768
    assert b > 48 * 2 * 64 * 3 * 2560 * 768 * 2        # the kernels alone
    flash = flops.flash_kernel_work(cell.config, cell.traffic)
    assert flash["fwd"][0] == 32 * 4096 * 4096 * (192 + 128)
    assert flash["dkv"][1] == 32 * 4096 * 2 * (3 * 192 + 3 * 128)
    kda_work = flops.kda_kernel_work(cell.config, cell.traffic)
    tokens = 4096 * 32
    assert kda_work["fwd"][0] == 7 * tokens * 128 * 128
    assert kda_work["bwd"][0] == 2 * kda_work["fwd"][0]
    # q k v o in bfloat16, the log-decay float32 a channel, beta a head
    assert kda_work["fwd"][1] == tokens * (4 * 128 * 2 + 128 * 4 + 4)
    assert kda_work["bwd"][1] == tokens * (2 * (3 * 128 * 2 + 128 * 4 + 4)
                                           + 128 * 2)
    # memory-bound by shapes alone, whatever a program's chunks are
    assert kda_work["fwd"][1] / 819e9 > kda_work["fwd"][0] / 197e12


def _ctx(op_calls, workload=CELL):
    cell = manifest.Cell(workload)
    return {"trace": {"op_calls": op_calls} if op_calls is not None else None,
            "peaks": PEAKS, "cell": cell, "traced_rounds": 4,
            "flops_module": manifest.load_module("flops",
                                                 cell.entry["config"])}


def test_kda_reader_finds_kernels_by_name_only():
    reader = manifest.load_module("metrics", "kda_kernels_roofline")
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx({
        "custom-call.7 custom-call bf16[1,4096,4096]": (100, 0.0),
        "fusion.3 fusion bf16[1,4096,4096] kLoop": (5, 0.1)})) is None
    calls = {"kda_fwd.5 custom-call bf16[1,4096,4096],f32[1,32,64,128,128]":
             (192, 0.9),
             "kda_bwd.7 custom-call bf16[1,4096,4096],f32[1,4096,4096]":
             (192, 1.5),
             "jvp_flash_fwd_.5 custom-call bf16[32,4096,128]": (32, 0.1)}
    share = reader.read(_ctx(calls))
    tokens = 4096 * 32
    fwd = tokens * (4 * 128 * 2 + 128 * 4 + 4) / 819e9
    bwd = tokens * (2 * (3 * 128 * 2 + 128 * 4 + 4) + 128 * 2) / 819e9
    assert abs(share - 100 * 192 * (fwd + bwd) / 2.4) < 1e-9
    assert 0 < share < 100
    assert reader.kind_of("kda_bwd.7 custom-call f32[8]") == "bwd"
    # a configuration without the layer has no work function: nothing
    assert reader.read(_ctx(calls, "axk1_lora_silo2_seq4096")) is None


def test_tokens_here_reader_reads_the_counters_or_nothing():
    from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics
    reader = manifest.load_module("metrics", "moe_tokens_here_share")
    REGISTRY.reset()
    assert reader.read(_ctx(None)) is None
    # a round without a group limit counts passes but no such tokens
    obs_metrics.record_moe_round(65536.0, 32 * 260.0, 32.0, 384.0, 0.0, 32.0)
    assert reader.read(_ctx(None)) is None
    obs_metrics.record_moe_round(196608.0, 48 * 90.0, 48.0, 3072.0, 0.0,
                                 48.0, 48 * 2048.0)
    assert abs(reader.read(_ctx(None)) - 100 * 48 * 2048 / (80 * 4096)) < 1e-9
    REGISTRY.reset()
