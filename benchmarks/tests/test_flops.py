"""Each FLOPs function against a value worked by hand."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest  # noqa: E402


def test_resnet56_forward_by_hand():
    cfg = manifest.load_json("configs", "resnet56_cifar.json")
    tr = manifest.load_json("traffic", "fedavg_c64.json")
    mod = manifest.load_module("flops", "resnet56_cifar")
    stem = 2 * 9 * 3 * 16 * 32 * 32                       # 884,736
    conv = 2 * 9 * 16 * 16 * 32 * 32                      # 4,718,592, every full 3x3
    stage1 = 18 * conv
    later = (conv // 2) + conv + 2 * 16 * 32 * 16 * 16 + 16 * conv
    assert later == 82_837_504
    by_hand = stem + stage1 + 2 * later + 2 * 64 * 10
    assert by_hand == 251_495_680
    assert mod.forward_flops_per_sample(cfg) == by_hand
    assert mod.flops_per_round(cfg, tr) == 3.0 * by_hand * 50_000


def test_mistral_lora_position_by_hand():
    cfg = manifest.load_json("configs", "mistral7b_v01_l4.json")
    tr = manifest.load_json("traffic", "lora_silo2.json")
    mod = manifest.load_module("flops", "mistral7b_v01_l4")
    assert mod.frozen_matmul_params_per_layer(cfg) == 218_103_808
    assert mod.adapter_params_per_layer(cfg) == 655_360
    per_pos = 4 * (4 * 218_103_808 + 6 * 655_360 + 6 * 4096 * 1024) \
        + 4 * 32000 * 4096
    assert per_pos == 4_130_340_864
    assert mod.flops_per_position(cfg, 1024) == per_pos
    assert mod.flops_per_round(cfg, tr) == float(per_pos * 32_768)
    work = mod.flash_kernel_work(cfg, tr)
    assert work["fwd"][0] == 8 * 2.0 * 1024 * 1024 * 128 * 32
    assert work["dkv"][0] == 2 * work["fwd"][0]
