"""The scope reader on a hand-built ``op_calls`` and table, each of the
thirteen metric files on it, ``tools/scope_table.py``'s rows, and the
manifest's thirteen entries with their cells."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import manifest, scope_time  # noqa: E402

scope_table = manifest.load_module("tools", "scope_table")

# instruction -> scope, as ``fedml_tpu.core.obs.scopes.table()`` gives it
TABLE = {"fusion.1": "attn.latent", "flash_fwd.2": "attn.latent",
         "flash_dkv.3": "attn.latent", "fusion.4": "lora",
         "fusion.5": "mlp", "fusion.6": "head", "gather.7": "embed",
         "fusion.8": "moe.route", "moe_grouped_dx.9": "moe.experts",
         "fusion.10": "engine.accumulate", "fusion.11": "local.update",
         "fusion.12": "local.batch", "fusion.13": "engine.server",
         "dynamic-slice.14": "engine.slot", "fusion.15": "norm",
         "fusion.16": "local.grad", "copy.17": None,
         "convolution.18": "cv.conv", "fusion.19": "cv.norm",
         "fusion.20": "cv.head", "kda_bwd.21": "attn.linear",
         "flash_win_dq.22": "attn.window", "fusion.23": "attn.full"}
# milliseconds over 2 traced rounds, under the profiler's reduced names
MS = {"fusion.1 fusion bf16[4096,7168] kOutput": 100,
      "flash_fwd.2 custom-call bf16[64,4096,128]": 40,
      "flash_dkv.3 custom-call bf16[64,4096,192],bf16[64,4096,128]": 60,
      "fusion.4 fusion f32[4096,8] kLoop": 10,
      "fusion.5 fusion bf16[4096,18432] kOutput": 30,
      "fusion.6 fusion f32[4096,20480] kOutput": 20,
      "gather.7 gather bf16[4096,7168]": 2,
      "fusion.8 fusion f32[4096,192] kLoop": 6,
      "moe_grouped_dx.9 custom-call bf16[7168,7168]": 50,
      "fusion.10 fusion f32[8,7168] kLoop": 1,
      "fusion.11 fusion f32[8,7168] kLoop": 2,
      "fusion.12 fusion s32[4] kLoop": 3,
      "fusion.13 fusion f32[8,7168] kLoop": 4,
      "dynamic-slice.14 dynamic-slice s32[1,4096]": 5,
      "fusion.15 fusion bf16[4096,7168] kLoop": 8,
      "fusion.16 fusion f32[] kLoop": 7,
      "copy.17 copy bf16[4096,7168]": 12,
      "convolution.18 convolution bf16[32,32,32,16]": 70,
      "fusion.19 fusion bf16[32,32,32,16] kLoop": 35,
      "fusion.20 fusion f32[32,10] kLoop": 3,
      "kda_bwd.21 custom-call bf16[1,4096,4096]": 44,
      "flash_win_dq.22 custom-call bf16[64,4096,192]": 16,
      "fusion.23 fusion bf16[4096,4096] kOutput": 24,
      # another program's event: its instruction is not the round's
      "fusion.99 fusion u32[2] kLoop": 8}
OP_CALLS = {k: (4, ms * 1e-3) for k, ms in MS.items()}
WANT = {"scope_engine_ms": (1 + 2 + 3 + 4 + 5) / 2,
        "scope_conv_ms": 35.0, "scope_norm_ms": 17.5,
        "scope_attn_full_ms": 12.0, "scope_attn_window_ms": 8.0,
        "scope_attn_latent_ms": 100.0, "scope_attn_linear_ms": 22.0,
        "scope_mlp_ms": 15.0, "scope_head_ms": 11.0, "scope_lora_ms": 5.0,
        "scope_moe_route_ms": 3.0, "scope_moe_experts_ms": 25.0,
        "scope_unscoped_share": 100.0 * (12 + 8) / sum(MS.values())}
ALL = ["resnet56_fedavg_c64", "mistral7b_lora_silo2",
       "axk1_lora_silo2_seq4096", "resnet56_fedavg_c64_x4",
       "ling3flash_lora_silo2_seq4096", "mimo_v2_flash_lora_silo2_seq4096"]
RESNET, LM, EXPERTS = [ALL[0], ALL[3]], [ALL[1], ALL[2]] + ALL[4:], \
    [ALL[2]] + ALL[4:]
TRAIN = "local training / LLM train step"
CELLS = {"scope_engine_ms": ("round engine", ALL),
         "scope_conv_ms": (TRAIN, RESNET), "scope_norm_ms": (TRAIN, RESNET),
         "scope_attn_full_ms": (TRAIN, [ALL[1], ALL[5]]),
         "scope_attn_window_ms": (TRAIN, [ALL[5]]),
         "scope_attn_latent_ms": (TRAIN, [ALL[2], ALL[4]]),
         "scope_attn_linear_ms": (TRAIN, [ALL[4]]),
         "scope_mlp_ms": (TRAIN, LM), "scope_head_ms": (TRAIN, LM),
         "scope_lora_ms": (TRAIN, LM),
         "scope_moe_route_ms": ("expert layer", EXPERTS),
         "scope_moe_experts_ms": ("expert layer", EXPERTS),
         "scope_unscoped_share": ("device", ALL)}


def _ctx(**over):
    return dict({"trace": {"op_calls": OP_CALLS, "window_s": 3.99},
                 "traced_rounds": 2, "traced_seconds": 4.0,
                 "cell": types.SimpleNamespace(name="a_cell")}, **over)


@pytest.fixture
def table(monkeypatch):
    """The program answers with the hand-built table."""
    monkeypatch.setattr(scope_time, "program_table",
                        lambda: (TABLE, {"seconds": 0.5, "stale": False}))


def test_join_sums_by_innermost_scope_unscoped_and_unjoined():
    got = scope_time.join(OP_CALLS, TABLE)
    assert got["attn.latent"] == pytest.approx(0.200)   # its kernels too
    assert got["lora"] == pytest.approx(0.010)          # not attn.latent's
    assert got[scope_time.UNSCOPED] == pytest.approx(0.012)
    assert got[scope_time.UNJOINED] == pytest.approx(0.008)
    assert sum(got.values()) == pytest.approx(sum(MS.values()) * 1e-3)
    assert scope_time.join({}, TABLE) == {"unscoped": 0.0, "unjoined": 0.0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_metric_reads_its_scopes(name, table):
    read = manifest.load_module("metrics", name).read
    assert read(_ctx()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_metric_reads_nothing_without_a_table_or_a_trace(
        name, monkeypatch):
    read = manifest.load_module("metrics", name).read
    monkeypatch.setattr(scope_time, "program_table", lambda: (None, None))
    assert read(_ctx()) is None                   # no program was noted
    monkeypatch.setattr(scope_time, "program_table",
                        lambda: pytest.fail("no trace, no question"))
    assert read(_ctx(trace=None)) is None


def test_the_join_is_made_once_a_run_and_says_what_it_cost(
        monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(
        scope_time, "program_table",
        lambda: calls.append(1) or (TABLE, {"seconds": 0.5}))
    ctx = _ctx()
    for name in WANT:
        manifest.load_module("metrics", name).read(ctx)
    assert len(calls) == 1
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line]
    assert [rec["info"] for rec in lines] == ["scopes"]
    assert lines[0]["build"] == {"seconds": 0.5}
    assert lines[0]["ms_a_round"]["attn.latent"] == pytest.approx(100.0)


def test_a_profile_cut_short_counts_the_rounds_it_holds(table):
    """The profiler keeps about 6.2 million device events: the one-chip
    ResNet cell's profile ends 3.87 s into its 5.31 s window of two rounds.
    The seconds are then over 1.46 rounds, not 2."""
    read = manifest.load_module("metrics", "scope_conv_ms").read
    whole = _ctx(trace={"op_calls": OP_CALLS, "window_s": 5.30},
                 traced_seconds=5.31)
    assert scope_time.rounds_kept(whole) == 2
    assert read(whole) == pytest.approx(35.0)
    cut = _ctx(trace={"op_calls": OP_CALLS, "window_s": 3.87},
               traced_seconds=5.31)
    assert scope_time.rounds_kept(cut) == pytest.approx(2 * 3.87 / 5.31)
    assert read(cut) == pytest.approx(70.0 * 5.31 / (2 * 3.87))


def test_a_program_without_the_module_reads_nothing(monkeypatch):
    """The parent of PR 36 has no ``core/obs/scopes.py``: the import
    fails and every reader returns None, none raises."""
    import fedml_tpu.core.obs
    monkeypatch.delattr(fedml_tpu.core.obs, "scopes")
    monkeypatch.setitem(sys.modules, "fedml_tpu.core.obs.scopes", None)
    assert scope_time.program_table() == (None, None)
    for name in WANT:
        assert manifest.load_module("metrics", name).read(_ctx()) is None


def test_a_kept_trace_gets_the_table_and_the_sums(table, monkeypatch,
                                                  tmp_path):
    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    kept = tmp_path / ".bench_trace" / "a_cell"
    kept.mkdir(parents=True)
    scope_time.by_scope(_ctx())
    assert not list(kept.iterdir())               # only when asked
    monkeypatch.setenv("BENCH_KEEP_TRACE", "1")
    scope_time.by_scope(_ctx())
    assert json.loads((kept / "scopes.json").read_text()) == TABLE
    sums = json.loads((kept / "scope_ms.json").read_text())
    assert sums["rounds_kept"] == 2
    assert sums["ms_a_round"]["moe.experts"] == pytest.approx(25.0)


def test_the_tools_rows_name_the_kernels_inside_a_scope():
    assert scope_table.kernel_of("flash_fwd.2 custom-call bf16[1]") == \
        "flash_fwd"
    assert scope_table.kernel_of("kda_bwd custom-call bf16[1]") == "kda_bwd"
    assert scope_table.kernel_of("custom-call.7 custom-call f32[]") is None
    assert scope_table.kernel_of("fusion.1 fusion bf16[1]") is None
    rows = {r[0]: r for r in scope_table.rows(OP_CALLS, TABLE, 2)}
    _, ms, ops, kernels, longest, kinds = rows["attn.latent"]
    assert ms == pytest.approx(100.0) and ops == 6
    assert kinds == {"fusion": pytest.approx(50.0),
                     "custom-call": pytest.approx(50.0)}
    assert kernels == {"flash_dkv": pytest.approx(30.0),
                       "flash_fwd": pytest.approx(20.0)}
    assert longest[0][0].startswith("fusion.1 ")
    assert rows["unjoined"][1] == pytest.approx(4.0)
    assert sum(r[1] for r in rows.values()) == pytest.approx(
        sum(MS.values()) / 2)


def test_manifest_has_the_thirteen_entries_with_their_cells():
    bench = manifest.benchmark()
    assert not manifest.check_names(bench)
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"].startswith("scope_")}
    assert sorted(entries) == sorted(CELLS) and len(entries) == 13
    assert [m["name"] for m in bench["per_layer"][-13:]] == list(CELLS)
    for name, (layer, cells) in CELLS.items():
        m = entries[name]
        assert m == {"name": name,
                     "unit": "%" if name == "scope_unscoped_share" else "ms",
                     "better": "lower", "source": "device_trace",
                     "layer": layer, "moves": "round_s",
                     "workloads": cells}, name
        assert os.path.exists(os.path.join(manifest.ROOT, "metrics",
                                           name + ".py"))
        for cell in cells:
            assert m in manifest.Cell(cell).per_layer
