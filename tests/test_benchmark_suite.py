"""The benchmark's fast CPU tests, each as a tier-1 test of its own.

``benchmarks/tests/`` lies outside ``tests/``, so no gate ran it: a span,
gauge or counter renamed in the program passed tier-1 and surfaced on the
chip as a per-layer metric reading ``null``. This module imports those
files unedited and re-exports their tests (parametrisation and fixtures as
the files have them) as ``<file>__<test>``, so each counts and names
itself when it fails. The tests that run whole rounds or subprocesses
(``test_correct.py``, ``test_rehearsal.py``, the first four of
``test_axk1.py``, the first two of ``test_ling3.py``, of ``test_mimo.py``,
of ``test_nemotron.py``, of ``test_kimi.py`` and of ``test_keye.py``: over
a minute each on a CPU) stay outside tier-1, as do ``test_kimi.py``'s and
``test_keye.py``'s checks against the model catalog, which read it from
``MODEL_CATALOG``.
"""

import importlib
import os
import sys

import pytest

from fedml_tpu.core import obs
from fedml_tpu.core.obs import REGISTRY
from fedml_tpu.core.obs import trace as obs_trace

_BENCH_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests")

# file -> the tests taken from it (None: every test of the file)
_TAKEN = {
    "test_flops": None,
    "test_manifest": None,
    "test_moe_compact_share": None,
    "test_program_metrics": None,
    "test_scopes": None,
    "test_trace_reduce": None,
    "test_axk1": ("test_flops_per_round_against_a_hand_count",
                  "test_flash_reader_finds_kernels_by_name_only",
                  "test_grouped_reader_needs_the_counter_and_the_named_"
                  "kernels",
                  "test_load_reader_reads_the_gauges_or_nothing"),
    "test_ling3": ("test_work_functions_against_hand_counts",
                   "test_kda_reader_finds_kernels_by_name_only",
                   "test_tokens_here_reader_reads_the_counters_or_nothing"),
    "test_mimo": ("test_manifest_entries_are_the_issues",
                  "test_work_functions_against_hand_counts",
                  "test_window_reader_finds_its_kernels_by_name_and_the_"
                  "flash_reader_not",
                  "test_block_share_reader_reads_the_gauge_or_nothing"),
    "test_nemotron": ("test_manifest_entries_are_the_issues",
                      "test_every_number_of_the_catalogs_config_is_in_the_"
                      "file",
                      "test_work_functions_against_hand_counts",
                      "test_ssd_reader_finds_kernels_by_name_only",
                      "test_the_two_scope_readers_read_their_scope_or_"
                      "nothing"),
    "test_kimi": ("test_manifest_entries_are_as_stated",
                  "test_work_functions_against_hand_counts",
                  "test_steep_share_reader_reads_the_counters_or_nothing"),
    "test_keye": ("test_manifest_entries_are_as_stated",
                  "test_work_functions_against_hand_counts",
                  "test_sparse_reader_finds_its_kernels_by_name_only",
                  "test_ratio_reader_reads_the_gauge_or_nothing"),
}
_LEFT_OUT = {
    # pins PR 26's five entries as the LAST five of BENCHMARK.json's
    # per_layer list; PRs 28 and 29 appended four more, so it fails since
    # then (PERF.md section 7 (e)) and only a `benchmark` PR may edit it
    ("test_program_metrics", "test_manifest_takes_the_new_entries"),
    # pins ``moe_compact_share``'s ``workloads`` to the axk1 cell alone;
    # PR 32 appended the second cell with experts, as the contract lets a
    # model_config PR do, and may not edit the file:
    # ``test_moe_compact_share_entry_is_as_accepted_but_for_its_cells``
    # stands in and pins every other key of the entry
    ("test_moe_compact_share",
     "test_manifest_names_the_reader_for_the_axk1_cell_alone"),
    # pins PR 36's thirteen ``scope_*`` entries as the LAST thirteen of
    # per_layer with their ``workloads`` lists to the letter; PR 38 appended
    # a seventh cell to those lists and three entries after them, as the
    # contract lets a model_config PR do, and may not edit the file (PERF.md
    # section 7 (h)): ``test_the_scope_entries_are_as_accepted_but_for_
    # their_cells`` stands in and pins every other key
    ("test_scopes", "test_manifest_has_the_thirteen_entries_with_their_cells"),
    # pins the Nemotron entries as the LAST three of per_layer and its cell
    # as the last of each list it joined; a later cell appended itself to
    # those lists and one entry after them, as the contract lets a
    # model_config PR do: the test of the same name below runs the file's
    # test unedited on the benchmark as it stood up to the Nemotron cell
    ("test_nemotron", "test_manifest_entries_are_the_issues"),
    # the same for the Kimi cell, whose test pins its metric as the last of
    # per_layer and its cell as the last of each list it joined: the
    # Keye cell appended itself and four entries after them
    ("test_kimi", "test_manifest_entries_are_as_stated"),
}


def _is_fixture(obj) -> bool:
    return type(obj).__name__ == "FixtureFunctionDefinition" \
        or hasattr(obj, "_pytestfixturefunction")


def _export():
    path = list(sys.path)
    sys.path.insert(0, _BENCH_TESTS)
    try:
        for file, taken in _TAKEN.items():
            mod = importlib.import_module(file)
            for name, obj in vars(mod).items():
                if _is_fixture(obj):
                    # a fixture keeps its name: the tests ask for it by it
                    assert globals().setdefault(name, obj) is obj, name
                elif (name.startswith("test_") and callable(obj)
                      and (taken is None or name in taken)
                      and (file, name) not in _LEFT_OUT):
                    globals()[f"{file}__{name}"] = obj
            missing = set(taken or ()) - set(vars(mod))
            assert not missing, f"{file}: no such tests {missing}"
    finally:
        # the files put benchmarks/ in front of sys.path for themselves;
        # what they import from there is in sys.modules by now
        sys.path[:] = path


_export()


@pytest.fixture(autouse=True)
def _leave_no_state():
    """These tests set gauges and clear the registry as they please;
    the files tier-1 runs after this one in the same process start from
    the documented defaults."""
    path = list(sys.path)
    yield
    sys.path[:] = path
    obs.configure(None)
    obs_trace.clear_finished()
    REGISTRY.reset()


def _up_to(bench, cell, config, metric):
    """``bench`` as it stood when ``cell``, ``config`` and the per-layer
    ``metric`` were its last entries: what later PRs appended (cells,
    configurations, per-layer entries, and cells in the per-layer lists)
    left out."""
    def cut(items, last):
        names = [x["name"] for x in items]
        return items[:names.index(last) + 1]

    cells = cut(bench["workloads"], cell)
    kept = {w["name"] for w in cells}
    return dict(bench, workloads=cells,
                configs=cut(bench["configs"], config),
                per_layer=[dict(m, workloads=[w for w in m["workloads"]
                                              if w in kept])
                           if "workloads" in m else m
                           for m in cut(bench["per_layer"], metric)])


def test_nemotron__test_manifest_entries_are_the_issues(monkeypatch):
    """``benchmarks/tests/test_nemotron.py``'s test of its manifest entries,
    unedited, on the benchmark as it stood up to the Nemotron cell (its
    configuration and its last per-layer entry): the file pins that cell
    as the last of every list it joined, which each cell appended later
    moves. What the later cells appended is pinned by their own tests."""
    sys.path.insert(0, _BENCH_TESTS)
    import test_nemotron
    manifest = test_nemotron.manifest
    whole = manifest.benchmark()
    monkeypatch.setattr(manifest, "benchmark", lambda: _up_to(
        whole, test_nemotron.CELL, test_nemotron.CONFIG,
        "scope_moe_latent_ms"))
    test_nemotron.test_manifest_entries_are_the_issues()


def test_kimi__test_manifest_entries_are_as_stated(monkeypatch):
    """``benchmarks/tests/test_kimi.py``'s test of its manifest entries,
    unedited, on the benchmark as it stood up to the Kimi cell (its
    configuration and its last per-layer entry), as the Nemotron one
    above runs."""
    sys.path.insert(0, _BENCH_TESTS)
    import test_kimi
    manifest = test_kimi.manifest
    whole = manifest.benchmark()
    monkeypatch.setattr(manifest, "benchmark", lambda: _up_to(
        whole, test_kimi.CELL, test_kimi.CONFIG, "kda_steep_decay_share"))
    test_kimi.test_manifest_entries_are_as_stated()


def test_expert_layer_metrics_list_the_cells_with_experts():
    """Every metric of the expert layer lists the cells whose configuration
    has routed experts, the axk1 cell first as PR 29 left it, and no
    other; each such cell's configuration brings the work functions the
    grouped reader calls."""
    sys.path.insert(0, os.path.dirname(_BENCH_TESTS))
    from harness import manifest
    bench = manifest.benchmark()
    with_experts = [
        w["name"] for w in bench["workloads"]
        if any(k in manifest.load_json("configs", w["config"] + ".json")
               for k in ("n_routed_experts", "num_experts"))]
    assert with_experts[0] == "axk1_lora_silo2_seq4096"
    latent = [w for w in with_experts if manifest.Cell(w).config.get(
        "moe_latent_size")]
    assert latent == ["nemotron3_super_lora_silo2_seq4096"]
    for m in bench["per_layer"]:
        if m["layer"] == "expert layer" and m["name"] != \
                "moe_tokens_here_share":
            # the latent projections' scope: the cells with a latent alone
            assert m["workloads"] == (
                latent if m["name"] == "scope_moe_latent_ms"
                else with_experts), m["name"]
            assert m["moves"] == "round_s"
    for cell in with_experts:
        flops = manifest.load_module(
            "flops", manifest.Cell(cell).entry["config"])
        assert hasattr(flops, "grouped_expert_work")
        assert hasattr(flops, "expert_layer_steps")


def test_moe_compact_share_entry_is_as_accepted_but_for_its_cells():
    """What ``test_manifest_names_the_reader_for_the_axk1_cell_alone``
    pinned, key by key, but for ``workloads``: that list begins with the
    axk1 cell and holds cells with experts alone (the test above)."""
    sys.path.insert(0, os.path.dirname(_BENCH_TESTS))
    from harness import manifest
    entry = [dict(m) for m in manifest.benchmark()["per_layer"]
             if m["name"] == "moe_compact_share"]
    assert len(entry) == 1
    cells = entry[0].pop("workloads")
    assert entry == [{
        "name": "moe_compact_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "expert layer",
        "moves": "round_s"}]
    assert cells[0] == "axk1_lora_silo2_seq4096"
    assert len(cells) == len(set(cells))


def test_the_scope_entries_are_as_accepted_but_for_their_cells():
    """What ``test_manifest_has_the_thirteen_entries_with_their_cells``
    pinned, key by key, but for ``workloads`` and for being the list's last
    thirteen: PR 36's thirteen entries stay together in their order, and
    each scope metric lists, in the benchmark's order, the cells whose
    configuration has that kind of layer; the scope metrics later PRs
    brought follow the same rule."""
    sys.path.insert(0, os.path.dirname(_BENCH_TESTS))
    from harness import manifest
    bench = manifest.benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    cfgs = {c: manifest.Cell(c).config for c in cells}
    lm = [c for c in cells if cfgs[c]["input"]["kind"] == "tokens"]
    resnet = [c for c in cells if c not in lm]
    experts = [c for c in lm if any(k in cfgs[c] for k in (
        "n_routed_experts", "num_experts"))]
    mixers = {c: cfgs[c].get("hybrid_override_pattern", "") for c in lm}
    latent = [c for c in lm if cfgs[c].get("kv_lora_rank")]
    linear = [c for c in lm if cfgs[c].get("layer_group_size")
              or cfgs[c].get("linear_attn_config")]
    window = [c for c in lm if cfgs[c].get("hybrid_layer_pattern")]
    sparse = [c for c in lm if cfgs[c].get("sa_config")]
    # grouped-query softmax layers: what is neither latent, linear, sparse
    # nor a single-mixer stack has them in every layer; a stack where it
    # says *
    full = [c for c in lm if (c not in latent and c not in linear
                              and c not in sparse and not mixers[c])
            or "*" in mixers[c]]

    def has_mlp(cfg):
        """A dense feed-forward somewhere: no experts, a shared expert, or
        layers before or between the expert ones."""
        freq = cfg.get("moe_layer_freq", 1)
        return (not any(k in cfg for k in ("n_routed_experts",
                                           "num_experts"))
                or any(cfg.get(k) for k in (
                    "n_shared_experts", "num_shared_experts",
                    "moe_shared_expert_intermediate_size"))
                or bool(cfg.get("first_k_dense_replace"))
                or (isinstance(freq, list) and 0 in freq)
                or bool(cfg.get("mlp_only_layers")))
    train = "local training / LLM train step"
    want = {"scope_engine_ms": ("round engine", cells),
            "scope_conv_ms": (train, resnet),
            "scope_norm_ms": (train, resnet),
            "scope_attn_full_ms": (train, full),
            "scope_attn_window_ms": (train, window),
            "scope_attn_latent_ms": (train, latent),
            "scope_attn_linear_ms": (train, linear),
            "scope_mlp_ms": (train, [c for c in lm if has_mlp(cfgs[c])]),
            "scope_head_ms": (train, lm),
            "scope_lora_ms": (train, lm),
            "scope_moe_route_ms": ("expert layer", experts),
            "scope_moe_experts_ms": ("expert layer", experts),
            "scope_unscoped_share": ("device", cells),
            "scope_attn_ssm_ms": (train, [c for c in lm if "M" in mixers[c]]),
            "scope_moe_latent_ms": ("expert layer", [
                c for c in experts if cfgs[c].get("moe_latent_size")]),
            "scope_attn_sparse_ms": (train, sparse),
            "scope_attn_index_ms": (train, sparse)}
    entries = [m for m in bench["per_layer"]
               if m["name"].startswith("scope_")]
    assert [m["name"] for m in entries] == list(want)
    at = [m["name"] for m in bench["per_layer"]].index("scope_engine_ms")
    assert bench["per_layer"][at:at + 13] == entries[:13]
    for m in entries:
        layer, listed = want[m["name"]]
        assert m == {"name": m["name"],
                     "unit": "%" if m["name"] == "scope_unscoped_share"
                     else "ms",
                     "better": "lower", "source": "device_trace",
                     "layer": layer, "moves": "round_s",
                     "workloads": listed}, m["name"]
        assert os.path.exists(os.path.join(manifest.ROOT, "metrics",
                                           m["name"] + ".py"))
        for cell in listed:
            assert m in manifest.Cell(cell).per_layer


def test_the_suite_takes_what_it_says():
    """At least the 20 tests of ``benchmarks/tests/`` this began with
    are collected here (24 cases with their parametrisation), some from
    every file named above; a test added to a file taken whole comes
    along by itself."""
    taken = sorted(n for n in globals() if "__test_" in n)
    assert len(taken) >= 20, taken
    for file in _TAKEN:
        assert any(n.startswith(file + "__") for n in taken), file
