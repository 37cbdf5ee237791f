"""The trace reduction on a hand-built trace: busy union, gaps, per-op sums,
round gaps and the attribution of a gap to the host's span."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce as tr  # noqa: E402

US = 1000


def _trace():
    ops = [("while.1 while s32[]", 0, 100 * US),           # wrapper over the next two
           ("fusion.1", 0, 40 * US), ("convolution.2", 50 * US, 50 * US),
           ("copy.3", 130 * US, 10 * US),       # a small program between rounds
           ("while.1 while s32[]", 200 * US, 100 * US),
           ("fusion.1", 200 * US, 40 * US), ("convolution.2", 240 * US, 60 * US)]
    modules = [("jit_round", 0, 100 * US), ("jit_put", 130 * US, 10 * US),
               ("jit_round", 200 * US, 100 * US)]
    host = [("bench.round", 0, 150 * US), ("bench.readback", 100 * US, 25 * US),
            ("bench.round", 150 * US, 150 * US)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_union_and_gaps():
    assert tr.union([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert tr.gaps([(0, 4), (5, 7)], 0, 10) == [(4, 5), (7, 10)]
    assert tr.clip([(0, 4), (5, 7)], 3, 6) == [(3, 4), (5, 6)]


def test_reduce_counts_time_once():
    s = tr.reduce(_trace())
    assert abs(s["window_s"] - 300e-6) < 1e-12
    # busy: [0,100] + [130,140] + [200,300] = 210 us (the 40-50 us hole is
    # under the while wrapper, so the union covers it)
    assert abs(s["busy_s"] - 210e-6) < 1e-12
    assert not any(k.startswith("while") for k in s["op_calls"])
    assert s["op_calls"]["fusion.1"][0] == 2
    assert abs(s["op_calls"]["fusion.1"][1] - 80e-6) < 1e-12
    assert abs(s["op_calls"]["convolution.2"][1] - 110e-6) < 1e-12
    assert s["program"] == "jit_round" and s["program_runs"] == 2
    # between the rounds: 100 us apart, 10 us of it busy
    assert s["round_gaps_ns"] == [90 * US]
    labels = dict((k, v) for k, v in s["idle_gaps"])
    assert abs(labels["bench.readback"] - 30e-6) < 1e-12   # gap 100-130
    assert abs(labels["bench.round"] - 60e-6) < 1e-12      # gap 140-200


def test_no_device_plane_reads_nothing():
    assert tr.reduce({"devices": {}, "host": []}) is None


def test_short_name_keeps_opcode_and_shapes():
    raw = ('%attn.115 = (bf16[256,1024,128]{2,1,0:T(8,128)(2,1)}, '
           'bf16[256,1024,128]{2,1,0}) custom-call(bf16[256,1024,128]{2,1,0} '
           '%bitcast.1880), custom_call_target="tpu_custom_call"')
    assert tr.short_name(raw) == ("attn.115 custom-call "
                                  "bf16[256,1024,128],bf16[256,1024,128]")
    loop = "%while.5 = (s32[], f32[3]{0}) while(%tuple.3), condition=%c"
    assert tr.is_container(tr.short_name(loop))
    fused = ("%fusion.7 = bf16[32,32,32,16]{0,3,2,1} fusion(bf16[2]{0} %a), "
             "kind=kOutput, calls=%f")
    assert tr.short_name(fused) == "fusion.7 fusion bf16[32,32,32,16] kOutput"
    assert not tr.is_container(tr.short_name(fused))
