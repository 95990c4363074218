"""The reduction from a profiler trace to busy time, scope time, gaps.

Synthetic traces pin the interval arithmetic; ``bench/testdata`` holds a
cut of a real one-chip trace in reduced form: the second traced round of
a ``room65k_1080p_w5.head1`` run on a TPU v5e, ops of 10 us or more.
"""
import glob
import json
import os

import pytest

import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(5, 8), (0, 3), (2, 4), (6, 7)]) == [(0, 4), (5, 8)]
    assert tr.length(tr.clip([(0, 4), (5, 8)], 2, 6)) == 3


def _trace():
    ops = {0: [(100, 200, "fusion", "jit(f)/repro.frame/full/repro.frame/bin/top_k"),
               (150, 180, "fusion", "jit(f)/repro.frame/full/repro.frame/bin/x"),
               (300, 400, "custom-call", "jit(f)/repro.frame/sparse/repro.frame/raster/repro.raster/pallas_fused/pallas_call")],
           1: [(100, 150, "fusion", "jit(f)/repro.frame/full/repro.frame/bin/top_k")]}
    host = [(90, 500, "bench/round"), (200, 300, "serve/commit"),
            (0, 1000, "serve/round")]
    return tr.Trace(ops, host, (90, 500))


def test_busy_and_idle_share_per_device():
    t = _trace()
    assert t.busy_s(0) == pytest.approx(200e-9)
    assert t.busy_s(1) == pytest.approx(50e-9)
    assert t.mean_busy_s() == pytest.approx(125e-9)
    assert t.window_s == pytest.approx(410e-9)


def test_scope_time_counts_nested_ops_once_and_sums_devices():
    t = _trace()
    assert t.scope_s("repro.frame/bin") == pytest.approx(150e-9)
    assert t.scope_s("repro.frame/raster") == pytest.approx(100e-9)
    assert t.scope_s("repro.frame/warp") == 0.0


def test_idle_gaps_are_named_by_the_narrowest_covering_host_span():
    t = _trace()
    gaps = {(d, a, b): t.host_name(a, b) for d, a, b in t.idle_gaps()}
    assert gaps[(0, 200, 300)] == "serve/commit"
    assert gaps[(0, 90, 100)] == "bench/round"
    b = t.breakdown()
    assert b["idle_gaps"][0][1] == pytest.approx(350e-9)   # device 1
    labels = dict(b["device_ops"])
    assert labels["repro.frame/full/repro.frame/bin/fusion"] == \
        pytest.approx(180e-9)


def test_tpu_op_names_give_instruction_and_opcode():
    sort = ("%sort.71 = (f32[1,8160,65536]{2,1,0:T(8,128)}, s32[1,8160,65536]"
            "{2,1,0:T(8,128)}) sort(f32[1,8160,65536]{2,1,0:T(8,128)} %x, "
            "s32[1,8160,65536]{2,1,0:T(8,128)} %iota.96), dimensions={2}")
    kernel = ("%pallas_fused.5 = (f32[8160,8,256]{2,1,0:T(8,128)S(1)}, "
              "f32[8160,2,1024]{2,1,0:T(2,128)}) custom-call(s32[8160]"
              "{0:T(1024)S(1)} %copy-done.112), custom_call_target="
              "\"tpu_custom_call\"")
    fusion = ("%fusion.37 = f32[2088960,4]{0,1:T(4,128)S(1)} fusion(f32[2088960,"
              "4]{0,1:T(4,128)S(1)} %broadcast.1040), kind=kCustom")
    assert tr.op_kind(sort) == ("sort.71", "sort")
    assert tr.op_kind(kernel) == ("pallas_fused.5", "custom-call")
    assert tr.op_kind(fusion) == ("fusion.37", "fusion")
    assert tr.op_kind("wrapped_sine.2") == ("wrapped_sine.2", "wrapped_sine")


def test_scopes_come_from_the_op_module_hlo_text():
    text = """HloModule jit_step
%body.3 (p: f32[8]) -> f32[8] {
  ROOT %sort.71 = f32[8]{0} sort(%p), dimensions={0}, metadata={op_name="jit(step)/repro.frame/full/repro.frame/bin/sort" stack_frame_id=3}
}
ENTRY %main (x: f32[8]) -> f32[8] {
  %copy.1 = f32[8]{0} copy(%x)
  ROOT %pallas_fused.5 = f32[8]{0} custom-call(%copy.1), metadata={op_name="jit(step)/repro.frame/raster/repro.raster/pallas_fused/pallas_call"}
}"""
    scopes = tr.hlo_scopes({"jit_step": [text]})["jit_step"]
    assert scopes["sort.71"].endswith("repro.frame/bin/sort")
    assert "repro.raster/pallas_fused" in scopes["pallas_fused.5"]
    assert "copy.1" not in scopes
    modules = [(0, 10, "jit_other"), (10, 50, "jit_step")]
    assert tr._module_at(modules, 12) == "jit_step"
    assert tr._module_at(modules, 50) == ""


def test_json_round_trip():
    t = _trace()
    u = tr.Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert u.busy_s(0) == t.busy_s(0) and u.host == t.host


def test_recorded_chip_trace_reduces():
    paths = glob.glob(os.path.join(TESTDATA, "*.json"))
    assert paths, "no recorded trace under bench/testdata"
    for p in paths:
        with open(p) as f:
            t = tr.Trace.from_json(json.load(f))
        assert 0 < t.mean_busy_s() <= t.window_s
        assert t.scope_s("repro.frame/raster") > 0
        assert t.scope_s("repro.frame/bin") > 0
        assert t.match_s(lambda kind, scope: kind == "custom-call" and
                         "repro.raster/pallas_fused" in scope) > 0
        b = t.breakdown()
        assert b["device_ops"] and len(b["device_ops"]) <= 10
