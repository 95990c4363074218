"""The ``room2m_1080p_w5`` configuration at a size the CPU holds, and
the per-layer readers it brought.

- A served window of the configuration's room, cut to 70,000 Gaussians
  (above the 65,536 of the other configurations, so the scene takes the
  131,072 bucket and the pair budget derived for it) on the middle
  128 x 64 pixels of its camera, is held to the plain reference (``reference.py``: key frames
  by ``key_frame``, sparse frames by ``sparse_frame``) under the
  configuration's own limits.
- ``intersect.stage1_pairs_per_frame`` and ``pipeline.rank_ms_per_frame``
  read a synthetic context, and stay silent where the program has no
  such counter or scope, as before they were added.
"""
import copy
import json
import math
import os
import time

import pytest

import harness
import trace_reduce as tr

CONFIG = os.path.join(harness.BENCH, "configs", "room2m_1080p_w5.json")
TESTDATA = os.path.join(harness.BENCH, "testdata", "w5_head1_round.json")


def _small_cell(n=70_000):
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(os.path.join(harness.BENCH, "traffic", "head1.json")) as f:
        mix = json.load(f)
    cfg = copy.deepcopy(cfg)
    # The room scales with the square root of the count, as the
    # configuration's does, so the splat density stays the same.
    # The camera is the middle 128 x 64 pixels of the configuration's
    # 1920 x 1088 one: the same focal length in pixels, so a tile holds
    # as many Gaussians as there.
    fov = math.radians(cfg["camera"]["fov_deg"])
    focal = cfg["image_height"] / 2 / math.tan(fov / 2)
    cfg.update(num_gaussians=n, image_width=128, image_height=64)
    cfg["camera"]["fov_deg"] = math.degrees(2 * math.atan(32 / focal))
    cfg["scene"]["room"] = 4.0 * math.sqrt(n / 65_536)
    cfg["render"]["impl"] = "jnp_chunked"
    cfg["serve"]["r_buckets"] = [16]
    return harness.Cell("room70k_small.head1", cfg, mix, 1, [], [])


def test_served_chain_matches_the_reference_under_the_limits():
    cell = _small_cell()
    assert cell.config["limits"] == {
        "key_rmse": 0.003, "key_tile_relerr_p50": 0.001,
        "sparse_rmse": 0.006, "sparse_tile_relerr_p90": 0.001}
    # A window long enough to hold a key frame (k = 5) however slow the
    # rounds on a loaded CPU: the check reads both kinds of frame.
    r = harness.run_cell(cell, 3_000_000_019, 8.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         log=lambda s: None)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    for name in cell.config["limits"]:
        assert r["checks"][name]["value"] is not None, name


def _ctx(counters=None, trace=None, frames=2):
    return harness.Context(cell=None, trace=trace, spans=[],
                           counters=counters or {}, frames=[None] * frames,
                           records=[], rounds=2, compiles=0, peaks=None)


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_stage1_pairs_reader_divides_by_the_frames_served():
    ctx = _ctx({"serve_stage1_pairs_total": 40_000_000.0,
                "serve_frames_total": 4.0})
    assert _read("intersect.stage1_pairs_per_frame", ctx) == \
        pytest.approx(10_000_000.0)


@pytest.mark.parametrize("counters", [
    {"serve_frames_total": 4.0},
    {"serve_stage1_pairs_total": 5.0, "serve_frames_total": 0.0},
    {}], ids=["no_counter", "no_frames", "nothing"])
def test_stage1_pairs_reader_is_silent_without_its_counter(counters):
    assert _read("intersect.stage1_pairs_per_frame", _ctx(counters)) is None


def test_rank_reader_sums_its_scope_on_both_branches():
    # Times in ns: the key branch's rank (sort 3 ms, scatter 1 ms nested
    # in it counted once), the sparse branch's (2 ms), and ops of other
    # stages that must be left out.
    scope = "jit(step)/repro.frame/{}/repro.frame/intersect/{}"
    ops = {0: [
        (0, 3_000_000, "sort", scope.format("full", "repro.frame/rank")),
        (1_000_000, 2_000_000, "scatter",
         scope.format("full", "repro.frame/rank")),
        (3_000_000, 5_000_000, "sort",
         scope.format("sparse", "repro.frame/rank")),
        (5_000_000, 9_000_000, "fusion",
         "jit(step)/repro.frame/full/repro.frame/raster/fusion"),
        (9_000_000, 9_500_000, "sort",
         "jit(step)/repro.frame/full/repro.frame/bin/sort")]}
    trace = tr.Trace(ops, [], (0, 10_000_000))
    assert _read("pipeline.rank_ms_per_frame", _ctx(trace=trace)) == \
        pytest.approx(2.5)


def test_rank_reader_is_silent_on_a_trace_without_its_scope():
    # A recorded round of a program from before the scope was added.
    with open(TESTDATA) as f:
        trace = tr.Trace.from_json(json.load(f))
    assert trace.scope_s("repro.frame/bin") > 0
    assert _read("pipeline.rank_ms_per_frame", _ctx(trace=trace)) is None
    empty = tr.Trace({0: []}, [], (0, 1))
    assert _read("pipeline.rank_ms_per_frame", _ctx(trace=empty)) is None
