"""Contribution-culling invariants (core/culling.py, DESIGN.md §12).

Pinned contracts:
  - ``cull_threshold=0.0`` (and the record_contrib instrumentation) is
    bit-exact with the pre-culling pipeline on full AND sparse frames,
    on both the jnp_chunked and pallas_fused raster impls;
  - the contribution statistics agree bit-for-bit across impls;
  - padding / masked bin lanes report exactly zero contribution, and the
    per-Gaussian prior is inf exactly on never-considered Gaussians;
  - ``cull_pairs`` keeps inf-prior Gaussians, respects the warp gate,
    demotes fully-culled slots, and counts what it removed;
  - a nonzero threshold reduces sort/raster work and re-render demand on
    a real trajectory while staying visually faithful (>= 30 dB PSNR vs
    the uncull render on sparse frames).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import culling
from repro.core.engine import render_trajectory
from repro.core.metrics import psnr
from repro.core.pipeline import (RenderConfig, render_full_frame,
                                 render_sparse_frame)
from repro.core.plan import rerender_demand
from repro.scenes.trajectory import dolly_trajectory

_BASE_FIELDS = ("is_full", "n_gaussians", "candidate_pairs", "raw_pairs",
                "sort_pairs", "raster_pairs", "active",
                "tiles_interpolated", "overflow_pairs", "overflow_tiles")


def _cfg(**kw):
    kw.setdefault("impl", "jnp_chunked")
    return RenderConfig(capacity=64, window=3, chunk=32, **kw)


def _frame_pair(scene, cam, cfg):
    """One full frame + one sparse frame warped from it."""
    poses = dolly_trajectory(2, start=(0.0, -0.3, -2.0),
                             target=(0.0, 0.0, 6.0))
    ref_cam = cam.with_pose(poses[0])
    tgt_cam = cam.with_pose(poses[1])
    out, state, rec_full = render_full_frame(scene, ref_cam, cfg)
    rgb, _, rec_sparse = render_sparse_frame(scene, ref_cam, tgt_cam,
                                             state, cfg)
    return out.rgb, rec_full, rgb, rec_sparse


@pytest.mark.parametrize("impl", ["jnp_chunked", "pallas_fused"])
def test_threshold_zero_bit_exact(small_scene, small_cam, impl):
    """Threading the contribution machinery (record_contrib=True,
    threshold 0) must not move a single bit of the render or the
    pre-existing record fields, full and sparse alike."""
    base = _frame_pair(small_scene, small_cam, _cfg(impl=impl))
    inst = _frame_pair(small_scene, small_cam,
                       _cfg(impl=impl, record_contrib=True))
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(inst[0]))
    np.testing.assert_array_equal(np.asarray(base[2]), np.asarray(inst[2]))
    for base_rec, inst_rec in ((base[1], inst[1]), (base[3], inst[3])):
        assert base_rec.lane_contrib is None
        assert inst_rec.lane_contrib is not None
        for name in _BASE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(base_rec, name)),
                np.asarray(getattr(inst_rec, name)), err_msg=name)
        assert int(base_rec.culled_pairs) == 0
        assert int(inst_rec.culled_pairs) == 0


def test_contrib_identical_across_impls(small_scene, small_cam):
    """jnp_chunked and pallas_fused share the blend math exactly, so the
    recorded contributions (and the derived prior) match bit-for-bit."""
    cfg_j = _cfg(impl="jnp_chunked", record_contrib=True)
    cfg_f = _cfg(impl="pallas_fused", record_contrib=True)
    _, st_j, rec_j = render_full_frame(small_scene, small_cam, cfg_j)
    _, st_f, rec_f = render_full_frame(small_scene, small_cam, cfg_f)
    np.testing.assert_array_equal(np.asarray(rec_j.lane_contrib),
                                  np.asarray(rec_f.lane_contrib))
    np.testing.assert_array_equal(np.asarray(st_j.contrib),
                                  np.asarray(st_f.contrib))


def test_pad_lanes_and_unseen_gaussians(small_scene, small_cam):
    """Lanes past a tile's bin count carry exactly 0 contribution; the
    prior is finite non-negative exactly where the Gaussian was binned
    somewhere and inf (keep-all) everywhere else."""
    cfg = _cfg(record_contrib=True)
    _, state, rec = render_full_frame(small_scene, small_cam, cfg)
    contrib = np.asarray(rec.lane_contrib)
    counts = np.asarray(rec.sort_pairs)
    assert contrib.shape[0] == counts.shape[0]
    for t in range(contrib.shape[0]):
        assert np.all(contrib[t, counts[t]:] == 0.0), t
    assert np.all(contrib >= 0.0)
    prior = np.asarray(state.contrib)
    finite = np.isfinite(prior)
    assert finite.any() and (~finite).any()
    assert np.all(prior[finite] >= 0.0)
    assert np.all(np.isinf(prior[~finite]))


def test_cull_pairs_unit():
    """Keep rules, the warp gate, slot demotion, and the removed count
    on a hand-built mask."""
    mask = jnp.asarray([[1, 1, 1],
                        [1, 1, 1],
                        [0, 0, 1],
                        [1, 0, 0]], bool)          # (N=4, R=3)
    slot_active = jnp.asarray([True, True, True])
    tile_ids = jnp.asarray([0, 1, 2], jnp.int32)
    prior = jnp.asarray([jnp.inf, 0.0, 1.0, 0.2])
    gate = jnp.asarray([True, True, False])        # slot 2 ungated
    new_mask, new_active, culled = culling.cull_pairs(
        mask, slot_active, tile_ids, prior, gate, 0.5)
    want = np.asarray([[1, 1, 1],       # inf prior: always kept
                       [0, 0, 1],       # 0.0 < 0.5: culled where gated
                       [0, 0, 1],       # only present in ungated slot 2
                       [0, 0, 0]], bool)  # 0.2 < 0.5: culled
    np.testing.assert_array_equal(np.asarray(new_mask), want)
    assert int(culled) == 3
    # No slot lost ALL its pairs here; now isolate g3 in its own slot.
    mask2 = jnp.asarray([[0, 0, 0],
                         [0, 0, 0],
                         [0, 0, 0],
                         [0, 1, 0]], bool)
    m2, active2, culled2 = culling.cull_pairs(
        mask2, slot_active, tile_ids, prior, gate, 0.5)
    assert not np.any(np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(active2),
                                  [True, False, True])
    assert int(culled2) == 1
    # Empty-before slots are NOT demoted (nothing was culled from them).
    m3, active3, _ = culling.cull_pairs(
        jnp.zeros((4, 3), bool), slot_active, tile_ids, prior, gate, 0.5)
    np.testing.assert_array_equal(np.asarray(active3), [True, True, True])


def test_cull_trajectory_reduces_work_keeps_quality(small_scene, small_cam):
    """The end-to-end claim on a streamed trajectory: a nonzero
    threshold culls pairs on sparse frames (never key frames), shrinks
    sort work and re-render demand, and the frames stay >= 30 dB PSNR
    against the uncull render."""
    poses = dolly_trajectory(6, start=(0.0, -0.3, -2.0),
                             target=(0.0, 0.0, 6.0))
    base_cfg = _cfg()
    cull_cfg = dataclasses.replace(base_cfg, cull_threshold=0.05)
    base = render_trajectory(small_scene, small_cam, poses, base_cfg)
    cull = render_trajectory(small_scene, small_cam, poses, cull_cfg)

    is_full = np.asarray(base.records.is_full)
    culled = np.asarray(cull.records.culled_pairs)
    assert np.all(culled[is_full] == 0)
    assert culled[~is_full].sum() > 0

    sort_base = np.asarray(base.records.sort_pairs).sum(axis=-1)
    sort_cull = np.asarray(cull.records.sort_pairs).sum(axis=-1)
    assert np.all(sort_cull <= sort_base)
    assert sort_cull[~is_full].sum() < sort_base[~is_full].sum()

    demand_base = np.asarray(rerender_demand(
        base.records.active, base.records.overflow_tiles))
    demand_cull = np.asarray(rerender_demand(
        cull.records.active, cull.records.overflow_tiles))
    assert np.all(demand_cull <= demand_base)

    # Key frames are bit-identical (culling never touches them) and
    # sparse frames stay visually faithful.
    for f in range(poses.shape[0]):
        if is_full[f]:
            np.testing.assert_array_equal(np.asarray(cull.frames[f]),
                                          np.asarray(base.frames[f]))
        else:
            assert float(psnr(cull.frames[f], base.frames[f])) >= 30.0
