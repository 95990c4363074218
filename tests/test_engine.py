"""Scanned streaming engine (core/engine.py): golden equivalence against
the legacy Python-loop driver, and batched multi-stream rendering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (render_streams, render_trajectory,
                               stream_phases)
from repro.core.pipeline import RenderConfig, render_trajectory_py
from repro.scenes.trajectory import dolly_trajectory

N_FRAMES = 7

_COUNT_FIELDS = ("n_gaussians", "candidate_pairs", "raw_pairs",
                 "sort_pairs", "raster_pairs", "tiles_interpolated",
                 "overflow_pairs", "overflow_tiles")


def _poses(n=N_FRAMES, dx=0.0):
    return dolly_trajectory(n, start=(dx, -0.3, -2.0),
                            target=(0.0, 0.0, 6.0))


@pytest.mark.parametrize("window,rcap", [(1, None), (3, None), (5, None),
                                         (3, 2)])
def test_scan_matches_python_loop(small_scene, small_cam, window, rcap):
    """One-executable scan == per-frame dispatch loop: frames within 1e-5,
    per-frame workload records exactly equal."""
    cfg = RenderConfig(window=window, rerender_capacity=rcap)
    poses = _poses()
    ref = render_trajectory_py(small_scene, small_cam, poses, cfg)
    got = render_trajectory(small_scene, small_cam, poses, cfg)
    np.testing.assert_allclose(np.asarray(got.frames),
                               np.asarray(ref.frames), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got.records.is_full),
                                  np.asarray(ref.records.is_full))
    np.testing.assert_array_equal(np.asarray(got.records.active),
                                  np.asarray(ref.records.active))
    for name in _COUNT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got.records, name)),
            np.asarray(getattr(ref.records, name)), err_msg=name)


def test_full_frame_schedule(small_scene, small_cam):
    """Frame f is full iff (f + phase) % window == 0, frame 0 always."""
    cfg = RenderConfig(window=3)
    res = render_trajectory(small_scene, small_cam, _poses(), cfg, phase=2)
    expect = [f == 0 or (f + 2) % 3 == 0 for f in range(N_FRAMES)]
    assert np.asarray(res.records.is_full).tolist() == expect


def test_stacked_records_indexing(small_scene, small_cam):
    """StackedRecords: attribute access is stacked, indexing is per-frame,
    and both views agree."""
    res = render_trajectory(small_scene, small_cam, _poses(),
                            RenderConfig(window=3))
    recs = res.records
    assert len(recs) == N_FRAMES
    t = small_cam.num_tiles
    assert recs.raster_pairs.shape == (N_FRAMES, t)
    assert recs[1].raster_pairs.shape == (t,)
    np.testing.assert_array_equal(np.asarray(recs[1].raster_pairs),
                                  np.asarray(recs.raster_pairs)[1])
    assert sum(int(r.is_full) for r in recs) == \
        int(np.asarray(recs.is_full).sum())


def test_keep_states_stacked(small_scene, small_cam):
    res = render_trajectory(small_scene, small_cam, _poses(),
                            RenderConfig(window=3), keep_states=True)
    h, w = small_cam.height, small_cam.width
    assert res.states is not None
    assert res.states.rgb.shape == (N_FRAMES, h, w, 3)
    assert res.states.source_mask.shape == (N_FRAMES, h, w)
    # the carried state's rgb is the composed frame
    np.testing.assert_allclose(np.asarray(res.states.rgb[1]),
                               np.asarray(res.frames[1]), atol=1e-6)


def test_frame_idx_survives_midtrajectory_keyframes(small_scene, small_cam):
    """state.frame_idx is the TRUE global index: mid-trajectory key frames
    (frames 3 and 6 at window=3) must not reset the counter — and the
    scanned engine must agree with the legacy loop on it."""
    cfg = RenderConfig(window=3)
    res = render_trajectory(small_scene, small_cam, _poses(), cfg,
                            keep_states=True)
    np.testing.assert_array_equal(np.asarray(res.states.frame_idx),
                                  np.arange(N_FRAMES))
    ref = render_trajectory_py(small_scene, small_cam, _poses(), cfg,
                               keep_states=True)
    np.testing.assert_array_equal(np.asarray(res.states.frame_idx),
                                  np.asarray(ref.states.frame_idx))


def test_streams_match_solo(small_scene, small_cam):
    """B=3 staggered vmapped streams each reproduce their solo render."""
    cfg = RenderConfig(window=4)
    b, f = 3, 6
    poses_b = jnp.stack([_poses(f, dx=0.03 * i) for i in range(b)])
    res = render_streams(small_scene, small_cam, poses_b, cfg)
    assert res.frames.shape == (b, f, small_cam.height, small_cam.width, 3)
    for i in range(b):
        solo = render_trajectory(small_scene, small_cam, poses_b[i], cfg,
                                 phase=int(res.phases[i]))
        np.testing.assert_allclose(np.asarray(res.frames[i]),
                                   np.asarray(solo.frames), atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(res.records.raster_pairs)[i],
            np.asarray(solo.records.raster_pairs))
        np.testing.assert_array_equal(
            np.asarray(res.records.is_full)[i],
            np.asarray(solo.records.is_full))


def test_stream_phase_staggering(small_scene, small_cam):
    """Past warmup, staggered streams never all re-key on the same step."""
    cfg = RenderConfig(window=4)
    b, f = 3, 6  # same shapes/cfg as test_streams_match_solo: shares the jit cache
    poses_b = jnp.stack([_poses(f, dx=0.03 * i) for i in range(b)])
    res = render_streams(small_scene, small_cam, poses_b, cfg)
    is_full = np.asarray(res.records.is_full)          # (B, F)
    assert bool(is_full[:, 0].all()), "frame 0 must be full on every stream"
    per_step = is_full[:, 1:].sum(axis=0)
    assert int(per_step.max()) <= int(np.ceil(b / cfg.window)), \
        f"key-frame spike: {per_step.tolist()}"


def test_stream_phases_cover_window():
    phases = np.asarray(stream_phases(4, 4))
    assert sorted(phases.tolist()) == [0, 1, 2, 3]
    phases = np.asarray(stream_phases(3, 5))
    assert len(set(phases.tolist())) == 3
    assert all(0 <= p < 5 for p in phases.tolist())
