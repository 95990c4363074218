"""TilePlan-driven rendering (DESIGN.md §2): the compacted sparse path is
equivalent to the dense path, compiles to (R, K)-shaped stages, and the
device-LDU schedule recorded inside the jitted scan matches the numpy
golden ``load_balance.schedule``."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, intersect, plan as plan_mod, projection, raster
from repro.core.load_balance import schedule
from repro.core.pipeline import (RenderConfig, render_full_frame,
                                 render_sparse_frame, render_trajectory)
from repro.core.streaming import (AcceleratorConfig, frameworks_from_stacked,
                                  simulate_sequence)
from repro.scenes.trajectory import dolly_trajectory

_PER_TILE_FIELDS = ("raw_pairs", "sort_pairs", "raster_pairs", "active",
                    "block_of_tile", "order_in_block")


def _poses(n=4):
    return dolly_trajectory(n, start=(0.0, -0.3, -2.0),
                            target=(0.0, 0.0, 6.0))


def _sparse_inputs(scene, cam, cfg):
    poses = _poses(2)
    full = jax.jit(render_full_frame, static_argnames="cfg")
    _, state, _ = full(scene, cam.with_pose(poses[0]), cfg=cfg)
    return cam.with_pose(poses[0]), cam.with_pose(poses[1]), state


def test_plan_basic_structure(small_cam):
    tx, ty = small_cam.tiles_x, small_cam.tiles_y
    t = tx * ty
    p = plan_mod.full_plan(tx, ty)
    assert p.num_slots == t
    assert sorted(np.asarray(p.tile_ids).tolist()) == list(range(t))
    assert bool(np.asarray(p.slot_active).all())

    rerender = jnp.zeros((t,), bool).at[jnp.array([1, 5, 9])].set(True)
    sp = plan_mod.sparse_plan(rerender, tx, ty, 2)
    assert sp.num_slots == 2
    assert int(np.asarray(sp.slot_active).sum()) == 2
    assert int(sp.overflow_tiles) == 1
    # selected slots really are re-render tiles
    assert all(bool(rerender[i]) for i in np.asarray(sp.tile_ids).tolist())


def test_compacted_sparse_matches_dense(small_scene, small_cam):
    """Plan equivalence: with enough slots for every re-render tile, the
    (R, K) compacted path reproduces the dense (T, K) path — frames to
    1e-5, FrameRecord pair counts exactly."""
    dense_cfg = RenderConfig(window=10, rerender_capacity=None)
    ref_cam, tgt_cam, state = _sparse_inputs(small_scene, small_cam,
                                             dense_cfg)
    sparse = jax.jit(render_sparse_frame, static_argnames="cfg")
    rgb_d, _, rec_d = sparse(small_scene, ref_cam, tgt_cam, state,
                             cfg=dense_cfg)
    n_rr = int(np.asarray(rec_d.active).sum())
    assert 0 < n_rr < small_cam.num_tiles, "test needs a partial re-render"

    cap_cfg = RenderConfig(window=10, rerender_capacity=n_rr)
    rgb_c, _, rec_c = sparse(small_scene, ref_cam, tgt_cam, state,
                             cfg=cap_cfg)
    assert int(rec_c.overflow_tiles) == 0
    np.testing.assert_allclose(np.asarray(rgb_c), np.asarray(rgb_d),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(rec_c.candidate_pairs),
                                  np.asarray(rec_d.candidate_pairs))
    np.testing.assert_array_equal(np.asarray(rec_c.overflow_pairs),
                                  np.asarray(rec_d.overflow_pairs))
    for name in _PER_TILE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(rec_c, name)),
                                      np.asarray(getattr(rec_d, name)),
                                      err_msg=name)


def test_full_frame_matches_dense_reference(small_scene, small_cam):
    """The all-tiles plan (Morton-permuted slots + scatter back) is a pure
    reordering: it must equal the dense render_from_bins reference."""
    cfg = RenderConfig()
    grid = intersect.make_tile_grid(small_cam)

    def dense(scene):
        proj = projection.preprocess(scene, small_cam, near=cfg.near)
        mask = intersect.tait_mask(proj, grid)
        bins = binning.build_tile_bins(mask, proj.depth, cfg.capacity)
        return raster.render_from_bins(proj, bins, grid), bins

    # Both sides in ONE program: preprocess's rounding depends on how XLA
    # fuses it with its consumers (an eager or separately jitted
    # reference moved cov2d by a few ulps and rgb by up to 2e-6), which
    # is not what this test pins — the plan's reordering is.
    (out, _, rec), (ref, bins) = jax.jit(
        lambda s: (render_full_frame(s, small_cam, cfg), dense(s)))(
        small_scene)
    np.testing.assert_allclose(np.asarray(out.rgb), np.asarray(ref.rgb),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.processed_pairs),
                                  np.asarray(ref.processed_pairs))
    np.testing.assert_array_equal(np.asarray(rec.sort_pairs),
                                  np.asarray(bins.count))


def _collect_shapes(jaxpr, acc):
    """All output-var shapes in a jaxpr, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _collect_shapes(inner, acc)


def test_sparse_stages_are_plan_shaped(small_scene, small_cam):
    """The compacted sparse frame compiles with a (T * K,) pair list and
    (R, K) bins and NO dense (N, T) mask or (T, K) bins — the wrappers
    really collapse onto the shared plan pipeline. The key frame bins
    all T tiles from the same pair list, with no (N, T) mask either."""
    rcap, kcap = 4, 128
    cfg = RenderConfig(window=10, rerender_capacity=rcap, capacity=kcap)
    ref_cam, tgt_cam, state = _sparse_inputs(small_scene, small_cam, cfg)
    n = small_scene.means.shape[0]
    t = small_cam.num_tiles
    assert rcap < t

    jx = jax.make_jaxpr(
        functools.partial(render_sparse_frame, cfg=cfg))(
        small_scene, ref_cam, tgt_cam, state)
    shapes = set()
    _collect_shapes(jx.jaxpr, shapes)
    assert (t * kcap,) in shapes, "pair list missing"
    assert (rcap, kcap) in shapes, "compacted (R, K) bins missing"
    assert (n, t) not in shapes, "dense (N, T) intersect mask still built"
    assert (t, kcap) not in shapes, "dense (T, K) bins still built"

    # ...while the full frame plans all T tiles (R = T).
    jx_full = jax.make_jaxpr(
        functools.partial(render_full_frame, cfg=cfg))(small_scene, tgt_cam)
    full_shapes = set()
    _collect_shapes(jx_full.jaxpr, full_shapes)
    assert (t * kcap,) in full_shapes
    assert (n, t) not in full_shapes
    assert (t, kcap) in full_shapes


def test_recorded_schedule_matches_numpy_golden(small_scene, small_cam):
    """The device LDU runs inside the jitted scan (no host callback) and
    its recorded block assignments match numpy ``schedule()`` on the
    identical workloads/active sets, frame by frame."""
    cfg = RenderConfig(window=2, ldu_blocks=8)
    res = render_trajectory(small_scene, small_cam, _poses(4), cfg)
    for f in range(4):
        rec = res.records[f]
        wl = np.asarray(rec.sort_pairs)
        active = np.asarray(rec.active)
        ref = schedule(wl, cfg.ldu_blocks, policy="ls_gaussian",
                       tiles_x=small_cam.tiles_x, tiles_y=small_cam.tiles_y,
                       active=active)
        np.testing.assert_array_equal(np.asarray(rec.block_of_tile),
                                      ref.block_of_tile, err_msg=f"frame {f}")
        np.testing.assert_array_equal(np.asarray(rec.order_in_block),
                                      ref.order_in_block, err_msg=f"frame {f}")
        # per-block load summary is consistent with the assignment
        loads = np.asarray(rec.block_load)
        assert loads.shape == (cfg.ldu_blocks,)
        for b in range(cfg.ldu_blocks):
            assert loads[b] == wl[ref.block_of_tile == b].sum()


def test_simulator_consumes_recorded_schedule(small_scene, small_cam):
    """policy='recorded' serves the FrameRecord's device schedule and
    reproduces the host-side ls_gaussian simulation exactly."""
    cfg = RenderConfig(window=2, ldu_blocks=8)
    res = render_trajectory(small_scene, small_cam, _poses(4), cfg)
    frames = frameworks_from_stacked(
        res.records, small_cam.tiles_x, small_cam.tiles_y,
        small_cam.width * small_cam.height)
    assert frames[0].num_blocks == cfg.ldu_blocks
    acfg = AcceleratorConfig(num_blocks=cfg.ldu_blocks)
    rec_t = simulate_sequence(frames, acfg, policy="recorded")
    ls_t = simulate_sequence(frames, acfg, policy="ls_gaussian",
                             workload_source="dpes", light_to_heavy=True)
    for a, b in zip(rec_t, ls_t):
        assert a.frame_end == pytest.approx(b.frame_end)
        assert a.sort_stall == pytest.approx(b.sort_stall)
        assert a.utilization == pytest.approx(b.utilization)

    bad = AcceleratorConfig(num_blocks=cfg.ldu_blocks * 2)
    with pytest.raises(ValueError, match="recorded schedule"):
        simulate_sequence(frames, bad, policy="recorded")


def test_scatter_slots_masks_inactive(small_cam):
    tx, ty = small_cam.tiles_x, small_cam.tiles_y
    t = tx * ty
    rerender = jnp.zeros((t,), bool).at[jnp.array([2, 7])].set(True)
    sp = plan_mod.sparse_plan(rerender, tx, ty, 4)  # 2 padded slots
    vals = jnp.full((4,), 9, jnp.int32)
    out = np.asarray(plan_mod.scatter_slots(sp, vals, t, fill=-3))
    assert (out[np.asarray(rerender)] == 9).all()
    assert (out[~np.asarray(rerender)] == -3).all()


def test_rerender_demand_dtype_contract():
    """rerender_demand is always int32, whatever mixture of jnp/numpy
    int/float/bool dtypes the stacked records arrive in, and it counts
    overflow_tiles on top of the active set (the serve layer compares it
    to bucket sizes on host with np.asarray)."""
    active = np.zeros((3, 8), bool)
    active[0, :5] = True
    active[2, :8] = True
    overflow = np.asarray([0, 0, 7])
    d = plan_mod.rerender_demand(active, overflow)
    assert d.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(d), [5, 0, 15])
    # Host-side float records (e.g. loaded from a JSON artifact) must
    # not silently promote the result to float.
    d_f = plan_mod.rerender_demand(active.astype(np.float64),
                                   overflow.astype(np.float32))
    assert d_f.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(d_f), [5, 0, 15])
    # int64 overflow counters (default numpy int on host) stay int32.
    d_i = plan_mod.rerender_demand(active, overflow.astype(np.int64))
    assert d_i.dtype == jnp.int32
    # Stacked (B, F, T) records reduce over the last axis only.
    stacked = np.broadcast_to(active, (2, 3, 8))
    d_b = plan_mod.rerender_demand(stacked, np.broadcast_to(overflow,
                                                            (2, 3)))
    assert d_b.shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(d_b),
                                  [[5, 0, 15], [5, 0, 15]])
