"""The pair-list binner (DESIGN.md §3) against the dense ``top_k`` oracle.

``pipeline._pair_list_bins`` enumerates TAIT pairs from each Gaussian's
tile rectangle and bins them with one sort; ``pipeline._dense_bins``
builds the (N, R) mask and runs ``binning.build_tile_bins``. Pinned:

  - bins (indices, valid, count, overflow), the plan's slot flags and
    the per-slot counters are bit-equal, on full and sparse plans, with
    bins that overflow K, duplicated depths, DPES limits and culling,
    through the one-key sort and the two-key one;
  - a budget smaller than the frame's stage-1 pairs drops the
    highest-indexed Gaussians' tail pairs, and ``pair_budget_overflow``
    counts them;
  - the budget ``pipeline.pair_budget`` derives: T * K for every scene
    bucket up to 65,536 (the same 1080p jaxpr as with T * K given),
    more for larger scenes, where a frame with more stage-1 pairs than
    T * K still bins like the oracle; the stage-1 count equals the
    dense mask's;
  - frames and records of a short trajectory are bit-equal to the dense
    path through the scanned engine, on ``jnp_chunked`` and the fused
    kernel in interpret mode.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, intersect, pipeline, plan as plan_mod
from repro.core.camera import look_at, make_camera
from repro.core.engine import render_trajectory
from repro.core.gaussians import GaussianScene
from repro.core.pipeline import RenderConfig
from repro.core.projection import preprocess
from repro.scenes.synthetic import structured_scene
from repro.scenes.trajectory import dolly_trajectory
from repro.serve.scenes import DEFAULT_SCENE_BUCKETS


def _inputs(scene, cam, case):
    """Projected scene, grid, plan, DPES limits and cull inputs for one
    parametrised case."""
    key = jax.random.PRNGKey(11)
    if case == "ties":
        # Every Gaussian twice: each depth (and mask column) is doubled,
        # so top_k's lower-index-first tie order decides the bins.
        scene = jax.tree_util.tree_map(
            lambda a: jnp.concatenate([a, a]), scene)
    proj = preprocess(scene, cam)
    grid = intersect.make_tile_grid(cam)
    t = grid.num_tiles
    if case == "sparse":
        rerender = jax.random.uniform(key, (t,)) < 0.35
        plan = plan_mod.sparse_plan(rerender, cam.tiles_x, cam.tiles_y,
                                    t // 2)
    else:
        plan = plan_mod.full_plan(cam.tiles_x, cam.tiles_y)
    limit = prior = gate = None
    if case == "dpes":
        r = plan.num_slots
        q = jax.random.uniform(key, (r,), minval=0.2, maxval=0.9)
        depth = jnp.where(proj.valid, proj.depth, jnp.nan)
        limit = jnp.nanquantile(depth, q)
        limit = jnp.where(jnp.arange(r) % 5 == 0, jnp.inf, limit)
    if case == "cull":
        k1, k2 = jax.random.split(key)
        n = proj.depth.shape[0]
        prior = jax.random.uniform(k1, (n,))
        prior = jnp.where(jnp.arange(n) % 7 == 0, jnp.inf, prior)
        gate = jax.random.uniform(k2, (t,)) < 0.7
    return proj, grid, plan, limit, prior, gate


def _stage(fn, cfg, proj, grid, plan, limit, prior, gate, **kw):
    """``fn`` jitted, with the grid's static tile counts closed over."""
    return jax.jit(lambda *a: fn(a[0], grid, a[1], cfg, *a[2:], **kw))(
        proj, plan, limit, prior, gate)


def _assert_same(got, ref):
    (bins, plan, stats), (rbins, rplan, rstats) = got, ref
    for name in ("indices", "valid", "count", "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(bins, name)),
                                      np.asarray(getattr(rbins, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(plan.slot_active),
                                  np.asarray(rplan.slot_active))
    for name in ("candidate_pairs", "raw_slots", "overflow_pairs",
                 "culled_pairs", "stage1_pairs"):
        np.testing.assert_array_equal(np.asarray(getattr(stats, name)),
                                      np.asarray(getattr(rstats, name)),
                                      err_msg=name)


@pytest.mark.parametrize("packed", [True, False],
                         ids=["one_key", "two_keys"])
@pytest.mark.parametrize("case,capacity", [
    ("full", 512), ("sparse", 512), ("overflow", 32), ("ties", 512),
    ("dpes", 64), ("cull", 64)])
def test_pair_list_bins_match_top_k(small_scene, wide_cam, case, capacity,
                                    packed, monkeypatch):
    if not packed:
        # The two-key sort, which N > 2**16 takes at 1080p.
        monkeypatch.setattr(binning, "_KEY_BITS", 8)
    args = _inputs(small_scene, wide_cam, case)
    cfg = RenderConfig(capacity=capacity,
                       cull_threshold=0.3 if case == "cull" else 0.0)
    got = _stage(pipeline._pair_list_bins, cfg, *args)
    ref = _stage(pipeline._dense_bins, cfg, *args)
    _assert_same(got, ref)
    bins, plan, stats = got
    assert int(stats.pair_budget_overflow) == 0
    assert int(jnp.sum(bins.count)) > 0
    # Each case exercises what it names.
    if case == "sparse":
        assert not bool(jnp.all(plan.slot_active))
    if case == "overflow":
        assert int(stats.overflow_pairs) > 0
    if case == "ties":
        single = _stage(pipeline._pair_list_bins, cfg,
                        *_inputs(small_scene, wide_cam, "full"))[2]
        np.testing.assert_array_equal(np.asarray(stats.raw_slots),
                                      2 * np.asarray(single.raw_slots))
    if case == "dpes":
        assert int(jnp.sum(bins.count + bins.overflow)) < int(
            jnp.sum(stats.raw_slots))
    if case == "cull":
        assert int(stats.culled_pairs) > 0


def test_pair_budget_overflow_counts_dropped(small_scene, wide_cam):
    """A budget below the frame's stage-1 pairs keeps the pairs of the
    lowest-indexed Gaussians, row-major inside each rectangle, and
    counts the rest; the bins equal the oracle on the pairs that were
    kept."""
    proj, grid, plan, *_ = _inputs(small_scene, wide_cam, "full")
    cfg = RenderConfig(capacity=64)
    stage1 = np.asarray(intersect.tait_stage1_mask(proj, grid))   # (N, T)
    total = int(stage1.sum())
    budget = total // 3
    bins, _, stats = _stage(pipeline._pair_list_bins, cfg, proj, grid,
                            plan, None, None, None, budget=budget)
    assert int(stats.pair_budget_overflow) == total - budget

    running = np.cumsum(stage1.reshape(-1)).reshape(stage1.shape)
    kept = stage1 & (running <= budget)
    assert int(kept.sum()) == budget
    mask = np.asarray(intersect.tait_mask(proj, grid)) & kept
    ref = binning.build_tile_bins(jnp.asarray(mask)[:, plan.tile_ids],
                                  proj.depth, cfg.capacity)
    for name in ("indices", "valid", "count", "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(bins, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)


@pytest.mark.parametrize("impl", ["jnp_chunked", "pallas_fused"])
def test_frames_match_dense_path(small_scene, small_cam, impl, monkeypatch):
    """A key frame and its sparse frames through the scanned engine:
    every frame and record field bit-equal to the dense path."""
    # T * K = 2,048 pair slots hold the 1,026 stage-1 pairs of the key
    # frame (at K = 64 the budget would drop two).
    cfg = RenderConfig(impl=impl, capacity=128, chunk=32, window=3,
                       rerender_capacity=8)
    poses = dolly_trajectory(5, start=(0.0, -0.3, -2.0),
                             target=(0.0, 0.0, 6.0))
    jax.clear_caches()
    got = render_trajectory(small_scene, small_cam, poses, cfg)
    monkeypatch.setattr(pipeline, "_pair_list_bins", pipeline._dense_bins)
    jax.clear_caches()      # retrace: the engine's jit is keyed on cfg
    ref = render_trajectory(small_scene, small_cam, poses, cfg)
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got.frames),
                                  np.asarray(ref.frames))
    for field in pipeline.FrameRecord._fields:
        a, b = getattr(got.records, field), getattr(ref.records, field)
        if a is None:
            assert b is None
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)
    assert not bool(np.asarray(got.records.is_full).all())
    assert not np.asarray(got.records.pair_budget_overflow).any()


def test_pair_budget_is_t_by_k_up_to_65536():
    """At 1080p and K = 1,024 every scene bucket up to 65,536 keeps the
    budget T * K; above, the budget is 8 slots a padded row where that
    is more, and small grids keep T * K at every size up to 65,536."""
    t, k = 120 * 68, 1024
    for n in DEFAULT_SCENE_BUCKETS:
        want = t * k if n <= 2 ** 16 else max(t * k, 8 * n)
        assert pipeline.pair_budget(t, k, n) == want
    assert pipeline.pair_budget(t, k, 65_536) == 8_355_840
    assert pipeline.pair_budget(t, k, 2 ** 20) == 8_388_608
    assert pipeline.pair_budget(t, k, 2 ** 21) == 2 ** 24
    assert pipeline.pair_budget(16, 16, 512) == 256
    assert pipeline.pair_budget(48, 32, 2 ** 16) == 48 * 32
    assert pipeline.pair_budget(48, 32, 70_000) == 560_000


def _jaxpr_1080p(n):
    """The jaxpr of a 1080p key frame of ``n`` Gaussians (shapes only),
    without the addresses of the Python functions it names."""
    cam = make_camera(look_at((0.0, -0.3, -2.0), (0.0, 0.0, 6.0)),
                      width=1920, height=1088)
    cfg = RenderConfig(capacity=1024, chunk=64, impl="jnp_chunked")
    plan = plan_mod.full_plan(cam.tiles_x, cam.tiles_y)
    scene = GaussianScene(*(jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (n, 3), (n, 3), (n, 4), (n,), (n, 16, 3))))
    text = str(jax.make_jaxpr(lambda sc: pipeline.render_planned_frame(
        sc, cam, plan, cfg))(scene))
    return re.sub(r" at 0x[0-9a-f]+", "", text)


@pytest.mark.parametrize("n,slots", [(65_536, 8_355_840),
                                     (2 ** 21, 2 ** 24)])
def test_1080p_pair_list_takes_the_derived_budget(n, slots, monkeypatch):
    """The pair list of a 1080p frame has the derived number of slots;
    at the 65,536 bucket the frame traces to the same jaxpr as with the
    budget T * K given, as before the budget was derived."""
    got = _jaxpr_1080p(n)
    assert f"i32[{slots}]" in got
    if n <= 2 ** 16:
        monkeypatch.setattr(pipeline, "_pair_list_bins", functools.partial(
            pipeline._pair_list_bins, budget=8160 * 1024))
        assert _jaxpr_1080p(n) == got


@pytest.fixture(scope="module")
def large_scene():
    """More Gaussians than 2**16: the derived budget exceeds T * K."""
    return structured_scene(jax.random.PRNGKey(21), 70_000, clutter=0.5)


@pytest.mark.parametrize("case", ["full", "sparse"])
def test_derived_budget_holds_pairs_past_t_by_k(large_scene, wide_cam,
                                                case):
    """A small-grid frame of 70,000 Gaussians has more stage-1 pairs
    than T * K but fewer than the derived budget: nothing is dropped,
    the bins equal the oracle's, and the stage-1 count is the dense
    mask's over the whole grid."""
    args = _inputs(large_scene, wide_cam, case)
    proj, grid = args[:2]
    cfg = RenderConfig(capacity=32)
    stage1 = int(np.asarray(intersect.tait_stage1_mask(proj, grid)).sum())
    assert grid.num_tiles * cfg.capacity < stage1 <= pipeline.pair_budget(
        grid.num_tiles, cfg.capacity, proj.depth.shape[0])
    got = _stage(pipeline._pair_list_bins, cfg, *args)
    _assert_same(got, _stage(pipeline._dense_bins, cfg, *args))
    stats = got[2]
    assert int(stats.pair_budget_overflow) == 0
    assert int(stats.stage1_pairs) == stage1
    assert int(stats.overflow_pairs) > 0
