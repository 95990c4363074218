"""Fused plan-slot kernel (kernels/raster_plan.py) parity and contract.

Interpret-mode sweeps of ``impl="pallas_fused"`` against ``jnp_chunked``
and the sequential ``ref`` oracle (DESIGN.md §9: on matching inputs the
three paths must agree to float tolerance; the fused path must ALSO
agree when its per-slot lanes arrive depth-shuffled, because the GSU
sort runs in-kernel). Small cases ride the fast tier; the
RenderConfig-default K=512 case and the engine-scan sweep are ``slow``.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, intersect, plan as plan_mod, projection
from repro.core.engine import render_trajectory
from repro.core.pipeline import (RenderConfig, render_full_frame,
                                 render_sparse_frame)
from repro.kernels import ops
from repro.scenes.trajectory import dolly_trajectory

ATOL = 2e-5


def _tile_inputs(scene, cam, capacity):
    proj = projection.preprocess(scene, cam)
    grid = intersect.make_tile_grid(cam)
    mask = intersect.tait_mask(proj, grid)
    bins = binning.build_tile_bins(mask, proj.depth, capacity)
    tg = binning.gather_tiles(proj, bins)
    return (tg.mean2d, tg.conic, tg.rgb, tg.opacity, tg.depth,
            grid.origins, bins.count)


def _shuffle_lanes(args, seed=0):
    """Permute each slot's first `count` lanes (attrs move together) —
    the kernel's input contract: packed, any depth order. Returns the
    shuffled args plus the per-slot permutations (lane_contrib follows
    INPUT lane order, so it permutes with the lanes)."""
    mean2d, conic, rgb, opacity, depth, origins, counts = args
    rng = np.random.default_rng(seed)
    outs = [np.asarray(a).copy() for a in (mean2d, conic, rgb, opacity,
                                           depth)]
    perms = []
    for r, c in enumerate(np.asarray(counts)):
        p = rng.permutation(int(c))
        perms.append(p)
        for o in outs:
            o[r, :int(c)] = o[r, :int(c)][p]
    return tuple(jnp.asarray(o) for o in outs) + (origins, counts), perms


@pytest.mark.parametrize("capacity,chunk", [
    (64, 16),
    (96, 32),     # non-pow2 K exercises the kernel's internal padding
    (128, 64),
    pytest.param(512, 64, marks=pytest.mark.slow),  # RenderConfig default
])
def test_fused_matches_jnp_and_ref(small_scene, small_cam, capacity, chunk):
    args = _tile_inputs(small_scene, small_cam, capacity)
    o_ref = ops.raster_tiles(*args, impl="ref")
    o_jnp = ops.raster_tiles(*args, impl="jnp_chunked", chunk=chunk)
    o_fused = ops.raster_tiles(*args, impl="pallas_fused", chunk=chunk)
    for got, want, tol in [(o_fused[0], o_jnp[0], 0.0),
                           (o_fused[1], o_jnp[1], 0.0),
                           (o_fused[2], o_jnp[2], 0.0),
                           (o_fused[3], o_jnp[3], 0.0)]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=tol)
    np.testing.assert_array_equal(np.asarray(o_fused[4]),
                                  np.asarray(o_jnp[4]))
    np.testing.assert_allclose(np.asarray(o_fused[0]), np.asarray(o_ref[0]),
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(o_fused[1]), np.asarray(o_ref[1]),
                               atol=ATOL)


def test_fused_sorts_in_kernel(small_scene, small_cam):
    """Depth-shuffled lanes must render identically: the GSU sort is
    part of the kernel, not a caller obligation. lane_contrib is the one
    output that rightly differs — it reports per-INPUT-lane mass, so it
    follows the applied permutation exactly."""
    args = _tile_inputs(small_scene, small_cam, 64)
    shuf_args, perms = _shuffle_lanes(args)
    o_sorted = ops.raster_tiles(*args, impl="pallas_fused", chunk=32)
    o_shuf = ops.raster_tiles(*shuf_args, impl="pallas_fused", chunk=32)
    for a, b in zip(o_shuf[:5], o_sorted[:5]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    contrib = np.asarray(o_sorted[5])
    contrib_shuf = np.asarray(o_shuf[5])
    counts = np.asarray(args[6])
    for r, p in enumerate(perms):
        c = int(counts[r])
        np.testing.assert_array_equal(contrib_shuf[r, :c],
                                      contrib[r, :c][p])
        np.testing.assert_array_equal(contrib_shuf[r, c:], 0.0)


def test_fused_keeps_input_order_on_depth_ties(small_scene, small_cam):
    """Equal depths blend in input lane order, as ``jnp_chunked`` does.

    Quantizing sorted depths keeps them sorted and makes ties common
    (bf16-rounded TPU geometry once did); an unstable sort would then
    blend tied Gaussians in another order than the binning chose."""
    mean2d, conic, rgb, opacity, depth, origins, counts = _tile_inputs(
        small_scene, small_cam, 64)
    tied = jnp.floor(depth * 2.0) / 2.0
    assert np.sum(np.diff(np.asarray(tied), axis=1) == 0) > 100
    args = (mean2d, conic, rgb, opacity, tied, origins, counts)
    o_jnp = ops.raster_tiles(*args, impl="jnp_chunked", chunk=32)
    o_fused = ops.raster_tiles(*args, impl="pallas_fused", chunk=32)
    for a, b in zip(o_fused, o_jnp):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_slots_render_empty(small_scene, small_cam):
    """slot_active=False slots (counts zeroed, the plan contract) read
    as empty: rgb 0, T=1, 0 processed pairs; active slots unchanged."""
    m, c, r, o, d, org, counts = _tile_inputs(small_scene, small_cam, 64)
    active = jnp.arange(counts.shape[0]) % 2 == 0
    counts_m = jnp.where(active, counts, 0)
    out = ops.raster_tiles(m, c, r, o, d, org, counts_m,
                           impl="pallas_fused", chunk=32,
                           slot_active=active)
    ref = ops.raster_tiles(m, c, r, o, d, org, counts,
                           impl="pallas_fused", chunk=32)
    na = ~np.asarray(active)
    assert np.all(np.asarray(out[0])[na] == 0.0)
    assert np.all(np.asarray(out[1])[na] == 1.0)
    assert np.all(np.asarray(out[4])[na] == 0)
    a = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(out[0])[a],
                                  np.asarray(ref[0])[a])
    np.testing.assert_array_equal(np.asarray(out[4])[a],
                                  np.asarray(ref[4])[a])


def test_empty_input_renders_background(small_cam):
    t, k = small_cam.num_tiles, 64
    z = jnp.zeros
    out = ops.raster_tiles(z((t, k, 2)), jnp.ones((t, k, 3)), z((t, k, 3)),
                           z((t, k)), z((t, k)), z((t, 2)),
                           z((t,), jnp.int32), impl="pallas_fused", chunk=32)
    assert np.allclose(out[0], 0.0)
    assert np.allclose(out[1], 1.0)
    assert int(np.asarray(out[4]).sum()) == 0


def test_fused_rejects_non_pow2_chunk(small_scene, small_cam):
    args = _tile_inputs(small_scene, small_cam, 64)
    with pytest.raises(ValueError, match="power of two"):
        ops.raster_tiles(*args, impl="pallas_fused", chunk=48)


# ---- full pipeline parity (plans, masked slots, overflow) ---------------

def _cfg(impl, **kw):
    base = dict(capacity=128, window=3, chunk=32)
    base.update(kw)
    return RenderConfig(impl=impl, **base)


def test_full_frame_parity(small_scene, small_cam):
    """All-tiles plan (R = T) through the fused path: bit-consistent
    frames and identical records vs jnp_chunked."""
    outs = {}
    for impl in ("jnp_chunked", "pallas_fused"):
        fn = jax.jit(functools.partial(render_full_frame, cfg=_cfg(impl)))
        outs[impl] = fn(small_scene, small_cam)
    a, b = outs["jnp_chunked"], outs["pallas_fused"]
    np.testing.assert_allclose(np.asarray(b[0].rgb), np.asarray(a[0].rgb),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(b[0].transmittance),
                               np.asarray(a[0].transmittance), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(b[2].raster_pairs),
                                  np.asarray(a[2].raster_pairs))


@pytest.mark.parametrize("rcap", [None, 8, 2])
def test_sparse_frame_parity(small_scene, small_cam, rcap):
    """Sparse plans across R — uncapped, compacted, and overflowing
    (rcap=2 forces re-render tiles past R to degrade to interpolation,
    identically on both paths)."""
    poses = dolly_trajectory(2, start=(0.0, -0.3, -2.0),
                             target=(0.0, 0.0, 6.0))
    outs = {}
    for impl in ("jnp_chunked", "pallas_fused"):
        cfg = _cfg(impl, rerender_capacity=rcap)
        full_fn = jax.jit(functools.partial(render_full_frame, cfg=cfg))
        _, state, _ = full_fn(small_scene, small_cam.with_pose(poses[0]))
        sparse_fn = jax.jit(functools.partial(render_sparse_frame, cfg=cfg))
        outs[impl] = sparse_fn(small_scene, small_cam.with_pose(poses[0]),
                               small_cam.with_pose(poses[1]), state)
    a, b = outs["jnp_chunked"], outs["pallas_fused"]
    np.testing.assert_allclose(np.asarray(b[0]), np.asarray(a[0]),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(b[2].raster_pairs),
                                  np.asarray(a[2].raster_pairs))
    assert int(b[2].overflow_tiles) == int(a[2].overflow_tiles)
    if rcap == 2:
        assert int(b[2].overflow_tiles) > 0  # the case actually overflows


def test_engine_scan_parity(small_scene, small_cam):
    """The scanned engine's full/sparse lax.cond both hit the fused path
    via RenderConfig.impl — whole-trajectory frames bit-consistent."""
    poses = dolly_trajectory(3, start=(0.0, -0.3, -2.0),
                             target=(0.0, 0.0, 6.0))
    res = {}
    for impl in ("jnp_chunked", "pallas_fused"):
        cfg = _cfg(impl, capacity=64, rerender_capacity=8, window=2)
        res[impl] = render_trajectory(small_scene, small_cam, poses, cfg)
    np.testing.assert_allclose(np.asarray(res["pallas_fused"].frames),
                               np.asarray(res["jnp_chunked"].frames),
                               atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(res["pallas_fused"].records.raster_pairs),
        np.asarray(res["jnp_chunked"].records.raster_pairs))


@pytest.mark.slow
def test_engine_scan_parity_large(small_scene, wide_cam):
    """Wider frame, default-capacity bins, longer trajectory."""
    poses = dolly_trajectory(5, start=(0.5, -0.5, -3.0),
                             target=(0.0, 0.0, 6.0))
    res = {}
    for impl in ("jnp_chunked", "pallas_fused"):
        cfg = RenderConfig(impl=impl, window=3, rerender_capacity=16)
        res[impl] = render_trajectory(small_scene, wide_cam, poses, cfg)
    np.testing.assert_allclose(np.asarray(res["pallas_fused"].frames),
                               np.asarray(res["jnp_chunked"].frames),
                               atol=1e-5)


def test_default_impl_tracks_backend():
    """pallas_fused is the default on TPU backends, jnp_chunked elsewhere
    — and RenderConfig() picks it up via its default factory."""
    expected = "pallas_fused" if jax.default_backend() == "tpu" \
        else "jnp_chunked"
    assert ops.default_impl() == expected
    assert RenderConfig().impl == expected
    # Explicit impl always wins over the backend default.
    assert dataclasses.replace(RenderConfig(), impl="ref").impl == "ref"
