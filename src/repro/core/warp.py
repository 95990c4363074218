"""TWSR — Tile-Warping-based Sparse Rendering (paper Sec. IV-A, Algo. 1).

Given a reference frame (color + estimated depth + truncated depth + a
source-validity mask), reproject it into the target viewpoint:

  1. ProjectTo3D: back-project every valid reference pixel with its
     estimated scene depth (and, separately, its truncated depth).
  2. ViewTransfer + Reproject: project the point cloud(s) into the target
     camera; z-buffer with a two-pass scatter-min (ties averaged, so the
     result is deterministic).
  3. Per 16x16 tile: count validly reprojected pixels N. If N > N0
     (default 5/6 of the tile, paper Sec. V-A) the tile is *interpolated*
     (missing pixels inpainted from neighbors — preprocess, sort AND raster
     all skipped). Otherwise the tile is queued for full re-rendering and
     its DPES early-stop depth is the max valid reprojected truncated
     depth (Sec. IV-B).
  4. No-cumulative-error mask: interpolated pixels are flagged and excluded
     as sources for the *next* frame's warp ("TW w/ mask", Fig. 7).

Everything is shape-static: tile decisions are boolean masks over the fixed
tile grid, so the whole transform jits and shards — and, because no shape
depends on a traced value, it is a valid ``lax.scan`` body and batches
under ``vmap`` (the scanned engine in core/engine.py relies on both).

The ``rerender_tile`` mask and ``dpes_depth`` priors produced here are the
inputs to ``plan.sparse_plan``: downstream, the re-render set is compacted
into a static-R ``TilePlan`` and rendered through the shared
``pipeline.render_planned_frame`` stage pipeline (DESIGN.md §2).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.camera import TILE, Camera, backproject, matmul
from repro.core.raster import tile_view, untile

# A pixel is a usable reprojection source only if enough opacity
# accumulated behind it in the reference render (otherwise its estimated
# depth is meaningless — background / barely-covered pixels).
MIN_COVERAGE = 0.25
# Paper: interpolate when > 5/6 of the tile's pixels arrived.
N0_RATIO = 5.0 / 6.0


class WarpResult(NamedTuple):
    rgb: jax.Array          # (H, W, 3) reprojected color (holes = 0)
    filled: jax.Array       # (H, W) bool — pixel received a source
    exp_depth: jax.Array    # (H, W) reprojected scene depth (holes = 0)
    trunc_depth: jax.Array  # (H, W) reprojected truncated depth (max-scatter)
    valid_per_tile: jax.Array   # (T,) int32 — N in Algo. 1
    interpolate_tile: jax.Array  # (T,) bool — Algo. 1 line 7 branch
    rerender_tile: jax.Array     # (T,) bool
    dpes_depth: jax.Array        # (T,) early-stop depth (inf if unusable)


def _scatter_zbuffer(ti: jax.Array, z: jax.Array, valid: jax.Array,
                     values: jax.Array, size: int):
    """Two-pass deterministic z-buffer scatter.

    ti: (S,) flat target pixel index; z: (S,) depth; valid: (S,) bool;
    values: (S, C). Returns (zmin (size,), out (size, C), hit (size,)).
    Ties within 1e-5 of the winning depth are averaged.
    """
    big = jnp.float32(1e30)
    zs = jnp.where(valid, z, big)
    ti_safe = jnp.where(valid, ti, 0)
    zmin = jnp.full((size,), big).at[ti_safe].min(zs, mode="drop")
    winner = valid & (zs <= zmin[ti_safe] * (1.0 + 1e-5))
    w = winner.astype(jnp.float32)
    cnt = jnp.zeros((size,)).at[ti_safe].add(w, mode="drop")
    acc = jnp.zeros((size, values.shape[-1])).at[ti_safe].add(
        values * w[:, None], mode="drop")
    hit = cnt > 0
    out = acc / jnp.maximum(cnt, 1.0)[:, None]
    return jnp.where(hit, zmin, 0.0), out, hit


def _project_points(ref_cam: Camera, depth_map: jax.Array, mask: jax.Array,
                    tgt_cam: Camera, near: float):
    """Back-project ``depth_map`` and reproject into the target view.

    Returns (ti, z, valid): (S,) flat target pixel index, target-view
    depth, and source validity (mask & in front & in bounds).
    """
    h, w = depth_map.shape
    pts = backproject(ref_cam, depth_map)                   # (H, W, 3)
    rot, t = tgt_cam.w2c[:3, :3], tgt_cam.w2c[:3, 3]
    pc = matmul(pts.reshape(-1, 3), rot.T) + t
    z = pc[:, 2]
    u = tgt_cam.fx * pc[:, 0] / jnp.maximum(z, near) + tgt_cam.cx
    v = tgt_cam.fy * pc[:, 1] / jnp.maximum(z, near) + tgt_cam.cy
    ui = jnp.floor(u).astype(jnp.int32)
    vi = jnp.floor(v).astype(jnp.int32)
    in_bounds = (ui >= 0) & (ui < w) & (vi >= 0) & (vi < h)
    valid = mask.reshape(-1) & (z > near) & in_bounds
    return vi * w + ui, z, valid


def viewpoint_transform(ref_rgb: jax.Array, ref_exp_depth: jax.Array,
                        ref_trunc_depth: jax.Array, ref_source_mask: jax.Array,
                        ref_cam: Camera, tgt_cam: Camera, *,
                        n0_ratio: float = N0_RATIO,
                        near: float = 0.05) -> WarpResult:
    """Algorithm 1 (viewpoint transformation + tile decisions)."""
    h, w = ref_rgb.shape[:2]
    size = h * w

    # --- 1. ProjectTo3D + 2. ViewTransfer/Reproject ----------------------
    ti, z, src_valid = _project_points(ref_cam, ref_exp_depth,
                                       ref_source_mask, tgt_cam, near)

    # Color + the pixel's own scene depth ride the same z-buffer.
    payload = jnp.concatenate(
        [ref_rgb.reshape(-1, 3), ref_exp_depth.reshape(-1, 1)], axis=-1)
    _, out, hit = _scatter_zbuffer(ti, z, src_valid, payload, size)
    rgb_t = out[:, :3].reshape(h, w, 3)
    filled = hit.reshape(h, w)

    # Reprojected scene depth = *target-view* z of the winning source.
    zmap, _, _ = _scatter_zbuffer(ti, z, src_valid,
                                  z[:, None], size)
    exp_depth_t = zmap.reshape(h, w)

    # --- truncated-depth point cloud (separate cloud, max-scatter) -------
    tim_raw, zm, mvalid = _project_points(ref_cam, ref_trunc_depth,
                                          ref_source_mask, tgt_cam, near)
    tim = jnp.where(mvalid, tim_raw, 0)
    trunc_t = jnp.zeros((size,)).at[tim].max(
        jnp.where(mvalid, zm, 0.0), mode="drop").reshape(h, w)

    # --- 3. per-tile decisions (Algo. 1 lines 5-12) ----------------------
    tx, ty = tgt_cam.tiles_x, tgt_cam.tiles_y
    filled_tiles = tile_view(filled[..., None].astype(jnp.int32), tx, ty)
    valid_per_tile = filled_tiles.sum(axis=(1, 2, 3))        # (T,)
    n0 = int(round(n0_ratio * TILE * TILE))
    interpolate_tile = valid_per_tile > n0
    rerender_tile = ~interpolate_tile

    # DPES: early-stop depth = max reprojected truncated depth over the
    # tile's valid pixels; unusable (inf) when nothing valid arrived.
    trunc_tiles = tile_view(trunc_t[..., None], tx, ty)[..., 0]
    tile_max_trunc = jnp.max(trunc_tiles, axis=(1, 2))
    dpes_depth = jnp.where(valid_per_tile > 0, tile_max_trunc, jnp.inf)
    # A re-rendered tile with zero arrivals gives no prior: keep inf.
    dpes_depth = jnp.where(tile_max_trunc > 0, dpes_depth, jnp.inf)

    return WarpResult(rgb=rgb_t, filled=filled, exp_depth=exp_depth_t,
                      trunc_depth=trunc_t, valid_per_tile=valid_per_tile,
                      interpolate_tile=interpolate_tile,
                      rerender_tile=rerender_tile, dpes_depth=dpes_depth)


def inpaint(rgb: jax.Array, filled: jax.Array, *, iters: int = 8) -> jax.Array:
    """Fill holes by iterative 3x3 neighbor averaging (Jacobi diffusion).

    Only missing pixels are written; valid pixels are fixed boundary
    conditions. With <= 1/6 of a tile missing (TW policy) a handful of
    iterations converges.
    """
    f = filled.astype(jnp.float32)[..., None]
    img = rgb * f

    kernel = jnp.ones((3, 3), jnp.float32)

    def blur(x):
        # (H, W, C) -> same, 3x3 box sum with zero padding.
        xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0)))
        s = (xp[:-2, :-2] + xp[:-2, 1:-1] + xp[:-2, 2:]
             + xp[1:-1, :-2] + xp[1:-1, 1:-1] + xp[1:-1, 2:]
             + xp[2:, :-2] + xp[2:, 1:-1] + xp[2:, 2:])
        return s

    def body(_, state):
        img_c, wgt = state
        num = blur(img_c * wgt)
        den = blur(wgt)
        fill_val = num / jnp.maximum(den, 1e-8)
        new_img = jnp.where(filled[..., None], rgb, fill_val)
        new_wgt = jnp.maximum(wgt, (den[..., :1] > 0).astype(jnp.float32))
        return new_img, new_wgt

    img_out, _ = jax.lax.fori_loop(0, iters, body, (img, f))
    return img_out


def pixel_warp_fill(warp: WarpResult, full_rgb: jax.Array) -> jax.Array:
    """PWSR baseline (Potamoi-style): keep every warped pixel, fill only the
    missing ones with freshly rendered values. Quality-only baseline for
    Fig. 7 — it still pays full preprocess+sort (see benchmarks)."""
    return jnp.where(warp.filled[..., None], warp.rgb, full_rgb)
