"""Plain 3D Gaussian Splatting renderer: the reference ``correct`` uses.

It imports nothing of the program under test. It follows the published
3DGS rasteriser (Kerbl et al. 2023) with the camera model the benchmark
configurations state:

- pinhole camera, square pixels, vertical field of view ``fov_deg``,
  principal point at the image centre, pixel centres at (i + 0.5);
- EWA projection of each Gaussian: covariance R S S^T R^T from a
  normalised (w, x, y, z) quaternion and exp(log scale), the perspective
  Jacobian with x/z and y/z clamped to 1.3 times the half field of view,
  and 0.3 added to the diagonal of the 2D covariance;
- colour from degree-3 real spherical harmonics in the direction from
  the camera centre to the Gaussian, plus 0.5, clamped at 0;
- a Gaussian is dropped when its depth is at most the near plane
  (0.05), its opacity is at most 1/255, its 2D covariance is singular,
  or the square of side 3 sqrt(largest eigenvalue) around it misses the
  image;
- every pixel blends the Gaussians that reach it front to back by
  camera depth (equal depths in index order): alpha = min(0.99,
  opacity exp(-d^T conic d / 2)), skipped below 1/255; a pixel stops
  before the Gaussian that would take its transmittance below 1e-4.
  The background is black. Each pixel also gets its transmittance, its
  expected depth (blend-weighted mean depth) and its truncated depth
  (the deepest Gaussian it blended).

Tiles are only a way to find the Gaussians near a pixel: a tile takes
every Gaussian whose alpha >= 1/255 ellipse has a bounding box that
meets it, which holds every Gaussian that can reach one of its pixels,
and blends up to ``capacity`` of them, nearest first. Pairs past that
are counted and returned, so a run can see whether the cap mattered.
A block of ``TILE_BLOCK`` tiles reads the Gaussians in depth order,
``CHUNK`` at a time, so that memory does not grow with the scene.

A stream's frames (``stream``) follow LS-Gaussian's schedule: a key
frame is rendered whole, and each frame after it is made from the one
before by the paper's sparse-frame rule, set out above ``MIN_COVERAGE``.

``dtype`` is the precision everything is computed in: float32 is the
reference; bfloat16 is the control that must fail the comparison.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TILE = 16
NEAR = 0.05
ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_EPS = 1e-4
DILATION = 0.3
FRUSTUM_MARGIN = 1.3
CAPACITY = 2048          # Gaussians blended per tile, nearest first
TILE_BLOCK = 1024        # tiles per block, to bound memory
CHUNK = 65536            # Gaussians a block tests at a time

SH_C0 = 0.28209479177387814
SH_C1 = 0.4886025119029199
SH_C2 = (1.0925484305920792, -1.0925484305920792, 0.31539156525252005,
         -1.0925484305920792, 0.5462742152960396)
SH_C3 = (-0.5900435899266435, 2.890611442640554, -0.4570457994644658,
         0.3731763325901154, -0.4570457994644658, 1.445305721320277,
         -0.5900435899266435)


class Intrinsics(NamedTuple):
    width: int
    height: int
    fx: float
    fy: float
    cx: float
    cy: float


def intrinsics(width: int, height: int, fov_deg: float) -> Intrinsics:
    f = 0.5 * height / math.tan(math.radians(fov_deg) / 2.0)
    return Intrinsics(int(width), int(height), f, f, width / 2.0,
                      height / 2.0)


def _mat3_apply(m, v):
    """m @ v for (..., 3, 3) and (..., 3), elementwise (no matmul unit)."""
    return jnp.sum(m * v[..., None, :], axis=-1)


def _sh_colour(sh, d):
    """Degree <= 3 SH colour in unit directions d. sh (N, K, 3), d (N, 3)."""
    k = sh.shape[1]
    x, y, z = d[:, 0:1], d[:, 1:2], d[:, 2:3]
    c = SH_C0 * sh[:, 0]
    if k > 1:
        c = c - SH_C1 * y * sh[:, 1] + SH_C1 * z * sh[:, 2] \
            - SH_C1 * x * sh[:, 3]
    if k > 4:
        xx, yy, zz, xy, yz, xz = x * x, y * y, z * z, x * y, y * z, x * z
        c = (c + SH_C2[0] * xy * sh[:, 4] + SH_C2[1] * yz * sh[:, 5]
             + SH_C2[2] * (2.0 * zz - xx - yy) * sh[:, 6]
             + SH_C2[3] * xz * sh[:, 7] + SH_C2[4] * (xx - yy) * sh[:, 8])
    if k > 9:
        c = (c + SH_C3[0] * y * (3 * xx - yy) * sh[:, 9]
             + SH_C3[1] * xy * z * sh[:, 10]
             + SH_C3[2] * y * (4 * zz - xx - yy) * sh[:, 11]
             + SH_C3[3] * z * (2 * zz - 3 * xx - 3 * yy) * sh[:, 12]
             + SH_C3[4] * x * (4 * zz - xx - yy) * sh[:, 13]
             + SH_C3[5] * z * (xx - yy) * sh[:, 14]
             + SH_C3[6] * x * (xx - 3 * yy) * sh[:, 15])
    return jnp.maximum(c + 0.5, 0.0)


def project(scene: dict, w2c, cam: Intrinsics, dtype):
    """Per-Gaussian screen quantities. Returns a dict of (N, ...) arrays."""
    f = lambda a: jnp.asarray(a).astype(dtype)
    means, log_scales = f(scene["means"]), f(scene["log_scales"])
    quats, logits, sh = f(scene["quats"]), f(scene["opacity_logits"]), \
        f(scene["sh"])
    w2c = f(w2c)
    rot, t = w2c[:3, :3], w2c[:3, 3]
    p = _mat3_apply(rot, means) + t
    x, y, z = p[:, 0], p[:, 1], p[:, 2]
    zs = jnp.maximum(z, NEAR)
    u = cam.fx * x / zs + cam.cx
    v = cam.fy * y / zs + cam.cy

    q = quats / jnp.sqrt(jnp.sum(quats * quats, axis=-1, keepdims=True))
    qw, qx, qy, qz = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    rg = jnp.stack([
        jnp.stack([1 - 2 * (qy * qy + qz * qz), 2 * (qx * qy - qw * qz),
                   2 * (qx * qz + qw * qy)], -1),
        jnp.stack([2 * (qx * qy + qw * qz), 1 - 2 * (qx * qx + qz * qz),
                   2 * (qy * qz - qw * qx)], -1),
        jnp.stack([2 * (qx * qz - qw * qy), 2 * (qy * qz + qw * qx),
                   1 - 2 * (qx * qx + qy * qy)], -1)], -2)   # (N, 3, 3)
    m = rg * jnp.exp(log_scales)[:, None, :]                 # R S
    cov3 = jnp.sum(m[:, :, None, :] * m[:, None, :, :], axis=-1)

    lim_x = FRUSTUM_MARGIN * cam.width / (2.0 * cam.fx)
    lim_y = FRUSTUM_MARGIN * cam.height / (2.0 * cam.fy)
    tx = jnp.clip(x / zs, -lim_x, lim_x) * zs
    ty = jnp.clip(y / zs, -lim_y, lim_y) * zs
    zero = jnp.zeros_like(zs)
    jac = jnp.stack([
        jnp.stack([cam.fx / zs, zero, -cam.fx * tx / (zs * zs)], -1),
        jnp.stack([zero, cam.fy / zs, -cam.fy * ty / (zs * zs)], -1)], -2)
    wm = jnp.sum(jac[:, :, :, None] * rot[None, None, :, :], axis=2)  # J R
    tmp = jnp.sum(wm[:, :, :, None] * cov3[:, None, :, :], axis=2)
    cov2 = jnp.sum(tmp[:, :, None, :] * wm[:, None, :, :], axis=-1)
    a = cov2[:, 0, 0] + DILATION
    b = cov2[:, 0, 1]
    c = cov2[:, 1, 1] + DILATION
    det = a * c - b * b
    dsafe = jnp.maximum(det, 1e-12)
    conic = jnp.stack([c / dsafe, -b / dsafe, a / dsafe], -1)
    lam1 = 0.5 * (a + c) + jnp.sqrt(jnp.maximum(
        0.25 * (a - c) * (a - c) + b * b, 1e-12))
    r3 = jnp.ceil(3.0 * jnp.sqrt(lam1))
    opacity = jax.nn.sigmoid(logits)

    cam_pos = -_mat3_apply(jnp.swapaxes(rot, 0, 1), t)
    d = means - cam_pos
    d = d / (jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True)) + 1e-12)
    colour = _sh_colour(sh, d)

    on_screen = ((u + r3 > 0) & (u - r3 < cam.width)
                 & (v + r3 > 0) & (v - r3 < cam.height))
    valid = (z > NEAR) & (opacity > ALPHA_MIN) & on_screen & (det > 1e-12)
    rho2 = 2.0 * jnp.log(jnp.maximum(opacity / ALPHA_MIN, 1.0 + 1e-6))
    half = jnp.stack([jnp.sqrt(rho2 * a), jnp.sqrt(rho2 * c)], -1)
    return dict(uv=jnp.stack([u, v], -1), conic=conic, depth=z,
                colour=colour, opacity=opacity, valid=valid, half=half)


def _meets(lo, hi, t_lo, t_hi):
    """Whether boxes [lo, hi] meet tiles [t_lo, t_hi), broadcast."""
    return ((lo[..., 0] < t_hi[..., 0]) & (hi[..., 0] > t_lo[..., 0])
            & (lo[..., 1] < t_hi[..., 1]) & (hi[..., 1] > t_lo[..., 1]))


def _select(s, n_valid, origins, limit, k: int, chunk: int):
    """Each tile's first ``k`` Gaussians in depth order ((Tb, k) indices;
    lanes past the count hold any index) and how many it takes in all.

    ``s`` holds the Gaussians' boxes, depths and indices in depth order,
    the ``n_valid`` valid ones first. The block keeps, in that order,
    those that could meet one of its tiles: valid, meeting the box
    around all its tiles, no deeper than its deepest limit. It tests
    them ``chunk`` at a time, and a chunk's members go to each tile's
    next free lanes. Every chunk is counted, as the pairs past the cap
    need all of them; one whose block is full only adds to the counts.
    """
    tb = origins.shape[0]
    t_lo, t_hi = origins, origins + TILE
    near = ((jnp.arange(s["depth"].shape[0]) < n_valid)
            & _meets(s["lo"], s["hi"], jnp.min(t_lo, 0), jnp.max(t_hi, 0))
            & (s["depth"] <= jnp.max(limit)))
    n_near = jnp.sum(near.astype(jnp.int32))
    (keep,) = jnp.nonzero(near, size=near.shape[0], fill_value=0)
    kc = min(k, chunk)
    lane = jnp.arange(k)
    first = -jnp.arange(chunk, dtype=jnp.float32)

    def body(st):
        c0, lanes, count = st
        at = jax.lax.dynamic_slice_in_dim(keep, c0, chunk)
        member = (_meets(s["lo"][at][None], s["hi"][at][None],
                         t_lo[:, None], t_hi[:, None])
                  & (c0 + jnp.arange(chunk) < n_near)[None, :]
                  & (s["depth"][at][None, :] <= limit[:, None]))
        got = jnp.sum(member, axis=1)

        def fill(lanes):
            _, pick = jax.lax.top_k(jnp.where(member, first, -jnp.inf), kc)
            new = s["index"][at][pick]
            take = jnp.take_along_axis(
                new, jnp.clip(lane[None, :] - count[:, None], 0, kc - 1),
                axis=1)
            return jnp.where(lane[None, :] < count[:, None], lanes, take)

        lanes = jax.lax.cond(jnp.any((count < k) & (got > 0)), fill,
                             lambda x: x, lanes)
        return c0 + chunk, lanes, count + got

    init = (jnp.int32(0), jnp.zeros((tb, k), jnp.int32),
            jnp.zeros((tb,), jnp.int32))
    _, lanes, count = jax.lax.while_loop(lambda st: st[0] < n_near, body,
                                         init)
    return lanes, count


def _blend(g, idx, count, origins, dtype):
    """Blend one block of tiles, each from its lanes ``idx`` (the first
    ``count`` of them, nearest first). Returns (Tb, 256, 3) rgb, then
    (Tb, 256) each: transmittance, expected depth, truncated depth."""
    k = idx.shape[1]
    lane_ok = jnp.arange(k)[None, :] < jnp.minimum(count, k)[:, None]
    n_lanes = jnp.max(jnp.minimum(count, k))

    pix = np.arange(TILE * TILE)
    px = origins[:, 0:1] + jnp.asarray(pix % TILE + 0.5, jnp.float32)
    py = origins[:, 1:2] + jnp.asarray(pix // TILE + 0.5, jnp.float32)
    px, py = px.astype(dtype), py.astype(dtype)
    tb = origins.shape[0]
    zero = jnp.zeros((tb, TILE * TILE), dtype)
    init = (jnp.int32(0), jnp.zeros((tb, TILE * TILE, 3), dtype),
            jnp.ones((tb, TILE * TILE), dtype),
            jnp.zeros((tb, TILE * TILE), bool), zero, zero, zero)

    def cond(s):
        return (s[0] < n_lanes) & ~jnp.all(s[3])

    def body(s):
        j, rgb, trans, done, dacc, wacc, tdepth = s
        gi = idx[:, j]
        ok = lane_ok[:, j][:, None]
        dx = px - g["uv"][gi, 0][:, None]
        dy = py - g["uv"][gi, 1][:, None]
        con = g["conic"][gi]
        power = (-0.5 * (con[:, 0:1] * dx * dx + con[:, 2:3] * dy * dy)
                 - con[:, 1:2] * dx * dy)
        alpha = jnp.minimum(ALPHA_MAX,
                            g["opacity"][gi][:, None] * jnp.exp(power))
        live = ok & (alpha >= ALPHA_MIN) & ~done
        test_t = trans * (1.0 - alpha)
        stop = live & (test_t < T_EPS)
        blend = live & ~stop
        w = jnp.where(blend, alpha * trans, 0.0)
        z = g["depth"][gi][:, None]
        rgb = rgb + w[..., None] * g["colour"][gi][:, None, :]
        dacc = dacc + w * z
        wacc = wacc + w
        tdepth = jnp.where(blend, jnp.maximum(tdepth, z), tdepth)
        trans = jnp.where(blend, test_t, trans)
        return j + 1, rgb, trans, done | stop, dacc, wacc, tdepth

    _, rgb, trans, _, dacc, wacc, tdepth = jax.lax.while_loop(
        cond, body, init)
    exp_depth = dacc / jnp.maximum(wacc, 1e-8)
    return rgb, trans, exp_depth, tdepth


def _depth_sorted(g, chunk: int):
    """The Gaussians' tile-test inputs in depth order (equal depths in
    index order; invalid ones last), padded to a multiple of ``chunk``,
    and how many are valid."""
    key = jnp.where(g["valid"], g["depth"], jnp.inf).astype(jnp.float32)
    order = jnp.argsort(key, stable=True)
    order = jnp.pad(order, (0, -key.shape[0] % chunk))
    s = dict(lo=(g["uv"] - g["half"])[order],
             hi=(g["uv"] + g["half"])[order], depth=g["depth"][order],
             index=order.astype(jnp.int32))
    return s, jnp.sum(g["valid"].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("cam", "dtype", "capacity",
                                             "chunk"))
def _render_tiles(scene, w2c, tile_ids, limits, cam: Intrinsics, dtype,
                  capacity: int, chunk: int = CHUNK):
    """The tiles ``tile_ids`` (row-major ids; -1 pads) of the frame at
    ``w2c``, each blended only from Gaussians no deeper than its limit.
    The id count is a multiple of ``TILE_BLOCK``, or smaller than it."""
    g = project(scene, w2c, cam, dtype)
    n = g["depth"].shape[0]
    k, chunk = min(capacity, n), min(chunk, n)
    s, n_valid = _depth_sorted(g, chunk)
    tx = cam.width // TILE
    origins = jnp.stack([(tile_ids % tx) * TILE, (tile_ids // tx) * TILE],
                        -1).astype(jnp.float32)
    origins = jnp.where(tile_ids[:, None] >= 0, origins, -1e6)
    m = tile_ids.shape[0]
    tb = min(TILE_BLOCK, m)

    def block(a):
        origins, limit = a
        idx, count = _select(s, n_valid, origins, limit, k, chunk)
        return (_blend(g, idx, count, origins, dtype),
                jnp.sum(jnp.maximum(count - k, 0)))

    out, overflow = jax.lax.map(
        block, (origins.reshape(m // tb, tb, 2),
                limits.astype(dtype).reshape(m // tb, tb)))
    rgb, trans, exp_depth, trunc_depth = (
        x.reshape((m, TILE, TILE) + x.shape[3:]).astype(jnp.float32)
        for x in out)
    return rgb, trans, exp_depth, trunc_depth, jnp.sum(overflow)


def _padded(ids: np.ndarray, size: int) -> np.ndarray:
    out = np.full(size, -1, np.int32)
    out[:len(ids)] = ids
    return out


class Image(NamedTuple):
    """A rendered or composed frame, (H, W, ...) float32 host arrays."""

    rgb: np.ndarray
    trans: np.ndarray
    exp_depth: np.ndarray
    trunc_depth: np.ndarray


def _untile(tiles: np.ndarray, cam: Intrinsics) -> np.ndarray:
    tx, ty = cam.width // TILE, cam.height // TILE
    x = tiles.reshape((ty, tx, TILE, TILE) + tiles.shape[3:])
    return x.swapaxes(1, 2).reshape((ty * TILE, tx * TILE) + tiles.shape[3:])


def _tiles(img: np.ndarray, cam: Intrinsics) -> np.ndarray:
    tx, ty = cam.width // TILE, cam.height // TILE
    x = img.reshape((ty, TILE, tx, TILE) + img.shape[2:])
    return x.swapaxes(1, 2).reshape((ty * tx, TILE, TILE) + img.shape[2:])


def render_tiles(scene: dict, w2c, cam: Intrinsics, tile_ids: np.ndarray,
                 limits: np.ndarray, *, slots: int, dtype=jnp.float32,
                 capacity: int = CAPACITY):
    """Per-tile (rgb, trans, exp_depth, trunc_depth) of ``tile_ids``, in
    ``slots`` padded slots, and the pairs past the cap."""
    slots = -(-slots // TILE_BLOCK) * TILE_BLOCK if slots > TILE_BLOCK \
        else slots
    pad = np.full(slots - len(limits), np.inf, np.float32)
    out = _render_tiles(scene, jnp.asarray(w2c, jnp.float32),
                        jnp.asarray(_padded(tile_ids, slots)),
                        jnp.asarray(np.concatenate(
                            [np.asarray(limits, np.float32), pad])),
                        cam, jnp.dtype(dtype), int(capacity))
    n = len(tile_ids)
    return tuple(np.asarray(x)[:n] for x in out[:4]), int(out[4])


def render_image(scene: dict, w2c, cam: Intrinsics, *, dtype=jnp.float32,
                 capacity: int = CAPACITY) -> Tuple[Image, int]:
    """The whole frame at ``w2c`` and the pairs past the cap."""
    t = (cam.width // TILE) * (cam.height // TILE)
    tiles, over = render_tiles(scene, w2c, cam, np.arange(t, dtype=np.int32),
                               np.full(t, np.inf, np.float32), slots=t,
                               dtype=dtype, capacity=capacity)
    return Image(*(_untile(x, cam) for x in tiles)), over


# The sparse-frame rule (LS-Gaussian, arXiv 2507.21572, Sec. IV-A and B,
# Algorithm 1 "TW w/ mask"), applied to the frame before:
#
# - every pixel of the frame before that its Gaussians cover by more
#   than MIN_COVERAGE (or, on a tile it interpolated, that the warp
#   filled) is lifted to 3D at its expected depth and projected into the
#   new view, to the pixel floor(u), floor(v); where several land on one
#   pixel, the nearest wins and those within a relative 1e-5 of its depth
#   are averaged. Truncated depths are lifted and projected the same way,
#   and each pixel keeps the largest that lands on it;
# - a 16 x 16 tile that more than N0 of its pixels reached is
#   interpolated: its holes are filled by INPAINT_ITERS rounds of 3 x 3
#   averaging over the pixels known so far. Every other tile is rendered
#   anew, up to the first ``slots`` of them along the Morton curve (the
#   rest are interpolated), from the Gaussians no deeper than the
#   largest truncated depth that reached it (no limit where none did);
# - depths and colour are composed alike, and a re-rendered pixel is a
#   source for the next frame where its coverage exceeds MIN_COVERAGE.
MIN_COVERAGE = 0.25
N0 = round(5.0 / 6.0 * TILE * TILE)
INPAINT_ITERS = 8
DEPTH_TIE = 1e-5


def morton_order(tiles_x: int, tiles_y: int) -> np.ndarray:
    """Row-major tile ids in Z-order (x bits even, y bits odd)."""
    def spread(a):
        out = np.zeros_like(a)
        for bit in range(16):
            out |= ((a >> bit) & 1) << (2 * bit)
        return out
    ty, tx = np.meshgrid(np.arange(tiles_y), np.arange(tiles_x),
                         indexing="ij")
    code = spread(tx.ravel()) | (spread(ty.ravel()) << 1)
    return np.argsort(code, kind="stable").astype(np.int32)


@functools.partial(jax.jit, static_argnames=("cam", "dtype"))
def _warp(rgb, exp_depth, trunc_depth, source, ref_w2c, tgt_w2c,
          cam: Intrinsics, dtype):
    h, w = cam.height, cam.width
    f = lambda a: jnp.asarray(a).astype(dtype)
    u = (jnp.arange(w, dtype=jnp.float32) + 0.5).astype(dtype)[None, :]
    v = (jnp.arange(h, dtype=jnp.float32) + 0.5).astype(dtype)[:, None]
    ref, tgt = f(ref_w2c), f(tgt_w2c)

    def land(depth):
        """Pixels of the frame before at ``depth`` -> flat pixel, z."""
        d = f(depth)
        p = jnp.stack([jnp.broadcast_to((u - cam.cx) / cam.fx * d, d.shape),
                       jnp.broadcast_to((v - cam.cy) / cam.fy * d, d.shape),
                       d], -1).reshape(-1, 3)
        world = _mat3_apply(jnp.swapaxes(ref[:3, :3], 0, 1), p - ref[:3, 3])
        q = _mat3_apply(tgt[:3, :3], world) + tgt[:3, 3]
        z = q[:, 2]
        zs = jnp.maximum(z, NEAR)
        ui = jnp.floor(cam.fx * q[:, 0] / zs + cam.cx).astype(jnp.int32)
        vi = jnp.floor(cam.fy * q[:, 1] / zs + cam.cy).astype(jnp.int32)
        ok = (source.reshape(-1) & (z > NEAR) & (ui >= 0) & (ui < w)
              & (vi >= 0) & (vi < h))
        return jnp.where(ok, vi * w + ui, 0), z, ok

    size = h * w
    at, z, ok = land(exp_depth)
    big = jnp.asarray(1e30, dtype)
    zk = jnp.where(ok, z, big)
    zmin = jnp.full((size,), big, dtype).at[at].min(zk)
    win = ok & (zk <= zmin[at] * (1.0 + DEPTH_TIE))
    cnt = jnp.zeros((size,), dtype).at[at].add(win.astype(dtype))
    acc = jnp.zeros((size, 3), dtype).at[at].add(
        f(rgb).reshape(-1, 3) * win[:, None].astype(dtype))
    filled = cnt > 0
    rgb_t = acc / jnp.maximum(cnt, 1.0)[:, None]
    depth_t = jnp.where(filled, zmin, 0.0)
    at2, z2, ok2 = land(trunc_depth)
    trunc_t = jnp.zeros((size,), dtype).at[at2].max(jnp.where(ok2, z2, 0.0))

    def per_tile(x):
        return x.reshape(h // TILE, TILE, w // TILE, TILE).swapaxes(
            1, 2).reshape(-1, TILE * TILE)
    count = jnp.sum(per_tile(filled.astype(jnp.int32)), axis=1)
    deepest = jnp.max(per_tile(trunc_t), axis=1)
    limit = jnp.where((count > 0) & (deepest > 0), deepest, jnp.inf)
    known = jnp.concatenate([rgb_t, depth_t[:, None], trunc_t[:, None]], -1)
    return (known.reshape(h, w, 5), filled.reshape(h, w), count,
            limit.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _inpaint(known, filled, dtype):
    """Holes filled by rounds of 3 x 3 averaging over known pixels."""
    x = known.astype(dtype)
    wgt = filled.astype(dtype)[..., None]

    def box(a):
        p = jnp.pad(a, ((1, 1), (1, 1), (0, 0)))
        return sum(p[i:i + a.shape[0], j:j + a.shape[1]]
                   for i in range(3) for j in range(3))

    img = x * wgt
    for _ in range(INPAINT_ITERS):
        num, den = box(img * wgt), box(wgt)
        img = jnp.where(filled[..., None], x, num / jnp.maximum(den, 1e-8))
        wgt = jnp.maximum(wgt, (den > 0).astype(dtype))
    return img.astype(jnp.float32)


class State(NamedTuple):
    """A frame as the next one warps it."""

    image: Image
    source: np.ndarray      # (H, W) bool


def key_frame(scene: dict, w2c, cam: Intrinsics, *, dtype=jnp.float32,
              capacity: int = CAPACITY) -> Tuple[State, int]:
    img, over = render_image(scene, w2c, cam, dtype=dtype,
                             capacity=capacity)
    return State(img, (1.0 - img.trans) > MIN_COVERAGE), over


def sparse_frame(scene: dict, before: State, ref_w2c, w2c, cam: Intrinsics,
                 *, slots: int, dtype=jnp.float32,
                 capacity: int = CAPACITY) -> Tuple[State, dict]:
    """The frame at ``w2c`` by the sparse-frame rule from ``before``
    (rendered at ``ref_w2c``), and what the rule decided."""
    img = before.image
    known, filled, count, limit = (np.asarray(a) for a in _warp(
        img.rgb, img.exp_depth, img.trunc_depth, before.source,
        jnp.asarray(ref_w2c, jnp.float32), jnp.asarray(w2c, jnp.float32),
        cam, jnp.dtype(dtype)))
    tx, ty = cam.width // TILE, cam.height // TILE
    order = morton_order(tx, ty)
    wanted = order[count[order] <= N0]
    ids = wanted[:slots]
    tiles, over = render_tiles(scene, w2c, cam, ids, limit[ids],
                               slots=slots, dtype=dtype, capacity=capacity)
    warped = np.asarray(_inpaint(known, filled, jnp.dtype(dtype)))
    comp = _tiles(np.concatenate(
        [warped, np.ones(warped.shape[:2] + (1,), np.float32)], -1), cam)
    comp[ids] = np.concatenate(
        [tiles[0], tiles[2][..., None], tiles[3][..., None],
         tiles[1][..., None]], -1)
    src = _tiles(filled, cam)
    src[ids] = (1.0 - tiles[1]) > MIN_COVERAGE
    comp = _untile(comp, cam)
    image = Image(comp[..., :3], comp[..., 5], comp[..., 3], comp[..., 4])
    return State(image, _untile(src, cam)), dict(
        rerender=len(ids), overflow_tiles=len(wanted) - len(ids),
        pairs_past_cap=over)


def stream(scene: dict, poses, cam: Intrinsics, *, slots: int,
           dtype=jnp.float32, capacity: int = CAPACITY, log=None):
    """One stream's frames at ``poses``: the first a key frame, each
    later one by the sparse-frame rule from the one before. Yields
    (rgb, what was decided)."""
    state, over = key_frame(scene, poses[0], cam, dtype=dtype,
                            capacity=capacity)
    yield state.image.rgb, dict(rerender=None, overflow_tiles=0,
                                pairs_past_cap=over)
    for ref_w2c, w2c in zip(poses[:-1], poses[1:]):
        state, info = sparse_frame(scene, state, ref_w2c, w2c, cam,
                                   slots=slots, dtype=dtype,
                                   capacity=capacity)
        yield state.image.rgb, info
