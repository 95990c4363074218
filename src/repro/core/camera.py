"""Pinhole camera model and pose utilities.

Intrinsics and image size are static (python numbers) so they participate in
jit specialization; the world-to-camera pose is a traced (4, 4) array so the
same compiled renderer serves a whole trajectory.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

TILE = 16  # 16x16-pixel tiles, as in the paper (Sec. II-A)

# Geometry matmuls run at full f32. On TPU the default precision rounds
# f32 operands to bf16 (8 mantissa bits): a 6 m depth then lands on a
# 3 cm grid, ties in depth become common, and reprojection misses by
# pixels at 1080p. CPU matmuls are f32 either way.
HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b):
    """``a @ b`` at full f32 precision on every backend."""
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Camera:
    """Pinhole camera. ``w2c`` maps world -> camera (x right, y down, +z fwd)."""

    w2c: jax.Array  # (4, 4)
    fx: float = dataclasses.field(metadata=dict(static=True))
    fy: float = dataclasses.field(metadata=dict(static=True))
    cx: float = dataclasses.field(metadata=dict(static=True))
    cy: float = dataclasses.field(metadata=dict(static=True))
    width: int = dataclasses.field(metadata=dict(static=True))
    height: int = dataclasses.field(metadata=dict(static=True))

    @property
    def tiles_x(self) -> int:
        return self.width // TILE

    @property
    def tiles_y(self) -> int:
        return self.height // TILE

    @property
    def num_tiles(self) -> int:
        return self.tiles_x * self.tiles_y

    def with_pose(self, w2c: jax.Array) -> "Camera":
        return dataclasses.replace(self, w2c=w2c)


def make_camera(w2c, *, width: int, height: int, fov_deg: float = 60.0) -> Camera:
    """Square-pixel camera from a vertical FOV."""
    if width % TILE or height % TILE:
        raise ValueError(f"image size must be a multiple of {TILE}")
    f = 0.5 * height / float(np.tan(np.radians(fov_deg) / 2.0))
    return Camera(w2c=jnp.asarray(w2c, jnp.float32), fx=f, fy=f,
                  cx=width / 2.0, cy=height / 2.0, width=width, height=height)


def look_at(eye, target, up=(0.0, 1.0, 0.0)) -> jax.Array:
    """World-to-camera matrix looking from ``eye`` at ``target``. (4, 4)."""
    eye = jnp.asarray(eye, jnp.float32)
    target = jnp.asarray(target, jnp.float32)
    up = jnp.asarray(up, jnp.float32)
    fwd = target - eye
    fwd = fwd / (jnp.linalg.norm(fwd) + 1e-12)
    right = jnp.cross(fwd, up)
    right = right / (jnp.linalg.norm(right) + 1e-12)
    down = jnp.cross(fwd, right)  # y points down in camera frame
    rot = jnp.stack([right, down, fwd], axis=0)  # (3, 3) world->cam rotation
    trans = -matmul(rot, eye)
    w2c = jnp.eye(4, dtype=jnp.float32)
    w2c = w2c.at[:3, :3].set(rot).at[:3, 3].set(trans)
    return w2c


def camera_position(cam: Camera) -> jax.Array:
    """Camera center in world coordinates. (3,)."""
    rot = cam.w2c[:3, :3]
    return -matmul(rot.T, cam.w2c[:3, 3])


def cam_to_world(cam: Camera) -> jax.Array:
    """(4, 4) inverse pose."""
    rot = cam.w2c[:3, :3]
    c2w = jnp.eye(4, dtype=cam.w2c.dtype)
    c2w = c2w.at[:3, :3].set(rot.T).at[:3, 3].set(
        -matmul(rot.T, cam.w2c[:3, 3]))
    return c2w


def pixel_grid(cam: Camera) -> Tuple[jax.Array, jax.Array]:
    """Pixel-center coordinates (u, v), each (H, W)."""
    u = jnp.arange(cam.width, dtype=jnp.float32) + 0.5
    v = jnp.arange(cam.height, dtype=jnp.float32) + 0.5
    return jnp.meshgrid(u, v, indexing="xy")


def backproject(cam: Camera, depth: jax.Array) -> jax.Array:
    """Lift every pixel to world space using per-pixel depth.

    depth: (H, W) positive camera-z depth. Returns (H, W, 3) world points.
    """
    u, v = pixel_grid(cam)
    x = (u - cam.cx) / cam.fx * depth
    y = (v - cam.cy) / cam.fy * depth
    pts_cam = jnp.stack([x, y, depth], axis=-1)            # (H, W, 3)
    rot = cam.w2c[:3, :3]
    return matmul(pts_cam - cam.w2c[:3, 3], rot)  # == rot.T @ (p - t)


def project(cam: Camera, pts_world: jax.Array):
    """World points -> (u, v, depth). pts_world: (..., 3)."""
    rot, t = cam.w2c[:3, :3], cam.w2c[:3, 3]
    pc = matmul(pts_world, rot.T) + t
    z = pc[..., 2]
    safe_z = jnp.where(jnp.abs(z) < 1e-8, 1e-8, z)
    u = cam.fx * pc[..., 0] / safe_z + cam.cx
    v = cam.fy * pc[..., 1] / safe_z + cam.cy
    return u, v, z
