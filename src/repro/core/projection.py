"""Preprocessing stage: cull + project Gaussians to the image plane.

Implements the EWA splatting projection used by 3DGS (Sec. II-A of the
paper): world covariance -> camera -> 2D via the perspective Jacobian,
plus everything TAIT (Sec. IV-C) needs downstream: eigenvalues and
eigenvectors of the 2D covariance, opacity-aware effective radii (eq. 4)
and the tight bounding box (eq. 6).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import gaussians as G
from repro.core.camera import Camera, camera_position, matmul

# Opacity threshold below which a Gaussian does not contribute (1/255),
# Sec. II-A / eq. (4).
ALPHA_THRESHOLD = 1.0 / 255.0
# Low-pass dilation added to the projected covariance diagonal, as in the
# reference 3DGS rasterizer (anti-aliasing floor).
COV2D_DILATION = 0.3


class ProjectedGaussians(NamedTuple):
    """Per-Gaussian screen-space quantities (all shape-static, N rows)."""

    mean2d: jax.Array      # (N, 2) pixel coords of projected center
    cov2d: jax.Array       # (N, 3) upper-tri 2D covariance (a, b, c)
    conic: jax.Array       # (N, 3) inverse covariance (A, B, C)
    depth: jax.Array       # (N,)  camera-space z
    rgb: jax.Array         # (N, 3) SH-evaluated color for this view
    opacity: jax.Array     # (N,)
    radius3: jax.Array     # (N,)  classic 3*sqrt(lambda1) radius (baseline AABB)
    eigvals: jax.Array     # (N, 2) (lambda1 >= lambda2) of cov2d
    minor_axis: jax.Array  # (N, 2) unit eigenvector of lambda2 (minor axis dir)
    r_major: jax.Array     # (N,)  TAIT effective semi-major radius, eq. (4)
    r_minor: jax.Array     # (N,)  TAIT effective semi-minor radius, eq. (4)
    tight_half_wh: jax.Array  # (N, 2) TAIT tight bbox half (W/2, H/2), eq. (6)
    valid: jax.Array       # (N,)  in-frustum & non-degenerate & visible


def _eig2x2(a, b, c):
    """Eigen-decomposition of symmetric [[a, b], [b, c]].

    Returns (lam1, lam2, minor_axis) with lam1 >= lam2 and minor_axis the
    unit eigenvector belonging to lam2.
    """
    mid = 0.5 * (a + c)
    half_diff = 0.5 * (a - c)
    disc = jnp.sqrt(jnp.maximum(half_diff * half_diff + b * b, 1e-12))
    lam1 = mid + disc
    lam2 = jnp.maximum(mid - disc, 1e-8)
    # Eigenvector for lam2: (b, lam2 - a) unless b ~ 0.
    ex = jnp.where(jnp.abs(b) > 1e-12, b, jnp.where(a <= c, 1.0, 0.0))
    ey = jnp.where(jnp.abs(b) > 1e-12, lam2 - a, jnp.where(a <= c, 0.0, 1.0))
    norm = jnp.sqrt(ex * ex + ey * ey) + 1e-12
    return lam1, lam2, jnp.stack([ex / norm, ey / norm], axis=-1)


def preprocess(scene: G.GaussianScene, cam: Camera, *,
               near: float = 0.05, frustum_margin: float = 1.3,
               dilation: float = COV2D_DILATION) -> ProjectedGaussians:
    """Project every Gaussian into the view; compute TAIT geometry.

    ``frustum_margin`` widens the cull window (a Gaussian slightly outside
    the image can still splat into it).
    """
    rot, t = cam.w2c[:3, :3], cam.w2c[:3, 3]
    p_cam = matmul(scene.means, rot.T) + t                # (N, 3)
    z = p_cam[..., 2]
    safe_z = jnp.maximum(z, near)

    u = cam.fx * p_cam[..., 0] / safe_z + cam.cx
    v = cam.fy * p_cam[..., 1] / safe_z + cam.cy
    mean2d = jnp.stack([u, v], axis=-1)

    # Perspective Jacobian (2x3) with the standard EWA clamping of x/z, y/z.
    lim_x = frustum_margin * cam.width / (2.0 * cam.fx)
    lim_y = frustum_margin * cam.height / (2.0 * cam.fy)
    tx = jnp.clip(p_cam[..., 0] / safe_z, -lim_x, lim_x) * safe_z
    ty = jnp.clip(p_cam[..., 1] / safe_z, -lim_y, lim_y) * safe_z
    inv_z = 1.0 / safe_z
    inv_z2 = inv_z * inv_z
    zeros = jnp.zeros_like(inv_z)
    j = jnp.stack([
        jnp.stack([cam.fx * inv_z, zeros, -cam.fx * tx * inv_z2], -1),
        jnp.stack([zeros, cam.fy * inv_z, -cam.fy * ty * inv_z2], -1),
    ], axis=-2)                                            # (N, 2, 3)

    cov3d = G.covariances(scene)                           # (N, 3, 3)
    m = matmul(j, rot[None, :, :])                         # (N, 2, 3)
    cov2d_full = matmul(matmul(m, cov3d), jnp.swapaxes(m, -1, -2))
    a = cov2d_full[..., 0, 0] + dilation
    b = cov2d_full[..., 0, 1]
    c = cov2d_full[..., 1, 1] + dilation

    det = a * c - b * b
    det_safe = jnp.maximum(det, 1e-12)
    conic = jnp.stack([c / det_safe, -b / det_safe, a / det_safe], axis=-1)

    lam1, lam2, minor_axis = _eig2x2(a, b, c)
    radius3 = jnp.ceil(3.0 * jnp.sqrt(lam1))

    opacity = G.opacities(scene)
    # eq. (4): effective radii where opacity falls to tau = 1/255.
    log_ratio = jnp.log(jnp.maximum(opacity / ALPHA_THRESHOLD, 1.0 + 1e-6))
    r_major = jnp.sqrt(2.0 * log_ratio * lam1)
    r_minor = jnp.sqrt(2.0 * log_ratio * lam2)
    # eq. (6): tight bbox; half-width = sqrt(Sigma'_X / lam1) * R_major etc.
    half_w = jnp.sqrt(jnp.maximum(a / lam1, 0.0)) * r_major
    half_h = jnp.sqrt(jnp.maximum(c / lam1, 0.0)) * r_major
    tight_half_wh = jnp.stack([half_w, half_h], axis=-1)

    cam_pos = camera_position(cam)
    dirs = scene.means - cam_pos
    dirs = dirs / (jnp.linalg.norm(dirs, axis=-1, keepdims=True) + 1e-12)
    rgb = G.eval_sh(scene.sh, dirs)

    in_front = z > near
    visible = opacity > ALPHA_THRESHOLD
    on_screen = ((u + radius3 > 0) & (u - radius3 < cam.width)
                 & (v + radius3 > 0) & (v - radius3 < cam.height))
    valid = in_front & visible & on_screen & (det > 1e-12)

    return ProjectedGaussians(
        mean2d=mean2d, cov2d=jnp.stack([a, b, c], -1), conic=conic,
        depth=z, rgb=rgb, opacity=opacity, radius3=radius3,
        eigvals=jnp.stack([lam1, lam2], -1), minor_axis=minor_axis,
        r_major=r_major, r_minor=r_minor, tight_half_wh=tight_half_wh,
        valid=valid)
