"""Seeded Gaussian scenes, made on the device in one jitted call.

The generator is the benchmark's own, so the scenes a run serves and
the reference renders come from the seed and from nothing the program
under test computes. ``structured_room`` follows the room-like
statistics of the program's ``structured_scene`` (flat, near-opaque
Gaussians pancaked onto five faces of a box, plus twelve clusters of
small splats): large flat regions that reproject well beside clutter
that does not, and per-tile Gaussian counts spanning over an order of
magnitude.

A scene is a dict of arrays: ``means`` (N, 3) world positions,
``log_scales`` (N, 3), ``quats`` (N, 4) unnormalised (w, x, y, z),
``opacity_logits`` (N,) and ``sh`` (N, (degree + 1)^2, 3) spherical
harmonic colour coefficients in the 3DGS convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SH_C0 = 0.28209479177387814
N_CLUSTERS = 12


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def structured_room(key: jax.Array, n: int, sh_degree: int,
                    clutter: float, room: float) -> dict:
    """One room-like scene of ``n`` Gaussians (see module docstring)."""
    n_flat = max(int(n * (1.0 - clutter) * 0.4), 16)
    n_clutter = n - n_flat
    kf, kc, kq, ko, ks, kcl = jax.random.split(key, 6)

    # Flat structure on five faces: 0 floor (y = +room), 1 back wall
    # (z = 2 room), 2 left (x = -room), 3 right (x = +room), 4 ceiling.
    face = jax.random.randint(kf, (n_flat,), 0, 5)
    uv = jax.random.uniform(jax.random.fold_in(kf, 1), (n_flat, 2),
                            minval=-room, maxval=room)
    fx = jnp.select([face == 2, face == 3], [-room, room], uv[:, 0])
    fy = jnp.select([face == 0, face == 4], [room, -room], uv[:, 1])
    fz = jnp.where(face == 1, 2 * room,
                   room + jax.random.uniform(jax.random.fold_in(kf, 2),
                                             (n_flat,), minval=0.0,
                                             maxval=room))
    flat_means = jnp.stack([fx, fy, fz], -1)
    thin = (jnp.stack([face == 2, face == 0, face == 1], -1)
            | jnp.stack([face == 3, face == 4, face == 1], -1))
    flat_scales = jnp.where(thin, -4.0, -0.8)

    centers = jax.random.uniform(kcl, (N_CLUSTERS, 3), minval=-0.7 * room,
                                 maxval=0.7 * room)
    centers = centers.at[:, 2].add(1.2 * room)
    assign = jax.random.randint(jax.random.fold_in(kcl, 1), (n_clutter,), 0,
                                N_CLUSTERS)
    jitter = jax.random.normal(kc, (n_clutter, 3)) * (0.15 * room)
    clutter_means = centers[assign] + jitter
    clutter_scales = jax.random.uniform(
        jax.random.fold_in(ks, 1), (n_clutter, 3), minval=-4.5, maxval=-2.5)

    kb1, kb2 = jax.random.split(jax.random.fold_in(ko, 7))
    flat_rgb = jnp.tile(jax.random.uniform(kb1, (1, 3), minval=0.4,
                                           maxval=0.8), (n_flat, 1))
    flat_rgb = flat_rgb + 0.05 * jax.random.normal(
        jax.random.fold_in(kb1, 1), (n_flat, 3))
    rgb = jnp.clip(jnp.concatenate(
        [flat_rgb, jax.random.uniform(kb2, (n_clutter, 3))], 0), 0.05, 0.95)
    k_sh = (sh_degree + 1) ** 2
    sh = jnp.zeros((n, k_sh, 3), jnp.float32).at[:, 0, :].set(
        (rgb - 0.5) / SH_C0)
    if k_sh > 1:
        sh = sh.at[:, 1:, :].set(0.08 * jax.random.normal(
            jax.random.fold_in(kb2, 2), (n, k_sh - 1, 3)))
    return {
        "means": jnp.concatenate([flat_means, clutter_means], 0),
        "log_scales": jnp.concatenate([flat_scales, clutter_scales], 0),
        "quats": jax.random.normal(kq, (n, 4)),
        "opacity_logits": jnp.concatenate([
            jnp.full((n_flat,), 2.5),
            jax.random.uniform(ko, (n_clutter,), minval=-1.0, maxval=2.5)]),
        "sh": sh,
    }


GENERATORS = {"structured_room": structured_room}


@functools.partial(jax.jit, static_argnames=("count", "generator", "n",
                                             "sh_degree", "clutter", "room"))
def _make(key, *, count, generator, n, sh_degree, clutter, room):
    keys = jax.random.split(key, count)
    fn = functools.partial(GENERATORS[generator], n=n, sh_degree=sh_degree,
                           clutter=clutter, room=room)
    return jax.vmap(fn)(keys)


def make_scenes(scene_cfg: dict, count: int, seed: int) -> dict:
    """``count`` scenes stacked on a leading axis, one jitted call."""
    out = _make(seed_key(seed), count=int(count),
                generator=scene_cfg["generator"],
                n=int(scene_cfg["num_gaussians"]),
                sh_degree=int(scene_cfg["sh_degree"]),
                clutter=float(scene_cfg["clutter"]),
                room=float(scene_cfg["room"]))
    return jax.block_until_ready(out)
