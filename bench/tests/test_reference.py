"""The reference's tile selection against the dense one it replaced.

``reference._render_tiles`` finds each tile's Gaussians by reading them
in depth order, ``chunk`` at a time, into a lane list per tile. Before,
one (tiles, N) membership mask and a ``top_k`` over N did it, which
cannot hold a 2,000,000-Gaussian scene. Both have to give the same
frames bit for bit, and the same count of pairs past the cap; here on
seeded small scenes with chunks smaller than N, so that several run.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import scenes
from conftest import BENCH

with open(os.path.join(BENCH, "traffic", "head1.json")) as f:
    HEAD1 = json.load(f)


@jax.jit
def _scene(key):
    return scenes.structured_room(key, 512, 3, 0.5, 4.0)


def _pose(seed, k):
    import poses
    return jnp.asarray(poses.Traffic(HEAD1, seed).pose(0, k))


@jax.jit
def _tied(scene):
    """Half of the Gaussians moved onto the other half's centres: pairs
    at equal depths, with different shapes and colours."""
    return dict(scene, means=scene["means"].at[256:].set(
        scene["means"][:256]))


@functools.partial(jax.jit, static_argnames="n")
def _padded(scene, n):
    """``n`` more Gaussians, all behind the camera's near plane."""
    pad = lambda x, v: jnp.concatenate(
        [x, jnp.full((n,) + x.shape[1:], v, x.dtype)])
    return dict(means=pad(scene["means"], -50.0),
                log_scales=pad(scene["log_scales"], -2.0),
                quats=pad(scene["quats"], 1.0),
                opacity_logits=pad(scene["opacity_logits"], 2.0),
                sh=pad(scene["sh"], 0.1))


@functools.partial(jax.jit, static_argnames=("cam", "dtype", "capacity"))
def _dense(scene, w2c, tile_ids, limits, cam, dtype, capacity):
    """The selection this replaced: a (Tb, N) mask, a top_k over N."""
    g = reference.project(scene, w2c, cam, dtype)
    key = jnp.where(g["valid"], g["depth"], jnp.inf).astype(jnp.float32)
    rank = jnp.argsort(jnp.argsort(key, stable=True)).astype(jnp.float32)
    lo, hi = g["uv"] - g["half"], g["uv"] + g["half"]
    tx = cam.width // reference.TILE
    origins = jnp.stack([(tile_ids % tx) * reference.TILE,
                         (tile_ids // tx) * reference.TILE],
                        -1).astype(jnp.float32)
    origins = jnp.where(tile_ids[:, None] >= 0, origins, -1e6)
    k = min(capacity, key.shape[0])

    def block(a):
        t_lo, limit = a
        t_hi = t_lo + reference.TILE
        member = ((lo[None, :, 0] < t_hi[:, None, 0])
                  & (hi[None, :, 0] > t_lo[:, None, 0])
                  & (lo[None, :, 1] < t_hi[:, None, 1])
                  & (hi[None, :, 1] > t_lo[:, None, 1])
                  & g["valid"][None, :]
                  & (g["depth"][None, :] <= limit[:, None]))
        _, idx = jax.lax.top_k(jnp.where(member, -rank, -jnp.inf), k)
        count = jnp.sum(member, axis=1)
        return (reference._blend(g, idx, count, t_lo, dtype),
                jnp.sum(jnp.maximum(count - k, 0)))

    m = tile_ids.shape[0]
    tb = min(reference.TILE_BLOCK, m)
    out, over = jax.lax.map(block, (origins.reshape(m // tb, tb, 2),
                                    limits.astype(dtype).reshape(m // tb, tb)))
    return tuple(x.reshape((m, reference.TILE, reference.TILE)
                           + x.shape[3:]).astype(jnp.float32)
                 for x in out) + (jnp.sum(over),)


def _frames(scene, w2c, ids, limits, cam, capacity, chunk,
            dtype=jnp.float32):
    args = (scene, w2c, jnp.asarray(ids, jnp.int32),
            jnp.asarray(limits, jnp.float32), cam, jnp.dtype(dtype),
            capacity)
    return (_dense(*args),
            reference._render_tiles(*args, chunk=chunk))


def _assert_equal(dense, chunked):
    names = ("rgb", "trans", "exp_depth", "trunc_depth", "pairs past cap")
    for name, a, b in zip(names, dense, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _limits(scene, w2c, cam, ids, rng):
    """Per-tile limits spread over the scene's depths, some infinite."""
    g = reference.project(scene, w2c, cam, jnp.float32)
    depth = np.asarray(g["depth"])[np.asarray(g["valid"])]
    lim = np.quantile(depth, rng.uniform(0.0, 1.0, len(ids)))
    lim[rng.random(len(ids)) < 0.25] = np.inf
    return lim.astype(np.float32)


CAM = reference.intrinsics(96, 64, 60.0)
FULL = np.arange(24, dtype=np.int32)


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 17])
@pytest.mark.parametrize("capacity,chunk", [(8, 64), (64, 100),
                                            (512, 96), (2048, 512)])
def test_chunked_selection_matches_dense(seed, capacity, chunk):
    scene = _scene(scenes.seed_key(seed))
    for k in (0, 45):
        _assert_equal(*_frames(scene, _pose(seed, k), FULL,
                               np.full(24, np.inf), CAM, capacity, chunk))


@pytest.mark.parametrize("capacity", [8, 512])
def test_chunked_selection_matches_dense_at_equal_depths(capacity):
    scene = _tied(_scene(scenes.seed_key(11)))
    g = reference.project(scene, _pose(11, 0), CAM, jnp.float32)
    depth = np.asarray(g["depth"])
    assert np.array_equal(depth[256:], depth[:256])
    _assert_equal(*_frames(scene, _pose(11, 0), FULL, np.full(24, np.inf),
                           CAM, capacity, 48))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_selection_matches_dense_under_depth_limits(dtype):
    rng = np.random.default_rng(5)
    scene = _scene(scenes.seed_key(5))
    w2c = _pose(5, 20)
    ids = rng.permutation(24)[:20].astype(np.int32)
    limits = _limits(scene, w2c, CAM, ids, rng)
    _assert_equal(*_frames(scene, w2c, ids, limits, CAM, 16, 64, dtype))


def test_chunked_selection_matches_dense_with_padded_tiles():
    rng = np.random.default_rng(9)
    scene = _scene(scenes.seed_key(9))
    w2c = _pose(9, 60)
    ids = np.full(32, -1, np.int32)
    ids[[0, 1, 2, 5, 6, 9, 12, 13, 20, 21]] = rng.permutation(24)[:10]
    limits = _limits(scene, w2c, CAM, ids, rng)
    _assert_equal(*_frames(scene, w2c, ids, limits, CAM, 32, 64))


def test_chunked_selection_matches_dense_over_blocks():
    """Two blocks of tiles, the second half padding, from a scene padded
    with Gaussians no tile takes: each block reads only those that could
    meet one of its tiles, and the invalid ones are left out."""
    cam = reference.intrinsics(528, 512, 60.0)
    tiles = (528 // 16) * (512 // 16)
    rng = np.random.default_rng(13)
    scene = _padded(_scene(scenes.seed_key(13)), n=1536)
    w2c = _pose(13, 30)
    ids = np.full(2 * reference.TILE_BLOCK, -1, np.int32)
    ids[:tiles] = reference.morton_order(33, 32)
    limits = np.full(len(ids), np.inf, np.float32)
    limits[:tiles] = _limits(scene, w2c, cam, ids[:tiles], rng)
    dense, chunked = _frames(scene, w2c, ids, limits, cam, 16, 128)
    assert int(dense[4]) > 0
    _assert_equal(dense, chunked)


def test_render_tiles_cpu_plan_at_2m_gaussians():
    """The reference's plan for a 1080p frame of 2,000,000 Gaussians in
    8,192 slots keeps its temporaries under 4 GB on the CPU too (the
    dense selection planned 16.5 GB)."""
    n, f = 2_000_000, jax.ShapeDtypeStruct
    scene = dict(means=f((n, 3), jnp.float32),
                 log_scales=f((n, 3), jnp.float32),
                 quats=f((n, 4), jnp.float32),
                 opacity_logits=f((n,), jnp.float32),
                 sh=f((n, 16, 3), jnp.float32))
    compiled = reference._render_tiles.lower(
        scene, f((4, 4), jnp.float32), f((8192,), jnp.int32),
        f((8192,), jnp.float32), reference.intrinsics(1920, 1088, 60.0),
        jnp.dtype(jnp.float32), reference.CAPACITY).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * 10 ** 9
