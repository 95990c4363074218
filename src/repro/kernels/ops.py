"""Jit'd public wrappers around the Pallas kernels.

Every op takes ``impl`` selecting between (DESIGN.md §9):
  - "pallas_fused": the fused per-slot sort+raster Pallas kernel
                    (kernels/raster_plan.py) — the default device path on
                    TPU backends (see ``default_impl``)
  - "pallas"      : the raster-only Pallas kernel over pre-sorted bins
                    (interpret=True on CPU, compiled on TPU)
  - "jnp_chunked" : vectorized pure-jnp path with identical chunked math —
                    the fast CPU execution path used by benchmarks
  - "ref"         : the sequential oracle (kernels/ref.py)
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.camera import TILE
from repro.kernels import ref as ref_kernels
from repro.kernels.raster_tile import raster_tiles_pallas
from repro.kernels.raster_plan import (blend_chunk, finish_blend,
                                       init_blend_state, pack_attributes,
                                       pixel_centers, raster_plan_fused)
from repro.kernels.preprocess import preprocess_geom_pallas
from repro.obs.trace import annotate


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Valid ``impl`` names for raster_tiles, in preference order — the single
# source of truth example CLIs build their --impl choices from.
RASTER_IMPLS = ("pallas_fused", "pallas", "jnp_chunked", "ref")


def default_impl() -> str:
    """The raster ``impl`` for this backend: the fused plan-slot kernel on
    TPU, the vectorized jnp path everywhere else (interpret-mode Pallas is
    a correctness tool, not an execution path — DESIGN.md §9)."""
    return "pallas_fused" if _on_tpu() else "jnp_chunked"


def _raster_tile_chunked_jnp(mean2d, conic, rgb, opacity, depth, origin,
                             count, *, chunk: int, tile: int):
    """One tile in pure jnp: the fused kernel's ``blend_chunk`` over
    pre-sorted lanes, chunk by chunk (same math, same summation order)."""
    k = opacity.shape[0]
    px, py = pixel_centers(origin[0], origin[1], tile)
    roll = functools.partial(jnp.roll, axis=1)

    def body(carry, blk):
        st, n_alive = carry
        alive = jnp.any(st.done == 0.0)
        st, contrib = blend_chunk(px, py, blk, st, roll)
        return (st, n_alive + alive.astype(jnp.int32)), contrib[0]

    blocks = pack_attributes(mean2d, conic, rgb, opacity, depth, k)
    blocks = blocks.reshape(-1, k // chunk, chunk).swapaxes(0, 1)
    (st, n_alive), contrib = jax.lax.scan(
        body, (init_blend_state(tile * tile), jnp.int32(0)), blocks)
    processed = jnp.minimum(n_alive * chunk, count).astype(jnp.int32)
    rgb_o, trans, exp_depth, trunc_depth = finish_blend(st)
    return (rgb_o.reshape(tile, tile, 3), trans.reshape(tile, tile),
            exp_depth.reshape(tile, tile), trunc_depth.reshape(tile, tile),
            processed, contrib.reshape(k))


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "tile"))
def raster_tiles(mean2d, conic, rgb, opacity, depth, origins, counts,
                 *, impl: str = "jnp_chunked", chunk: int = 64,
                 tile: int = TILE, slot_active=None):
    """Rasterize a batch of tiles: inputs (R, K, ...) -> 6 outputs.

    The leading axis is whatever tile set the caller planned — all T
    tiles on the dense path, or a TilePlan's R compacted slots (the
    production path in core/pipeline.py, where raster cost scales with
    the re-render slot count). Returns (rgb, transmittance,
    expected_depth, truncated_depth, processed_pairs, lane_contrib):
    ``processed_pairs`` is (R,) int32 pairs traversed before the
    early-stop exit (chunk-granular for pallas/jnp_chunked, exact for
    ref); ``lane_contrib`` is (R, K) float32 per-lane blend contribution
    — the sum of blend weights ``alpha * T_before`` over the tile's
    pixels, reported in INPUT lane order on every impl (the fused kernel
    unscrambles its in-kernel sort), exactly 0 for padding / masked /
    never-blended lanes. It is the temporal-prior statistic
    ``core/culling.py`` thresholds on (DESIGN.md §12).

    ``slot_active`` (R,) bool is the TilePlan slot mask, consumed only by
    ``impl="pallas_fused"`` (masked slots skip the in-kernel sort).
    Contract: an inactive slot has ``counts == 0`` — the plan pipeline
    guarantees it by masking intersections with ``plan.slot_active``
    before binning — so every impl renders it as empty and the mask is a
    cost hint, not a semantic input (DESIGN.md §9).
    """
    with annotate(f"repro.raster/{impl}"):
        if impl == "pallas_fused":
            return raster_plan_fused(mean2d, conic, rgb, opacity, depth,
                                     origins, counts, slot_active,
                                     chunk=chunk, tile=tile,
                                     interpret=not _on_tpu())
        if impl == "pallas":
            return raster_tiles_pallas(mean2d, conic, rgb, opacity, depth,
                                       origins, counts, chunk=chunk,
                                       tile=tile, interpret=not _on_tpu())
        if impl == "jnp_chunked":
            fn = functools.partial(_raster_tile_chunked_jnp, chunk=chunk,
                                   tile=tile)
            return jax.vmap(fn)(mean2d, conic, rgb, opacity, depth,
                                origins, counts)
        if impl == "ref":
            return ref_kernels.raster_tiles_ref(mean2d, conic, rgb,
                                                opacity, depth, origins,
                                                tile=tile)
    raise ValueError(f"unknown impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("block_n",))
def _preprocess_geom_pallas_jit(means, log_scales, quats, opacity, w2c,
                                intrin, *, block_n: int):
    return preprocess_geom_pallas(means, log_scales, quats, opacity, w2c,
                                  intrin, block_n=block_n,
                                  interpret=not _on_tpu())


def preprocess_geom(means, log_scales, quats, opacity, w2c, intrin,
                    *, impl: str = "pallas", block_n: int = 256):
    """Fused CCU preprocess. See kernels/preprocess.py for outputs.

    ``impl="ref"`` requires concrete (non-traced) ``intrin`` since the
    oracle builds a static Camera; it is meant for tests.
    """
    if impl == "pallas":
        return _preprocess_geom_pallas_jit(means, log_scales, quats, opacity,
                                           w2c, intrin, block_n=block_n)
    if impl == "ref":
        return ref_kernels.preprocess_geom_ref(means, log_scales, quats,
                                               opacity, w2c, intrin)
    raise ValueError(f"unknown impl {impl!r}")
