"""TilePlan — the compacted per-frame render plan (DESIGN.md §2).

The paper's central claim is that streaming 3DGS should do work
proportional to what actually changed: TWSR picks the re-render tile set
and its DPES-predicted per-tile workloads are what the LDU schedules. The
``TilePlan`` is that decision reified as a first-class device value:

  tile_ids       (R,) int32  tile ids in Morton visit order, active
                             slots first — R is a *static* slot count, so
                             every downstream stage (intersect, binning,
                             sort, raster) compiles to shapes that scale
                             with R instead of the full tile count T.
  slot_active    (R,) bool   padded slots (beyond the re-render set) are
                             inactive and contribute nothing.
  workload       (R,) int32  DPES-predicted pairs per slot (filled after
                             binning; recorded as ``sort_pairs``, the
                             host LDU's scheduling input).
  overflow_tiles ()   int32  re-render tiles dropped because they did not
                             fit in R (they degrade to interpolation).

Full frames carry an all-tiles plan (R = T); TWSR sparse frames carry the
warp-predicted re-render set compacted to ``R = rerender_capacity``. Both
render through the same ``pipeline.render_planned_frame``. Everything is
shape-static and jnp, so plans are built inside the jitted ``lax.scan``
streaming engine (core/engine.py) with no host callback. The LDU block
schedule is not part of the plan: nothing on the chip reads it, and the
accelerator simulator builds it on the host with numpy
``load_balance.schedule`` (core/streaming.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import load_balance


class TilePlan(NamedTuple):
    """Compacted frame plan; see module docstring for the field contract."""

    tile_ids: jax.Array        # (R,) int32
    slot_active: jax.Array     # (R,) bool
    workload: jax.Array        # (R,) int32
    overflow_tiles: jax.Array  # () int32

    @property
    def num_slots(self) -> int:
        return self.tile_ids.shape[0]


def _blank(tile_ids: jax.Array, slot_active: jax.Array,
           overflow_tiles: jax.Array) -> TilePlan:
    r = tile_ids.shape[0]
    return TilePlan(
        tile_ids=tile_ids.astype(jnp.int32), slot_active=slot_active,
        workload=jnp.zeros((r,), jnp.int32),
        overflow_tiles=overflow_tiles)


def full_plan(tiles_x: int, tiles_y: int) -> TilePlan:
    """All-tiles plan (R = T) in Morton visit order — key frames."""
    visit = jnp.argsort(load_balance.morton_rank(tiles_x, tiles_y))
    t = tiles_x * tiles_y
    return _blank(visit, jnp.ones((t,), bool), jnp.int32(0))


def sparse_plan(rerender: jax.Array, tiles_x: int, tiles_y: int,
                capacity: Optional[int]) -> TilePlan:
    """Compact the TWSR re-render set into R = ``capacity`` plan slots.

    Re-render tiles are taken in Morton order; with more re-render tiles
    than slots, the Morton tail overflows (counted, degrades to
    interpolation). ``capacity=None`` keeps R = T (no compaction — the
    dense reference path).
    """
    t = rerender.shape[0]
    r = t if capacity is None else min(int(capacity), t)
    rank = load_balance.morton_rank(tiles_x, tiles_y)
    # Active tiles first (in Morton order), inactive Morton-ordered after.
    ids = jnp.argsort(jnp.where(rerender, rank, t + rank))[:r]
    slot_active = rerender[ids]
    overflow = (jnp.sum(rerender.astype(jnp.int32))
                - jnp.sum(slot_active.astype(jnp.int32)))
    return _blank(ids, slot_active, overflow)


def scatter_slots(plan: TilePlan, values: jax.Array, num_tiles: int,
                  fill=0) -> jax.Array:
    """(R, ...) per-slot values -> (T, ...) per-tile, ``fill`` elsewhere.

    Inactive slots are masked to ``fill`` so padded slots never leak
    stale values into the per-tile view.
    """
    shape = (num_tiles,) + values.shape[1:]
    masked = jnp.where(
        plan.slot_active.reshape((-1,) + (1,) * (values.ndim - 1)),
        values, jnp.asarray(fill, values.dtype))
    return jnp.full(shape, fill, values.dtype).at[plan.tile_ids].set(masked)


def rerender_demand(active, overflow_tiles):
    """Per-frame re-render *demand*: tiles that wanted re-rendering.

    The exact inverse of ``sparse_plan``'s compaction: ``active`` (the
    ``FrameRecord.active`` flags, last axis T) counts the tiles that won a
    plan slot and ``overflow_tiles`` the Morton tail that was dropped to
    interpolation — their sum is the slot count an uncapped plan would
    have used. Works on stacked ``(F, ..., T)`` record arrays (jnp or
    numpy); the serving layer's ``serve.cache.suggest_capacity`` feeds
    quantiles of this into the bucketed-R executable choice.

    Dtype contract: the result is always int32 regardless of the inputs'
    dtypes (``overflow_tiles`` records arrive as whatever the engine
    stacked — int32 on device, sometimes int64/float via numpy on host),
    so host callers can read it with ``np.asarray`` and compare against
    bucket sizes without silent float truncation. Demand can never be
    negative, and T caps each frame's count, so int32 cannot overflow.
    """
    return (jnp.sum(jnp.asarray(active).astype(jnp.int32), axis=-1)
            + jnp.asarray(overflow_tiles).astype(jnp.int32))

