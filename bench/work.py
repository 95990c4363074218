"""Operations and bytes of the raster stage, counted from its shapes.

The raster stage turns each planned tile's depth-ordered Gaussians into
its 16 x 16 pixels. Its work is counted the same way whatever implements
it, from the frame records the engine returns:

- operations: every pair a tile traverses (``raster_pairs``) is blended
  into each of the tile's 256 pixels at ``OPS_PER_BLEND`` operations;
- bytes read: each pair entering the stage (``sort_pairs``) is read once
  as ``ATTR_BYTES`` of attributes, and each planned tile reads its pair
  count and origin;
- bytes written: each planned tile writes ``PIXEL_BYTES`` per pixel.

An in-kernel depth sort is not counted: the binning already orders the
pairs, so a kernel that sorts again does work the stage does not need.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

TILE_PIXELS = 16 * 16
# Per (pixel, pair): offset (2 subtractions), the quadratic form
# (dx^2, dy^2, dx dy, three products with the conic, two adds, one
# scale: 9), exp (1), times opacity (1), clamp to 0.99 (1), the 1/255
# test (1), 1 - alpha and T (1 - alpha) (2), the 1e-4 test (1), the
# weight alpha T (1), colour accumulation (3 multiply-adds: 6), depth
# accumulation (2) and weight sum (2).
OPS_PER_BLEND = 29
# mean (2), conic (3), colour (3), opacity and depth, float32.
ATTR_BYTES = 10 * 4
# Pair count (int32) and tile origin (2 float32) per planned tile.
TILE_HEADER_BYTES = 3 * 4
# rgb, transmittance, expected depth, truncated depth, float32.
PIXEL_BYTES = 6 * 4


def frame_records(result) -> List[dict]:
    """Per-frame counts of one serve round (a ``StreamsResult``)."""
    recs = result.records
    active = np.asarray(result.frame_active).reshape(-1)
    raster = np.asarray(recs.raster_pairs)
    sort = np.asarray(recs.sort_pairs)
    tiles = np.asarray(recs.active)
    full = np.asarray(recs.is_full).reshape(-1)
    t = raster.shape[-1]
    raster, sort, tiles = (a.reshape(-1, t) for a in (raster, sort, tiles))
    return [{"is_full": bool(full[i]),
             "raster_pairs": int(raster[i].sum()),
             "sort_pairs": int(sort[i].sum()),
             "tiles": int(tiles[i].sum())}
            for i in np.flatnonzero(active)]


def raster_ops(records: List[dict]) -> float:
    return float(OPS_PER_BLEND * TILE_PIXELS *
                 sum(r["raster_pairs"] for r in records))


def raster_bytes(records: List[dict]) -> float:
    return float(sum(ATTR_BYTES * r["sort_pairs"]
                     + (TILE_HEADER_BYTES + PIXEL_BYTES * TILE_PIXELS)
                     * r["tiles"] for r in records))


def least_time(records: List[dict], peaks: dict) -> Tuple[float, str]:
    """The raster stage's least time on a chip with ``peaks``, and the
    bound that sets it (``compute`` or ``memory``)."""
    t_ops = raster_ops(records) / float(peaks["flops_per_s"])
    t_mem = raster_bytes(records) / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
