"""Per-tile binning + depth sort (the paper's "Sorting" stage, TPU-native).

GPU 3DGS builds dynamically-sized per-tile pair lists with a global radix
sort over (tileID | depth) keys. XLA needs static shapes, so both binners
here return fixed-capacity bins: per tile or plan slot, the indices of the
K nearest intersecting Gaussians in depth order, ties to the lower index,
pairs past K dropped but counted (DESIGN.md §3).

- ``bin_pair_list`` (the renderer's path for TAIT): one sort of a pair
  list of fixed length by (tile, depth rank), sliced into K-wide bins.
- ``build_tile_bins`` (the oracle): a ``top_k`` over a dense (N, R) mask
  per slot. The tests pin the pair list to it bit for bit; the ablation
  intersect methods and ``raster.render_from_bins`` bin through it.

Both are row-agnostic: the plan-driven renderer gets (R, K) compacted
bins for the TilePlan's R slots (DESIGN.md §2); the dense reference path
passes (N, T) and gets (T, K). The gather indices + validity mask are
what the Pallas rasterizer consumes; invalid lanes hold index 0.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.projection import ProjectedGaussians


class TileBins(NamedTuple):
    indices: jax.Array   # (T, K) int32 gaussian ids, depth-ascending
    valid: jax.Array     # (T, K) bool
    count: jax.Array     # (T,)  int32 number of valid entries (<= K)
    overflow: jax.Array  # (T,)  int32 pairs dropped because count > K
    capacity: int

    @property
    def total_pairs(self) -> jax.Array:
        return jnp.sum(self.count)


class TileGaussians(NamedTuple):
    """Per-tile gathered splat data — direct input to the rasterizer."""

    mean2d: jax.Array   # (T, K, 2)
    conic: jax.Array    # (T, K, 3)
    rgb: jax.Array      # (T, K, 3)
    opacity: jax.Array  # (T, K)
    depth: jax.Array    # (T, K)
    valid: jax.Array    # (T, K) bool


def build_tile_bins(mask_nt: jax.Array, depth: jax.Array, capacity: int,
                    *, depth_limit: jax.Array | None = None) -> TileBins:
    """Select and depth-sort up to ``capacity`` Gaussians per tile/slot.

    mask_nt: (N, T) intersection mask — or (N, R) for a plan's compacted
    slots; depth: (N,) camera z.
    depth_limit: optional (T,)/(R,) per-tile early-stop depth from DPES —
    pairs beyond it are culled *before* sorting (paper Sec. IV-B: "Any
    Gaussians beyond this depth will not be involved in sorting").
    """
    n = mask_nt.shape[0]
    mask_tn = mask_nt.T                                       # (T, N)
    if depth_limit is not None:
        mask_tn = mask_tn & (depth[None, :] <= depth_limit[:, None])
    key = jnp.where(mask_tn, depth[None, :], jnp.inf)         # (T, N)
    # Stable ascending sort: invalid entries (inf) sink to the end.
    neg_topk, idx = jax.lax.top_k(-key, min(capacity, n))     # (T, K)
    sorted_depth = -neg_topk
    valid = jnp.isfinite(sorted_depth)
    count_full = jnp.sum(mask_tn, axis=1).astype(jnp.int32)   # (T,)
    count = jnp.minimum(count_full, capacity).astype(jnp.int32)
    overflow = jnp.maximum(count_full - capacity, 0).astype(jnp.int32)
    return TileBins(indices=jnp.where(valid, idx, 0).astype(jnp.int32),
                    valid=valid, count=count, overflow=overflow,
                    capacity=capacity)


# Bits of a non-negative int32 sort key.
_KEY_BITS = 31


def sort_1d(operands, num_keys: int = 1, is_stable: bool = False):
    """``jax.lax.sort`` of 1-D operands, kept 1-D under ``vmap``.

    vmap would sort one (B, P) array along its last axis; a TPU v5e lays
    out a (1, P) array in (1, 128) tiles and sorts it ~6x slower than a
    (P,) one. Batched operands are sorted one row at a time instead.
    """
    @jax.custom_batching.custom_vmap
    def sort(*ops):
        return tuple(jax.lax.sort(ops, num_keys=num_keys,
                                  is_stable=is_stable))

    @sort.def_vmap
    def sort_rows(axis_size, in_batched, *ops):
        ops = tuple(o if b else jnp.broadcast_to(o, (axis_size,) + o.shape)
                    for o, b in zip(ops, in_batched))
        return (jax.lax.map(lambda row: sort(*row), ops),
                (True,) * len(ops))

    return sort(*operands)


def _lower_bound(a: jax.Array, lo: jax.Array, hi: jax.Array,
                 target: jax.Array) -> jax.Array:
    """Per query, the first index in ``[lo, hi)`` of ascending ``a`` whose
    value is >= ``target`` (``hi`` if none): a vectorized binary search."""
    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        more = lo < hi
        right = more & (a[jnp.minimum(mid, a.shape[0] - 1)] < target)
        return (jnp.where(right, mid + 1, lo),
                jnp.where(more & ~right, mid, hi))

    lo, _ = jax.lax.fori_loop(0, a.shape[0].bit_length(), step,
                              (jnp.broadcast_to(lo, target.shape),
                               jnp.broadcast_to(hi, target.shape)))
    return lo


def bin_pair_list(tile: jax.Array, group: jax.Array, rank: jax.Array,
                  gauss: jax.Array, *, num_groups: int, num_tiles: int,
                  num_gaussians: int, tile_ids: jax.Array,
                  slot_active: jax.Array, capacity: int,
                  rank_limit: Optional[jax.Array] = None
                  ) -> Tuple[TileBins, jax.Array]:
    """Bin a pair list into a plan's R slots with one sort.

    Per pair: its tile id; its group in ``[0, num_groups)``, group 0 the
    pairs to bin and the others only counted, ``num_groups`` for a slot
    of the list that holds no pair; its Gaussian's rank in ascending
    depth order, ties to the lower index; and its Gaussian index
    ``gauss``. Per slot: ``tile_ids`` and ``slot_active`` from the plan,
    and optionally ``rank_limit``, the DPES early stop as a rank (pairs
    of higher rank lie beyond the limit).

    The pairs are sorted once by (tile, group, rank), one int32 key when
    the three fit in 31 bits (at 1080p when N <= 2**16), else two keys,
    carrying the Gaussian index. Binary searches over the sorted keys
    find each tile's run of each group and the part of its group-0 run
    within the limit; a slot's bin is the first ``capacity`` entries of
    its tile's group-0 run. The bins equal ``build_tile_bins`` on the
    same pairs, bit for bit.

    Returns ``(bins, counts)``: the (R, K) bins and the (R, num_groups)
    pairs of each group per slot, 0 on inactive slots.
    """
    k = min(capacity, num_gaussians)
    end = num_tiles * num_groups             # major key past every run
    major = jnp.where(group < num_groups, tile * num_groups + group, end)
    rank_bits = max(1, (num_gaussians - 1).bit_length())
    if (end + 1) << rank_bits <= 2 ** _KEY_BITS:
        key, gauss = sort_1d(((major << rank_bits) | rank, gauss))
        major, rank = key >> rank_bits, key & ((1 << rank_bits) - 1)
    else:
        major, rank, gauss = sort_1d((major, rank, gauss), num_keys=2)

    edges = _lower_bound(major, 0, major.shape[0],
                         jnp.arange(end + 1, dtype=jnp.int32))
    runs = edges[:-1].reshape(num_tiles, num_groups)[tile_ids]   # (R, G)
    ends = edges[num_groups::num_groups][tile_ids]               # (R,)
    counts = jnp.diff(jnp.concatenate([runs, ends[:, None]], axis=1),
                      axis=1)
    counts = jnp.where(slot_active[:, None], counts, 0)
    start = runs[:, 0]
    within = counts[:, 0] if rank_limit is None else _lower_bound(
        rank, start, start + counts[:, 0],
        rank_limit.astype(jnp.int32)) - start
    count = jnp.minimum(within, capacity).astype(jnp.int32)
    overflow = jnp.maximum(within - capacity, 0).astype(jnp.int32)

    padded = jnp.concatenate([gauss, jnp.zeros((k,), gauss.dtype)])
    window = jax.vmap(lambda s: jax.lax.dynamic_slice(padded, (s,), (k,)))(
        start)                                                 # (R, K)
    valid = jnp.arange(k)[None, :] < count[:, None]
    bins = TileBins(indices=jnp.where(valid, window, 0).astype(jnp.int32),
                    valid=valid, count=count, overflow=overflow,
                    capacity=capacity)
    return bins, counts


def gather_tiles(proj: ProjectedGaussians, bins: TileBins) -> TileGaussians:
    """Gather per-tile splat attributes. (T, K, ...)."""
    idx = bins.indices
    return TileGaussians(
        mean2d=proj.mean2d[idx], conic=proj.conic[idx], rgb=proj.rgb[idx],
        opacity=jnp.where(bins.valid, proj.opacity[idx], 0.0),
        # NOTE: invalid entries get depth 0 (not inf): they blend with w=0 and
        # 0 * inf would poison the depth accumulators with NaN.
        depth=jnp.where(bins.valid, proj.depth[idx], 0.0),
        valid=bins.valid)
