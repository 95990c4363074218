"""Gaussian-tile intersection tests (paper Sec. IV-C).

Four tests over the same (N gaussians x T tiles) domain, all returning a
boolean mask (N, T). Every test reads only ``origins``/``centers`` from
the grid argument, so they equally accept a compacted ``TileSlots`` view
(``take_tiles``) and then return a plan-shaped (N, R) mask — this is how
the plan-driven renderer (core/pipeline.py) makes sparse-frame intersect
cost scale with the re-render slot count R instead of T:

- ``aabb_mask``    : original 3DGS — circumscribed square of the 3-sigma
                     circle (coarse baseline, many false positives).
- ``obb_mask``     : GSCore-style oriented-bounding-box separating-axis test
                     (comparison point in Fig. 9).
- ``tait_mask``    : the paper's two-stage test — opacity-aware tight bbox
                     (stage 1, eqs. 4+6) then the single minor-axis distance
                     rejection (stage 2, eq. 7).
- ``exact_mask``   : analytic ellipse-vs-rectangle oracle (FlashGS-class
                     accuracy) used for validation and Fig. 9's lower bound.

Note on eq. (7): as printed, ``|l| cos(theta) + r > R_minor`` would reject
tiles whose centers lie within one tile-circumradius *inside* the ellipse
boundary, i.e. it can drop true intersections. We implement the safe
(conservative) form ``|l| cos(theta) - r > R_minor`` => reject, which keeps
TAIT a superset of the exact test; the property test
``tests/test_intersect.py::test_tait_between_exact_and_aabb`` enforces it.
This sign choice is recorded in DESIGN.md §3.

``tait_pairs`` is TAIT as a pair list instead of a mask: each Gaussian's
stage-1 rectangle enumerated into a fixed budget of (Gaussian, tile)
slots, stage 2 tested per pair. Both forms call the same elementwise
tests (``_overlap``, ``_minor_keep``), so the pairs are exactly the
mask's true entries (DESIGN.md §3).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import binning
from repro.core.camera import TILE, Camera
from repro.core.projection import ProjectedGaussians
from repro.obs.trace import annotate

# Circumcircle radius of a 16x16 tile (r in eq. 7).
TILE_CIRCUMRADIUS = float(TILE) * (2.0 ** 0.5) / 2.0


class TileGrid(NamedTuple):
    tiles_x: int
    tiles_y: int
    centers: jax.Array  # (T, 2) pixel coords of tile centers
    origins: jax.Array  # (T, 2) pixel coords of tile upper-left corners

    @property
    def num_tiles(self) -> int:
        return self.tiles_x * self.tiles_y


class TileSlots(NamedTuple):
    """Compacted view of R plan slots — duck-typed grid for the tests."""

    centers: jax.Array  # (R, 2) pixel coords of slot tile centers
    origins: jax.Array  # (R, 2) pixel coords of slot tile upper-left


def take_tiles(grid: TileGrid, tile_ids: jax.Array) -> TileSlots:
    """Gather the grid rows of a plan's tile ids: (T,)-world -> (R,)-world."""
    return TileSlots(centers=grid.centers[tile_ids],
                     origins=grid.origins[tile_ids])


def make_tile_grid(cam: Camera) -> TileGrid:
    tx = jnp.arange(cam.tiles_x, dtype=jnp.float32) * TILE
    ty = jnp.arange(cam.tiles_y, dtype=jnp.float32) * TILE
    ox, oy = jnp.meshgrid(tx, ty, indexing="xy")
    origins = jnp.stack([ox.ravel(), oy.ravel()], axis=-1)       # (T, 2)
    centers = origins + TILE / 2.0
    return TileGrid(cam.tiles_x, cam.tiles_y, centers, origins)


def _overlap(lo_x, lo_y, hi_x, hi_y, ox, oy) -> jax.Array:
    """Rectangle [lo, hi] vs the tile with upper-left corner (ox, oy).

    Elementwise over broadcast (gaussian, tile) inputs: the dense mask
    is this test, and ``tile_rects`` solves it for each Gaussian's
    rectangle of tiles.
    """
    return ((lo_x < ox + TILE) & (hi_x > ox)
            & (lo_y < oy + TILE) & (hi_y > oy))


def _minor_keep(dx, dy, minor_x, minor_y, r_minor) -> jax.Array:
    """TAIT stage 2, elementwise: keep unless the component of (tile
    center - ellipse center) along the minor axis exceeds R_minor + the
    tile circumradius (the safe form of eq. 7).

    Elementwise, not an einsum: it fuses into its consumer instead of
    materialising an (N, T, 2) float tensor (4.3 GB at 65,536 Gaussians
    x 1080p), and stays f32 where a TPU dot would round to bf16.
    """
    along_minor = jnp.abs(dx * minor_x + dy * minor_y)
    return along_minor - TILE_CIRCUMRADIUS <= r_minor


def _rect_overlap(mean2d, half_wh, grid: TileGrid) -> jax.Array:
    """Axis-aligned rectangle (center, half-extent) vs every tile. (N, T)."""
    lo = mean2d - half_wh                                       # (N, 2)
    hi = mean2d + half_wh
    return _overlap(lo[:, 0:1], lo[:, 1:2], hi[:, 0:1], hi[:, 1:2],
                    grid.origins[None, :, 0], grid.origins[None, :, 1])


def aabb_mask(proj: ProjectedGaussians, grid: TileGrid) -> jax.Array:
    """Original 3DGS test: square of half-extent 3*sqrt(lambda1). (N, T)."""
    r = proj.radius3[:, None]
    half = jnp.concatenate([r, r], axis=-1)
    return _rect_overlap(proj.mean2d, half, grid) & proj.valid[:, None]


def tait_stage1_mask(proj: ProjectedGaussians, grid: TileGrid) -> jax.Array:
    """Stage 1: opacity-aware tight bbox of the effective ellipse. (N, T)."""
    return _rect_overlap(proj.mean2d, proj.tight_half_wh, grid) & proj.valid[:, None]


def tait_mask(proj: ProjectedGaussians, grid: TileGrid) -> jax.Array:
    """Full two-stage TAIT test (stage 1 bbox, then eq. 7 rejection)."""
    stage1 = tait_stage1_mask(proj, grid)
    dx = grid.centers[None, :, 0] - proj.mean2d[:, 0:1]          # (N, T)
    dy = grid.centers[None, :, 1] - proj.mean2d[:, 1:2]
    return stage1 & _minor_keep(dx, dy, proj.minor_axis[:, 0:1],
                                proj.minor_axis[:, 1:2],
                                proj.r_minor[:, None])


def tile_rects(mean2d: jax.Array, half_wh: jax.Array, valid: jax.Array,
               tiles_x: int, tiles_y: int) -> Tuple[jax.Array, jax.Array]:
    """Each Gaussian's stage-1 tile rectangle on the grid. (N, 2) each.

    Returns ``(first, extent)``: the first tile column and row, and how
    many columns and rows the bbox (center ``mean2d``, half-extent
    ``half_wh``) covers, 0 where ``valid`` is False or the bbox is off the
    grid. ``_overlap`` holds for tile column i iff ``lo < TILE * (i + 1)``
    and ``hi > TILE * i``, i.e. for ``floor(lo / TILE) <= i <=
    ceil(hi / TILE) - 1``; division by TILE is exact in float32, so the
    rectangle is the mask's row exactly.
    """
    lo = mean2d - half_wh
    hi = mean2d + half_wh
    last_tile = jnp.array([tiles_x - 1, tiles_y - 1], jnp.float32)
    first = jnp.maximum(jnp.floor(lo / TILE), 0.0)
    last = jnp.minimum(jnp.ceil(hi / TILE) - 1.0, last_tile)
    extent = jnp.where(valid[:, None],
                       jnp.maximum(last - first + 1.0, 0.0), 0.0)
    first = jnp.minimum(first, last_tile)     # keeps the cast in range
    return first.astype(jnp.int32), extent.astype(jnp.int32)


# Saturation point of the running pair count: far above any budget, and
# a sum of two saturated counts still fits in int32.
_COUNT_CAP = 2 ** 30


class TilePairs(NamedTuple):
    """(Gaussian, tile) pairs from ``tait_pairs``, P = the pair budget."""

    rank: jax.Array     # (P,) int32 the Gaussian's position in depth order
    gauss: jax.Array    # (P,) int32 Gaussian index
    tile: jax.Array     # (P,) int32 tile id (meaningless where ~stage1)
    stage1: jax.Array   # (P,) bool — a real stage-1 pair (not padding)
    hit: jax.Array      # (P,) bool — passes both TAIT stages
    extra: jax.Array    # (E, P) float32 the caller's per-Gaussian rows
    depth: jax.Array    # (N,) float32 depths in ascending (rank) order
    dropped: jax.Array  # () int32 stage-1 pairs past the budget
    total: jax.Array    # () int32 stage-1 pairs, the budget's demand


def _spread(rows: jax.Array, start: jax.Array, budget: int) -> jax.Array:
    """(F, N) int32 per-Gaussian rows -> (F, budget) per pair slot.

    Gaussian g owns the slots from ``start[g]`` to the next Gaussian's
    start. Each row's differences between consecutive Gaussians are
    scattered to the starts and summed up along the slots: int32 sums
    wrap, so every slot reads its owner's value exactly. On a TPU v5e
    that is ~2 ms a row at 8.4 M slots, where a gather by owner takes
    ~72 ms.
    """
    diff = rows - jnp.pad(rows[:, :-1], ((0, 0), (1, 0)))
    runs = jnp.zeros((rows.shape[0], budget), jnp.int32).at[:, start].add(
        diff, mode="drop")
    return jnp.cumsum(runs, axis=1)


def _bits(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def _floats(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def tait_pairs(proj: ProjectedGaussians, grid: TileGrid, budget: int,
               extra: Optional[jax.Array] = None) -> TilePairs:
    """TAIT over the whole grid as a list of ``budget`` pair slots.

    Each Gaussian's stage-1 rectangle (``tile_rects``) takes a contiguous
    run of slots, row-major, at the exclusive running sum of rectangle
    areas in Gaussian order, and stage 2 is tested per pair. Rectangles
    past the budget lose their tail pairs, the highest-indexed Gaussians'
    first, counted in ``dropped``; ``total`` counts every stage-1 pair
    (both saturating at ``_COUNT_CAP``). Each
    pair carries its Gaussian's rank in ascending depth, ties to the
    lower index: the order ``top_k`` ranks in. ``extra`` (E, N),
    optional, holds float rows the caller wants per pair
    (``TilePairs.extra``).
    """
    if not 0 < budget <= _COUNT_CAP - grid.num_tiles:
        raise ValueError(f"pair budget {budget} out of range")
    tx = grid.tiles_x
    if grid.num_tiles * (tx + 1) >= 2 ** 31:
        raise ValueError(f"{grid.num_tiles} tiles are too many to pack")
    n = proj.depth.shape[0]
    with annotate("repro.frame/rank"):
        _, order = binning.sort_1d(
            (proj.depth, jnp.arange(n, dtype=jnp.int32)), is_stable=True)
        rank = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
    first, extent = tile_rects(proj.mean2d, proj.tight_half_wh, proj.valid,
                               tx, grid.tiles_y)
    area = extent[:, 0] * extent[:, 1]
    end = jax.lax.associative_scan(
        lambda a, b: jnp.minimum(a + b, _COUNT_CAP), area)
    start = end - area
    # The rectangle as one int: its first tile's id and its width.
    rect = (first[:, 1] * tx + first[:, 0]) * (tx + 1) + extent[:, 0]
    floats = (proj.mean2d[:, 0], proj.mean2d[:, 1], proj.minor_axis[:, 0],
              proj.minor_axis[:, 1], proj.r_minor)
    if extra is not None:
        floats += tuple(extra)
    rows = jnp.stack([jnp.arange(n, dtype=jnp.int32), rank, start, rect]
                     + [_bits(v) for v in floats])
    gauss, rank, first_slot, rect, mx, my, ux, uy, r_minor, *extra_rows = \
        _spread(rows, start, budget)

    p = jnp.arange(budget, dtype=jnp.int32)
    q = p - first_slot                           # index inside the rect
    width = jnp.maximum(rect % (tx + 1), 1)
    base = rect // (tx + 1)
    row = q // width
    col = base % tx + q - row * width
    row = base // tx + row
    dx = (col.astype(jnp.float32) * TILE + TILE / 2.0) - _floats(mx)
    dy = (row.astype(jnp.float32) * TILE + TILE / 2.0) - _floats(my)
    keep = _minor_keep(dx, dy, _floats(ux), _floats(uy), _floats(r_minor))
    stage1 = p < end[-1]
    return TilePairs(
        rank=rank, gauss=gauss, tile=row * tx + col, stage1=stage1,
        hit=stage1 & keep,
        extra=jnp.stack([_floats(v) for v in extra_rows]) if extra_rows
        else jnp.zeros((0, budget), jnp.float32),
        depth=proj.depth[order], dropped=jnp.maximum(end[-1] - budget, 0),
        total=end[-1])


def obb_mask(proj: ProjectedGaussians, grid: TileGrid) -> jax.Array:
    """GSCore-style OBB vs tile square, separating-axis theorem. (N, T).

    OBB axes = ellipse eigenvectors with half-extents (R_major, R_minor);
    tile axes = x/y with half-extent TILE/2. Four candidate separating axes.
    """
    minor = proj.minor_axis                                     # (N, 2)
    major = jnp.stack([-minor[:, 1], minor[:, 0]], axis=-1)     # perpendicular
    d = grid.centers[None, :, :] - proj.mean2d[:, None, :]      # (N, T, 2)
    half_t = TILE / 2.0
    rmaj = proj.r_major[:, None]
    rmin = proj.r_minor[:, None]

    # Axis 1: image x. OBB projects to |maj_x|*rmaj + |min_x|*rmin.
    obb_px = jnp.abs(major[:, 0:1]) * rmaj + jnp.abs(minor[:, 0:1]) * rmin
    sep_x = jnp.abs(d[..., 0]) > (obb_px + half_t)
    # Axis 2: image y.
    obb_py = jnp.abs(major[:, 1:2]) * rmaj + jnp.abs(minor[:, 1:2]) * rmin
    sep_y = jnp.abs(d[..., 1]) > (obb_py + half_t)
    # Axis 3: ellipse major axis. Tile projects to half_t*(|ax|+|ay|).
    tile_pm = half_t * (jnp.abs(major[:, 0:1]) + jnp.abs(major[:, 1:2]))
    sep_maj = jnp.abs(jnp.einsum("ntc,nc->nt", d, major)) > (rmaj + tile_pm)
    # Axis 4: ellipse minor axis.
    tile_pn = half_t * (jnp.abs(minor[:, 0:1]) + jnp.abs(minor[:, 1:2]))
    sep_min = jnp.abs(jnp.einsum("ntc,nc->nt", d, minor)) > (rmin + tile_pn)

    separated = sep_x | sep_y | sep_maj | sep_min
    return (~separated) & proj.valid[:, None]


def exact_mask(proj: ProjectedGaussians, grid: TileGrid) -> jax.Array:
    """Analytic oracle: does the effective ellipse touch the tile rectangle?

    The effective ellipse is {p : (p-mu)^T Sigma^-1 (p-mu) <= rho2} with
    rho2 = 2 ln(o / tau) (matching eq. 4's radii). A rectangle intersects
    iff the minimum of the quadratic over the rectangle is <= rho2. The
    minimum is attained at the center (if inside the rect) or on one of the
    four edges; each edge minimum has a closed form (clamped 1D quadratic).
    """
    mu = proj.mean2d                                           # (N, 2)
    con_a, con_b, con_c = proj.conic[:, 0], proj.conic[:, 1], proj.conic[:, 2]
    opac = proj.opacity
    rho2 = 2.0 * jnp.log(jnp.maximum(opac / (1.0 / 255.0), 1.0 + 1e-6))

    lo = grid.origins                                           # (T, 2)
    hi = grid.origins + TILE

    def quad(dx, dy):
        return con_a[:, None] * dx * dx + 2.0 * con_b[:, None] * dx * dy \
            + con_c[:, None] * dy * dy

    # Center inside rectangle -> minimum is 0.
    inside = ((mu[:, None, 0] >= lo[None, :, 0]) & (mu[:, None, 0] <= hi[None, :, 0])
              & (mu[:, None, 1] >= lo[None, :, 1]) & (mu[:, None, 1] <= hi[None, :, 1]))

    # Edge minima. For a vertical edge x = x0, y in [y0, y1]:
    # minimize A dx^2 + 2B dx dy + C dy^2 over dy => dy* = -B dx / C, clamp.
    def vedge(x0):
        dx = x0[None, :] - mu[:, 0:1]                           # (N, T)
        dy_star = -con_b[:, None] * dx / jnp.maximum(con_c[:, None], 1e-12)
        dy = jnp.clip(dy_star, lo[None, :, 1] - mu[:, 1:2],
                      hi[None, :, 1] - mu[:, 1:2])
        return quad(dx, dy)

    def hedge(y0):
        dy = y0[None, :] - mu[:, 1:2]
        dx_star = -con_b[:, None] * dy / jnp.maximum(con_a[:, None], 1e-12)
        dx = jnp.clip(dx_star, lo[None, :, 0] - mu[:, 0:1],
                      hi[None, :, 0] - mu[:, 0:1])
        return quad(dx, dy)

    qmin = jnp.minimum(jnp.minimum(vedge(lo[:, 0]), vedge(hi[:, 0])),
                       jnp.minimum(hedge(lo[:, 1]), hedge(hi[:, 1])))
    qmin = jnp.where(inside, 0.0, qmin)
    return (qmin <= rho2[:, None]) & proj.valid[:, None]


def pair_count(mask: jax.Array) -> jax.Array:
    """Total Gaussian-tile pairs a test admits (Fig. 9 metric)."""
    return jnp.sum(mask.astype(jnp.int32))


def per_tile_count(mask: jax.Array) -> jax.Array:
    """(T,) pairs per tile — the tile workload before DPES."""
    return jnp.sum(mask.astype(jnp.int32), axis=0)


def intersect(proj: ProjectedGaussians, grid: TileGrid, method: str) -> jax.Array:
    fns = {"aabb": aabb_mask, "obb": obb_mask, "tait": tait_mask,
           "tait_stage1": tait_stage1_mask, "exact": exact_mask}
    return fns[method](proj, grid)
