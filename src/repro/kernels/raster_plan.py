"""Fused Pallas TPU kernel: per-slot depth sort + raster in one pass.

This is the plan-slot production kernel (DESIGN.md §9): one grid step per
TilePlan slot; the slot's K compacted Gaussians are loaded into VMEM
once, depth-sorted by a bitonic network (the full attribute record rides
the compare-exchanges as the payload), and immediately alpha-blended
chunk by chunk — keys and values never leave VMEM between the sort and
the raster, which is the paper's no-HBM-roundtrip streaming contract.

Input contract (see DESIGN.md §9):
  - each slot's ``count`` real pairs occupy lanes ``[0, count)`` in ANY
    depth order; lanes past ``count`` are padding (ignored — the sort
    keys them +inf and the blend masks their opacity to 0);
  - ``slot_active`` False implies ``count == 0`` on the plan path
    (pipeline masks intersections by ``plan.slot_active`` before
    binning); the wrapper enforces the conjunction either way.

TPU layout (what Mosaic accepts):
  - per-slot scalars (the effective pair count and the tile origin) are
    scalar-prefetched into SMEM;
  - the slot's attributes arrive as one ``(ROWS, K)`` block, K on the
    128-lane axis, one attribute per sublane row (``_ROW``);
  - the sort exchanges lanes i and i^stride with two lane rotations
    (``pltpu.roll``) and a select — no gathers, no reshapes;
  - the sorted block is split into ``(n_chunks, ROWS, G)`` VMEM scratch
    so the blend loop reads chunk i with a leading-axis index;
  - per-pixel results leave as one lane-dense ``(8, P)`` block and the
    per-lane contribution plus sort permutation as one ``(2, K)`` block.

Masked / empty slots cost ~nothing: the bitonic network is gated behind
``pl.when(count > 0)`` and the blend ``while_loop`` runs zero chunks.

VMEM per slot at K=1024, G=64: the (16, K) block is 64 KiB, held twice
(input and sorted scratch) plus its chunked copy, and the blend's
(256, G) intermediates.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.camera import TILE
from repro.kernels.raster_tile import ALPHA_MAX, ALPHA_MIN, T_EPS

# Sublane row of each attribute in the per-slot (ROWS, K) block. Rows
# 0-7 double as the blend's accumulation matrix: w @ block[:8].T gives
# colour, w*depth and w (the ONE row) in one MXU pass. SRC is the lane's
# original index, written in-kernel: it rides the sort so the wrapper can
# report contributions in input lane order.
_ROW = dict(r=0, g=1, b=2, depth=3, one=4, opac=5, mx=6, my=7, ca=8, cb=9,
            cc=10, src=11)
ROWS = 16          # attribute rows, padded to a multiple of 8 sublanes
_ACC = 8           # accumulator columns: r, g, b, w*depth, w, (unused)
_PIX_ROWS = 8      # rgb(3), T, exp depth, trunc depth, processed, pad
_HI = jax.lax.Precision.HIGHEST


class BlendState(NamedTuple):
    """Per-pixel blend state: ``acc`` (P, 8), the rest (P, 1) columns."""

    acc: jax.Array      # columns per _ROW[:8]: sum of w * row
    t_run: jax.Array    # transmittance after the blended prefix
    done: jax.Array     # 1.0 once the pixel early-stopped (sticky)
    td_max: jax.Array   # deepest blended Gaussian (truncated depth)


def init_blend_state(p: int) -> BlendState:
    z = jnp.zeros((p, 1), jnp.float32)
    return BlendState(jnp.zeros((p, _ACC), jnp.float32),
                      jnp.ones((p, 1), jnp.float32), z, z)


def pixel_centers(origin_x, origin_y, tile: int):
    """(P, 1) pixel-centre columns of a tile, row-major pixel order."""
    pid = jax.lax.broadcasted_iota(jnp.int32, (tile * tile, 1), 0)
    iy = pid // tile
    ix = pid - iy * tile
    return (ix.astype(jnp.float32) + origin_x + 0.5,
            iy.astype(jnp.float32) + origin_y + 0.5)


def _shift_in(x, d: int, roll):
    """x shifted right by d along the lane axis, ones shifted in."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= d, roll(x, d), 1.0)


def blend_chunk(px, py, blk, st: BlendState, roll):
    """Front-to-back blend of one G-chunk of depth-sorted Gaussians.

    ``px``/``py`` are (P, 1) pixel centres, ``blk`` the chunk's
    (ROWS, G) attribute block (``_ROW`` layout), ``roll(x, shift)``
    rotates the lane axis with ``jnp.roll`` semantics. Returns the new
    state and the chunk's per-lane contribution (1, G): the sum of blend
    weights over the tile's pixels.

    The fused kernel and the ``jnp_chunked`` mirror (kernels/ops.py) both
    call this, so they agree to the last bit on CPU. The transmittance
    prefix is a Hillis-Steele product (log2 G lane rotations, no
    cumprod). The per-pixel sums are one f32 matmul: unlike an XLA
    reduction, its summation order does not move with the surrounding
    fusion, so a frame's pixels do not depend on which outputs the
    caller keeps.
    """
    a = {name: blk[r:r + 1] for name, r in _ROW.items()}   # (1, G) rows
    dx = px - a["mx"]                               # (P, G)
    dy = py - a["my"]
    power = (-0.5 * (a["ca"] * dx * dx + a["cc"] * dy * dy)
             - a["cb"] * dx * dy)
    alpha = jnp.minimum(a["opac"] * jnp.exp(power), ALPHA_MAX)
    alpha = jnp.where(alpha >= ALPHA_MIN, alpha, 0.0)

    cp = 1.0 - alpha                                # inclusive prefix
    d = 1
    while d < cp.shape[1]:
        cp = cp * _shift_in(cp, d, roll)
        d *= 2
    tp = st.t_run * cp                              # T after blending j
    t_before = st.t_run * _shift_in(cp, 1, roll)
    # tp is monotone within the chunk, so (tp >= eps) is exactly the
    # sequential sticky-done prefix; `done` carries stickiness across
    # chunks (CUDA drops the triggering Gaussian and never blends that
    # pixel again).
    blend = (tp >= T_EPS) & (st.done == 0.0)
    w = jnp.where(blend, alpha * t_before, 0.0)     # (P, G)

    acc = st.acc + jax.lax.dot_general(
        w, blk[:_ACC], (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)         # (P, 8) MXU
    last = tp[:, tp.shape[1] - 1:]
    new = BlendState(
        acc=acc,
        t_run=jnp.min(jnp.where(blend, tp, st.t_run), axis=1,
                      keepdims=True),
        done=jnp.maximum(st.done, jnp.where(last < T_EPS, 1.0, 0.0)),
        td_max=jnp.maximum(st.td_max, jnp.max(
            jnp.where(blend & (alpha > 0.0), a["depth"], 0.0), axis=1,
            keepdims=True)))
    return new, jnp.sum(w, axis=0, keepdims=True)


def finish_blend(st: BlendState):
    """(rgb (P, 3), T, expected depth, truncated depth) from the state."""
    ed = st.acc[:, _ROW["depth"]:_ROW["depth"] + 1] / jnp.maximum(
        st.acc[:, _ROW["one"]:_ROW["one"] + 1], 1e-8)
    return st.acc[:, 0:3], st.t_run, ed, st.td_max


def _lane_roll(x, shift):
    return pltpu.roll(x, shift, 1)


def _bitonic_sort(x, k: int):
    """Ascending bitonic sort of the (ROWS, k) block by (depth, SRC).

    Lanes i and i^stride meet through two lane rotations; the whole
    record swaps with its key. A bitonic network is not stable, so equal
    depths are ordered by their input lane (the SRC row): the output is
    then unique, and depth-sorted input (the binning's order) comes out
    unchanged, ties included.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    d, s = _ROW["depth"], _ROW["src"]
    span = 2
    while span <= k:
        up = (lane & span) == 0
        stride = span // 2
        while stride >= 1:
            upper = (lane & stride) != 0        # partner is lane - stride
            partner = jnp.where(upper, _lane_roll(x, stride),
                                _lane_roll(x, k - stride))
            key, pkey = x[d:d + 1], partner[d:d + 1]
            src, psrc = x[s:s + 1], partner[s:s + 1]
            tie = pkey == key
            before = (pkey < key) | (tie & (psrc < src))
            after = (pkey > key) | (tie & (psrc > src))
            keep_min = upper != up      # (a select of bools fails Mosaic)
            take = (keep_min & before) | (~keep_min & after)
            x = jnp.where(take, partner, x)
            stride //= 2
        span *= 2
    return x


def _fused_kernel(count_ref, origin_ref, attr_ref, pix_out, lane_out,
                  sorted_scr, chunk_scr, contrib_scr, *, k: int, chunk: int,
                  tile: int):
    s = pl.program_id(0)
    count = count_ref[s]
    n_chunks = k // chunk
    p = tile * tile

    # ---- GSU: bitonic depth sort over the slot's K lanes (in VMEM) ----
    # Padding lanes get +inf keys and zero opacity so they sink to the
    # end and blend nothing; SRC rows record each lane's input index.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, k), 0)
    pad = lane >= count
    x = attr_ref[0]
    x = jnp.where(row == _ROW["src"], lane.astype(jnp.float32), x)
    x = jnp.where((row == _ROW["depth"]) & pad, jnp.inf, x)
    x = jnp.where((row == _ROW["opac"]) & pad, 0.0, x)
    sorted_scr[...] = x

    @pl.when(count > 0)
    def _():
        sorted_scr[...] = _bitonic_sort(sorted_scr[...], k)

    # Sorted depth: padding -> 0 (not inf): it blends with w=0 and
    # 0 * inf would NaN the depth accumulators.
    y = sorted_scr[...]
    y = jnp.where((row == _ROW["depth"]) & pad, 0.0, y)
    for c in range(n_chunks):
        chunk_scr[c] = y[:, c * chunk:(c + 1) * chunk]
    contrib_scr[...] = jnp.zeros_like(contrib_scr)

    # ---- VRU: chunked front-to-back blend ----
    px, py = pixel_centers(origin_ref[2 * s], origin_ref[2 * s + 1], tile)
    used_chunks = jnp.minimum((count + chunk - 1) // chunk, n_chunks)

    def body(carry):
        i, st = carry
        st, contrib = blend_chunk(px, py, chunk_scr[i], BlendState(*st),
                                  _lane_roll)
        contrib_scr[i] = contrib
        return i + 1, tuple(st)

    def cond(carry):
        i, st = carry
        return (i < used_chunks) & (jnp.min(BlendState(*st).done) == 0.0)

    n_done, st = jax.lax.while_loop(
        cond, body, (jnp.int32(0), tuple(init_blend_state(p))))
    rgb, trans, exp_depth, trunc_depth = finish_blend(BlendState(*st))

    # Per-pixel results as (P, 1) columns -> one lane-dense (8, P) block.
    processed = jnp.minimum(n_done * chunk, count)
    cols = (rgb[:, 0:1], rgb[:, 1:2], rgb[:, 2:3], trans, exp_depth,
            trunc_depth,
            jnp.full((p, 1), processed, jnp.int32).astype(jnp.float32))
    lanes128 = jax.lax.broadcasted_iota(jnp.int32, (p, 128), 1)
    packed = jnp.zeros((p, 128), jnp.float32)
    for j, col in enumerate(cols):
        packed = jnp.where(lanes128 == j, col, packed)
    pix_out[0] = packed.T[:_PIX_ROWS]

    for c in range(n_chunks):
        lane_out[0, 0:1, c * chunk:(c + 1) * chunk] = contrib_scr[c]
    lane_out[0, 1:2, :] = y[_ROW["src"]:_ROW["src"] + 1]


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pack_attributes(mean2d, conic, rgb, opacity, depth, k_pad: int):
    """(..., K, c) per-lane attributes -> (..., ROWS, k_pad) blocks."""
    lead = opacity.shape[:-1]
    k = opacity.shape[-1]
    planes = {"depth": depth, "opac": opacity, "one": jnp.ones_like(depth),
              "mx": mean2d[..., 0], "my": mean2d[..., 1],
              "ca": conic[..., 0], "cb": conic[..., 1], "cc": conic[..., 2],
              "r": rgb[..., 0], "g": rgb[..., 1], "b": rgb[..., 2]}
    rows = [jnp.zeros(lead + (k,), jnp.float32)] * ROWS
    for name, plane in planes.items():
        rows[_ROW[name]] = plane.astype(jnp.float32)
    packed = jnp.stack(rows, axis=-2)
    pad = [(0, 0)] * (packed.ndim - 1) + [(0, k_pad - k)]
    return jnp.pad(packed, pad)


def raster_plan_fused(mean2d, conic, rgb, opacity, depth, origins, counts,
                      slot_active=None, *, chunk: int = 64, tile: int = TILE,
                      interpret: bool = True):
    """Fused sort+raster over plan slots. Inputs (R, K, ...) compacted bins.

    Per-slot lanes need NOT be depth-sorted — the kernel sorts (that is
    the point); they must be packed (real pairs first, see module
    docstring). ``slot_active`` (R,) bool gates whole slots (default:
    ``counts > 0``). K is padded to a power of two internally; ``chunk``
    must be a power of two (so it divides the padded K).

    Returns rgb (R, tile, tile, 3), trans, exp_depth, trunc_depth (each
    (R, tile, tile)), processed (R,) int32, lane_contrib (R, K) float32.
    The contribution is reported in INPUT lane order even though the
    kernel blends in sorted order: the original lane index rides the sort
    as one more payload row and the inverse permutation is applied by
    scatter out here, so the kernel itself stays gather/scatter-free.
    Masked slots skip the sort (identity permutation) and report zeros.
    """
    r, k = opacity.shape
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} must be a power of two")
    counts = counts.astype(jnp.int32)
    if slot_active is not None:
        counts = jnp.where(slot_active, counts, 0)

    k_pad = _pow2_at_least(max(k, chunk))
    p = tile * tile
    attrs = pack_attributes(mean2d, conic, rgb, opacity, depth, k_pad)
    kernel = functools.partial(_fused_kernel, k=k_pad, chunk=chunk,
                               tile=tile)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(r,),
        in_specs=[pl.BlockSpec((1, ROWS, k_pad), lambda i, *_: (i, 0, 0))],
        out_specs=(
            pl.BlockSpec((1, _PIX_ROWS, p), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 2, k_pad), lambda i, *_: (i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((ROWS, k_pad), f32),
            pltpu.VMEM((k_pad // chunk, ROWS, chunk), f32),
            pltpu.VMEM((k_pad // chunk, 1, chunk), f32),
        ])
    pix, lanes = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((r, _PIX_ROWS, p), f32),
                   jax.ShapeDtypeStruct((r, 2, k_pad), f32)),
        interpret=interpret,
    )(counts, origins.astype(f32).reshape(-1), attrs)

    rgb_o = jnp.swapaxes(pix[:, 0:3], 1, 2).reshape(r, tile, tile, 3)
    trans_o, depth_o, tdepth_o = (pix[:, j].reshape(r, tile, tile)
                                  for j in (3, 4, 5))
    processed_o = pix[:, 6, 0].astype(jnp.int32)
    # Undo the in-kernel sort: SRC is each sorted lane's original index,
    # a true permutation of [0, k_pad) per slot (padding lanes included),
    # so one scatter recovers input-lane order exactly.
    src = lanes[:, 1].astype(jnp.int32)
    rows = jnp.arange(r, dtype=jnp.int32)[:, None]
    contrib = jnp.zeros((r, k_pad), f32).at[rows, src].set(lanes[:, 0])
    return rgb_o, trans_o, depth_o, tdepth_o, processed_o, contrib[:, :k]
