"""Temporal contribution culling for TWSR sparse frames (DESIGN.md §12).

At each key frame the rasterizer reports, per Gaussian, the total blend
mass it contributed to the frame (``RenderOutput.gauss_contrib`` — the
sum of ``alpha * T_before`` over every pixel it was blended into). The
streaming loop stores ``inf`` for Gaussians that were never *considered*
at the key frame (not binned into any tile), so newly-visible Gaussians
are always kept, and carries the result across frames as
``FrameState.contrib``.

On sparse frames this module maps the prior through the viewpoint warp:
culling applies only in plan slots whose tile has usable reprojection
sources (``WarpResult.valid_per_tile > 0`` — elsewhere the warp saw
nothing, so the prior says nothing about that view) and removes
intersection pairs whose Gaussian contributed less than the threshold
*before* binning, so sort and raster work shrink with the prior. Slots
whose pairs are all culled are demoted to interpolation
(``slot_active = False``), which feeds straight back into
``plan.rerender_demand`` and the serving layer's capacity suggestions.

``cull_threshold = 0.0`` (the default) keeps the pipeline bit-exact with
the uncull path: the pass is structurally skipped via a Python-level
branch on the static ``RenderConfig``, not merely an all-keep mask.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def warp_gate(valid_per_tile: jax.Array) -> jax.Array:
    """(T,) warp source-pixel counts -> (T,) bool cull gate.

    True where the viewpoint transform found at least one usable
    reprojection source in the tile — only there does the key-frame
    contribution prior describe what the new view needs.
    """
    return valid_per_tile > 0


def cull_pairs(mask: jax.Array, slot_active: jax.Array, tile_ids: jax.Array,
               prior: jax.Array, gate: jax.Array, threshold: float
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the contribution prior to the (N, R) intersection mask.

    mask (N, R) bool   pair mask after the plan's slot_active masking
    slot_active (R,)   the plan's active-slot flags
    tile_ids (R,)      the plan's tile ids (to gather the gate per slot)
    prior (N,)         key-frame per-Gaussian contribution; ``inf`` means
                       "not considered at the key frame" and always keeps
    gate (T,)          bool, True where the warp has usable priors
    threshold          keep iff ``prior >= threshold``

    Returns ``(mask, slot_active, culled_pairs)``: the culled pair mask,
    the slot flags with fully-culled slots demoted (they degrade to
    warp/interpolation exactly like plan-capacity overflow), and the
    scalar count of pairs removed.
    """
    keep = prior >= threshold                      # inf prior -> True
    gated = gate[tile_ids] & slot_active           # (R,) slots we may cull
    new_mask = mask & (keep[:, None] | ~gated[None, :])
    culled = (jnp.sum(mask.astype(jnp.int32))
              - jnp.sum(new_mask.astype(jnp.int32)))
    pre = jnp.sum(mask.astype(jnp.int32), axis=0)
    post = jnp.sum(new_mask.astype(jnp.int32), axis=0)
    return new_mask, demote_emptied(slot_active, pre, post), culled


def cull_pair_list(hit: jax.Array, tile: jax.Array, prior: jax.Array,
                   slot_active: jax.Array, tile_ids: jax.Array,
                   gate: jax.Array, threshold: float) -> jax.Array:
    """``cull_pairs`` on a pair list: (P,) True where a pair is culled.

    hit (P,) the pairs that passed intersection; tile (P,) each pair's
    tile id; prior (P,) its Gaussian's key-frame contribution; the other
    arguments as in ``cull_pairs``. Pairs on tiles outside the plan's
    active slots are never culled. Demote slots afterwards with
    ``demote_emptied`` on the per-slot counts the binning returns.
    """
    gated = jnp.zeros(gate.shape, bool).at[tile_ids].set(
        gate[tile_ids] & slot_active)              # (T,) tiles we may cull
    return hit & ~(prior >= threshold) & gated[tile]


def demote_emptied(slot_active: jax.Array, pre: jax.Array,
                   post: jax.Array) -> jax.Array:
    """Demote the slots whose pairs (``pre`` per slot) were all culled
    (``post`` == 0): they degrade to warp/interpolation exactly like
    plan-capacity overflow."""
    return slot_active & ~((pre > 0) & (post == 0))
