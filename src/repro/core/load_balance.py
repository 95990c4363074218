"""LDU — Load Distribution Unit scheduling policies (paper Sec. V-B).

Assigns tiles to the accelerator's B parallel rasterization blocks.

The paper's policy ("ls_gaussian"):
  1. traverse tiles in Morton (Z-order) for spatial/memory locality;
  2. greedy sequential fill: a tile joins the current block unless the
     block's cumulative predicted workload would exceed (1 + 1/N) * W,
     where W = ideal per-block load and N = average tiles per block —
     then it opens the next block;
  3. inside each block, tiles execute light-to-heavy so the (shared,
     serial) sorting unit always finishes a tile's sort before the
     rasterizer drains the previous tile (removes intra-block bubbles).

Baselines: "static_blocked" (contiguous raster-order chunks),
"round_robin" (tile i -> block i mod B), "dynamic" (greedy
shortest-queue, models the GPU hardware scheduler).

``schedule`` (numpy, host-side) is the one LDU: the accelerator
simulator (core/streaming.py) builds each frame's schedule with it from
the recorded DPES workloads (``FrameRecord.sort_pairs``) and re-render
set (``active``). Nothing on the chip reads a schedule — the raster grid
visits plan slots in Morton order — so the renderer computes none.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    block_of: np.ndarray        # (T,) block id per tile (-1 = not scheduled)
    order_in_block: np.ndarray  # (T,) execution position within its block
    num_blocks: int

    def tiles_of_block(self, b: int) -> np.ndarray:
        ids = np.where(self.block_of == b)[0]
        return ids[np.argsort(self.order_in_block[ids], kind="stable")]


def morton_order(tiles_x: int, tiles_y: int) -> np.ndarray:
    """Tile visit order following the Z-order curve. (T,) tile indices."""
    def interleave(x: np.ndarray) -> np.ndarray:
        x = x.astype(np.uint32)
        x = (x | (x << 8)) & 0x00FF00FF
        x = (x | (x << 4)) & 0x0F0F0F0F
        x = (x | (x << 2)) & 0x33333333
        x = (x | (x << 1)) & 0x55555555
        return x

    ty, tx = np.meshgrid(np.arange(tiles_y), np.arange(tiles_x), indexing="ij")
    code = interleave(tx.ravel()) | (interleave(ty.ravel()) << 1)
    return np.argsort(code, kind="stable")


def schedule(workload: np.ndarray, num_blocks: int, *,
             policy: str = "ls_gaussian",
             tiles_x: Optional[int] = None, tiles_y: Optional[int] = None,
             active: Optional[np.ndarray] = None) -> Schedule:
    """Build a tile->block schedule.

    workload: (T,) predicted pairs per tile (the LDU uses DPES estimates).
    active: optional (T,) bool — only these tiles are scheduled (TWSR
    re-render set); inactive tiles get block -1.
    """
    workload = np.asarray(workload, np.int64)
    t_total = workload.shape[0]
    if active is None:
        active = np.ones((t_total,), bool)
    active = np.asarray(active, bool)
    tile_ids = np.where(active)[0]
    t = len(tile_ids)
    block_of = np.full((t_total,), -1, np.int64)
    order_in = np.zeros((t_total,), np.int64)
    b = max(num_blocks, 1)

    if t == 0:
        return Schedule(block_of, order_in, b)

    if policy == "static_blocked":
        chunk = -(-t // b)
        for i, tid in enumerate(tile_ids):
            block_of[tid] = min(i // chunk, b - 1)
    elif policy == "round_robin":
        for i, tid in enumerate(tile_ids):
            block_of[tid] = i % b
    elif policy == "dynamic":
        # GPU-scheduler model: next tile (raster order) goes to the block
        # with the least accumulated work.
        loads = np.zeros(b)
        for tid in tile_ids:
            j = int(np.argmin(loads))
            block_of[tid] = j
            loads[j] += workload[tid]
    elif policy == "ls_gaussian":
        if tiles_x is None or tiles_y is None:
            raise ValueError("ls_gaussian policy needs tiles_x/tiles_y for "
                             "Morton traversal")
        visit = morton_order(tiles_x, tiles_y)
        visit = visit[active[visit]]
        w_ideal = max(workload[tile_ids].sum() / b, 1.0)
        n_avg = max(t / b, 1.0)
        cap = (1.0 + 1.0 / n_avg) * w_ideal
        # Paper rule: a tile that would push the current block past the cap
        # is "deferred to the next block". Taken literally this strands the
        # overflow of a fragmented traversal in the LAST block; we harden
        # it by deferring cyclically (next block with room, least-loaded as
        # the final fallback) — recorded in DESIGN.md §3.
        accs = np.zeros(b)
        cur = 0
        for tid in visit:
            wl = float(workload[tid])
            if accs[cur] + wl > cap:
                for _ in range(b):
                    cur = (cur + 1) % b
                    if accs[cur] + wl <= cap:
                        break
                else:
                    cur = int(np.argmin(accs))
            block_of[tid] = cur
            accs[cur] += wl
    else:
        raise ValueError(f"unknown policy {policy!r}")

    # Intra-block execution order: the paper's light-to-heavy for
    # ls_gaussian, arrival order otherwise.
    for j in range(b):
        ids = np.where(block_of == j)[0]
        if len(ids) == 0:
            continue
        if policy == "ls_gaussian":
            perm = ids[np.argsort(workload[ids], kind="stable")]
        else:
            perm = ids
        order_in[perm] = np.arange(len(perm))
    return Schedule(block_of, order_in, b)


def morton_rank(tiles_x: int, tiles_y: int) -> jax.Array:
    """(T,) Z-order visit priority per tile id (jnp; constant under jit):
    the order of a ``TilePlan``'s slots (core/plan.py).

    ``rank[tid]`` is the position of tile ``tid`` along the Morton curve,
    so ``jnp.argsort(rank)`` equals the numpy ``morton_order`` traversal.
    """
    def interleave(x: jax.Array) -> jax.Array:
        x = x.astype(jnp.uint32)
        x = (x | (x << 8)) & jnp.uint32(0x00FF00FF)
        x = (x | (x << 4)) & jnp.uint32(0x0F0F0F0F)
        x = (x | (x << 2)) & jnp.uint32(0x33333333)
        x = (x | (x << 1)) & jnp.uint32(0x55555555)
        return x

    ty, tx = jnp.meshgrid(jnp.arange(tiles_y), jnp.arange(tiles_x),
                          indexing="ij")
    code = interleave(tx.ravel()) | (interleave(ty.ravel()) << 1)
    order = jnp.argsort(code, stable=True)
    t = tiles_x * tiles_y
    return jnp.zeros((t,), jnp.int32).at[order].set(
        jnp.arange(t, dtype=jnp.int32))


def load_stats(sched: Schedule, workload: np.ndarray) -> dict:
    """Imbalance diagnostics: per-block totals, max/mean ratio."""
    loads = np.zeros(sched.num_blocks)
    for j in range(sched.num_blocks):
        ids = np.where(sched.block_of == j)[0]
        loads[j] = workload[ids].sum()
    mean = loads.mean() if loads.size else 0.0
    return {
        "block_loads": loads,
        "max_over_mean": float(loads.max() / mean) if mean > 0 else 1.0,
        "cv": float(loads.std() / mean) if mean > 0 else 0.0,
    }
