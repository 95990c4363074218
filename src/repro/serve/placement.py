"""Device placement: shard stream slots across devices, degrade to one.

``engine.render_streams`` vmaps B streams on one device; under vmap the
full/sparse ``lax.cond`` lowers to a select, so every stream pays BOTH
branches every step (the caveat in core/engine.py). ``shard_map`` over a
1-D "streams" mesh fixes both costs at once: each device renders only
its B/D local slots, and when the local shard is a single stream the
scan body keeps a genuine ``lax.cond`` — that device executes only the
branch its stream actually takes, so concurrent streams stop paying each
other's full-render branches (with B == device count, the phase stagger
finally saves device FLOPs, not just recorded workload).

Multi-scene serving (DESIGN.md §10) adds one input: ``multi_scene=True``
builds ``fn(scenes, poses, counts, phases, carries, slot_scene)`` where
``scenes`` is a stacked ``(S, N, ...)`` pytree (replicated across the
mesh) and ``slot_scene`` is sharded with the slots — each device gathers
only its local slots' scenes from the replicated stack. This is why the
batcher packs same-scene streams into contiguous groups of B/D slots:
a device whose local slots share one scene gathers one scene's arrays,
and with local B = 1 the gather feeds a genuine per-stream ``lax.cond``
just like the single-scene path.

Degrades gracefully: ``stream_mesh`` returns None unless >1 device can
split B evenly (it trims to the largest divisor), and ``build_render_fn``
then falls back to the plain single-device ``render_streams`` — the
serve loop never branches on topology.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import engine
from repro.core.camera import Camera
from repro.core.engine import StreamsResult
from repro.core.pipeline import RenderConfig, StackedRecords


def stream_mesh(num_slots: int, devices=None) -> Optional[Mesh]:
    """1-D "streams" mesh over the most devices that divide ``num_slots``.

    None when that is a single device — the caller should use the plain
    vmapped path.
    """
    devices = list(jax.devices() if devices is None else devices)
    d = min(len(devices), int(num_slots))
    while d > 1 and num_slots % d:
        d -= 1
    if d <= 1:
        return None
    return Mesh(np.asarray(devices[:d]), ("streams",))


def build_render_fn(cam: Camera, cfg: RenderConfig,
                    mesh: Optional[Mesh] = None, *,
                    multi_scene: bool = False):
    """The uniform serving-layer entry point.

    ``multi_scene=False`` (legacy):
    ``fn(scene, poses, counts, phases, carries) -> StreamsResult``.
    ``multi_scene=True``:
    ``fn(scenes, poses, counts, phases, carries, slot_scene)`` with
    ``scenes`` stacked ``(S, N, ...)`` and ``slot_scene`` (B,) int32.

    With a mesh, a jitted shard_map of the masked stream scan (slots —
    and slot_scene — split over "streams"; scene stack and camera
    replicated); without one, ``engine.render_streams``. One compiled
    executable per (scene_bucket, B, F, cfg) either way — the serve
    cache (serve/cache.py) keys these builders by bucket.
    """
    if mesh is None:
        if multi_scene:
            def fn(scenes, poses, counts, phases, carries, slot_scene):
                return engine.render_streams(
                    scenes, cam, poses, cfg, phases=phases, counts=counts,
                    carries=carries, slot_scene=slot_scene)
        else:
            def fn(scene, poses, counts, phases, carries):
                return engine.render_streams(scene, cam, poses, cfg,
                                             phases=phases, counts=counts,
                                             carries=carries)
        return fn

    squeeze = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
    expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

    if multi_scene:
        def local_fn(scenes, poses, counts, phases, carries, slot_scene):
            # Shapes here are the per-device shard: (B/D, F, 4, 4) etc.;
            # `scenes` is the full replicated (S, N, ...) stack and each
            # local slot gathers its own scene from it.
            take = lambda sid: jax.tree_util.tree_map(
                lambda a: a[sid], scenes)
            if poses.shape[0] == 1:
                # Single local stream: skip vmap so the full/sparse
                # lax.cond stays a real branch on this device.
                carry_end, (frames, recs, active) = engine.stream_scan(
                    take(slot_scene[0]), cam, poses[0], counts[0],
                    phases[0], cfg, squeeze(carries))
                return (expand(carry_end), frames[None], expand(recs),
                        active[None])
            run = lambda p, c, ph, cy, sid: engine.stream_scan(
                take(sid), cam, p, c, ph, cfg, cy)
            carry_end, (frames, recs, active) = jax.vmap(run)(
                poses, counts, phases, carries, slot_scene)
            return carry_end, frames, recs, active

        sharded = P("streams")
        smapped = jax.jit(jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), sharded, sharded, sharded, sharded, sharded),
            out_specs=(sharded, sharded, sharded, sharded),
            check_vma=False))

        def fn(scenes, poses, counts, phases, carries, slot_scene):
            counts = jnp.asarray(counts, jnp.int32)
            phases = jnp.asarray(phases, jnp.int32)
            slot_scene = jnp.asarray(slot_scene, jnp.int32)
            carry_end, frames, recs, active = smapped(
                scenes, poses, counts, phases, carries, slot_scene)
            return StreamsResult(frames=frames,
                                 records=StackedRecords(recs),
                                 phases=phases, counts=counts,
                                 frame_active=active, carries=carry_end)
        return fn

    def local_fn(scene, poses, counts, phases, carries):
        # Shapes here are the per-device shard: (B/D, F, 4, 4) etc.
        if poses.shape[0] == 1:
            # Single local stream: skip vmap so the full/sparse
            # lax.cond stays a real branch on this device.
            carry_end, (frames, recs, active) = engine.stream_scan(
                scene, cam, poses[0], counts[0], phases[0], cfg,
                squeeze(carries))
            return (expand(carry_end), frames[None], expand(recs),
                    active[None])
        run = lambda p, c, ph, cy: engine.stream_scan(
            scene, cam, p, c, ph, cfg, cy)
        carry_end, (frames, recs, active) = jax.vmap(run)(
            poses, counts, phases, carries)
        return carry_end, frames, recs, active

    sharded = P("streams")
    smapped = jax.jit(jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), sharded, sharded, sharded, sharded),
        out_specs=(sharded, sharded, sharded, sharded),
        check_vma=False))

    def fn(scene, poses, counts, phases, carries):
        counts = jnp.asarray(counts, jnp.int32)
        phases = jnp.asarray(phases, jnp.int32)
        carry_end, frames, recs, active = smapped(scene, poses, counts,
                                                  phases, carries)
        return StreamsResult(frames=frames, records=StackedRecords(recs),
                             phases=phases, counts=counts,
                             frame_active=active, carries=carry_end)
    return fn
