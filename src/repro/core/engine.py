"""On-device scanned streaming engine: one executable per trajectory.

``pipeline.render_trajectory_py`` (the golden reference) is a host-side
Python loop: every frame re-dispatches one of two separately-jitted
functions and appends to Python lists — a per-frame host roundtrip, i.e.
exactly the global-sync barrier the paper's streaming design argues
against. This module folds the whole full/sparse streaming loop into a
single ``lax.scan`` so an entire trajectory compiles ONCE and runs with
no host involvement, and ``jax.vmap``s that scan over a leading stream
axis for batched multi-user serving. Both frame branches are thin
wrappers over the plan-driven ``pipeline.render_planned_frame`` — the
TilePlan construction AND the device-LDU schedule it records run inside
this scan (DESIGN.md §2), and both branches raster through
``RenderConfig.impl`` (DESIGN.md §9: the fused plan-slot Pallas kernel
on TPU backends by default), so every stream and the serve loop inherit
the kernel selection with no engine-level switches.

Scan carry layout (``EngineCarry``):

  state     : ``FrameState`` — the reference frame a sparse frame warps
              from (rgb, expected depth, truncated depth, source mask,
              true global frame index). ``state.frame_idx`` carries the
              real frame number: key frames receive it explicitly (a
              mid-trajectory key frame must NOT reset the counter) and
              sparse frames increment it.
  prev_pose : (4, 4) world-to-camera of the previous frame — the warp's
              reference camera (the previous frame is always the
              reference, full or sparse).
  step      : () int32 global frame index, drives the full/sparse
              ``lax.cond``: frame ``f`` is fully rendered when
              ``(f + phase) % window == 0`` (frame 0 is always full —
              there is nothing to warp from).

``phase`` staggers the key-frame schedule between concurrent streams:
with B streams sharing one scene, identical phases would make every
stream pay its expensive full render on the same step (a periodic load
spike B times the steady state). ``stream_phases`` spreads the offsets
so at most ``ceil(B / window)`` streams re-key per step. Caveat: under
``vmap`` the batched ``lax.cond`` lowers to a select, so the XLA
executable runs BOTH branches for every stream at every step — the
stagger does not reduce this process's device FLOPs. What it staggers
is the *recorded workload* (full-render pair counts per step), i.e.
the schedule a real per-stream dispatcher or the accelerator simulator
(core/streaming.py) serves — which is where the serving-load claim
lives and is measured.

Why records became stacked arrays: ``lax.scan`` emits its per-step
outputs as arrays with a leading frame axis ``(F, ...)`` — there is no
Python list to accumulate on device. ``StackedRecords`` (pipeline.py)
wraps that stacked ``FrameRecord`` pytree: benchmarks consume the
``(F, ...)``/``(B, F, ...)`` arrays vectorized (one host transfer per
trajectory instead of one per frame), while ``records[i]`` still
recovers a per-frame ``FrameRecord`` view for spot checks.

Serving extensions (consumed by ``repro.serve``, DESIGN.md §8): streams
are *resumable* and *ragged*. ``render_streams`` takes per-stream
active-frame ``counts`` (frames past a stream's count are padding: zero
frames, blanked records, and — crucially — a frozen carry whose global
step does not advance, so the key-frame schedule is preserved across
stalls) plus initial ``carries`` (``init_carry``/``init_stream_carries``
for fresh streams), and returns the final carries — a continuous batcher
threads sessions through successive fixed-shape chunks with active
frames bit-identical to a solo run. Streams need not share a scene:
with ``slot_scene`` given, the scene argument is a stacked ``(S, N,
...)`` pytree and each stream gathers its own scene before scanning
(multi-scene serving, DESIGN.md §10).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.camera import Camera
from repro.core.pipeline import (FrameRecord, FrameState, RenderConfig,
                                 StackedRecords, TrajectoryResult,
                                 contrib_enabled, render_full_frame,
                                 render_sparse_frame)
from repro.obs.trace import annotate


class EngineCarry(NamedTuple):
    """Scan state threaded across frames (see module docstring)."""

    state: FrameState       # reference frame for the next warp
    prev_pose: jax.Array    # (4, 4) previous frame's world-to-camera
    step: jax.Array         # () int32 global frame index


class StreamsResult(NamedTuple):
    frames: jax.Array           # (B, F, H, W, 3)
    records: StackedRecords     # fields (B, F, ...)
    phases: jax.Array           # (B,) int32 key-frame phase offsets
    counts: jax.Array           # (B,) int32 active-frame counts
    frame_active: jax.Array     # (B, F) bool — frame within its count
    carries: EngineCarry        # final per-stream carries, fields (B, ...)


def _zero_state(cam: Camera,
                n_gaussians: Optional[int] = None) -> FrameState:
    """Shape/dtype-correct placeholder state for step 0 (always full).

    ``n_gaussians`` sizes the contribution-prior leaf when the config
    threads it (``pipeline.contrib_enabled``); the inf fill is the
    keep-all prior, and frame 0 is always full so it is never read.
    """
    h, w = cam.height, cam.width
    contrib = None if n_gaussians is None \
        else jnp.full((n_gaussians,), jnp.inf, jnp.float32)
    return FrameState(
        rgb=jnp.zeros((h, w, 3), jnp.float32),
        exp_depth=jnp.zeros((h, w), jnp.float32),
        trunc_depth=jnp.zeros((h, w), jnp.float32),
        source_mask=jnp.zeros((h, w), bool),
        frame_idx=jnp.int32(0),
        contrib=contrib)


def init_carry(cam: Camera, pose: jax.Array,
               n_gaussians: Optional[int] = None) -> EngineCarry:
    """Fresh stream carry: zero state at global step 0 (first frame full).

    ``pose`` seeds ``prev_pose``; frame 0 is always a full render, so the
    warp never reads it — any valid (4, 4) world-to-camera works.
    ``n_gaussians`` (the scene's Gaussian count) is required exactly when
    ``pipeline.contrib_enabled(cfg)`` — it sizes the carried prior so the
    carry's pytree structure matches the scan body's output.
    """
    return EngineCarry(state=_zero_state(cam, n_gaussians),
                       prev_pose=jnp.asarray(pose, jnp.float32),
                       step=jnp.int32(0))


def init_stream_carries(cam: Camera, poses_batch: jax.Array,
                        n_gaussians: Optional[int] = None) -> EngineCarry:
    """Batched fresh carries, fields (B, ...), one per stream slot."""
    return jax.vmap(lambda p: init_carry(cam, p, n_gaussians))(
        poses_batch[:, 0])


def _mask_record(rec: FrameRecord, keep: jax.Array) -> FrameRecord:
    """Blank an inactive (padding) frame's record: zero counts, no active
    tiles, unscheduled LDU blocks — so masked frames read as no work."""
    def m(v, blank):
        return jnp.where(keep, v, jnp.asarray(blank, v.dtype))
    return FrameRecord(
        is_full=m(rec.is_full, False),
        n_gaussians=m(rec.n_gaussians, 0),
        candidate_pairs=m(rec.candidate_pairs, 0),
        raw_pairs=m(rec.raw_pairs, 0),
        sort_pairs=m(rec.sort_pairs, 0),
        raster_pairs=m(rec.raster_pairs, 0),
        active=m(rec.active, False),
        tiles_interpolated=m(rec.tiles_interpolated, 0),
        overflow_pairs=m(rec.overflow_pairs, 0),
        overflow_tiles=m(rec.overflow_tiles, 0),
        block_of_tile=m(rec.block_of_tile, -1),
        order_in_block=m(rec.order_in_block, 0),
        block_load=m(rec.block_load, 0),
        culled_pairs=m(rec.culled_pairs, 0),
        pair_budget_overflow=m(rec.pair_budget_overflow, 0),
        stage1_pairs=m(rec.stage1_pairs, 0),
        lane_contrib=None if rec.lane_contrib is None
        else m(rec.lane_contrib, 0.0))


def make_frame_step(scene, cam: Camera, cfg: RenderConfig,
                    phase: jax.Array):
    """Build the unified per-frame transition ``frame_step(carry, pose)``.

    Returns ``(new_carry, (rgb, record))``; full-vs-sparse is a
    ``lax.cond`` on the carried global step, so the function is a valid
    ``lax.scan`` body (and batches under ``vmap`` with per-stream
    ``phase``).
    """

    def frame_step(carry: EngineCarry, pose: jax.Array):
        tgt_cam = cam.with_pose(pose)
        ref_cam = cam.with_pose(carry.prev_pose)

        def full_branch(state: FrameState):
            with annotate("repro.frame/full"):
                out, new_state, rec = render_full_frame(
                    scene, tgt_cam, cfg, frame_idx=carry.step)
            return out.rgb, new_state, rec

        def sparse_branch(state: FrameState):
            with annotate("repro.frame/sparse"):
                return render_sparse_frame(scene, ref_cam, tgt_cam, state,
                                           cfg)

        if cfg.window == 1:
            # Statically always-full: skip compiling the warp branch.
            rgb, new_state, rec = full_branch(carry.state)
        else:
            is_full = (carry.step == 0) | \
                ((carry.step + phase) % cfg.window == 0)
            rgb, new_state, rec = jax.lax.cond(
                is_full, full_branch, sparse_branch, carry.state)
        new_carry = EngineCarry(state=new_state, prev_pose=pose,
                                step=carry.step + 1)
        return new_carry, (rgb, rec)

    return frame_step


def _scene_n(scene, cfg: RenderConfig) -> Optional[int]:
    """Gaussian count for carry init, or None when priors are off.

    Works on single (N, ...) and stacked (S, N, ...) scene pytrees."""
    return scene.means.shape[-2] if contrib_enabled(cfg) else None


def _scan_core(scene, cam: Camera, poses: jax.Array, phase: jax.Array,
               cfg: RenderConfig, keep_states: bool):
    step_fn = make_frame_step(scene, cam, cfg, phase)
    init = EngineCarry(state=_zero_state(cam, _scene_n(scene, cfg)),
                       prev_pose=poses[0], step=jnp.int32(0))

    def body(carry, pose):
        new_carry, (rgb, rec) = step_fn(carry, pose)
        ys = (rgb, rec, new_carry.state) if keep_states else (rgb, rec)
        return new_carry, ys

    _, ys = jax.lax.scan(body, init, poses)
    return ys


@functools.partial(jax.jit, static_argnames=("cfg", "keep_states"))
def _scan_trajectory(scene, cam, poses, phase, cfg, keep_states):
    return _scan_core(scene, cam, poses, phase, cfg, keep_states)


def stream_scan(scene, cam: Camera, poses: jax.Array, count: jax.Array,
                phase: jax.Array, cfg: RenderConfig, carry: EngineCarry):
    """Masked, resumable single-stream scan — the serving-layer primitive.

    Renders frames ``0 .. count-1`` of ``poses`` starting from ``carry``
    (use :func:`init_carry` for a fresh stream). Frames at or beyond
    ``count`` are padding: the carry passes through untouched (the global
    step does NOT advance, so the key-frame schedule is preserved across
    stalls), the frame reads as zeros, and the record is blanked via
    ``_mask_record``. Because padded frames always trail the active prefix
    within a chunk, active frames are bit-identical to an unmasked run —
    the serving batcher (repro.serve) relies on this to resume sessions
    chunk by chunk.

    Not jitted here: ``render_streams`` wraps the vmapped version in one
    jit, and ``serve.placement`` shard_maps it across devices.

    Returns ``(carry_end, (frames, records, frame_active))``.
    """
    step_fn = make_frame_step(scene, cam, cfg, phase)

    def body(carry, xs):
        pose, i = xs
        new_carry, (rgb, rec) = step_fn(carry, pose)
        keep = i < count
        carry_out = jax.tree_util.tree_map(
            lambda n, o: jnp.where(keep, n, o), new_carry, carry)
        return carry_out, (jnp.where(keep, rgb, 0.0),
                           _mask_record(rec, keep), keep)

    idx = jnp.arange(poses.shape[0], dtype=jnp.int32)
    return jax.lax.scan(body, carry, (poses, idx))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _scan_streams(scene, cam, poses_batch, counts, phases, carries, cfg):
    fn = lambda poses, count, phase, carry: stream_scan(
        scene, cam, poses, count, phase, cfg, carry)
    return jax.vmap(fn)(poses_batch, counts, phases, carries)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _scan_streams_scenes(scenes, cam, poses_batch, counts, phases, carries,
                         slot_scene, cfg):
    """Multi-scene variant: ``scenes`` fields carry a leading stacked
    scene axis (S, N, ...) and each stream gathers its own scene by
    ``slot_scene`` before running the identical masked scan — so a
    stream's math is value-for-value the same as a single-scene run on
    that scene, and one executable serves any assignment of B streams to
    the S stacked scenes."""
    def fn(poses, count, phase, carry, sid):
        scene = jax.tree_util.tree_map(lambda a: a[sid], scenes)
        return stream_scan(scene, cam, poses, count, phase, cfg, carry)
    return jax.vmap(fn)(poses_batch, counts, phases, carries, slot_scene)


def render_trajectory(scene, cam: Camera, poses: jax.Array,
                      cfg: RenderConfig, *, keep_states: bool = False,
                      phase: Union[int, jax.Array] = 0
                      ) -> TrajectoryResult:
    """Render a pose sequence as ONE jit-compiled ``lax.scan``.

    Numerically matches ``pipeline.render_trajectory_py`` (for
    ``phase=0``) but dispatches a single executable for the whole
    trajectory instead of one per frame.

    poses: (F, 4, 4) world-to-camera per frame. ``phase`` shifts the
    key-frame schedule: frame f is full when (f + phase) % window == 0
    (frame 0 is always full).
    """
    ys = _scan_trajectory(scene, cam, poses, jnp.int32(phase), cfg,
                          keep_states)
    if keep_states:
        frames, recs, states = ys
    else:
        (frames, recs), states = ys, None
    return TrajectoryResult(frames=frames, records=StackedRecords(recs),
                            states=states)


def stream_phases(num_streams: int, window: int) -> jax.Array:
    """(B,) evenly staggered key-frame phase offsets in [0, window)."""
    stride = max(1, window // max(num_streams, 1))
    return (jnp.arange(num_streams, dtype=jnp.int32) * stride) % window


def render_streams(scene, cam: Camera, poses_batch: jax.Array,
                   cfg: RenderConfig, *,
                   phases: Optional[Union[Sequence[int], jax.Array]] = None,
                   counts: Optional[Union[Sequence[int], jax.Array]] = None,
                   carries: Optional[EngineCarry] = None,
                   slot_scene: Optional[Union[Sequence[int],
                                              jax.Array]] = None
                   ) -> StreamsResult:
    """Batched multi-stream rendering: vmap the scanned engine over B
    concurrent camera sessions sharing one scene — or, with
    ``slot_scene``, over B sessions spread across S stacked scenes.

    poses_batch: (B, F, 4, 4). Each stream runs the full streaming loop
    independently (own carry, own key-frame schedule); ``phases``
    (default: ``stream_phases``) staggers the expensive full renders so
    the aggregate *recorded* per-step workload stays flat instead of
    spiking every ``window`` frames (see the module docstring for the
    vmap/select caveat: this vmapped executable itself computes both
    branches per stream regardless of phase).

    ``counts`` (default: all F) gives each stream its own active-frame
    count — trajectories of ragged length ride one fixed-(B, F) batch,
    with frames at or beyond a stream's count masked out (zero frames,
    blanked records, frozen carry). ``carries`` (default: fresh
    :func:`init_carry` per stream) resumes each stream mid-trajectory;
    the final per-stream carries come back in ``StreamsResult.carries``,
    so chunked serving loops (repro.serve.batcher) can thread sessions
    through successive fixed-shape batches.

    ``slot_scene`` (default: None — single shared scene) switches to the
    multi-scene gather path (the serving layer's scene registry,
    DESIGN.md §10): ``scene`` must then be a *stacked* scene pytree with
    fields ``(S, N, ...)`` (e.g. ``serve.scenes.SceneRegistry.stack``)
    and ``slot_scene`` gives each stream slot its scene index in
    ``[0, S)``. Masked (count-0) slots should point at index 0 — they
    trace the render like any slot, so their scene must exist. Because
    the gather happens before the per-stream scan, an active stream is
    value-identical to a single-scene ``render_trajectory`` over its own
    scene (pinned by tests/test_serve_scenes.py).
    """
    b, f = poses_batch.shape[0], poses_batch.shape[1]
    if phases is None:
        phases = stream_phases(b, cfg.window)
    phases = jnp.asarray(phases, jnp.int32)
    if counts is None:
        counts = jnp.full((b,), f, jnp.int32)
    counts = jnp.asarray(counts, jnp.int32)
    if carries is None:
        carries = init_stream_carries(cam, poses_batch,
                                      _scene_n(scene, cfg))
    if slot_scene is not None:
        carry_end, (frames, recs, active) = _scan_streams_scenes(
            scene, cam, poses_batch, counts, phases, carries,
            jnp.asarray(slot_scene, jnp.int32), cfg)
        return StreamsResult(frames=frames, records=StackedRecords(recs),
                             phases=phases, counts=counts,
                             frame_active=active, carries=carry_end)
    carry_end, (frames, recs, active) = _scan_streams(
        scene, cam, poses_batch, counts, phases, carries, cfg)
    return StreamsResult(frames=frames, records=StackedRecords(recs),
                         phases=phases, counts=counts, frame_active=active,
                         carries=carry_end)
