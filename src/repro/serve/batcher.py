"""Continuous batching: churning sessions -> fixed (B, F) engine batches.

The engine compiles one executable per (B, F, cfg) shape, so the batcher
never changes shape *within* a round as streams come and go. It keeps B
slots; each round it binds waiting sessions to free slots, pops up to
``chunk`` pending poses per bound session into a dense (B, chunk, 4, 4)
batch, and masks everything else: a slot with fewer pending poses gets a
shorter ``count`` (the engine freezes its carry past the count — the
key-frame schedule resumes exactly where it paused), and an unbound slot
rides along with ``count=0`` and a throwaway fresh carry. The engine's
masking guarantees padded slots/frames contribute nothing and active
streams render bit-identically to a solo ``render_trajectory`` — pinned
by tests/test_serve.py.

Two serving axes beyond the fixed-B original (DESIGN.md §10):

- **scene-aware packing.** Sessions carry a ``scene_id``; ``admit``
  packs same-scene streams into *contiguous slot groups* of ``group``
  slots (the server sets ``group`` to the per-device shard B/D, so
  ``placement.py`` lands whole scene groups on devices) and ``build``
  emits ``slot_scene`` — per-slot indices into the round's distinct
  ``scene_ids`` — for the engine's stacked-scene gather. Idle slots
  reuse local scene 0 (they are count-0 masked, the scene is only
  traced). ``admit``'s optional ``allowed`` set enforces the server's
  same-bucket-per-round rule.
- **elastic B.** ``resize`` grows/shrinks the slot count between rounds.
  Shrinking unbinds the sessions in the removed slots — their carries
  live on the session, so they rejoin the waiting queue and resume later
  bit-identically (the elastic-B carry rule, pinned by
  tests/test_serve_scenes.py).

``build`` pops poses (and their enqueue stamps) out of the sessions;
``commit`` writes back the final carries, optionally copies the rendered
frames to host memory and retains them on the session
(``collect_frames=True``), stamps per-frame latencies once the pixels
are there, and releases slots of drained-and-closed sessions (detaching
them from the manager). With a tracer on, ``build`` times its ``stack``
and ``upload`` phases and ``commit`` its ``carry`` write-backs and
per-slot ``fetch`` copies, each nested in the server's ``build`` or
``commit`` span on the group's track.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.camera import Camera
from repro.core.engine import EngineCarry, StreamsResult
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.session import SessionManager, StreamSession

_EYE = np.eye(4, dtype=np.float32)


class SlotBatch(NamedTuple):
    """One round's dense engine input plus the host-side bookkeeping."""

    poses: jax.Array        # (B, F, 4, 4)
    counts: jax.Array       # (B,) int32 active-frame counts
    phases: jax.Array       # (B,) int32 per-slot key-frame phases
    carries: EngineCarry    # stacked (B, ...) resume carries
    sids: Tuple[Optional[int], ...]          # slot -> session id (or None)
    enq_times: Tuple[Tuple[float, ...], ...]  # per-slot popped stamps
    slot_scene: jax.Array   # (B,) int32 index into scene_ids (idle -> 0)
    scene_ids: Tuple[Optional[int], ...]  # round's distinct scenes, local order

    @property
    def active_frames(self) -> int:
        return int(np.asarray(self.counts).sum())

    @property
    def bound_slots(self) -> int:
        return sum(s is not None for s in self.sids)


class Committed(NamedTuple):
    """What ``ContinuousBatcher.commit`` did with one group's result."""

    detached: List[StreamSession]   # sessions drained and detached
    delivered: float                # the latency stamp of the group's frames
    fetch_bytes: int                # bytes of frames copied to host memory


class ContinuousBatcher:
    """Scene-aware B-slot batcher over ``engine.render_streams``."""

    def __init__(self, slots: int, chunk: int, cam: Camera, *,
                 group: Optional[int] = None,
                 collect_frames: bool = False,
                 bucket: Optional[Tuple[int, int]] = None,
                 n_gaussians: Optional[int] = None,
                 tracer: Optional[Tracer] = None):
        if slots < 1 or chunk < 1:
            raise ValueError(f"need slots >= 1 and chunk >= 1, got "
                             f"{slots}, {chunk}")
        self.slots = int(slots)
        self.chunk = int(chunk)
        self.cam = cam
        # The scene bucket this batcher's slot group serves (None for
        # the single-bucket/legacy use). Purely informational — the
        # server keeps one batcher per bucket for its ragged
        # mixed-bucket rounds (DESIGN.md §11) and this tag makes traces
        # and reprs say which group is which.
        self.bucket = bucket
        # Contiguity granularity for same-scene packing; the server sets
        # this to the per-device shard size B/D. None -> one group (no
        # sharding, packing preference is moot).
        self.group = int(group) if group else self.slots
        self.collect_frames = bool(collect_frames)
        # Gaussian count of the scenes this batcher serves — required
        # when the engine config threads the contribution prior
        # (pipeline.contrib_enabled), so fresh carries match the scan
        # body's pytree structure. None = prior machinery off.
        self.n_gaussians = n_gaussians
        # Serve-loop tracer (repro/obs/trace.py): resizes are marked as
        # instant events on this batcher's bucket track, so a Perfetto
        # view shows WHEN elastic B snapped next to the round spans.
        # Defaults to the shared disabled tracer — zero overhead, no
        # None checks.
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._slot_sid: List[Optional[int]] = [None] * self.slots
        # Idle slots are all identical (count 0, eye pose, zero state) —
        # one shared template instead of fresh device zeros every round.
        self._idle_carry = engine.init_carry(cam, _EYE, n_gaussians)

    @property
    def bound(self) -> int:
        return sum(s is not None for s in self._slot_sid)

    def __repr__(self) -> str:
        return (f"ContinuousBatcher(slots={self.slots}, "
                f"chunk={self.chunk}, bound={self.bound}, "
                f"bucket={self.bucket})")

    def bound_sids(self) -> List[int]:
        """Session ids currently bound to a slot, slot order."""
        return [s for s in self._slot_sid if s is not None]

    # -- elastic B ---------------------------------------------------------
    def resize(self, new_slots: int, manager: SessionManager, *,
               group: Optional[int] = None) -> List[int]:
        """Grow/shrink the slot batch between rounds (bucketed B).

        Shrinking unbinds sessions in slots >= ``new_slots``; their
        carries live on the session, so nothing is dropped — they rejoin
        ``manager.waiting()`` and resume on a later round exactly where
        they paused. Returns the unbound session ids.
        """
        if new_slots < 1:
            raise ValueError(f"need slots >= 1, got {new_slots}")
        self.tracer.instant("resize", track=f"bucket {self.bucket}",
                            args={"from": self.slots, "to": int(new_slots)})
        unbound: List[int] = []
        for i in range(new_slots, self.slots):
            sid = self._slot_sid[i]
            if sid is None:
                continue
            sess = manager.sessions.get(sid)
            if sess is not None:
                sess.slot = None
            unbound.append(sid)
        self._slot_sid = self._slot_sid[:new_slots] + \
            [None] * max(0, new_slots - self.slots)
        self.slots = int(new_slots)
        self.group = int(group) if group else self.slots
        return unbound

    # -- admission ---------------------------------------------------------
    def _slot_groups(self) -> List[range]:
        g = max(1, min(self.group, self.slots))
        return [range(s, min(s + g, self.slots))
                for s in range(0, self.slots, g)]

    def _pick_slot(self, scene_id, manager: SessionManager) -> Optional[int]:
        """Free slot preference: a group already serving ``scene_id`` >
        a fully-free group > any free slot (lowest index per tier)."""
        same = empty = anywhere = None
        for grp in self._slot_groups():
            free = [i for i in grp if self._slot_sid[i] is None]
            if not free:
                continue
            occupied = [self._slot_sid[i] for i in grp
                        if self._slot_sid[i] is not None]
            scenes_in = {manager.sessions[s].scene_id for s in occupied
                         if s in manager.sessions}
            if scene_id in scenes_in and same is None:
                same = free[0]
            if not occupied and empty is None:
                empty = free[0]
            if anywhere is None:
                anywhere = free[0]
        if same is not None:
            return same
        return empty if empty is not None else anywhere

    def admit(self, manager: SessionManager,
              allowed: Optional[Set] = None) -> int:
        """Bind waiting sessions (oldest first) to free slots, packing
        same-scene streams into contiguous groups. ``allowed`` (optional)
        restricts admission to sessions of those scene_ids — the
        server's one-scene-bucket-per-round rule."""
        admitted = 0
        for sess in manager.waiting():
            if allowed is not None and sess.scene_id not in allowed:
                continue
            i = self._pick_slot(sess.scene_id, manager)
            if i is None:
                break
            sess.slot = i
            self._slot_sid[i] = sess.sid
            admitted += 1
        return admitted

    # -- batch assembly ----------------------------------------------------
    def empty_batch(self, slots: Optional[int] = None) -> SlotBatch:
        """An all-idle (count-0) batch that touches no session state —
        shape-identical to a real round, so it drives executable warmup
        without popping poses from bound sessions. ``slots`` overrides
        the batch size (warmup across B buckets)."""
        b, f = self.slots if slots is None else int(slots), self.chunk
        carries = [self._idle_carry] * b
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
        return SlotBatch(poses=jnp.asarray(np.tile(_EYE, (b, f, 1, 1))),
                         counts=jnp.zeros((b,), jnp.int32),
                         phases=jnp.zeros((b,), jnp.int32), carries=stacked,
                         sids=(None,) * b, enq_times=((),) * b,
                         slot_scene=jnp.zeros((b,), jnp.int32),
                         scene_ids=())

    def build(self, manager: SessionManager) -> SlotBatch:
        """Pop up to ``chunk`` poses per bound session into a dense batch."""
        b, f = self.slots, self.chunk
        poses = np.tile(_EYE, (b, f, 1, 1))
        counts = np.zeros((b,), np.int32)
        phases = np.zeros((b,), np.int32)
        slot_scene = np.zeros((b,), np.int32)
        scene_ids: List[Optional[int]] = []
        scene_local: dict = {}
        carries: List[EngineCarry] = []
        sids: List[Optional[int]] = []
        stamps: List[Tuple[float, ...]] = []
        for i, sid in enumerate(self._slot_sid):
            sess = manager.sessions.get(sid) if sid is not None else None
            if sid is not None and sess is None:
                # Detached externally since the last round: free the slot
                # now (commit only handles cancellation mid-flight).
                self._slot_sid[i] = sid = None
            slot_stamps: List[float] = []
            if sess is not None:
                phases[i] = sess.phase
                if sess.scene_id not in scene_local:
                    scene_local[sess.scene_id] = len(scene_ids)
                    scene_ids.append(sess.scene_id)
                slot_scene[i] = scene_local[sess.scene_id]
                k = 0
                while sess.pending and k < f:
                    pose, t_enq = sess.pending.popleft()
                    poses[i, k] = pose
                    slot_stamps.append(t_enq)
                    k += 1
                counts[i] = k
                if k:
                    # Pad the tail with the last real pose: masked frames
                    # still trace the render, so keep their inputs tame.
                    poses[i, k:] = poses[i, k - 1]
                if sess.carry is None:
                    sess.carry = engine.init_carry(self.cam, poses[i, 0],
                                                   self.n_gaussians)
                carries.append(sess.carry)
                sids.append(sid)
            else:
                carries.append(self._idle_carry)
                sids.append(None)
            stamps.append(tuple(slot_stamps))
        tk = f"bucket {self.bucket}"
        with self.tracer.span("stack", track=tk):
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *carries)
        with self.tracer.span("upload", track=tk):
            poses, counts, phases, slot_scene = (
                jnp.asarray(a) for a in (poses, counts, phases, slot_scene))
        return SlotBatch(poses=poses, counts=counts, phases=phases,
                         carries=stacked, sids=tuple(sids),
                         enq_times=tuple(stamps), slot_scene=slot_scene,
                         scene_ids=tuple(scene_ids))

    def commit(self, batch: SlotBatch, result: StreamsResult,
               manager: SessionManager, now: float,
               clock: Optional[Callable[[], float]] = None) -> Committed:
        """Write back carries, copy frames to host, stamp latencies;
        detach drained sessions.

        ``now`` is when the device finished the group. A frame's latency
        runs from its pose's enqueue stamp to its pixels in host memory:
        where frames are copied (``collect_frames``) and ``clock`` is
        given, that is ``clock()`` read after the group's last copy,
        else ``now``. Returns the sessions detached this round (their
        slots free up for the next ``admit``; the server keeps them for
        final stats), the stamp, and the bytes copied.
        """
        tk = f"bucket {self.bucket}"
        counts = np.asarray(batch.counts)
        served: List[Tuple[int, StreamSession, int]] = []
        fetched = 0
        for i, sid in enumerate(batch.sids):
            if sid is None:
                continue
            if sid not in manager.sessions:
                # Cancelled externally (manager.detach) mid-flight: the
                # rendered chunk has no consumer, but the slot must not
                # leak.
                if self._slot_sid[i] == sid:
                    self._slot_sid[i] = None
                continue
            sess = manager.sessions[sid]
            with self.tracer.span("carry", track=tk):
                sess.carry = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                    result.carries)
            n = int(counts[i])
            sess.frames_rendered += n
            if self.collect_frames and n:
                with self.tracer.span("fetch", track=tk):
                    frames = np.asarray(result.frames[i][:n])
                sess.frames.append(frames)
                fetched += frames.nbytes
            served.append((i, sess, n))
        delivered = clock() if fetched and clock is not None else now
        detached: List[StreamSession] = []
        for i, sess, n in served:
            sess.latencies.extend(delivered - t
                                  for t in batch.enq_times[i][:n])
            if sess.done:
                manager.detach(sess.sid)
                sess.slot = None
                self._slot_sid[i] = None
                detached.append(sess)
        return Committed(detached, delivered, fetched)
