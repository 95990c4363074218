"""Scene registry: many scenes, bucketed Gaussian counts, shared executables.

The serve layer's target setting (DESIGN.md §10) is fleets of edge
cameras that each observe *their own* scene while sharing accelerator
capacity — so the number of Gaussians N becomes a serving-time shape,
and an unmanaged N would compile one XLA executable per scene. The
registry removes N from the compile space the same way ``cache.py``
bounds R: every registered scene is padded up to a fixed ladder of
bucket sizes (``DEFAULT_SCENE_BUCKETS``), and the executable cache keys
on the *bucket*, not the scene — any two same-bucket scenes render
through one executable, with the actual Gaussian arrays passed as traced
runtime inputs.

Padding must be exact, not approximate: a padded scene has to render
bit-identically to the original. Padding rows are therefore *invalid by
construction* — ``opacity_logit = PAD_OPACITY_LOGIT`` puts their opacity
orders of magnitude below ``projection.ALPHA_THRESHOLD``, so
``preprocess`` marks them ``valid=False`` for EVERY camera pose, every
intersection test masks them out, and they can never claim a bin lane,
a pair count, or a blend contribution (``tests/test_serve_scenes.py``
pins frames AND records bit-exact against the unpadded scene).

Entries are refcounted by attached streams (``acquire``/``release`` —
the server pins a scene for each live session) so ``evict`` can never
pull a scene out from under an in-flight stream.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.gaussians import GaussianScene
from repro.serve.cache import validate_buckets

# Pow-2 ladder: padding waste is bounded by 2x, and the distinct-
# executable family is bounded by the handful of bucket sizes a fleet's
# scenes actually span (each bucket in use is one more compile per
# (B, R) key — see server._key_for). It reaches 2**21, which holds the
# 2,000,000 Gaussians of the paper's scenes.
DEFAULT_SCENE_BUCKETS = tuple(2 ** e for e in range(8, 22))

# sigmoid(-20) ~ 2e-9, far below projection.ALPHA_THRESHOLD (1/255):
# padding Gaussians fail the `visible` cull for every pose.
PAD_OPACITY_LOGIT = -20.0


def snap_scene_bucket(n: int, buckets: Sequence[int] = DEFAULT_SCENE_BUCKETS
                      ) -> int:
    """Smallest bucket covering ``n`` Gaussians.

    Unlike R (where the largest bucket caps demand and the excess
    degrades to interpolation), a scene cannot be truncated without
    changing its content — a scene beyond the largest bucket is an
    error, not a clamp.
    """
    validate_buckets(buckets, "scene_buckets")
    for b in buckets:
        if n <= b:
            return int(b)
    raise ValueError(
        f"scene with {n} Gaussians exceeds the largest scene bucket "
        f"{buckets[-1]}; extend the bucket ladder")


def pad_scene(scene: GaussianScene, n_bucket: int) -> GaussianScene:
    """Pad a scene to ``n_bucket`` rows with inert (never-valid) Gaussians.

    The pad rows are benign everywhere: finite math through preprocess
    (unit quaternion, unit scale, zero SH) but ``valid=False`` for every
    pose via the opacity cull — so the padded scene renders bit-identical
    to the original.
    """
    n = scene.num_gaussians
    if n_bucket < n:
        raise ValueError(f"cannot pad {n} Gaussians down to {n_bucket}")
    if n_bucket == n:
        return scene
    p = n_bucket - n
    quats = jnp.zeros((p, 4), scene.quats.dtype).at[:, 0].set(1.0)
    return GaussianScene(
        means=jnp.concatenate(
            [scene.means, jnp.zeros((p, 3), scene.means.dtype)]),
        log_scales=jnp.concatenate(
            [scene.log_scales, jnp.zeros((p, 3), scene.log_scales.dtype)]),
        quats=jnp.concatenate([scene.quats, quats]),
        opacity_logits=jnp.concatenate(
            [scene.opacity_logits,
             jnp.full((p,), PAD_OPACITY_LOGIT,
                      scene.opacity_logits.dtype)]),
        sh=jnp.concatenate(
            [scene.sh, jnp.zeros((p,) + scene.sh.shape[1:],
                                 scene.sh.dtype)]))


@dataclasses.dataclass
class SceneEntry:
    """One registered scene (already padded to its bucket).

    ``bucket`` is the scene's *stackable shape signature*
    ``(padded N, SH coefficient count K)``: two scenes stack into one
    ``(S, N, ...)`` pytree — and therefore share an executable — iff
    their buckets are equal. N alone is not enough: a degree-0 and a
    degree-1 scene have different ``sh`` shapes, which are different
    lowerings just like different N.
    """

    scene_id: int
    scene: GaussianScene        # padded: num_gaussians == bucket[0]
    true_n: int                 # Gaussians before padding
    bucket: Tuple[int, int]     # (padded N, sh K) — what the cache keys on
    registered_at: float = 0.0
    refs: int = 0               # live sessions pinned to this scene
    streams_seen: int = 0       # lifetime attach count (metrics)
    padded_bytes: int = 0       # device bytes of the padded scene arrays


def scene_bytes(scene: GaussianScene) -> int:
    """Total bytes of a scene pytree's arrays — the residency a padded
    scene actually occupies (obs gauges read this per bucket)."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(scene))


class SceneRegistry:
    """Register/evict scenes; group them by padded-N bucket.

    The registry is host-side bookkeeping — scene arrays live on device
    (whatever backing ``jnp.concatenate`` produced at registration) and
    are handed to the executable as traced inputs. ``stack`` builds the
    per-round ``(S, N_bucket, ...)`` stacked pytree the engine's
    ``slot_scene`` gather indexes (core/engine.py).
    """

    def __init__(self, buckets: Sequence[int] = DEFAULT_SCENE_BUCKETS):
        validate_buckets(buckets, "scene_buckets")
        self.buckets = tuple(int(b) for b in buckets)
        self._entries: Dict[int, SceneEntry] = {}
        self._next_id = 0
        self.registered = 0
        self.evicted = 0

    # -- lifecycle ---------------------------------------------------------
    def register(self, scene: GaussianScene, *,
                 now: float = 0.0) -> SceneEntry:
        n_bucket = snap_scene_bucket(scene.num_gaussians, self.buckets)
        padded = pad_scene(scene, n_bucket)
        entry = SceneEntry(scene_id=self._next_id,
                           scene=padded,
                           true_n=scene.num_gaussians,
                           bucket=(n_bucket, int(scene.sh.shape[1])),
                           registered_at=now,
                           padded_bytes=scene_bytes(padded))
        self._next_id += 1
        self._entries[entry.scene_id] = entry
        self.registered += 1
        return entry

    def evict(self, scene_id: int) -> SceneEntry:
        entry = self.get(scene_id)
        if entry.refs > 0:
            raise ValueError(
                f"scene {scene_id} has {entry.refs} attached stream(s); "
                f"drain them before evicting")
        self.evicted += 1
        return self._entries.pop(scene_id)

    def acquire(self, scene_id: int) -> None:
        entry = self.get(scene_id)
        entry.refs += 1
        entry.streams_seen += 1

    def release(self, scene_id: int) -> None:
        entry = self.get(scene_id)
        if entry.refs <= 0:
            raise ValueError(f"scene {scene_id} released more than acquired")
        entry.refs -= 1

    # -- queries -----------------------------------------------------------
    def get(self, scene_id: int) -> SceneEntry:
        if scene_id not in self._entries:
            raise KeyError(f"unknown scene {scene_id!r}; registered: "
                           f"{self.ids()}")
        return self._entries[scene_id]

    def ids(self) -> Tuple[int, ...]:
        """Registration order — what traffic round-robins over."""
        return tuple(self._entries)

    def by_bucket(self, bucket: Tuple[int, int]) -> List[int]:
        return [i for i, e in self._entries.items() if e.bucket == bucket]

    def bucket_of(self, scene_id: int) -> Tuple[int, int]:
        return self.get(scene_id).bucket

    def buckets_in_use(self) -> Tuple[Tuple[int, int], ...]:
        return tuple(sorted({e.bucket for e in self._entries.values()}))

    def __contains__(self, scene_id: int) -> bool:
        return scene_id in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    # -- device-side view --------------------------------------------------
    def stack(self, scene_ids: Sequence[int], size: int) -> GaussianScene:
        """Stacked ``(size, N_bucket, ...)`` scene pytree for one round.

        ``scene_ids`` is the round's distinct scenes (the batcher's
        local-stack order — ``SlotBatch.slot_scene`` indexes it); the
        stack is padded to ``size`` by repeating the first entry so the
        stacked shape depends only on (bucket, B), never on how many
        distinct scenes happen to be in flight — the executable-cache
        key stays ``(scene_bucket, B, ...)`` with no S axis. All ids
        must share one bucket (the server's same-bucket-per-round rule).
        """
        if not scene_ids:
            raise ValueError("stack needs at least one scene id")
        if size < len(scene_ids):
            raise ValueError(f"{len(scene_ids)} scenes do not fit a "
                             f"stack of {size}")
        entries = [self.get(i) for i in scene_ids]
        buckets = {e.bucket for e in entries}
        if len(buckets) > 1:
            raise ValueError(
                f"one round's scenes must share a bucket, got {buckets}")
        scenes = [e.scene for e in entries]
        scenes += [scenes[0]] * (size - len(scenes))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *scenes)

    def residency(self) -> Dict[Tuple[int, int], dict]:
        """Per-bucket residency summary — scenes resident, padded bytes
        held on device, and live stream refcounts. This is what the
        server's ``scene_residency_*`` gauges publish (DESIGN.md §13)."""
        out: Dict[Tuple[int, int], dict] = {}
        for e in self._entries.values():
            r = out.setdefault(e.bucket, {"scenes": 0, "padded_bytes": 0,
                                          "refs": 0})
            r["scenes"] += 1
            r["padded_bytes"] += e.padded_bytes
            r["refs"] += e.refs
        return out

    def stats(self) -> dict:
        return {
            "scenes": len(self._entries),
            "registered": self.registered,
            "evicted": self.evicted,
            "buckets_in_use": list(self.buckets_in_use()),
            "padded_bytes": sum(e.padded_bytes
                                for e in self._entries.values()),
            "per_bucket": {str(b): r for b, r in self.residency().items()},
            "per_scene": {
                str(i): {"true_n": e.true_n, "bucket": e.bucket,
                         "refs": e.refs, "streams_seen": e.streams_seen,
                         "padded_bytes": e.padded_bytes}
                for i, e in self._entries.items()},
        }
