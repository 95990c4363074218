"""Serving subsystem (repro.serve): masked/ragged batching equivalence,
session lifecycle, continuous batcher bookkeeping, bucketed executable
cache, device placement, and the serve loop smoke."""
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.pipeline import RenderConfig
from repro.scenes.trajectory import dolly_trajectory
from repro.serve import (ContinuousBatcher, ExecutableCache, PoissonTraffic,
                         ServeConfig, SessionManager, StreamServer,
                         TrafficConfig, build_render_fn, snap_capacity,
                         stream_mesh, suggest_capacity)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RECORD_FIELDS = ("is_full", "n_gaussians", "candidate_pairs", "raw_pairs",
                  "sort_pairs", "raster_pairs", "active",
                  "tiles_interpolated", "overflow_pairs", "overflow_tiles",
                  "block_of_tile", "order_in_block", "block_load")


def _poses(n, dx=0.0):
    return dolly_trajectory(n, start=(dx, -0.3, -2.0),
                            target=(0.0, 0.0, 6.0))


def _assert_records_equal(got, ref, sl=slice(None), msg=""):
    for name in _RECORD_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name))[sl],
            np.asarray(getattr(ref, name))[sl], err_msg=f"{msg}:{name}")


# --- masked / ragged batching equivalence (the batcher's contract) --------

def test_masked_slots_match_solo(small_scene, small_cam):
    """A B-slot batch with idle slots and ragged counts: every active
    stream matches its solo ``render_trajectory`` to 1e-5 on frames and
    bit-exact on records, across phase offsets; padded frames read as
    zero frames / blanked records."""
    cfg = RenderConfig(window=3)
    b, f = 4, 5
    counts = (5, 0, 3, 0)
    phases = (0, 1, 2, 0)
    poses_b = jnp.stack([_poses(f, dx=0.04 * i) for i in range(b)])
    res = engine.render_streams(small_scene, small_cam, poses_b, cfg,
                                phases=phases, counts=counts)
    assert np.asarray(res.frame_active).tolist() == \
        [[k < c for k in range(f)] for c in counts]
    for i, c in enumerate(counts):
        if c == 0:
            assert not np.asarray(res.records.active)[i].any()
            np.testing.assert_array_equal(np.asarray(res.frames[i]), 0.0)
            continue
        solo = engine.render_trajectory(small_scene, small_cam, poses_b[i],
                                        cfg, phase=phases[i])
        # active prefix: bit-exact records, 1e-5 frames (scan prefix
        # property: frames 0..c-1 only depend on poses 0..c-1)
        np.testing.assert_allclose(np.asarray(res.frames[i][:c]),
                                   np.asarray(solo.frames[:c]), atol=1e-5)
        _assert_records_equal(res.records[i], solo.records.stacked,
                              sl=slice(0, c), msg=f"slot{i}")
        # masked tail: zero frames, no recorded work
        np.testing.assert_array_equal(np.asarray(res.frames[i][c:]), 0.0)
        assert not np.asarray(res.records.active)[i, c:].any()
        assert not np.asarray(res.records.is_full)[i, c:].any()


def test_chunked_resume_matches_one_shot(small_scene, small_cam):
    """Carry threading: a trajectory served in fixed-size chunks (ragged
    final chunk) is bit-identical in records and 1e-5 in frames to the
    one-shot scan — the key-frame schedule survives the chunk seams."""
    cfg = RenderConfig(window=3)
    b, chunk, total = 2, 4, 9
    phases = (1, 2)
    full = jnp.stack([_poses(total, dx=0.05 * i) for i in range(b)])
    ref = [engine.render_trajectory(small_scene, small_cam, full[i], cfg,
                                    phase=phases[i]) for i in range(b)]

    carries = engine.init_stream_carries(small_cam, full)
    got_frames = [[] for _ in range(b)]
    got_recs = [[] for _ in range(b)]
    for start in range(0, total, chunk):
        n = min(chunk, total - start)
        sl = full[:, start:start + n]
        pad = jnp.concatenate(
            [sl, jnp.repeat(sl[:, -1:], chunk - n, axis=1)], axis=1) \
            if n < chunk else sl
        res = engine.render_streams(small_scene, small_cam, pad, cfg,
                                    phases=phases,
                                    counts=(n,) * b, carries=carries)
        carries = res.carries
        for i in range(b):
            got_frames[i].append(np.asarray(res.frames[i][:n]))
            got_recs[i].append(
                jax.tree_util.tree_map(lambda a, i=i: np.asarray(a)[i, :n],
                                       res.records.stacked))
    for i in range(b):
        frames = np.concatenate(got_frames[i])
        np.testing.assert_allclose(frames, np.asarray(ref[i].frames),
                                   atol=1e-5)
        recs = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs), *got_recs[i])
        _assert_records_equal(recs, ref[i].records.stacked,
                              msg=f"stream{i}")


# --- sessions -------------------------------------------------------------

def test_phase_assignment_least_loaded():
    m = SessionManager(window=4)
    sessions = [m.attach(closed=False) for _ in range(6)]
    assert [s.phase for s in sessions] == [0, 1, 2, 3, 0, 1]
    m.detach(sessions[2].sid)       # frees phase 2
    assert m.attach(closed=False).phase == 2
    assert len(m) == 6


def test_session_queue_and_done():
    m = SessionManager(window=3)
    s = m.attach(np.stack([np.eye(4, dtype=np.float32)] * 4), now=1.0)
    assert len(s.pending) == 4 and s.closed and not s.done
    s.pending.clear()
    assert s.done
    live = m.attach(closed=False)
    live.submit(np.eye(4, dtype=np.float32)[None], now=2.0)
    assert not live.done  # open stream never auto-detaches
    with pytest.raises(ValueError):
        m.attach(closed=True)  # closed + empty would never detach
    assert m._phase_load == [1, 1, 0]  # the failed attach freed its phase


# --- batcher --------------------------------------------------------------

def test_batcher_admit_build_commit(small_cam):
    m = SessionManager(window=4)
    bat = ContinuousBatcher(slots=2, chunk=3, cam=small_cam)
    eye = np.eye(4, dtype=np.float32)
    s0 = m.attach(np.stack([eye] * 2), now=0.0)   # drains in round 1
    s1 = m.attach(np.stack([eye] * 4), now=0.0)
    s2 = m.attach(np.stack([eye] * 1), now=0.0)   # waits for a slot
    assert bat.admit(m) == 2 and bat.bound == 2
    batch = bat.build(m)
    assert batch.sids == (s0.sid, s1.sid)
    assert np.asarray(batch.counts).tolist() == [2, 3]
    assert batch.active_frames == 5
    assert s2.slot is None

    # commit with a fake result: carries echo back, all sessions advance
    fake = SimpleNamespace(carries=batch.carries)
    detached = bat.commit(batch, fake, m, now=1.5).detached
    assert [s.sid for s in detached] == [s0.sid]
    assert s0.frames_rendered == 2 and list(s0.latencies) == [1.5, 1.5]
    assert s1.frames_rendered == 3 and len(s1.pending) == 1
    assert bat.admit(m) == 1      # s2 takes the freed slot
    assert bat.build(m).sids == (s2.sid, s1.sid)


def test_batcher_external_detach_frees_slot(small_cam):
    """A stream cancelled via manager.detach mid-flight must not leak
    its slot."""
    m = SessionManager(window=4)
    bat = ContinuousBatcher(slots=1, chunk=2, cam=small_cam)
    eye = np.eye(4, dtype=np.float32)
    s0 = m.attach(np.stack([eye] * 4), now=0.0)
    bat.admit(m)
    batch = bat.build(m)
    m.detach(s0.sid)              # cancelled while the chunk renders
    assert bat.commit(batch, SimpleNamespace(carries=batch.carries),
                      m, now=1.0).detached == []
    assert bat.bound == 0         # the slot is free again
    s1 = m.attach(np.stack([eye] * 2), now=1.0)
    assert bat.admit(m) == 1 and bat.build(m).sids == (s1.sid,)

    # detach BETWEEN rounds (before build): build() itself frees the slot
    m.detach(s1.sid)
    assert bat.build(m).sids == (None,)
    assert bat.bound == 0
    s2 = m.attach(np.stack([eye] * 2), now=2.0)
    assert bat.admit(m) == 1 and bat.build(m).sids == (s2.sid,)


# --- bucketed cache + capacity selection ----------------------------------

def test_snap_capacity():
    assert snap_capacity(3, (8, 16, 32)) == 8
    assert snap_capacity(8, (8, 16, 32)) == 8
    assert snap_capacity(9, (8, 16, 32)) == 16
    assert snap_capacity(999, (8, 16, 32)) == 32


def test_suggest_capacity_from_records():
    # 6 sparse frames wanting 10 tiles (2 active + 8 overflow), 1 full
    # frame (ignored), 1 padding frame (masked out via frame_mask).
    t = 16
    active = np.zeros((8, t), bool)
    active[:, :2] = True
    overflow = np.full((8,), 8)
    is_full = np.zeros((8,), bool)
    is_full[0] = True
    active[7] = False
    overflow[7] = 0           # padding frame: would drag the quantile down
    mask = np.ones((8,), bool)
    mask[7] = False
    recs = SimpleNamespace(active=active, overflow_tiles=overflow,
                           is_full=is_full)
    assert suggest_capacity(recs, 0.9, (4, 16, 32), frame_mask=mask) == 16
    assert suggest_capacity(recs, 0.9, (4, 16, 32)) == 16  # quantile robust
    # no sparse frames observed -> smallest bucket
    empty = SimpleNamespace(active=active[:1], overflow_tiles=overflow[:1],
                            is_full=is_full[:1])
    assert suggest_capacity(empty, 0.9, (4, 16, 32)) == 4


def test_executable_cache_counts():
    cache = ExecutableCache()
    built = []
    fn_a = cache.get(("b8", "r16"), lambda: built.append("a") or (lambda: "a"))
    assert cache.get(("b8", "r16"), lambda: built.append("!") or None) is fn_a
    cache.get(("b8", "r32"), lambda: built.append("b") or (lambda: "b"))
    assert built == ["a", "b"]
    assert cache.stats()["distinct_executables"] == 2
    assert cache.hits == 1 and cache.misses == 2
    with pytest.raises(KeyError):
        cache.get(("never", "built"))


# --- placement ------------------------------------------------------------

def test_stream_mesh_single_device_degrades(small_scene, small_cam):
    assert stream_mesh(8) is None          # test process sees ONE device
    # mesh=None falls back to the plain engine path: same executable as
    # render_streams (shares shapes/cfg with test_masked_slots_match_solo
    # so this hits a warm jit cache).
    cfg = RenderConfig(window=3)
    b, f = 4, 5
    poses = jnp.stack([_poses(f, dx=0.04 * i) for i in range(b)])
    counts = jnp.asarray([5, 0, 3, 0], jnp.int32)
    phases = jnp.asarray([0, 1, 2, 0], jnp.int32)
    carries = engine.init_stream_carries(small_cam, poses)
    fn = build_render_fn(small_cam, cfg, None)
    got = fn(small_scene, poses, counts, phases, carries)
    ref = engine.render_streams(small_scene, small_cam, poses, cfg,
                                phases=phases, counts=counts)
    np.testing.assert_allclose(np.asarray(got.frames),
                               np.asarray(ref.frames), atol=1e-6)


@pytest.mark.slow
def test_sharded_streams_match_single_device():
    """8 slots over 8 host devices (local B=1 -> real lax.cond per
    device): frames within 1e-5 and records bit-exact vs the plain
    single-logical-batch path."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(_REPO, "src"), JAX_PLATFORMS="cpu")
    script = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.core.camera import make_camera, look_at
        from repro.core.pipeline import RenderConfig
        from repro.scenes.synthetic import structured_scene
        from repro.scenes.trajectory import dolly_trajectory
        from repro.serve import build_render_fn, stream_mesh

        scene = structured_scene(jax.random.PRNGKey(7), 300, clutter=0.5)
        cam = make_camera(look_at((0.0, -0.3, -2.0), (0.0, 0.0, 6.0)),
                          width=48, height=48)
        cfg = RenderConfig(window=3, rerender_capacity=4, capacity=256)
        b, f = 8, 4
        poses = jnp.stack([dolly_trajectory(
            f, start=(0.03 * i, -0.3, -2.0), target=(0.0, 0.0, 6.0))
            for i in range(b)])
        counts = jnp.asarray([4, 3, 4, 0, 2, 4, 1, 4], jnp.int32)
        phases = engine.stream_phases(b, cfg.window)
        carries = engine.init_stream_carries(cam, poses)

        mesh = stream_mesh(b)
        assert mesh is not None and mesh.size == 8, mesh
        sharded = build_render_fn(cam, cfg, mesh)(
            scene, poses, counts, phases, carries)
        plain = engine.render_streams(scene, cam, poses, cfg,
                                      phases=phases, counts=counts)
        err = float(jnp.max(jnp.abs(sharded.frames - plain.frames)))
        rec_ok = all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                     for a, b in zip(
                         jax.tree_util.tree_leaves(sharded.records.stacked),
                         jax.tree_util.tree_leaves(plain.records.stacked)))
        carry_ok = all(bool(np.allclose(np.asarray(a), np.asarray(b),
                                        atol=1e-5))
                       for a, b in zip(
                           jax.tree_util.tree_leaves(sharded.carries),
                           jax.tree_util.tree_leaves(plain.carries)))
        print(json.dumps({"err": err, "rec_ok": rec_ok,
                          "carry_ok": carry_ok}))
    """)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["err"] < 1e-5
    assert r["rec_ok"] and r["carry_ok"]


# --- serve loop smoke (the CI tier-1 smoke: 4 streams, 2 buckets) ---------

def test_serve_smoke(small_scene, small_cam):
    cfg = RenderConfig(window=4, capacity=256)
    scfg = ServeConfig(slots=4, chunk=3, r_buckets=(4, 8), quantile=0.9,
                       adapt_every=2)
    srv = StreamServer(small_scene, small_cam, cfg, scfg)
    traffic = PoissonTraffic(TrafficConfig(n_streams=4, rate=2.0,
                                           min_frames=4, max_frames=7,
                                           seed=1))
    rep = srv.run(traffic, max_rounds=40)
    assert rep["streams_served"] == 4
    assert rep["streams_finished"] == 4     # everything drained + detached
    assert rep["frames"] >= 16
    assert 0.0 < rep["slot_utilization"] <= 1.0
    assert rep["latency_p50_ms"] is not None
    assert rep["latency_p99_ms"] >= rep["latency_p50_ms"]
    # bucketed executables: at most one compile per R bucket
    assert rep["cache"]["distinct_executables"] <= len(scfg.r_buckets)
    assert rep["cache"]["misses"] == rep["cache"]["distinct_executables"]
    assert rep["capacity"] in scfg.r_buckets
    assert not srv.manager.sessions      # no leaked sessions or slots
    assert srv.batcher.bound == 0
