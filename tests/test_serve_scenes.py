"""Multi-scene serving (DESIGN.md §10): scene-bucket padding parity, the
registry lifecycle, the (B, R) bucket policy, scene-aware slot packing,
the engine's slot_scene gather vs solo renders, end-to-end server
parity across scene mixing, chunk seams, and an elastic-B resize, and a
scene above 65,536 Gaussians served against the program's oracles."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binning, engine, intersect, pipeline, raster
from repro.core.camera import make_camera
from repro.core.pipeline import RenderConfig, render_full_frame
from repro.core.projection import preprocess
from repro.scenes.synthetic import random_blob_scene, structured_scene
from repro.scenes.trajectory import dolly_trajectory
from repro.serve import (AdmissionConfig, BucketPolicy, ContinuousBatcher,
                         SceneRegistry, ServeConfig, SessionManager,
                         StreamServer, pad_scene, snap_scene_bucket,
                         suggest_buckets)
from repro.serve.scenes import DEFAULT_SCENE_BUCKETS

_RECORD_FIELDS = ("is_full", "n_gaussians", "candidate_pairs", "raw_pairs",
                  "sort_pairs", "raster_pairs", "active",
                  "tiles_interpolated", "overflow_pairs", "overflow_tiles",
                  "block_of_tile", "order_in_block", "block_load")


def _poses(n, dx=0.0):
    return dolly_trajectory(n, start=(dx, -0.3, -2.0),
                            target=(0.0, 0.0, 6.0))


def _scenes(k, n=260, n_step=30):
    """k distinct same-bucket structured scenes (bucket 512 for the
    defaults: 260..260+30k Gaussians, SH degree 1)."""
    return [structured_scene(jax.random.PRNGKey(100 + i), n + n_step * i,
                             clutter=0.3 + 0.1 * i) for i in range(k)]


# --- scene-bucket padding (must be exact, not approximate) ----------------

def test_snap_scene_bucket():
    assert snap_scene_bucket(3, (256, 512)) == 256
    assert snap_scene_bucket(256, (256, 512)) == 256
    assert snap_scene_bucket(257, (256, 512)) == 512
    with pytest.raises(ValueError):
        snap_scene_bucket(513, (256, 512))      # scenes never truncate
    with pytest.raises(ValueError):
        snap_scene_bucket(10, (512, 256))       # buckets must ascend


def test_pad_scene_renders_bit_identical(small_scene, small_cam):
    """Padding Gaussians are invalid for every pose (opacity cull), so
    the padded scene is bit-identical in frames AND records — including
    n_gaussians, pair counts, and the LDU schedule."""
    padded = pad_scene(small_scene, 1024)
    assert padded.num_gaussians == 1024
    cfg = RenderConfig(capacity=128)
    fn = jax.jit(render_full_frame, static_argnames="cfg")
    out_p, _, rec_p = fn(padded, small_cam, cfg=cfg)
    out_o, _, rec_o = fn(small_scene, small_cam, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(out_p.rgb),
                                  np.asarray(out_o.rgb))
    for name in _RECORD_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(rec_p, name)),
            np.asarray(getattr(rec_o, name)), err_msg=name)
    with pytest.raises(ValueError):
        pad_scene(small_scene, small_scene.num_gaussians - 1)


def test_pad_scene_contrib_parity(small_scene, small_cam):
    """The contribution statistics obey the same padding contract: the
    padded scene's per-lane contributions are bit-identical, its
    per-Gaussian prior matches on the real prefix, and every padding
    Gaussian reads as never-considered (inf = keep-all)."""
    n = small_scene.num_gaussians
    padded = pad_scene(small_scene, 1024)
    cfg = RenderConfig(capacity=128, record_contrib=True)
    fn = jax.jit(render_full_frame, static_argnames="cfg")
    _, st_p, rec_p = fn(padded, small_cam, cfg=cfg)
    _, st_o, rec_o = fn(small_scene, small_cam, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(rec_p.lane_contrib),
                                  np.asarray(rec_o.lane_contrib))
    prior_p = np.asarray(st_p.contrib)
    prior_o = np.asarray(st_o.contrib)
    assert prior_p.shape == (1024,)
    np.testing.assert_array_equal(prior_p[:n], prior_o)
    assert np.all(np.isinf(prior_p[n:]))


# --- registry lifecycle ---------------------------------------------------

def test_registry_register_evict_refs():
    reg = SceneRegistry((256, 512))
    e0 = reg.register(_scenes(1)[0])            # 260 -> bucket 512
    e1 = reg.register(random_blob_scene(jax.random.PRNGKey(1), 100))
    assert e0.bucket == (512, 4) and e1.bucket == (256, 1)
    assert reg.ids() == (0, 1) and len(reg) == 2
    assert reg.by_bucket((512, 4)) == [0]
    assert reg.buckets_in_use() == ((256, 1), (512, 4))

    reg.acquire(e0.scene_id)
    with pytest.raises(ValueError):
        reg.evict(e0.scene_id)                  # pinned by a live stream
    reg.release(e0.scene_id)
    reg.evict(e0.scene_id)
    assert e0.scene_id not in reg and len(reg) == 1
    with pytest.raises(KeyError):
        reg.get(e0.scene_id)
    with pytest.raises(ValueError):
        reg.release(e1.scene_id)                # never acquired


def test_registry_stack_rules():
    reg = SceneRegistry((256, 512))
    a, b = (reg.register(s) for s in _scenes(2))
    blob = reg.register(random_blob_scene(jax.random.PRNGKey(2), 80))
    stack = reg.stack([a.scene_id, b.scene_id], 4)
    assert stack.means.shape == (4, 512, 3)     # padded to size w/ repeats
    np.testing.assert_array_equal(np.asarray(stack.means[2]),
                                  np.asarray(stack.means[0]))
    with pytest.raises(ValueError):
        reg.stack([a.scene_id, blob.scene_id], 4)   # bucket mismatch
    with pytest.raises(ValueError):
        reg.stack([a.scene_id, b.scene_id], 1)      # does not fit
    with pytest.raises(ValueError):
        reg.stack([], 2)


# --- the 2-axis (B, R) bucket policy --------------------------------------

def test_bucket_policy_picks():
    pol = BucketPolicy(b_buckets=(2, 4, 8), r_buckets=(4, 16))
    assert pol.max_keys == 6
    assert pol.pick_slots(0) == 2               # empty queue: smallest B
    assert pol.pick_slots(2) == 2
    assert pol.pick_slots(3) == 4
    assert pol.pick_slots(100) == 8             # flood: largest B caps
    assert pol.pick_capacity([]) == 4           # nothing observed yet
    assert pol.pick_capacity([3, 3, 3, 20]) == 16
    assert pol.pick(5, [2, 2]) == (8, 4)
    with pytest.raises(ValueError):
        BucketPolicy(b_buckets=(4, 2))
    with pytest.raises(ValueError):
        BucketPolicy(quantile=1.5)


def test_suggest_buckets_from_records():
    from types import SimpleNamespace
    t = 16
    active = np.zeros((6, t), bool)
    active[:, :2] = True
    recs = SimpleNamespace(active=active, overflow_tiles=np.full((6,), 8),
                           is_full=np.zeros((6,), bool))
    pol = BucketPolicy(b_buckets=(2, 4), r_buckets=(4, 16, 32))
    assert suggest_buckets(recs, queue_depth=3, policy=pol) == (4, 16)


# --- scene-aware slot packing + elastic resize ----------------------------

def test_batcher_packs_same_scene_groups(small_cam):
    """With group=2 over B=4 slots, same-scene streams co-locate into
    contiguous groups regardless of arrival interleaving."""
    m = SessionManager(window=4)
    bat = ContinuousBatcher(slots=4, chunk=2, cam=small_cam, group=2)
    eye = np.eye(4, dtype=np.float32)
    order = [10, 20, 10, 20]                    # interleaved scene ids
    sessions = [m.attach(np.stack([eye] * 2), scene_id=s) for s in order]
    assert bat.admit(m) == 4
    batch = bat.build(m)
    by_slot = [m.sessions[sid].scene_id for sid in batch.sids]
    assert by_slot == [10, 10, 20, 20]          # grouped, not interleaved
    # slot_scene indexes the round's distinct scene_ids
    assert batch.scene_ids == (10, 20)
    assert np.asarray(batch.slot_scene).tolist() == [0, 0, 1, 1]
    assert sessions[0].slot == 0                # oldest kept its group


def test_batcher_admit_allowed_filter(small_cam):
    m = SessionManager(window=4)
    bat = ContinuousBatcher(slots=2, chunk=2, cam=small_cam)
    eye = np.eye(4, dtype=np.float32)
    s_a = m.attach(np.stack([eye] * 2), scene_id=1)
    s_b = m.attach(np.stack([eye] * 2), scene_id=2)
    assert bat.admit(m, allowed={2}) == 1       # bucket rule: only scene 2
    assert s_a.slot is None and s_b.slot == 0


def test_batcher_resize_preserves_carries(small_cam):
    m = SessionManager(window=4)
    bat = ContinuousBatcher(slots=3, chunk=2, cam=small_cam)
    eye = np.eye(4, dtype=np.float32)
    sessions = [m.attach(np.stack([eye] * 4), scene_id=0) for _ in range(3)]
    bat.admit(m)
    carry = engine.init_carry(small_cam, eye)
    for s in sessions:
        s.carry = carry
    unbound = bat.resize(2, m)
    assert unbound == [sessions[2].sid]
    assert bat.slots == 2 and sessions[2].slot is None
    assert sessions[2].carry is carry           # carry untouched by unbind
    assert [s.sid for s in m.waiting()] == [sessions[2].sid]
    bat.resize(4, m)
    assert bat.slots == 4 and bat.admit(m) == 1  # rebinds the unbound one
    assert bat.empty_batch().poses.shape == (4, 2, 4, 4)
    assert bat.empty_batch(slots=2).counts.shape == (2,)


# --- slot_scene gather parity vs solo renders -----------------------------

def test_multi_scene_streams_match_solo(small_cam):
    """Streams attached to DIFFERENT scenes through the stacked
    slot_scene gather bit-match their solo single-scene renders (records
    exact, frames to float tolerance) across phases and ragged counts;
    masked slots (scene 0) stay blank."""
    reg = SceneRegistry((256, 512))
    entries = [reg.register(s) for s in _scenes(2)]
    cfg = RenderConfig(window=3, rerender_capacity=8, capacity=128)
    b, f = 4, 5
    slot_scene = (0, 1, 1, 0)
    counts = (5, 4, 3, 0)
    phases = (0, 1, 2, 0)
    poses = jnp.stack([_poses(f, dx=0.04 * i) for i in range(b)])
    stack = reg.stack([e.scene_id for e in entries], b)
    res = engine.render_streams(stack, small_cam, poses, cfg,
                                phases=phases, counts=counts,
                                slot_scene=slot_scene)
    for i in range(b):
        if counts[i] == 0:
            np.testing.assert_array_equal(np.asarray(res.frames[i]), 0.0)
            continue
        solo = engine.render_trajectory(entries[slot_scene[i]].scene,
                                        small_cam, poses[i], cfg,
                                        phase=phases[i])
        c = counts[i]
        np.testing.assert_allclose(np.asarray(res.frames[i][:c]),
                                   np.asarray(solo.frames[:c]), atol=1e-5)
        for name in _RECORD_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res.records, name))[i, :c],
                np.asarray(getattr(solo.records, name))[:c],
                err_msg=f"slot{i}:{name}")


# --- end-to-end: scene mixing + chunk seams + a B-resize event ------------

def test_server_multi_scene_resize_parity(small_cam):
    """Four streams over two scenes served through elastic-B rounds
    (including a forced shrink/grow resize mid-flight) reproduce their
    solo trajectories: chunk seams, slot unbinding, and scene stacking
    all preserve the carries bit-exactly (frames to float tolerance)."""
    reg = SceneRegistry((256, 512))
    entries = [reg.register(s) for s in _scenes(2)]
    # One R bucket so the solo reference can pin the same
    # rerender_capacity (an adapting R mid-trajectory has no solo
    # equivalent — that axis is covered by test_serve's demand tests).
    cfg = RenderConfig(window=3, capacity=128, rerender_capacity=8)
    scfg = ServeConfig(chunk=2, r_buckets=(8,), b_buckets=(2, 4),
                       adapt_every=2, collect_frames=True,
                       scene_buckets=(256, 512))
    srv = StreamServer(reg, small_cam, cfg, scfg)

    total = 7
    sessions = []
    for i in range(4):
        sessions.append(srv.attach(
            np.asarray(_poses(total, dx=0.05 * i)),
            scene_id=entries[i % 2].scene_id))
    # queue depth 4 -> first busy round resizes 2 -> 4
    assert srv.batcher.slots == 2
    srv.step()
    assert srv.batcher.slots == 4 and srv.slots_history == [2, 4]

    # force a shrink mid-flight: detach-eligible streams drain at
    # different times because chunk=2 over 7 frames staggers by arrival;
    # keep stepping until everything drained (max_rounds bounds it).
    report = srv.run(max_rounds=30)
    assert report["streams_finished"] == 4
    assert not srv.manager.sessions and srv.batcher.bound == 0
    assert len(set(report["slots_history"])) >= 2   # a resize was served

    for i, sess in enumerate(sessions):
        got = np.concatenate(sess.frames)
        assert got.shape[0] == total
        solo = engine.render_trajectory(entries[i % 2].scene, small_cam,
                                        jnp.asarray(_poses(total,
                                                           dx=0.05 * i)),
                                        cfg, phase=sess.phase)
        np.testing.assert_allclose(got, np.asarray(solo.frames), atol=1e-5)

    # every scene's refcount released; eviction now legal
    for e in entries:
        assert reg.get(e.scene_id).refs == 0
        srv.evict_scene(e.scene_id)
    assert len(reg) == 0


def test_server_detach_releases_scene_pin(small_cam):
    """Cancelling via the server (not bare manager.detach) drops the
    scene refcount, so eviction stays possible after cancellations."""
    reg = SceneRegistry((256, 512))
    entry = reg.register(_scenes(1)[0])
    srv = StreamServer(reg, small_cam,
                       RenderConfig(window=3, capacity=128),
                       ServeConfig(slots=2, chunk=2, r_buckets=(8,),
                                   scene_buckets=(256, 512)))
    sess = srv.attach(np.asarray(_poses(4)), scene_id=entry.scene_id)
    assert reg.get(entry.scene_id).refs == 1
    srv.detach(sess.sid)
    assert reg.get(entry.scene_id).refs == 0
    srv.evict_scene(entry.scene_id)     # no longer pinned
    assert len(reg) == 0


@pytest.mark.slow
def test_sharded_multi_scene_matches_single_device():
    """8 slots over 8 host devices with 4 distinct scenes and contiguous
    scene groups of B/D slots (local B=1 -> per-device scene gather +
    real lax.cond): frames within 1e-5 and records bit-exact vs the
    plain single-logical-batch slot_scene path."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(repo, "src"), JAX_PLATFORMS="cpu")
    script = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.core.camera import make_camera, look_at
        from repro.core.pipeline import RenderConfig
        from repro.scenes.synthetic import structured_scene
        from repro.scenes.trajectory import dolly_trajectory
        from repro.serve import SceneRegistry, build_render_fn, stream_mesh

        reg = SceneRegistry((256, 512))
        ids = [reg.register(structured_scene(
            jax.random.PRNGKey(50 + i), 260 + 20 * i,
            clutter=0.4 + 0.1 * i)).scene_id for i in range(4)]
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
        # contiguous scene groups of B/D = 1..2 slots
        slot_scene = jnp.asarray([0, 0, 1, 1, 2, 2, 3, 3], jnp.int32)
        stack = reg.stack(ids, b)

        mesh = stream_mesh(b)
        assert mesh is not None and mesh.size == 8, mesh
        sharded = build_render_fn(cam, cfg, mesh, multi_scene=True)(
            stack, poses, counts, phases, carries, slot_scene)
        plain = build_render_fn(cam, cfg, None, multi_scene=True)(
            stack, poses, counts, phases, carries, slot_scene)
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


def test_server_bucket_isolation_and_reuse(small_cam):
    """Scenes in different (N, K) buckets are served in separate slot
    GROUPS — each group single-bucket through its own executable, but a
    ragged round may dispatch both groups together (DESIGN.md §11) —
    while same-bucket scenes share one executable. The cache never
    compiles more than one executable per key."""
    reg = SceneRegistry((256, 512))
    same_a, same_b = [reg.register(s) for s in _scenes(2)]
    blob = reg.register(random_blob_scene(jax.random.PRNGKey(5), 90))
    cfg = RenderConfig(window=3, capacity=128)
    scfg = ServeConfig(slots=2, chunk=2, r_buckets=(8,),
                       scene_buckets=(256, 512))
    srv = StreamServer(reg, small_cam, cfg, scfg)
    for sid in (same_a.scene_id, same_b.scene_id, blob.scene_id):
        srv.attach(np.asarray(_poses(4)), scene_id=sid)
    report = srv.run(max_rounds=20)
    assert report["streams_finished"] == 3
    # one executable per scene bucket (B and R are single-bucket here)
    assert report["cache"]["distinct_executables"] == 2
    assert report["cache"]["hits"] >= 1     # same-bucket scenes reused one
    # every GROUP is single-bucket (the stackability invariant) ...
    for r in report["rounds_trace"]:
        for g in r.get("groups", []):
            buckets = {reg.bucket_of(i) for i in g["scene_ids"]}
            assert buckets <= {tuple(g["scene_bucket"])}
    # ... and with both buckets demanding from round one, the default
    # (mixed, uncapped) planner actually mixed them in one round.
    assert any(len(r.get("groups", [])) > 1
               for r in report["rounds_trace"])


def test_server_skew_starvation_bounded_wait(small_cam):
    """The starvation regression (the bug this PR fixes): 10:1 stream
    skew across two scene buckets with ``max_groups_per_round=1`` (the
    worst case — only one bucket can render per round). Aging must bound
    the minority bucket's wait by ``max_wait_rounds``, every stream must
    finish, and the mixed-round frames must match solo renders exactly
    (the scheduler moves WHEN a stream renders, never WHAT it renders).
    """
    reg = SceneRegistry((256, 512))
    major = reg.register(_scenes(1)[0])                        # (512, 4)
    minor = reg.register(random_blob_scene(jax.random.PRNGKey(7), 90))
    cfg = RenderConfig(window=3, capacity=128, rerender_capacity=8)
    scfg = ServeConfig(chunk=2, r_buckets=(8,), b_buckets=(2, 4),
                       scene_buckets=(256, 512), collect_frames=True,
                       admission=AdmissionConfig(max_wait_rounds=2,
                                                 max_groups_per_round=1))
    srv = StreamServer(reg, small_cam, cfg, scfg)

    total = 4
    majors = [srv.attach(np.asarray(_poses(total, dx=0.04 * i)),
                         scene_id=major.scene_id) for i in range(10)]
    minority = srv.attach(np.asarray(_poses(total, dx=-0.2)),
                          scene_id=minor.scene_id)
    report = srv.run(max_rounds=60)
    assert report["streams_finished"] == 11

    # the wait bound held for EVERY bucket, lifetime max
    assert report["fairness"]["max_wait_rounds"] <= 2
    minority_stats = report["per_bucket"][str(minor.bucket)]
    assert minority_stats["frames"] == total
    assert minority_stats["max_wait_rounds"] <= 2
    assert minority_stats["served_rounds"] >= 1
    assert 0.0 < minority_stats["share"] <= 1.0
    assert 0.0 < report["fairness"]["jain_service"] <= 1.0
    # one-bucket-per-round cap respected
    assert all(len(r.get("groups", [])) <= 1
               for r in report["rounds_trace"])
    # compile bound: <= policy.max_keys per bucket in use (2 B x 1 R x 2)
    assert report["cache"]["distinct_executables"] <= 4

    # scheduling changed WHEN, not WHAT: bit-parity vs solo renders
    for sess, entry in ((minority, minor), (majors[0], major)):
        got = np.concatenate(sess.frames)
        solo = engine.render_trajectory(
            entry.scene, small_cam,
            jnp.asarray(_poses(total, dx=-0.2 if sess is minority
                               else 0.0)),
            cfg, phase=sess.phase)
        np.testing.assert_allclose(got, np.asarray(solo.frames),
                                   atol=1e-5)


# --- scenes above 65,536 Gaussians ----------------------------------------

def test_default_ladder_reaches_the_paper_scene():
    """The default ladder runs in powers of two to 2**21, which holds
    the 2,000,000 Gaussians of the paper's scenes."""
    assert DEFAULT_SCENE_BUCKETS == tuple(2 ** e for e in range(8, 22))
    assert snap_scene_bucket(65_536) == 65_536
    assert snap_scene_bucket(70_000) == 131_072
    assert snap_scene_bucket(2_000_000) == 2_097_152
    with pytest.raises(ValueError):
        snap_scene_bucket(2 ** 21 + 1)


@pytest.fixture(scope="module")
def scene70k():
    return structured_scene(jax.random.PRNGKey(70), 70_000, clutter=0.5)


def _cam128x64():
    return make_camera(np.asarray(_poses(1)[0]), width=128, height=64)


def _large_cfg():
    return RenderConfig(window=5, capacity=64, rerender_capacity=16)


def test_large_scene_padding_renders_bit_identical(scene70k):
    """70,000 Gaussians register in the 131,072 bucket. A key frame and
    the four sparse frames after it render the padded scene as the
    unpadded one, frames and every record field bit for bit, though the
    two trace with different pair budgets (1,048,576 and 560,000)."""
    entry = SceneRegistry().register(scene70k)
    assert entry.bucket == (131_072, 4) and entry.true_n == 70_000
    cam, cfg = _cam128x64(), _large_cfg()
    poses = jnp.asarray(_poses(5))
    got = engine.render_trajectory(entry.scene, cam, poses, cfg)
    ref = engine.render_trajectory(scene70k, cam, poses, cfg)
    np.testing.assert_array_equal(np.asarray(got.frames),
                                  np.asarray(ref.frames))
    for name in pipeline.FrameRecord._fields:
        a, b = getattr(got.records, name), getattr(ref.records, name)
        if a is None:
            assert b is None
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert int(np.asarray(got.records.is_full).sum()) == 1
    assert int(np.asarray(got.records.stage1_pairs)[0]) > \
        cam.num_tiles * cfg.capacity


def test_large_scene_served_frames_match_oracles(scene70k, monkeypatch):
    """A window of 5 frames of the 70,000-Gaussian scene served through
    ``StreamServer`` equals the same frames binned by the dense ``top_k``
    oracle (``_dense_bins``); its key frame equals the dense mask's bins
    drawn by the plain raster (``render_from_bins``, ``kernels/ref.py``);
    and the serve counters hold
    the oracle's pair counts, the stage-1 ones those of the dense mask
    over the whole grid, none past the pair budget."""
    reg = SceneRegistry()
    entry = reg.register(scene70k)
    cam, cfg = _cam128x64(), _large_cfg()
    poses = np.asarray(_poses(5))
    srv = StreamServer(reg, cam, cfg, ServeConfig(
        slots=1, chunk=5, r_buckets=(16,), collect_frames=True))
    sess = srv.attach(poses, scene_id=entry.scene_id)
    counters = srv.run(max_rounds=10)["metrics"]["counters"]
    got = np.concatenate(sess.frames)
    assert got.shape[0] == 5

    monkeypatch.setattr(pipeline, "_pair_list_bins", pipeline._dense_bins)
    jax.clear_caches()      # retrace: the engine's jit is keyed on cfg
    dense = engine.render_trajectory(entry.scene, cam, jnp.asarray(poses),
                                     cfg, phase=sess.phase)
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_allclose(got, np.asarray(dense.frames), atol=1e-5)
    recs = dense.records
    full = np.asarray(recs.is_full)
    assert full[0] and not full.all()

    grid = intersect.make_tile_grid(cam)
    stage1 = [int(np.asarray(intersect.tait_stage1_mask(
        preprocess(entry.scene, cam.with_pose(jnp.asarray(p))), grid)).sum())
        for p in poses]
    np.testing.assert_array_equal(np.asarray(recs.stage1_pairs), stage1)
    assert counters["serve_stage1_pairs_total"] == sum(stage1)
    assert counters["serve_pair_budget_overflow_total"] == 0
    assert counters["serve_overflow_pairs_total"] == int(
        np.asarray(recs.overflow_pairs).sum()) > 0

    proj = preprocess(entry.scene, cam.with_pose(jnp.asarray(poses[0])))
    bins = binning.build_tile_bins(intersect.tait_mask(proj, grid),
                                   proj.depth, cfg.capacity)
    key = raster.render_from_bins(proj, bins, grid, impl="ref")
    np.testing.assert_allclose(got[0], np.asarray(key.rgb), atol=2e-5)
