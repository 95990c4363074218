#!/usr/bin/env python3
"""Bring-up smoke run of the streaming renderer on TPU.

    python chip_smoke.py             # one chip: the serve path + kernel parity
    python chip_smoke.py --chips 4   # four chips: sharded serve vs one device

One process runs every phase. It drives the main path through the entry
points a user calls — ``SceneRegistry`` -> ``StreamServer`` ->
``warmup()`` -> ``attach()`` -> ``run()`` — with the raster compiled as
the fused Mosaic kernel (``impl="pallas_fused"``), and checks what comes
out. Any failed check, a missing TPU, or a copy of this file outside a
checkout of the repo exits non-zero before the final line.

Widths are those of ``configs/lsgaussian.py``: a 1920x1088 camera (8,160
16-pixel tiles), K = 1024 pairs per tile, SH degree 3. The scene is a
seeded ``structured_scene`` of 65,536 Gaussians, the largest scene
bucket whose pair budget is T x K; the benchmark's ``room2m_1080p_w5``
cell serves the config's 2 M Gaussians.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from typing import List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# pallas_fused against jnp_chunked (and sharded against one device), rgb
# in [0, 1]. Both sides run the same f32 math, but in different XLA
# programs whose fusion may move a value by an ulp; that can flip a blend
# test (alpha >= 1/255, T >= 1e-4) on a rare pixel, and one flipped
# Gaussian moves that pixel by at most ~1/255. The mean bounds the bulk.
MAX_ABS_TOL = 1e-2
MEAN_ABS_TOL = 1e-5
# A serve-step compile at these sizes takes tens of seconds; the eager
# host-side ops a serving round runs compile in milliseconds.
SERVE_COMPILE_SECONDS = 1.0


@dataclasses.dataclass(frozen=True)
class Size:
    """One smoke configuration (``full()`` is what the chip runs)."""

    width: int
    height: int
    gaussians: int
    sh_degree: int
    capacity: int                 # K: pairs per tile
    chunk: int                    # G: blend chunk
    streams: int                  # streams attached on one chip
    frames: int                   # frames per stream
    round_frames: int             # F: frames per stream per serve round
    r_buckets: Tuple[int, ...]    # sparse-frame slot counts R

    @classmethod
    def full(cls) -> "Size":
        from repro.configs.lsgaussian import CONFIG
        # 1080p sparse frames on these trajectories re-render 1,350-3,096
        # of 8,160 tiles (p50 2,304, on a TPU v5e), so R starts at 2,048
        # and adapts up to 4,096.
        return cls(width=CONFIG.image_width, height=CONFIG.image_height,
                   gaussians=65_536, sh_degree=CONFIG.sh_degree,
                   capacity=CONFIG.tile_capacity, chunk=64, streams=2,
                   frames=6, round_frames=4, r_buckets=(2048, 4096))


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compiles, tagged with the phase they happened in."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.events: List[Tuple[str, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((self.phase, str(kw.get("fun_name")), secs))

    def of(self, phase: str) -> List[Tuple[str, float]]:
        return [(n, s) for p, n, s in self.events if p == phase]


def trajectories(n: int, frames: int, seed: int) -> List["np.ndarray"]:
    """Seeded dolly / orbit camera paths at the paper's 90 FPS motion."""
    import numpy as np
    from repro.scenes.trajectory import dolly_trajectory, orbit_trajectory
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if i % 2 == 0:
            start = (rng.uniform(-0.4, 0.4), rng.uniform(-0.4, 0.1),
                     rng.uniform(-3.0, -1.5))
            out.append(np.asarray(dolly_trajectory(
                frames, start=start, target=(0.0, 0.0, 6.0))))
        else:
            out.append(np.asarray(orbit_trajectory(
                frames, radius=rng.uniform(5.0, 8.0), target=(0.0, 0.0, 6.0),
                height=rng.uniform(-1.0, 0.0))))
    return out


def check_agreement(name: str, a, b) -> None:
    import numpy as np
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    mx, mean = float(d.max()), float(d.mean())
    say(f"{name}: max_abs_diff={mx!r} mean_abs_diff={mean!r} "
        f"(tolerance max {MAX_ABS_TOL}, mean {MEAN_ABS_TOL})")
    check(mx <= MAX_ABS_TOL and mean <= MEAN_ABS_TOL,
          f"{name} outside tolerance")


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def build(size: Size, seed: int, n_scenes: int, slots: int,
          round_frames: int, r_buckets: Sequence[int]):
    """Scenes, camera and server, built through the public entry points."""
    import jax
    from repro.core.camera import make_camera, look_at
    from repro.core.pipeline import RenderConfig
    from repro.scenes.synthetic import structured_scene
    from repro.serve import SceneRegistry, ServeConfig, StreamServer

    registry = SceneRegistry()
    ids = [registry.register(structured_scene(
        jax.random.PRNGKey(seed + s), size.gaussians,
        sh_degree=size.sh_degree)).scene_id for s in range(n_scenes)]
    cam = make_camera(look_at((0.0, -0.3, -2.0), (0.0, 0.0, 6.0)),
                      width=size.width, height=size.height)
    cfg = RenderConfig(capacity=size.capacity, chunk=size.chunk,
                       impl="pallas_fused")
    scfg = ServeConfig(slots=slots, chunk=round_frames,
                       r_buckets=tuple(r_buckets), collect_frames=True)
    return registry, ids, cam, cfg, StreamServer(registry, cam, cfg, scfg)


def lowered_serve_step(server, registry, ids, cam, cfg, r: int, mesh) -> str:
    """StableHLO text of the serve step the server builds for (B, R)."""
    import jax
    from repro.serve import build_render_fn
    fn = build_render_fn(cam, dataclasses.replace(cfg, rerender_capacity=r),
                         mesh, multi_scene=True)
    batch = server.batcher.empty_batch()
    stack = registry.stack(ids, len(batch.sids))
    return jax.jit(lambda *a: fn(*a).frames).lower(
        stack, batch.poses, batch.counts, batch.phases, batch.carries,
        batch.slot_scene).as_text()


def serve(server, ids, trajs, log: CompileLog):
    """warmup() then attach every stream and run() it to completion."""
    log.phase = "warmup"
    warmup_s = server.warmup()
    timing = server.cache.stats()["per_key_timing"]
    log.phase = "serve"
    sessions = [server.attach(p, scene_id=ids[i % len(ids)])
                for i, p in enumerate(trajs)]
    t0 = time.perf_counter()
    report = server.run()
    serve_s = time.perf_counter() - t0
    log.phase = "after"
    return warmup_s, timing, sessions, report, serve_s


def check_served(sessions, trajs, report):
    import numpy as np
    want = sum(len(p) for p in trajs)
    check(report["frames"] == want,
          f"served {report['frames']} of {want} queued frames")
    frames = []
    for sess, poses in zip(sessions, trajs):
        check(sess.done and sess.frames_rendered == len(poses),
              f"stream {sess.sid} rendered {sess.frames_rendered} of "
              f"{len(poses)}")
        frames.append(np.concatenate(sess.frames))
    check(all(np.isfinite(f).all() for f in frames), "non-finite frame")
    check(all(f.max() > 0.0 for f in frames), "an all-black stream")


def run_one_chip(size: Size, seed: int, log: CompileLog) -> None:
    """The serve path on one device, then fused-vs-jnp kernel parity."""
    import jax
    import numpy as np
    from repro.core.pipeline import render_full_frame, render_sparse_frame

    registry, ids, cam, cfg, server = build(
        size, seed, 1, 1, size.round_frames, size.r_buckets)
    say(f"config: N={size.gaussians} resolution={size.width}x"
        f"{size.height} tiles={cam.num_tiles} K={size.capacity} "
        f"chunk={size.chunk} sh_degree={size.sh_degree} B=1 "
        f"R_buckets={list(size.r_buckets)} F={size.round_frames} "
        f"impl={cfg.impl}")

    # The serve step as the server builds it, lowered: the raster must be
    # a Mosaic custom call, not an interpreted kernel.
    mosaic = "tpu_custom_call" in lowered_serve_step(
        server, registry, ids, cam, cfg, size.r_buckets[0], None)
    say(f"tpu_custom_call in lowered serve step: {mosaic}")
    check(mosaic, "raster is not a Mosaic kernel")

    trajs = trajectories(size.streams, size.frames, seed)
    warmup_s, timing, sessions, report, serve_s = serve(
        server, ids, trajs, log)
    compile_ms = sum(t["compile_ms"] or 0.0 for t in timing.values())
    check_served(sessions, trajs, report)
    counters = report["metrics"]["counters"]
    after = server.cache.stats()["per_key_timing"]
    slow = [(n, s) for n, s in log.of("serve")
            if s >= SERVE_COMPILE_SECONDS]
    say(f"served: frames={report['frames']} streams={len(sessions)} "
        f"rounds={report['busy_rounds']} all_finite=True "
        f"capacity_history={report['capacity_history']}")
    say(f"warmup_seconds={warmup_s!r} serve_compile_seconds="
        f"{compile_ms / 1e3!r} executables={len(timing)} "
        f"serve_wall_seconds={serve_s!r}")
    demand = report["metrics"]["histograms"]["device_rerender_demand"]
    say(f"rerender demand per sparse frame (tiles): count={demand['count']} "
        f"min={demand['min']} p50={demand['p50']} max={demand['max']}")
    say(f"overflow: tiles_past_R={counters['serve_overflow_tiles_total']} "
        f"pairs_past_K={counters['serve_overflow_pairs_total']}")
    say(f"compiles after warmup: serve executables="
        f"{len(after) - len(timing)} slow={slow} small_host_ops="
        f"{len(log.of('serve')) - len(slow)}")
    check(set(after) == set(timing) and all(
        after[k]["compile_ms"] == timing[k]["compile_ms"] for k in timing),
        "a serve executable compiled after warmup")
    check(not slow, f"compiles after warmup: {slow}")

    # Kernel parity on the chip: one key frame and one sparse frame warped
    # from the SAME reference state, fused kernel against jnp_chunked.
    scene = registry.get(ids[0]).scene
    poses = trajs[0]
    cam0, cam1 = cam.with_pose(poses[0]), cam.with_pose(poses[1])
    full = jax.jit(render_full_frame, static_argnames="cfg")
    sparse = jax.jit(render_sparse_frame, static_argnames="cfg")
    fcfg = dataclasses.replace(cfg, rerender_capacity=size.r_buckets[-1])
    jcfg = dataclasses.replace(fcfg, impl="jnp_chunked")
    key_f, state, _ = full(scene, cam0, cfg=fcfg)
    key_j, _, _ = full(scene, cam0, cfg=jcfg)
    sp_f, _, rec_f = sparse(scene, cam0, cam1, state, cfg=fcfg)
    sp_j, _, _ = sparse(scene, cam0, cam1, state, cfg=jcfg)
    rerendered = int(np.asarray(rec_f.active).sum())
    say(f"parity frames: key frame {cam.num_tiles} tiles, sparse frame "
        f"{rerendered} re-rendered tiles")
    check(rerendered > 0, "the sparse parity frame re-rendered nothing")
    check_agreement("parity key frame (pallas_fused vs jnp_chunked)",
                    key_f.rgb, key_j.rgb)
    check_agreement("parity sparse frame (pallas_fused vs jnp_chunked)",
                    sp_f, sp_j)
    check(bool(np.isfinite(np.asarray(sp_f)).all()), "non-finite parity")


def run_four_chips(size: Size, seed: int, log: CompileLog) -> None:
    """Sharded serving (4 slots, local B = 1) against one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import engine
    from repro.serve import stream_mesh

    r = size.r_buckets[0]
    registry, ids, cam, cfg, server = build(
        size, seed, 2, 4, size.frames, (r,))
    say(f"config: N={size.gaussians} x 2 scenes resolution={size.width}x"
        f"{size.height} K={size.capacity} slots=4 local_B=1 R={r} "
        f"F={size.frames} impl={cfg.impl}")

    # Placement, read off the lowered serve step: the shard_map takes the
    # scene stack's leaves replicated (no mesh axis) and every per-slot
    # input split over the 4-device "streams" axis.
    text = lowered_serve_step(server, registry, ids, cam, cfg, r,
                              stream_mesh(4))
    manual = re.search(r"sdy\.manual_computation\(.*?in_shardings=\[(.*?)\]"
                       r" out_shardings", text)
    check(manual is not None, "no shard_map in the lowered serve step")
    specs = re.findall(r"<@mesh, \[(.*?)\]>", manual.group(1))
    n_scene = len(jax.tree_util.tree_leaves(registry.stack(ids, 4)))
    replicated = all('"streams"' not in s for s in specs[:n_scene])
    split = bool(specs[n_scene:]) and all(
        s.startswith('{"streams"}') for s in specs[n_scene:])
    four = '"streams"=4' in text
    say(f"placement: streams_mesh_of_4={four} "
        f"scene_stack_replicated={replicated} slots_split={split} "
        f"tpu_custom_call={'tpu_custom_call' in text}")
    check(four, "the serve step's mesh is not 4 devices")
    check(replicated, "the scene stack is not replicated")
    check(split, "stream slots are not split over the devices")
    check("tpu_custom_call" in text, "raster is not a Mosaic kernel")

    trajs = trajectories(4, size.frames, seed)
    _, _, sessions, report, serve_s = serve(server, ids, trajs, log)
    check_served(sessions, trajs, report)
    peaks = [peak_bytes(d) for d in jax.devices()[:4]]
    say(f"sharded: devices={report['num_devices']} frames="
        f"{report['frames']} serve_wall_seconds={serve_s!r} "
        f"peak_bytes_per_device={peaks}")
    check(report["num_devices"] == 4,
          f"served on {report['num_devices']} devices, not 4")
    # Each device rendered its own 1080p stream, so their peaks are alike;
    # had everything landed on device 0 the others would hold next to
    # nothing.
    check(all(p is not None for p in peaks), "no memory stats")
    check(min(peaks) * 4 >= max(peaks), "a device did no rendering")

    # The reference: each stream alone on device 0 through render_streams.
    # Streams alternate between the two scenes, so a device that did not
    # see the whole (replicated) scene stack renders the wrong scene.
    rcfg = dataclasses.replace(cfg, rerender_capacity=r)
    for sess, poses in zip(sessions, trajs):
        ref = engine.render_streams(
            registry.stack([sess.scene_id], 1), cam,
            jnp.asarray(poses)[None], rcfg, phases=[sess.phase],
            slot_scene=[0])
        check_agreement(
            f"stream {sess.sid} scene {sess.scene_id} phase {sess.phase} "
            f"sharded vs single device", np.concatenate(sess.frames),
            ref.frames[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: not inside a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    log = CompileLog()
    size = Size.full()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(size, args.seed, log)
        else:
            run_one_chip(size, args.seed, log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"peak_bytes_in_use={peak_bytes(dev)} total_seconds="
        f"{time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
