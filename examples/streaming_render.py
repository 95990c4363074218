"""End-to-end driver: LS-Gaussian streaming rendering over a trajectory.

Renders a 90 FPS camera path with TWSR (window n=5), DPES and TAIT via the
scanned streaming engine (ONE compiled executable for the whole
trajectory, stacked per-frame records); prints per-frame quality +
workload stats, then runs the accelerator simulator over the recorded
workloads — the full paper pipeline in one script. ``--streams B``
additionally renders B concurrent staggered camera sessions with one
vmapped dispatch (the many-users serving scenario); ``--scenes K``
attaches those streams round-robin over K distinct synthetic scenes
registered in a ``SceneRegistry`` (padded to one bucket, rendered
through the engine's per-slot scene gather — DESIGN.md §10).

  PYTHONPATH=src python examples/streaming_render.py --frames 20
  PYTHONPATH=src python examples/streaming_render.py --streams 4
  PYTHONPATH=src python examples/streaming_render.py --streams 4 --scenes 3
  PYTHONPATH=src python examples/streaming_render.py --impl pallas_fused

``--impl`` selects the raster kernel (DESIGN.md §9); ``default`` picks
the fused Pallas plan-slot kernel on TPU and jnp elsewhere.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.camera import make_camera
from repro.core.engine import render_streams, render_trajectory
from repro.core.metrics import psnr, ssim
from repro.core.pipeline import RenderConfig, render_full_frame
from repro.core.streaming import AcceleratorConfig, frameworks_from_stacked, \
    simulate_sequence, throughput
from repro.scenes.synthetic import structured_scene
from repro.scenes.trajectory import dolly_trajectory


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=20)
    ap.add_argument("--window", type=int, default=5)
    ap.add_argument("--size", type=int, default=192)
    ap.add_argument("--gaussians", type=int, default=3000)
    ap.add_argument("--streams", type=int, default=0,
                    help="also render B concurrent staggered streams")
    ap.add_argument("--scenes", type=int, default=1,
                    help="attach the streams round-robin over K distinct "
                         "scenes (implies --streams >= K)")
    from repro.kernels.ops import RASTER_IMPLS, default_impl
    ap.add_argument("--impl", default="default",
                    choices=("default",) + RASTER_IMPLS,
                    help="raster kernel (default: per-backend choice)")
    args = ap.parse_args()

    impl = default_impl() if args.impl == "default" else args.impl

    scene = structured_scene(jax.random.PRNGKey(7), args.gaussians,
                             clutter=0.35)
    cam = make_camera(jax.numpy.eye(4), width=args.size, height=args.size)
    poses = dolly_trajectory(args.frames, start=(0.0, -0.3, -3.0),
                             target=(0.0, 0.0, 6.0))
    cfg = RenderConfig(window=args.window, impl=impl)
    print(f"raster impl: {impl} (backend: {jax.default_backend()})")

    print(f"streaming {args.frames} frames, window n={args.window} "
          f"(1 full render per {args.window} frames, single lax.scan)")
    res = render_trajectory(scene, cam, poses, cfg)

    # stacked record arrays: one host transfer for the whole trajectory
    is_full = np.asarray(res.records.is_full)
    active = np.asarray(res.records.active).sum(axis=1)
    interp = np.asarray(res.records.tiles_interpolated)
    raster_pairs = np.asarray(res.records.raster_pairs).sum(axis=1)

    full_fn = jax.jit(render_full_frame, static_argnames="cfg")
    total_pairs_full = 0
    for f in range(args.frames):
        ref, _, _ = full_fn(scene, cam.with_pose(poses[f]), cfg=cfg)
        q = float(psnr(res.frames[f], ref.rgb))
        kind = "FULL  " if is_full[f] else "sparse"
        total_pairs_full += int(ref.processed_pairs.sum())
        print(f"frame {f:3d} [{kind}] psnr={q:6.2f}dB "
              f"rr_tiles={int(active[f]):3d} "
              f"interp={int(interp[f]):3d} "
              f"pairs={int(raster_pairs[f]):6d}")
    total_pairs_sparse = int(raster_pairs.sum())
    print(f"\nrasterized pairs: {total_pairs_sparse} vs always-full "
          f"{total_pairs_full} -> {total_pairs_full / max(total_pairs_sparse, 1):.2f}x reduction")

    # accelerator simulation over the recorded workloads
    frames = frameworks_from_stacked(res.records, cam.tiles_x, cam.tiles_y,
                                     args.size * args.size)
    acfg = AcceleratorConfig(num_blocks=32)
    gpu = throughput(simulate_sequence(
        frames, acfg, policy="dynamic", workload_source="raw",
        light_to_heavy=False, streaming=False), acfg.num_blocks)
    ls = throughput(simulate_sequence(
        frames, acfg, policy="ls_gaussian", workload_source="dpes",
        light_to_heavy=True, streaming=True), acfg.num_blocks)
    print(f"accelerator sim: {gpu['cycles_per_frame']:.0f} -> "
          f"{ls['cycles_per_frame']:.0f} cycles/frame "
          f"({gpu['cycles_per_frame'] / ls['cycles_per_frame']:.2f}x), "
          f"raster utilization {100 * gpu['utilization']:.0f}% -> "
          f"{100 * ls['utilization']:.0f}%")

    if args.scenes > 1:
        args.streams = max(args.streams, args.scenes)
    if args.streams > 0:
        b = args.streams
        k = max(args.scenes, 1)
        offsets = np.linspace(0.0, 0.1, b)
        poses_b = jnp.stack([
            dolly_trajectory(args.frames, start=(float(dx), -0.3, -3.0),
                             target=(0.0, 0.0, 6.0)) for dx in offsets])
        if k > 1:
            # Multi-scene serving shape: K same-bucket scenes stacked by
            # a SceneRegistry, streams assigned round-robin, the engine
            # gathering each slot's scene on device (DESIGN.md §10).
            from repro.serve import SceneRegistry
            from repro.serve.scenes import DEFAULT_SCENE_BUCKETS
            # Extend the bucket ladder past --gaussians so any requested
            # scene size registers (a scene is never truncated).
            buckets = list(DEFAULT_SCENE_BUCKETS)
            while buckets[-1] < args.gaussians:
                buckets.append(buckets[-1] * 2)
            registry = SceneRegistry(tuple(buckets))
            registry.register(scene)
            for i in range(1, k):
                registry.register(structured_scene(
                    jax.random.PRNGKey(100 + i), args.gaussians,
                    clutter=0.2 + 0.5 * (i % 3) / 2))
            slot_scene = np.arange(b) % k
            stacked = registry.stack(list(registry.ids()[:k]), b)
            bucket = registry.get(registry.ids()[0]).bucket
            print(f"\nbatched multi-scene serving: {b} streams round-robin "
                  f"over {k} scenes (bucket {bucket}), one vmapped scan")
            print(f"slot -> scene: {slot_scene.tolist()}")
            sres = render_streams(stacked, cam, poses_b, cfg,
                                  slot_scene=slot_scene)
        else:
            print(f"\nbatched serving: {b} concurrent streams, one vmapped "
                  f"scan, staggered key frames")
            sres = render_streams(scene, cam, poses_b, cfg)
        sfull = np.asarray(sres.records.is_full)        # (B, F)
        spairs = np.asarray(sres.records.raster_pairs).sum(axis=2)
        print(f"phases: {np.asarray(sres.phases).tolist()}")
        for f in range(args.frames):
            marks = "".join("F" if sfull[i, f] else "." for i in range(b))
            print(f"step {f:3d} [{marks}] full_renders={int(sfull[:, f].sum())} "
                  f"pairs={int(spairs[:, f].sum()):7d}")
        peak = int(sfull[:, 1:].sum(axis=0).max()) if args.frames > 1 else 0
        print(f"peak concurrent full renders after warmup: {peak} "
              f"(unstaggered would be {b})")


if __name__ == "__main__":
    main()
