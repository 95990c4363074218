"""Quickstart: build a synthetic scene, render one frame, save a PPM,
then stream a short trajectory through the scanned engine.

  PYTHONPATH=src python examples/quickstart.py [--out /tmp/frame.ppm]
  PYTHONPATH=src python examples/quickstart.py --impl pallas_fused

``--impl`` selects the raster kernel (DESIGN.md §9): ``default`` picks
per backend (fused Pallas kernel on TPU, jnp elsewhere); forcing
``pallas_fused`` off-TPU runs the kernel in interpret mode — slow, but
exactly the CI parity smoke.
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.camera import look_at, make_camera
from repro.core.engine import render_trajectory
from repro.core.pipeline import RenderConfig, render_full_frame
from repro.scenes.synthetic import structured_scene
from repro.scenes.trajectory import dolly_trajectory


def save_ppm(path: str, img) -> None:
    arr = (np.clip(np.asarray(img), 0, 1) * 255).astype(np.uint8)
    with open(path, "wb") as f:
        f.write(f"P6\n{arr.shape[1]} {arr.shape[0]}\n255\n".encode())
        f.write(arr.tobytes())


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/quickstart.ppm")
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--gaussians", type=int, default=4000)
    ap.add_argument("--capacity", type=int, default=512,
                    help="K: max sorted pairs per tile")
    from repro.kernels.ops import RASTER_IMPLS, default_impl
    ap.add_argument("--impl", default="default",
                    choices=("default",) + RASTER_IMPLS,
                    help="raster kernel (default: per-backend choice)")
    args = ap.parse_args()

    impl = default_impl() if args.impl == "default" else args.impl

    scene = structured_scene(jax.random.PRNGKey(0), args.gaussians,
                             clutter=0.5)
    cam = make_camera(look_at((0.0, -0.5, -3.0), (0.0, 0.0, 6.0)),
                      width=args.size, height=args.size)
    cfg = RenderConfig(intersect_method="tait", capacity=args.capacity,
                      impl=impl)
    print(f"raster impl: {impl} (backend: {jax.default_backend()})")
    out, state, rec = jax.jit(render_full_frame,
                              static_argnames="cfg")(scene, cam, cfg=cfg)
    save_ppm(args.out, out.rgb)
    print(f"rendered {args.size}x{args.size} from {args.gaussians} "
          f"gaussians -> {args.out}")
    print(f"  pairs sorted:     {int(rec.sort_pairs.sum())}")
    print(f"  pairs rasterized: {int(rec.raster_pairs.sum())} "
          f"(early stop saved "
          f"{int(rec.sort_pairs.sum()) - int(rec.raster_pairs.sum())})")
    print(f"  mean coverage:    "
          f"{float(1 - out.transmittance.mean()):.3f}")

    # Stream a short trajectory: the whole full/sparse loop is ONE
    # compiled lax.scan — no per-frame dispatch from the host.
    n_frames, window = 6, 3
    poses = dolly_trajectory(n_frames, start=(0.0, -0.5, -3.0),
                             target=(0.0, 0.0, 6.0))
    res = render_trajectory(scene, cam, poses,
                            RenderConfig(window=window, impl=impl,
                                         capacity=args.capacity))
    full = np.asarray(res.records.is_full)
    pairs = np.asarray(res.records.raster_pairs).sum(axis=1)
    print(f"\nstreamed {n_frames} frames (window n={window}, one scan):")
    print(f"  schedule:         "
          f"{''.join('F' if f else 's' for f in full)}")
    print(f"  pairs per frame:  {pairs.tolist()}")
    print(f"  sparse-frame cost: "
          f"{pairs[~full].mean() / max(pairs[full].mean(), 1):.2f}x "
          f"of a full frame")


if __name__ == "__main__":
    main()
