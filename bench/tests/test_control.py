"""The control: the reference in bfloat16, in the program's place.

At a size the CPU holds, the control's frames have to fail the check
that the program's frames pass (on the chip it is run at each cell's
own size by ``bench/calibrate.py --control``). The reference in float32
must also agree with the program's own key and sparse frames to
rounding, or the check would compare against another renderer.
"""
import time

import jax
import numpy as np

import check
import harness
import poses
import reference
import scenes
from conftest import tiny_cell

def test_bfloat16_control_is_not_correct():
    cell = tiny_cell()
    m = harness.measure(cell, 2 ** 33 + 9, 3.0, False,
                        t_start=time.perf_counter(), require_tpu=False,
                        log=lambda s: None)
    quiet = lambda s: None
    sound = check.check(cell, m.scene_arrays, m.sample, log=quiet)
    control = check.check(cell, m.scene_arrays, m.sample, log=quiet,
                          dtype="bfloat16")
    assert sound.correct, sound.lines
    assert not control.correct, control.lines
    for name in ("key_rmse", "sparse_rmse"):
        assert control.lines[name]["value"] > \
            100 * sound.lines[name]["value"]


def test_reference_matches_program_key_frame():
    from repro.core.camera import make_camera
    from repro.core.gaussians import GaussianScene
    from repro.core.pipeline import RenderConfig, render_full_frame
    cell = tiny_cell()
    cfg = cell.config
    arr = scenes.make_scenes(dict(cfg["scene"],
                                  num_gaussians=cfg["num_gaussians"],
                                  sh_degree=cfg["sh_degree"]), 1, 5)
    pose = poses.Traffic(cell.mix, 5).pose(0, 3)
    scene = {k: v[0] for k, v in arr.items()}
    cam = make_camera(pose, width=cfg["image_width"],
                      height=cfg["image_height"], fov_deg=60.0)
    rcfg = RenderConfig(capacity=cfg["tile_capacity"], chunk=64,
                        impl="jnp_chunked")
    out, _, rec = jax.jit(render_full_frame, static_argnames="cfg")(
        GaussianScene(scene["means"], scene["log_scales"], scene["quats"],
                      scene["opacity_logits"], scene["sh"]), cam, cfg=rcfg)
    assert int(rec.overflow_pairs) == 0
    ref, dropped = reference.render_image(scene, pose, reference.intrinsics(
        cfg["image_width"], cfg["image_height"], 60.0))
    assert dropped == 0
    assert check.rmse(np.asarray(out.rgb), ref.rgb) < 1e-6


def test_reference_matches_program_sparse_frames():
    """Two chains of a key frame and four sparse frames, with R below the
    tiles the warp leaves to render, so the overflow rule is held too."""
    from repro.core.camera import make_camera
    from repro.core.engine import render_trajectory
    from repro.core.gaussians import GaussianScene
    from repro.core.pipeline import RenderConfig
    cell = tiny_cell()
    cfg = cell.config
    arr = scenes.make_scenes(dict(cfg["scene"],
                                  num_gaussians=cfg["num_gaussians"],
                                  sh_degree=cfg["sh_degree"]), 1, 5)
    traffic = poses.Traffic(cell.mix, 5)
    w2c = np.stack([traffic.pose(0, k) for k in range(10)])
    scene = {k: v[0] for k, v in arr.items()}
    cam = make_camera(w2c[0], width=cfg["image_width"],
                      height=cfg["image_height"], fov_deg=60.0)
    r = check.slots(cfg)
    rcfg = RenderConfig(capacity=cfg["tile_capacity"], chunk=64, window=5,
                        impl="jnp_chunked", rerender_capacity=r)
    out = render_trajectory(
        GaussianScene(scene["means"], scene["log_scales"], scene["quats"],
                      scene["opacity_logits"], scene["sh"]), cam,
        jax.numpy.asarray(w2c), cfg=rcfg)
    frames, rec = out[0], out[1]
    icam = reference.intrinsics(cfg["image_width"], cfg["image_height"],
                                60.0)
    overflowed = 0
    for k0 in (0, 5):
        for k, (want, info) in enumerate(
                reference.stream(scene, w2c[k0:k0 + 5], icam, slots=r),
                start=k0):
            if info["rerender"] is not None:
                assert info["rerender"] == int(np.sum(rec.active[k]))
                assert info["overflow_tiles"] == int(rec.overflow_tiles[k])
                overflowed += info["overflow_tiles"]
            assert check.rmse(np.asarray(frames[k]), want) < 1e-6, k
    assert overflowed > 0
