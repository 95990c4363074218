"""Compile rehearsal of every cell's serve executable for a described
TPU v5e (``v5e:2x2``): the step each cell's window drives, at its own
shapes, with the raster compiled as the Mosaic kernel; and of the plain
reference's frame, at today's scenes and at the 2,000,000 Gaussians of
the paper's. Nothing runs, so this says nothing about results or speed;
it catches what the chip's compiler would refuse (layouts, VMEM,
memory) before a chip run.

The topology is described inside a module fixture, never while a
module is imported, and the persistent compile cache is off while it is
in use (a compile for a described chip cannot be read back here).
"""
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import harness

HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _cells():
    """(config, mix) of every cell."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return [(w["config"], w["traffic"])
                for w in json.load(f)["workloads"]]


def _cell(config: str, mix: str) -> harness.Cell:
    def read(*path):
        with open(os.path.join(harness.BENCH, *path)) as f:
            return json.load(f)
    traffic = read("traffic", mix + ".json")
    return harness.Cell(f"{config}.{mix}", read("configs", config + ".json"),
                        traffic, int(traffic["streams"]), [], [])


def _serve_step(cell, devices):
    """The cell's serve step and its arguments' shapes on ``devices``."""
    import jax.numpy as jnp
    import poses
    from repro.core.camera import make_camera
    from repro.core.gaussians import GaussianScene
    from repro.core.pipeline import RenderConfig
    from repro.serve import ContinuousBatcher, build_render_fn
    cfg, mix = cell.config, cell.mix
    b = int(mix["streams"])
    cam = make_camera(poses.Traffic(mix, 0).pose(0, 0),
                      width=cfg["image_width"], height=cfg["image_height"],
                      fov_deg=cfg["camera"]["fov_deg"])
    rcfg = RenderConfig(capacity=cfg["tile_capacity"],
                        chunk=cfg["render"]["chunk"],
                        window=cfg["render"]["window"],
                        impl=cfg["render"]["impl"],
                        rerender_capacity=cfg["serve"]["r_buckets"][-1])
    mesh = Mesh(np.asarray(devices[:b]), ("streams",)) if b > 1 else None
    fn = build_render_fn(cam, rcfg, mesh, multi_scene=True)
    n, k = cfg["num_gaussians"], (cfg["sh_degree"] + 1) ** 2
    scenes = jax.eval_shape(lambda: GaussianScene(
        jnp.zeros((b, n, 3)), jnp.zeros((b, n, 3)), jnp.zeros((b, n, 4)),
        jnp.zeros((b, n)), jnp.zeros((b, n, k, 3))))
    batch = jax.eval_shape(
        lambda: ContinuousBatcher(b, 1, cam).empty_batch())
    sharding = NamedSharding(mesh, P()) if mesh is not None \
        else SingleDeviceSharding(devices[0])
    sds = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        t)
    args = sds((scenes, batch.poses, batch.counts, batch.phases,
                batch.carries, batch.slot_scene))
    return jax.jit(lambda *a: fn(*a).frames), args


@pytest.mark.parametrize("config,mix", _cells())
def test_cell_serve_step_compiles_for_v5e(topo, config, mix):
    from repro.kernels import ops
    cell = _cell(config, mix)
    step, args = _serve_step(cell, topo.devices)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        jax.clear_caches()          # no CPU trace of the raster is reused
        try:
            compiled = step.lower(*args).compile()
        finally:
            jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("n,bound", [(65_536, 1_500_000_000),
                                     (2_000_000, 4_000_000_000)])
def test_reference_frame_compiles_for_v5e(topo, n, bound):
    """The reference's 1080p frame in 8,192 slots, on one chip. Its
    temporaries do not grow with the scene (the dense selection planned
    0.90 GB at 65,536 Gaussians and did not fit at 2,000,000)."""
    import jax.numpy as jnp
    import reference
    one = SingleDeviceSharding(topo.devices[0])
    f = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    scene = dict(means=f(n, 3), log_scales=f(n, 3), quats=f(n, 4),
                 opacity_logits=f(n), sh=f(n, 16, 3))
    compiled = reference._render_tiles.lower(
        scene, f(4, 4), f(8192, dtype=jnp.int32), f(8192),
        reference.intrinsics(1920, 1088, 60.0), jnp.dtype(jnp.float32),
        reference.CAPACITY).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= bound
