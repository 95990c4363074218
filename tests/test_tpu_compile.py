"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler (Mosaic for Pallas kernels) refuses layouts that the
Pallas interpreter accepts: misaligned blocks, dynamic slices of values,
too much VMEM. These tests compile the main path's kernel and one key
frame at the widths of ``configs/lsgaussian.py`` (1920x1088, K = 1024)
for a described v5e chip, so such a refusal fails here and not on the
chip. Nothing runs: they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and the
xdist worker that runs this file is the one that takes it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.lsgaussian import CONFIG
from repro.core.camera import TILE, look_at, make_camera
from repro.core.gaussians import GaussianScene
from repro.core.pipeline import RenderConfig, render_full_frame
from repro.kernels import ops
from repro.kernels.raster_plan import raster_plan_fused

K = CONFIG.tile_capacity                                # 1024
TILES_1080P = (CONFIG.image_width // TILE) * (CONFIG.image_height // TILE)
SPARSE_R = 2048          # the smallest re-render bucket chip_smoke.py serves
HBM_BYTES = 16 * 10 ** 9                                # one v5e chip


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
        # A compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep it out of the cache.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile_fused(r, one_chip):
    f32 = jnp.float32
    shapes = [((r, K, 2), f32), ((r, K, 3), f32), ((r, K, 3), f32),
              ((r, K), f32), ((r, K), f32), ((r, 2), f32),
              ((r,), jnp.int32), ((r,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    fn = jax.jit(lambda *a: raster_plan_fused(*a, chunk=64,
                                              interpret=False))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("r", [TILES_1080P, SPARSE_R],
                         ids=["key_frame_R8160", "sparse_R2048"])
def test_fused_raster_compiles_for_v5e(one_chip, r):
    compiled = _compile_fused(r, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_1080p_key_frame_compiles_with_mosaic_raster(one_chip):
    """One 1080p key frame through ``render_full_frame`` with the fused
    kernel compiled, not interpreted: ``ops`` picks interpret mode from
    the attached backend, which here is the CPU, so the test steers it."""
    assert TILES_1080P == 8160
    n = 65_536
    cam = make_camera(look_at((0.0, -0.3, -2.0), (0.0, 0.0, 6.0)),
                      width=CONFIG.image_width, height=CONFIG.image_height)
    sh_k = (CONFIG.sh_degree + 1) ** 2
    scene = jax.eval_shape(lambda: GaussianScene(
        jnp.zeros((n, 3)), jnp.zeros((n, 3)), jnp.zeros((n, 4)),
        jnp.zeros((n,)), jnp.zeros((n, sh_k, 3))))
    cfg = RenderConfig(capacity=K, chunk=64, impl="pallas_fused")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        jax.clear_caches()      # no CPU trace of the raster may be reused
        try:
            compiled = jax.jit(render_full_frame, static_argnames="cfg").lower(
                _sds(scene, one_chip), _sds(cam, one_chip), cfg=cfg).compile()
        finally:
            jax.clear_caches()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
