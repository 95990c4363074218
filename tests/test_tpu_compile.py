"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler (Mosaic for Pallas kernels) refuses layouts that the
Pallas interpreter accepts: misaligned blocks, dynamic slices of values,
too much VMEM. These tests compile the main path's kernel, one key frame
and one sparse frame at the widths of ``configs/lsgaussian.py``
(1920x1088, K = 1024, 65,536 Gaussians) for a described v5e chip, so
such a refusal fails here and not on the chip, and hold the key frame's
planned temporaries below those of the dense binning it replaced.
Nothing runs: they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and the
xdist worker that runs this file is the one that takes it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.lsgaussian import CONFIG
from repro.core import pipeline
from repro.core.camera import TILE, look_at, make_camera
from repro.core.gaussians import GaussianScene
from repro.core.pipeline import (FrameState, RenderConfig,
                                 render_full_frame, render_sparse_frame)
from repro.kernels import ops
from repro.kernels.raster_plan import raster_plan_fused

K = CONFIG.tile_capacity                                # 1024
TILES_1080P = (CONFIG.image_width // TILE) * (CONFIG.image_height // TILE)
SPARSE_R = 2048          # the smallest re-render bucket chip_smoke.py serves
BENCH_R = 4096           # the re-render bucket of the benchmark's cells
N_1080P = 65_536         # the top of the scene-bucket ladder
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


def _frame_inputs(one_chip):
    cam = make_camera(look_at((0.0, -0.3, -2.0), (0.0, 0.0, 6.0)),
                      width=CONFIG.image_width, height=CONFIG.image_height)
    sh_k = (CONFIG.sh_degree + 1) ** 2
    n = N_1080P
    scene = jax.eval_shape(lambda: GaussianScene(
        jnp.zeros((n, 3)), jnp.zeros((n, 3)), jnp.zeros((n, 4)),
        jnp.zeros((n,)), jnp.zeros((n, sh_k, 3))))
    return _sds(scene, one_chip), _sds(cam, one_chip)


def _compile_mosaic(fn, args):
    """``fn`` compiled for the described chip with the fused kernel
    compiled, not interpreted: ``ops`` picks interpret mode from the
    attached backend, which here is the CPU, so the test steers it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        jax.clear_caches()      # no CPU trace of the raster may be reused
        try:
            return jax.jit(fn).lower(*args).compile()
        finally:
            jax.clear_caches()


def _key_frame(one_chip):
    scene, cam = _frame_inputs(one_chip)
    cfg = RenderConfig(capacity=K, chunk=64, impl="pallas_fused")
    return (lambda s, c: render_full_frame(s, c, cfg)), (scene, cam)


@pytest.fixture(scope="module")
def key_frame(one_chip):
    return _compile_mosaic(*_key_frame(one_chip))


def test_1080p_key_frame_compiles_with_mosaic_raster(key_frame):
    assert TILES_1080P == 8160
    assert "tpu_custom_call" in key_frame.as_text()
    assert key_frame.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_1080p_key_frame_pair_list_beats_dense_memory(key_frame, one_chip):
    """The pair list plans fewer temporary bytes than the dense (N, T)
    mask and per-tile ``top_k`` it replaced on the same key frame."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pipeline, "_pair_list_bins", pipeline._dense_bins)
        dense = _compile_mosaic(*_key_frame(one_chip))
    pair_bytes = key_frame.memory_analysis().temp_size_in_bytes
    dense_bytes = dense.memory_analysis().temp_size_in_bytes
    print(f"1080p key frame temp bytes: pair list {pair_bytes}, "
          f"dense {dense_bytes}")
    assert pair_bytes < dense_bytes


def test_1080p_sparse_frame_compiles_with_mosaic_raster(one_chip):
    scene, cam = _frame_inputs(one_chip)
    h, w = CONFIG.image_height, CONFIG.image_width
    state = _sds(jax.eval_shape(lambda: FrameState(
        rgb=jnp.zeros((h, w, 3)), exp_depth=jnp.zeros((h, w)),
        trunc_depth=jnp.zeros((h, w)), source_mask=jnp.zeros((h, w), bool),
        frame_idx=jnp.int32(0))), one_chip)
    cfg = RenderConfig(capacity=K, chunk=64, impl="pallas_fused",
                       rerender_capacity=BENCH_R)
    compiled = _compile_mosaic(
        lambda s, r, t, st: render_sparse_frame(s, r, t, st, cfg),
        (scene, cam, cam, state))
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"1080p sparse frame (R = {BENCH_R}) temp bytes: {temp}")
    assert "tpu_custom_call" in compiled.as_text()
    assert temp < HBM_BYTES
