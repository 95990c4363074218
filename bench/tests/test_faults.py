"""The check must fail a run whose timed path is broken underneath.

Each test drives a whole run of a tiny cell on the CPU (the look for a
chip skipped), with the program's serve step wrapped so that it breaks
in one of the ways a renderer-server can, and sees ``correct`` come out
false; a sound run of the same cell comes out true. The tiny cell's
limits (``conftest.TINY``) are for this size only: sound CPU runs read
key_rmse, sparse_rmse and sparse_tile_relerr_p90 ~1e-7, the bfloat16
control over 1e-2. The faults of the serve step are caught on the key
frames and, for the exchange, on stream 1's frames; the faults of the
sparse path (``faults.py``) on the sparse frames alone, since their key
frames are sound.
"""
import time

import jax
import jax.numpy as jnp
import pytest

import faults
import harness
from conftest import tiny_cell


def _stale(res, args):
    """The step returns its state unchanged: the carry it was given, and
    the frame that carry holds, instead of the new one."""
    carries = args[4]
    return res._replace(frames=carries.state.rgb[:, None],
                        carries=carries)


def _half_batch(res, args):
    """Half of the batch left out: the second half of the slots, or of
    each frame's rows when there is one slot, comes back black."""
    f = res.frames
    b = f.shape[0]
    if b > 1:
        return res._replace(frames=f.at[b // 2:].set(0.0))
    return res._replace(frames=f.at[:, :, f.shape[2] // 2:].set(0.0))


def _no_exchange(res, args):
    """The exchange between devices left out: every slot gets slot 0's
    frame, as if only the first device's output were gathered."""
    f = res.frames
    return res._replace(frames=jnp.broadcast_to(f[:1], f.shape))


def _altered(res, args):
    """An answer altered where it is produced: one tile of every frame
    brightened by 0.5."""
    return res._replace(frames=res.frames.at[:, :, :16, :16].add(0.5))


def _run(cell, seed=5):
    m = harness.run_cell(cell, seed, 3.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         log=lambda s: None)
    return m


@pytest.fixture
def broken(monkeypatch):
    import repro.serve.server as server

    def install(fault):
        build = server.build_render_fn

        def wrapped(*a, **kw):
            fn = build(*a, **kw)
            return lambda *args: fault(fn(*args), args)
        monkeypatch.setattr(server, "build_render_fn", wrapped)
    return install


@pytest.mark.parametrize("streams,window", [(1, 5), (2, 5), (1, 1)],
                         ids=["one_stream", "two_streams", "window_1"])
def test_sound_run_is_correct(streams, window):
    r = _run(tiny_cell(streams=streams, scenes=streams, window=window))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0


@pytest.mark.parametrize("fault,streams", [
    (_stale, 1), (_half_batch, 1), (_half_batch, 2), (_no_exchange, 2),
    (_altered, 1)],
    ids=["state_unchanged", "half_frame", "half_batch", "no_exchange",
         "answer_altered"])
def test_broken_step_is_not_correct(broken, fault, streams):
    broken(fault)
    r = _run(tiny_cell(streams=streams, scenes=streams))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_sparse_path_is_not_correct(monkeypatch, fault):
    # The engine's jitted functions keep their traces: drop them, so the
    # fault is traced in, and again after, so no later test serves it.
    jax.clear_caches()
    faults.install(fault, monkeypatch.setattr)
    try:
        r = _run(tiny_cell())
    finally:
        jax.clear_caches()
    assert not r["correct"], r["checks"]
    assert r["checks"]["key_rmse"]["value"] < \
        r["checks"]["key_rmse"]["limit"]
