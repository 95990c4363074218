"""Distributed behaviour on 8 host devices (subprocess: device count must
be set before jax init, and the main test process stays single-device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, timeout=900) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(_REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    r = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import param_shardings, batch_shardings
        from repro.train.optimizer import OptimizerConfig
        from repro.train import train_step as TS
        from repro.train.data import DataConfig, batch_at

        cfg = get_config("yi-9b").reduced()
        opt = OptimizerConfig(warmup_steps=1)
        data = DataConfig(batch_size=4, seq_len=64,
                          vocab_size=cfg.vocab_size)
        state = TS.init_train_state(jax.random.PRNGKey(0), cfg)
        batch = batch_at(data, 0)

        # single device reference
        ref_step = jax.jit(TS.make_train_step(cfg, opt))
        _, ref_metrics = ref_step(state, batch)

        mesh = make_host_mesh(4, 2)
        st_sh = param_shardings(state, mesh)
        state_d = jax.device_put(state, st_sh)
        batch_d = jax.device_put(batch, batch_shardings(batch, mesh))
        step = jax.jit(TS.make_train_step(cfg, opt, mesh),
                       in_shardings=(st_sh, batch_shardings(batch, mesh)))
        new_state, metrics = step(state_d, batch_d)
        print(json.dumps({
            "ref_loss": float(ref_metrics["loss"]),
            "sharded_loss": float(metrics["loss"]),
            "ref_gnorm": float(ref_metrics["grad_norm"]),
            "sharded_gnorm": float(metrics["grad_norm"]),
        }))
    """))
    assert abs(r["ref_loss"] - r["sharded_loss"]) < 1e-3, r
    assert abs(r["ref_gnorm"] - r["sharded_gnorm"]) \
        < 1e-2 * max(r["ref_gnorm"], 1), r


@pytest.mark.slow
def test_elastic_remesh_checkpoint():
    """Save on a 4x2 mesh, restore onto 2x4 — loss identical after load."""
    r = _run(textwrap.dedent("""
        import json, tempfile
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import param_shardings
        from repro.train import checkpoint as ckpt
        from repro.train.optimizer import OptimizerConfig
        from repro.train import train_step as TS
        from repro.train.data import DataConfig, batch_at
        from repro.train.train_step import make_loss_fn

        cfg = get_config("starcoder2-7b").reduced()
        data = DataConfig(batch_size=4, seq_len=32,
                          vocab_size=cfg.vocab_size)
        batch = batch_at(data, 0)
        state = TS.init_train_state(jax.random.PRNGKey(0), cfg)

        mesh_a = make_host_mesh(4, 2)
        state_a = jax.device_put(state, param_shardings(state, mesh_a))
        loss_a = float(jax.jit(make_loss_fn(cfg))(
            state_a.params, batch)[0])
        d = tempfile.mkdtemp()
        ckpt.save(d, 1, state_a)

        mesh_b = make_host_mesh(2, 4)
        template = jax.eval_shape(lambda: state)
        sh_b = param_shardings(template, mesh_b)
        state_b, step, _ = ckpt.restore(d, template, shardings=sh_b)
        loss_b = float(jax.jit(make_loss_fn(cfg))(
            state_b.params, batch)[0])
        leaf = jax.tree_util.tree_leaves(state_b.params)[0]
        print(json.dumps({"loss_a": loss_a, "loss_b": loss_b,
                          "resharded": str(leaf.sharding)[:60]}))
    """))
    assert abs(r["loss_a"] - r["loss_b"]) < 1e-5, r


@pytest.mark.slow
def test_int8_compressed_psum_error_feedback():
    """Compressed DP all-reduce: per-step error bounded, bias vanishes
    across steps thanks to error feedback."""
    r = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed.compression import (compressed_psum,
                                                   zero_residuals)

        mesh = jax.make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 256)) * 0.1

        def step(x, r):
            return compressed_psum(x, "data", r)

        f = jax.shard_map(step, mesh=mesh,
                          in_specs=(P("data"), P("data")),
                          out_specs=(P("data"), P("data")))
        exact = jnp.mean(g, axis=0)
        res = jnp.zeros_like(g)
        errs = []
        accum_err = jnp.zeros_like(exact)
        for it in range(6):
            mean_g, res = f(g, res)
            err = mean_g[0] - exact
            accum_err = accum_err + err
            errs.append(float(jnp.max(jnp.abs(err))))
        print(json.dumps({
            "per_step_err": errs,
            "accum_err": float(jnp.max(jnp.abs(accum_err))),
            "exact_scale": float(jnp.max(jnp.abs(exact)))}))
    """))
    scale = max(r["exact_scale"], 1e-6)
    assert r["per_step_err"][0] < 0.2 * scale, r
    # error feedback: accumulated bias across 6 steps stays ~one-step sized
    assert r["accum_err"] < 6 * 0.2 * scale, r


@pytest.mark.slow
def test_dryrun_cell_on_host_mesh():
    """The dry-run path end-to-end on a small real mesh (actually runs)."""
    r = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import (param_shardings,
                                                batch_shardings)
        from repro.models import model as M

        cfg = get_config("moonshot-v1-16b-a3b").reduced()
        mesh = make_host_mesh(2, 4)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, param_shardings(params, mesh))
        batch = {"tokens": jnp.ones((4, 32), jnp.int32)}
        batch = jax.device_put(batch, batch_shardings(batch, mesh))

        def fwd(p, b):
            logits, aux, _ = M.forward(p, b, cfg)
            return logits

        with mesh:
            out = jax.jit(fwd)(params, batch)
        print(json.dumps({"shape": list(out.shape),
                          "finite": bool(jnp.isfinite(out).all())}))
    """))
    assert r["finite"], r
    assert r["shape"][0] == 4
