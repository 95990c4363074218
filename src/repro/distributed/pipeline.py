"""Pipeline parallelism over the "pod" axis (beyond-paper, DESIGN.md §5).

The multi-pod mesh's "pod" axis can act as DP (default) or as GPipe-style
pipeline stages — cross-pod ICI is the slowest fabric, and pipelining
sends only (micro_batch, seq, d_model) activations across it once per
microbatch instead of all-reducing every gradient.

Mechanics (shard_map over "pod"):
  - the layer-stacked params (L, ...) are sharded P("pod", ...): stage s
    holds layers [s*L/P, (s+1)*L/P);
  - microbatches stream through a circular ``collective_permute``; stage s
    idles for s warmup ticks (GPipe bubble = (P-1)/(M+P-1));
  - the returned activations are the LAST stage's outputs, re-distributed.

Forward-only here (decode/prefill pipelining + inference serving); the
train path composes with jax.grad through ppermute. Correctness is tested
on an 8-device host mesh in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(layer_fn: Callable, params_stacked, x, *,
                   mesh: Mesh, num_micro: int, axis: str = "pod"):
    """Run ``layer_fn`` stacks as a pipeline over ``axis``.

    layer_fn(params_slice, x) -> x, applied to the local layer shard via
    an inner scan. x: (B, S, D) with B divisible by num_micro.
    params_stacked: pytree with leading layer dim divisible by the axis
    size.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % num_micro == 0, (b, num_micro)
    micro = b // num_micro

    def local_layers(local_params, h):
        def body(carry, lp):
            return layer_fn(lp, carry), None
        out, _ = jax.lax.scan(body, h, local_params)
        return out

    def staged(local_params, x_local):
        stage = jax.lax.axis_index(axis)
        # all microbatches start on stage 0: gather x there.
        x_all = jax.lax.all_gather(x_local, axis, tiled=True)  # (B,S,D)
        mbs = x_all.reshape(num_micro, micro, *x_all.shape[1:])
        n_ticks = num_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t (if any); others use received
            inject = mbs[jnp.minimum(t, num_micro - 1)]
            h_in = jnp.where((stage == 0), inject, buf)
            h_out = local_layers(local_params, h_in)
            # live iff this stage is processing a real microbatch
            live = (t >= stage) & (t - stage < num_micro)
            h_out = jnp.where(live, h_out, buf)
            # last stage writes its finished microbatch to the output slot
            done_idx = t - (n_stages - 1)
            is_done = (stage == n_stages - 1) & (done_idx >= 0) \
                & (done_idx < num_micro)
            outputs = jax.lax.cond(
                is_done,
                lambda o: o.at[jnp.maximum(done_idx, 0)].set(h_out),
                lambda o: o, outputs)
            nxt = jax.lax.ppermute(h_out, axis, perm)
            return (nxt, outputs), None

        buf0 = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)
        (_, outputs), _ = jax.lax.scan(tick, (buf0, outs0),
                                       jnp.arange(n_ticks))
        # outputs are only valid on the last stage; gather and select it so
        # the out_spec can be replicated-over-pod.
        gathered = jax.lax.all_gather(outputs, axis)   # (P, M, micro, ...)
        out = gathered[n_stages - 1].reshape(b, *x_all.shape[1:])
        return out

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), params_stacked)
    fn = jax.shard_map(staged, mesh=mesh,
                       in_specs=(param_specs, P(axis)),
                       out_specs=P(),
                       check_vma=False)
    return fn(params_stacked, x)


def bubble_fraction(num_stages: int, num_micro: int) -> float:
    """GPipe bubble overhead — the schedule-efficiency napkin number."""
    return (num_stages - 1) / (num_micro + num_stages - 1)
