"""Microbatch pipeline parallelism over one mesh axis (GPipe schedule).

Stage ``s`` lives on device ``s`` of ``axis_name``; microbatches are
injected at device 0 and streamed one hop per step with ``ppermute``, so
``M`` microbatches through ``S`` stages take ``M + S - 1`` steps.  Stages
must be shape-preserving (activation in == activation out), which is the
usual transformer-block contract.

When the mesh axis does not match the stage count (e.g. a 1-device test
mesh) the schedule degenerates to a sequential scan over stages -- same
numerics, no overlap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward"]


def _sequential(stage_fn, x, stage_params, n_stages: int):
    def body(carry, s):
        p_s = jax.tree.map(lambda a: a[s], stage_params)
        return jax.vmap(lambda mb: stage_fn(p_s, mb))(carry), None

    out, _ = jax.lax.scan(body, x, jnp.arange(n_stages))
    return out


def pipeline_forward(stage_fn, x, stage_params, mesh, axis_name: str = "pod"):
    """Run ``x: [M, ...]`` microbatches through ``S`` stacked stages.

    ``stage_params`` leaves have leading dim ``S``; ``stage_fn(params, mb)``
    applies one stage to one microbatch.  Returns ``[M, ...]`` outputs.
    """
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if sizes.get(axis_name, 1) != n_stages:
        return _sequential(stage_fn, x, stage_params, n_stages)

    m = x.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_device(p, xs):
        w = jax.tree.map(lambda a: a[0], p)  # this device's stage
        idx = jax.lax.axis_index(axis_name)
        # the carry turns device-varying after one step (it holds this
        # stage's output), so the initial carry must be typed varying too
        buf, outs = jax.lax.pcast(
            (jnp.zeros_like(xs[0]), jnp.zeros_like(xs)), axis_name, to="varying"
        )

        def step(carry, t):
            buf, outs = carry
            x_in = jnp.where(idx == 0, xs[jnp.minimum(t, m - 1)], buf)
            y = stage_fn(w, x_in)
            o_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            take = (idx == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(take, outs.at[o_idx].set(y), outs)
            return (jax.lax.ppermute(y, axis_name, perm), outs), None

        (_, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(m + n_stages - 1))
        return outs[None]

    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(axis_name), P()), out_specs=P(axis_name)
    )
    return fn(stage_params, x)[-1]
