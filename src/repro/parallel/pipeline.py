"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

The production dry-run mesh uses pod=DP (DESIGN.md §5); this module provides
the PP building block for deployments that trade a pod axis for pipeline
stages (e.g. (stage, data, model) on 3D-torus slices). Implementation is
the standard JAX pattern: shard_map over 'stage', a rotating microbatch
schedule of T = n_micro + n_stages - 1 ticks, and jax.lax.ppermute to hand
activations to the next stage. Bubble fraction = (S-1)/(M+S-1).

`pipeline(fn)` is generic: `fn(stage_params, x) -> x` is any per-stage
computation whose params are stacked on a leading stage axis.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS


def pipeline_apply(fn: Callable, mesh: Mesh, params, microbatches,
                   stage_axis: str = "stage"):
    """One-shot convenience wrapper (builds in_specs from the params tree)."""
    in_specs = (jax.tree.map(lambda _: PS(stage_axis), params), PS())
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]
    n_micro = microbatches.shape[0]

    def pipelined(params, mb):
        local = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(stage_axis)
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(mb[0])
        outs = jnp.zeros_like(mb)

        def tick(state, t):
            buf, outs = state
            mb_idx = t - sid
            x_in = jnp.where(sid == 0,
                             mb[jnp.clip(mb_idx, 0, n_micro - 1)], buf)
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            y = fn(local, x_in)
            y = jnp.where(active, y, x_in)
            outs = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(y),
                lambda o: o, outs)
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, 0), stage_axis)
        return outs

    f = jax.shard_map(pipelined, mesh=mesh, in_specs=in_specs,
                      out_specs=PS(), check_vma=False)
    return f(params, microbatches)
