"""GPipe-style pipeline parallelism over an optional 'pipe' mesh axis.

Off in the assigned production mesh (data x model), but a first-class
feature: at >4k chips a deployment trades DP ways for stages.  The
schedule is the classic shard_map loop: every tick each stage computes its
microbatch and collective-permutes the activation to the next stage;
bubbles compute garbage that is masked at collection (M + S - 1 ticks for
M microbatches over S stages, bubble fraction (S-1)/(M+S-1)).

`pipeline_apply` is schedule-only: it takes an arbitrary per-stage
function, so tests verify it against the sequential composition.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable,            # (stage_params, x (mb, ...)) -> y (mb, ...)
    stage_params,                  # pytree, leaves stacked (S, ...) sharded on pipe
    x,                             # (M, mb, ...) microbatched input
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "pipe",
):
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    m = x.shape[0]
    assert m >= 1

    def per_stage(params_local, x_local):
        # params_local: (1, ...) leaves — this stage's slice
        params_here = jax.tree.map(lambda p: p[0], params_local)
        s_idx = jax.lax.axis_index(axis)
        ticks = m + n_stages - 1

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t (clamped); others use the buffer
            inject = x_local[jnp.minimum(t, m - 1)]
            x_in = jnp.where(s_idx == 0, inject, buf)
            y = stage_fn(params_here, x_in)
            # pass activations downstream (stage i -> i+1)
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            buf_next = jax.lax.ppermute(y, axis, perm)
            # last stage collects tick t as microbatch t-(S-1)
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (s_idx == n_stages - 1)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outputs,
            )
            return (buf_next, outputs), None

        buf0 = jnp.zeros_like(x_local[0])
        out0 = jnp.zeros_like(x_local)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(ticks)
        )
        # broadcast the last stage's outputs (all other stages hold zeros)
        return jax.lax.psum(outputs, axis)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
