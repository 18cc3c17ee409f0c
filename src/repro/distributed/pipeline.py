"""Pipeline parallelism over the ``pod`` axis (GPipe schedule via shard_map +
collective_permute).

The multi-pod mesh's ``pod`` axis can act as pure DP (default) or as a
pipeline: stage s holds layers [s*L/S, (s+1)*L/S); microbatches flow through
a collective-permute ring.  The schedule runs T = M + S - 1 ticks; stage 0
injects microbatch t at tick t; the last stage emits outputs from tick S-1 on
(the GPipe bubble = (S-1)/T).

``pipeline_apply`` is the forward building block (inference/eval pipelines and
the PP dry-run); training composes it with jax.grad as usual — permutes
transpose to reverse-ring permutes automatically.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_apply(stage_fn: Callable, stage_params: Any, x_micro: jax.Array,
                   *, mesh: Mesh, axis: str = "pod") -> jax.Array:
    """Run microbatches through a stage pipeline.

    stage_fn(params_leaf_slice, x) -> y, same shape as x.
    stage_params: pytree with leading dim S (stages) on every leaf.
    x_micro: (M, b, ...) microbatched input (replicated across the axis).
    Returns (M, b, ...) outputs (replicated).
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def inner(params, xs):
        # params: leaves (1, ...) — this device-group's stage slice
        params_local = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros((M,) + xs.shape[1:], xs.dtype)

        def tick(carry, t):
            state, outs = carry
            inject = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(sid == 0, xs[inject], state)
            y = stage_fn(params_local, x_in)
            # last stage emits microbatch t-(S-1) at tick t
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = (sid == S - 1) & (t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y, outs[out_idx]), out_idx, 0)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(T, dtype=jnp.int32))
        # broadcast the last stage's outputs to every group member
        outs = jax.lax.psum(jnp.where(sid == S - 1, outs, 0.0), axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda x: hasattr(x, "ndim")), P())
    return shard_map(inner, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)(stage_params, x_micro)
