"""Gradient compression for data-parallel reduction.

int8 all-gather reduction: each device quantizes its local gradient shard to
int8 with a per-tensor fp32 scale, all-gathers the (int8, scale) pairs over
the data axis, and dequantize-sums locally.  Link payload vs a bf16
all-reduce: AG moves (g-1)/g * size_int8 where AR moves 2(g-1)/g * size_bf16
-> ~4x less ICI traffic, at a quantization error bounded by max|g|/254 per
element (validated in tests/test_compression.py).

Error feedback (residual carried into the next step) removes the systematic
bias; the residual tensor lives in the training state when enabled.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean-reduce ``x`` over a mesh axis with int8 payload.

    Call INSIDE a shard_map over ``axis_name``.  Payload per device:
    all-gather of int8 (1/2 the bf16 bytes, 1/4 the fp32 bytes) + g scales.
    """
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)              # (g, ...) int8 payload
    scales = jax.lax.all_gather(scale, axis_name)      # (g,)
    g = qs.shape[0]
    total = jnp.tensordot(scales.astype(jnp.float32),
                          qs.astype(jnp.float32), axes=((0,), (0,)))
    return (total / g).astype(x.dtype)


def compressed_grad_mean(grads: Any, mesh: Mesh, axis_name: str = "data",
                         errors: Optional[Any] = None
                         ) -> Tuple[Any, Optional[Any]]:
    """DP gradient mean with int8 compression (+ optional error feedback).

    grads: replicated-over-``axis_name`` pytree of *local* (per-shard)
    gradients.  With error feedback, pass the residual pytree; returns
    (reduced grads, new residuals).
    """
    def one(g, e):
        g_in = g + (e if e is not None else 0.0)

        fn = shard_map(partial(compressed_psum_mean, axis_name=axis_name),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        reduced = fn(g_in)
        new_e = (g_in - reduced) if e is not None else None
        return reduced, new_e

    if errors is None:
        out = jax.tree.map(lambda g: one(g, None)[0], grads)
        return out, None
    pairs = jax.tree.map(one, grads, errors)
    reduced = jax.tree.map(lambda p: p[0], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    return reduced, new_err
