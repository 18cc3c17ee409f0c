"""Jitted public wrapper: padding, layout, backend dispatch, custom_vjp.

Forward runs the Pallas kernel (interpret=True off-TPU); backward
rematerializes through the ref.py oracle (standard recompute-bwd: the fwd
kernel's O(S) memory is preserved because the bwd is itself chunkable; a
dedicated bwd kernel is an optimization documented in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (
    DEFAULT_BLOCK_KV, DEFAULT_BLOCK_Q, flash_attention_fwd,
)
from repro.kernels.flash_attention.ref import attention_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = DEFAULT_BLOCK_Q,
                    block_kv: int = DEFAULT_BLOCK_KV):
    """q: (b, h, s, d); k/v: (b, kv, t, d) head-major. Differentiable."""
    return _fwd_impl(q, k, v, causal, window, softcap, block_q, block_kv)


def _fwd_impl(q, k, v, causal, window, softcap, block_q, block_kv):
    b, h, s, d = q.shape
    t = k.shape[2]
    if t % block_kv and not causal:
        # the kernel masks padded KV positions only through the causal mask
        raise ValueError(
            f"non-causal flash_attention needs the KV length ({t}) to be a "
            f"multiple of block_kv ({block_kv})")
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_kv)
    vp = _pad_to(v, 2, block_kv)
    out = flash_attention_fwd(qp, kp, vp, causal=causal, window=window,
                              softcap=softcap, block_q=block_q,
                              block_kv=block_kv, interpret=not _on_tpu())
    return out[:, :, :s, :]


def _vjp_fwd(q, k, v, causal, window, softcap, block_q, block_kv):
    out = _fwd_impl(q, k, v, causal, window, softcap, block_q, block_kv)
    return out, (q, k, v)


def _vjp_bwd(causal, window, softcap, block_q, block_kv, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: attention_ref(
        q_, k_, v_, causal=causal, window=window, softcap=softcap), q, k, v)
    return vjp(g)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
