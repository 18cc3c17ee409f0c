"""Winograd F(2x2, 3x3) convolution Pallas kernel (the paper's headline
cuDNN algorithm, §I/§V — "Winograd Nonfused" had the highest IPC).

ops.py extracts overlapping 4x4 input tiles (stride 2) with XLA; the kernel
does the transform-domain work per tile block entirely in VMEM:

    V = B^T d B          (input transform,  4x4 per tile)
    M = V * U            (batched (16,cin)x(16,cin,cout) contraction -> MXU)
    Y = A^T M A          (output transform, 2x2 per tile)

U (the filter transform) is precomputed once in ops.py.  grid = (batch,
tile_rows); each step processes a full row of tiles, laid out (4, 4, TW, cin)
so every transform position is one (TW, cin) slab.  The two transforms are
unrolled over their constant 0/±1 matrices into VPU adds, and the contraction
is 16 plain (TW, cin) @ (cin, cout) dots: no in-kernel dot has a batch
dimension, which the TPU compiler requires.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BT = np.array([[1, 0, -1, 0], [0, 1, 1, 0], [0, -1, 1, 0], [0, 1, 0, -1]],
              np.float32)
AT = np.array([[1, 1, 1, 0], [0, 1, -1, -1]], np.float32)
G = np.array([[1, 0, 0], [0.5, 0.5, 0.5], [0.5, -0.5, 0.5], [0, 0, 1]],
             np.float32)


def _combine(coeffs, terms):
    """sum(c * t) over the nonzero constants c, unrolled at trace time."""
    acc = None
    for c, t in zip(coeffs, terms):
        if c == 0:
            continue
        t = t if c == 1 else -t if c == -1 else float(c) * t
        acc = t if acc is None else acc + t
    return acc


def _sandwich(mat: np.ndarray, x):
    """``mat @ x @ mat.T`` for a nested list ``x`` of equal-shape slabs."""
    left = [[_combine(row, [xj[k] for xj in x]) for k in range(len(x[0]))]
            for row in mat]
    return [[_combine(row, left_i) for row in mat] for left_i in left]


def _wino_kernel(tiles_ref, u_ref, o_ref):
    # tiles: (1, 1, 4, 4, TW, cin); u: (4, 4, cin, cout); o: (1, 1, 2, 2, TW, cout)
    d = [[tiles_ref[0, 0, j, k].astype(jnp.float32) for k in range(4)]
         for j in range(4)]
    v = _sandwich(BT, d)                                 # V = BT d B
    m = [[jnp.dot(v[i][l], u_ref[i, l].astype(jnp.float32),
                  preferred_element_type=jnp.float32) for l in range(4)]
         for i in range(4)]                              # M = V (.) U
    y = _sandwich(AT, m)                                 # Y = AT M A
    for i in range(2):
        for l in range(2):
            o_ref[0, 0, i, l] = y[i][l].astype(o_ref.dtype)


def winograd_tiles(tiles: jax.Array, u: jax.Array, *,
                   interpret: bool = True) -> jax.Array:
    """tiles: (b, th, 4, 4, tw, cin); u: (4, 4, cin, cout)
    -> (b, th, 2, 2, tw, cout)."""
    b, th, _, _, tw, cin = tiles.shape
    cout = u.shape[-1]
    return pl.pallas_call(
        _wino_kernel,
        grid=(b, th),
        in_specs=[
            pl.BlockSpec((1, 1, 4, 4, tw, cin), lambda ib, it: (ib, it, 0, 0, 0, 0)),
            pl.BlockSpec((4, 4, cin, cout), lambda ib, it: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 2, 2, tw, cout),
                               lambda ib, it: (ib, it, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, th, 2, 2, tw, cout), tiles.dtype),
        interpret=interpret,
    )(tiles, u)
