"""Jitted Winograd conv wrapper: tile extraction + kernel + reassembly."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.winograd.kernel import G, winograd_tiles


def conv3x3_winograd(x: jax.Array, w: jax.Array,
                     padding: str = "SAME") -> jax.Array:
    """x: (b, H, W, cin); w: (3, 3, cin, cout). F(2x2,3x3) Winograd."""
    if w.shape[:2] != (3, 3):
        raise ValueError(f"winograd kernel requires 3x3 filters, got {w.shape}")
    b, H, W, cin = x.shape
    cout = w.shape[-1]
    if padding == "SAME":
        x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        H, W = H + 2, W + 2
    oh, ow = H - 2, W - 2
    th, tw = (oh + 1) // 2, (ow + 1) // 2
    x = jnp.pad(x, ((0, 0), (0, 2 * th + 2 - H), (0, 2 * tw + 2 - W), (0, 0)))
    i = jnp.arange(th) * 2
    j = jnp.arange(tw) * 2
    tiles = x[:, i[:, None] + jnp.arange(4)[None]]            # (b, th, 4, W', cin)
    tiles = tiles[:, :, :, j[:, None] + jnp.arange(4)[None]]  # (b, th, 4, tw, 4, cin)
    tiles = tiles.transpose(0, 1, 2, 4, 3, 5)                  # (b, th, 4, 4, tw, cin)

    g = jnp.asarray(G, x.dtype)
    u = jnp.einsum("ij,jkcf,lk->ilcf", g, w.astype(x.dtype), g)  # (4,4,cin,cout)

    y = winograd_tiles(tiles, u, interpret=jax.default_backend() != "tpu")
    out = y.transpose(0, 1, 2, 4, 3, 5).reshape(b, 2 * th, 2 * tw, cout)
    return out[:, :oh, :ow]
