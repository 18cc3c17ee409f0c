"""Mamba2 (SSD) mixer: chunked-parallel training path + recurrent decode.

Implements the state-space-dual algorithm of Mamba2 with a ``lax.scan`` over
sequence chunks (state carried across chunks) — the scan gives the simulator a
clean while-loop trip count, and the per-chunk work is matmul-dominated so it
maps onto the MXU.

Shapes: d_inner = expand*d_model, heads = d_inner/64 (headdim p=64), ngroups=1,
state n = cfg.ssm_state.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import lc
from repro.models.layers import ParamSpec, rms_norm

HEADDIM = 64
CHUNK = 128


def _dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    heads = max(d_inner // HEADDIM, 1)
    headdim = d_inner // heads
    return d_inner, heads, headdim, cfg.ssm_state


def ssm_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    d_inner, heads, headdim, n = _dims(cfg)
    conv_ch = d_inner + 2 * n
    return {
        "in_proj": ParamSpec((d, 2 * d_inner + 2 * n + heads), ("fsdp", "ffn")),
        "conv_w": ParamSpec((cfg.ssm_conv, conv_ch), (None, "ffn"), init="fan_in"),
        "conv_b": ParamSpec((conv_ch,), ("ffn",), init="zeros"),
        "dt_bias": ParamSpec((heads,), ("ssm_heads",), init="zeros"),
        "a_log": ParamSpec((heads,), ("ssm_heads",), init="zeros"),
        "d_skip": ParamSpec((heads,), ("ssm_heads",), init="ones"),
        "norm": ParamSpec((d_inner,), ("ffn",), init="zeros"),
        "out_proj": ParamSpec((d_inner, d), ("ffn", "fsdp")),
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    d_inner, heads, headdim, n = _dims(cfg)
    z, x, B, C, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + n, 2 * d_inner + 2 * n], axis=-1)
    return z, x, B, C, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. x: (b, s, c); w: (width, c)."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        pad, w[:, None, :].astype(x.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return jax.nn.silu(out + b.astype(x.dtype))


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., l) -> (..., l, l) lower-tri segment sums Σ_{k=j+1..i} a_k."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array,
                state0: jax.Array, chunk: int = CHUNK
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    xdt:   (b, s, h, p)  — inputs pre-multiplied by dt
    dA:    (b, s, h)     — per-step log decay (dt * A, A<0)
    B, C:  (b, s, n)     — shared across heads (ngroups=1)
    state0:(b, h, p, n)
    Returns y: (b, s, h, p), final state.
    """
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    nc = max(s // chunk, 1)
    chunk = s // nc
    rs = lambda t: t.reshape((b, nc, chunk) + t.shape[2:]).swapaxes(0, 1)
    xdt_c, dA_c, B_c, C_c = rs(xdt), rs(dA), rs(B), rs(C)   # leading chunk dim

    def step(state, inp):
        xc, ac, bc, cc = inp                    # (b, chunk, ...)
        a_cum = jnp.cumsum(ac, axis=1)          # (b, l, h)
        # intra-chunk: M[b,h,i,j] = C_i.B_j * exp(a_cum_i - a_cum_j) for j<=i
        L = jnp.exp(_segsum(ac.swapaxes(1, 2)))           # (b, h, l, l)
        scores = jnp.einsum("bin,bjn->bij", cc, bc)       # (b, l, l)
        M = (scores[:, None] * L).astype(xc.dtype)        # (b, h, l, l)
        y_diag = jnp.einsum("bhij,bjhp->bihp", M, xc)
        # contribution of incoming state
        sdecay = jnp.exp(a_cum)                            # (b, l, h)
        y_off = jnp.einsum("bin,bhpn,bih->bihp",
                           cc.astype(jnp.float32), state,
                           sdecay).astype(xc.dtype)
        # state update
        total = a_cum[:, -1:, :]                           # (b, 1, h)
        rdecay = jnp.exp(total - a_cum)                    # (b, l, h)
        new_state = state * jnp.exp(total)[:, 0, :, None, None] + jnp.einsum(
            "bjn,bjh,bjhp->bhpn", bc.astype(jnp.float32),
            rdecay.astype(jnp.float32), xc.astype(jnp.float32))
        new_state = lc(new_state, ("batch", "ssm_heads", None, None))
        return new_state, y_diag + y_off

    state, ys = jax.lax.scan(step, state0.astype(jnp.float32),
                             (xdt_c, dA_c, B_c, C_c))
    y = ys.swapaxes(0, 1).reshape(b, s, h, p)
    return y, state


def ssm_mixer(params: Dict, cfg: ModelConfig, x: jax.Array
              ) -> jax.Array:
    """Training/prefill path. x: (b, s, d) -> (b, s, d)."""
    d_inner, heads, headdim, n = _dims(cfg)
    b, s, _ = x.shape
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xs, B, C, dt = _split_proj(cfg, zxbcdt)
    xbc = _causal_conv(jnp.concatenate([xs, B, C], axis=-1),
                       params["conv_w"], params["conv_b"])
    xs, B, C = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))      # (b, s, h)
    A = -jnp.exp(params["a_log"].astype(jnp.float32))                # (h,)
    xh = xs.reshape(b, s, heads, headdim)
    xh = lc(xh, ("batch", None, "ssm_heads", None))
    xdt = (xh.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    dA = lc(dt * A, ("batch", None, "ssm_heads"))                     # (b, s, h)
    state0 = jnp.zeros((b, heads, headdim, n), jnp.float32)
    y, _ = ssd_chunked(xdt, dA, B, C, state0)
    y = lc(y, ("batch", None, "ssm_heads", None))
    y = y + xh * params["d_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(b, s, d_inner)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, params["out_proj"])


# ---------------------------------------------------------------------------
# Decode (recurrent, O(1) per token)
# ---------------------------------------------------------------------------

def ssm_decode_step(params: Dict, cfg: ModelConfig, x: jax.Array,
                    cache: Dict[str, jax.Array]
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (b, 1, d); cache: {state: (...,b,h,p,n) fp32, conv: (...,b,w-1,c)},
    whose leading axes (a stacked cache's layer axes, of size 1) it keeps."""
    d_inner, heads, headdim, n = _dims(cfg)
    b = x.shape[0]
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xs, B, C, dt = _split_proj(cfg, zxbcdt)
    xbc_new = jnp.concatenate([xs, B, C], axis=-1)                   # (b, 1, c)
    conv = cache["conv"]
    window = jnp.concatenate(                                        # (..., b, w, c)
        [conv, jnp.broadcast_to(xbc_new, conv.shape[:-3] + xbc_new.shape)], axis=-2)
    conv_out = jnp.sum(window * params["conv_w"].astype(window.dtype),
                       axis=-2).reshape(b, -1)
    xbc = jax.nn.silu(conv_out + params["conv_b"].astype(conv_out.dtype))
    xs1, B1, C1 = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)    # (b, c)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) +
                          params["dt_bias"].astype(jnp.float32))     # (b, h)
    A = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xs1.reshape(b, heads, headdim).astype(jnp.float32)
    dA = jnp.exp(dt1 * A)                                            # (b, h)
    state = cache["state"] * dA[..., None, None] + jnp.einsum(
        "bn,bhp->bhpn", B1.astype(jnp.float32), xh * dt1[..., None])
    y = jnp.einsum("bn,...bhpn->...bhp", C1.astype(jnp.float32), state)
    y = y + xh * params["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(b, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    new_cache = {"state": state, "conv": window[..., 1:, :]}
    return out, new_cache
