"""RWKV6 (Finch) mixer: data-dependent decay linear attention.

Time-mixing implements the WKV6 recurrence
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (w_t in (0,1), data-dependent)
    y_t = r_t^T S_{t-1} + (r_t . (u * k_t)) v_t
with a chunked-parallel training path (scan over chunks, matmuls within) and a
recurrent O(1)-state decode path.  Data-dependent token-shift (ddlerp) and the
decay LoRA follow arXiv:2404.05892; LayerNorms are replaced by RMSNorm for
uniformity with the rest of the zoo (documented in DESIGN.md).

Ops run under flat ``jax.named_scope``s, as in ``transformer.py``:
``time_mix`` (token shift, projections, decay LoRA, ``ln_x``, gate and
output), ``wkv`` (the recurrence alone: the chunk scan, or the decode
step's read, bonus, decay and update of the state) and ``channel_mix``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import lc
from repro.models.layers import ParamSpec, rms_norm

CHUNK = 64   # pairwise (i,j,dim) decay tensor is O(chunk^2*d): heads sharded
LORA_R = 32
DECAY_LORA_R = 64
MIX_NAMES = ("r", "k", "v", "w", "g")


def _dims(cfg: ModelConfig) -> Tuple[int, int]:
    heads = max(cfg.d_model // cfg.rwkv_head_dim, 1)
    return heads, cfg.d_model // heads


def rwkv_time_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    heads, hd = _dims(cfg)
    specs: Dict[str, ParamSpec] = {
        "mu_base": ParamSpec((len(MIX_NAMES), d), (None, None), init="zeros"),
        "mu_x": ParamSpec((d,), (None,), init="zeros"),
        "lora_a": ParamSpec((d, len(MIX_NAMES) * LORA_R), ("fsdp", None), scale=0.1),
        "lora_b": ParamSpec((len(MIX_NAMES), LORA_R, d), (None, None, None), init="zeros"),
        "w0": ParamSpec((d,), (None,), init="zeros"),
        "w_lora_a": ParamSpec((d, DECAY_LORA_R), ("fsdp", None), scale=0.1),
        "w_lora_b": ParamSpec((DECAY_LORA_R, d), (None, None), init="zeros"),
        "u": ParamSpec((d,), (None,), init="zeros"),
        "wr": ParamSpec((d, d), ("fsdp", "qkv")),
        "wk": ParamSpec((d, d), ("fsdp", "qkv")),
        "wv": ParamSpec((d, d), ("fsdp", "qkv")),
        "wg": ParamSpec((d, d), ("fsdp", "qkv")),
        "wo": ParamSpec((d, d), ("qkv", "fsdp")),
        "ln_x": ParamSpec((d,), (None,), init="zeros"),
    }
    return specs


def rwkv_channel_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mu_k": ParamSpec((d,), (None,), init="zeros"),
        "mu_r": ParamSpec((d,), (None,), init="zeros"),
        "wk": ParamSpec((d, f), ("fsdp", "ffn")),
        "wv": ParamSpec((f, d), ("ffn", "fsdp")),
        "wr": ParamSpec((d, d), ("fsdp", None)),
    }


def _shift(x: jax.Array, prev: jax.Array) -> jax.Array:
    """x_{t-1} sequence; prev: (b, 1, d) carry from the previous segment."""
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _ddlerp(params: Dict, x: jax.Array, xs: jax.Array) -> Dict[str, jax.Array]:
    """Data-dependent token-shift producing the 5 mixed inputs."""
    dx = xs - x
    base = x + dx * params["mu_x"].astype(x.dtype)
    lora = jnp.einsum("bsd,dr->bsr", jnp.tanh(base), params["lora_a"])
    lora = lora.reshape(x.shape[:2] + (len(MIX_NAMES), LORA_R))
    adj = jnp.einsum("bsmr,mrd->bsmd", lora, params["lora_b"])
    mix = params["mu_base"].astype(x.dtype)[None, None] + adj
    out = {}
    for i, name in enumerate(MIX_NAMES):
        out[name] = x + dx * mix[:, :, i]
    return out


def wkv_chunked(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
                u: jax.Array, state0: jax.Array, chunk: int = CHUNK
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked WKV6.

    r,k,v: (b, s, h, hd); logw: (b, s, h, hd) (log decay, <0); u: (h, hd)
    state0: (b, h, hd, hd)  [k-dim x v-dim]

    A length that ``chunk`` does not divide is padded at the tail with
    k = v = 0 and log w = 0, which leave the state as it is; the padded
    positions' outputs are dropped.
    """
    b, s, h, hd = r.shape
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s
    if pad:
        r, k, v, logw = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for t in (r, k, v, logw))
    rs = lambda t: t.reshape(b, nc, c, h, hd).swapaxes(0, 1)
    r_c, k_c, v_c, w_c = rs(r), rs(k), rs(v), rs(logw)

    ii, jj = jnp.meshgrid(jnp.arange(c), jnp.arange(c), indexing="ij")
    strict = (jj < ii)

    def step(state, inp):
        rc, kc, vc, wc = (t.astype(jnp.float32) for t in inp)
        P = jnp.cumsum(wc, axis=1)                       # (b, c, h, hd) log cumprod
        Pprev = P - wc                                   # logP_{i-1}
        # pairwise decay: exp(Pprev_i - P_j) on the k-dim, j < i
        diff = Pprev[:, :, None] - P[:, None, :]         # (b, i, j, h, hd)
        decay = jnp.exp(jnp.where(strict[None, :, :, None, None], diff, -jnp.inf))
        A = jnp.einsum("bihd,bijhd,bjhd->bhij", rc, decay, kc)
        A = A + jnp.einsum("bihd,hd,bihd->bhi", rc, u.astype(jnp.float32),
                           kc)[..., None] * jnp.eye(c)[None, None]
        y = jnp.einsum("bhij,bjhd->bihd", A, vc)
        # incoming state contribution
        y = y + jnp.einsum("bihd,bhde->bihe", rc * jnp.exp(Pprev), state)
        # state update: S_out = diag(exp(P_c)) S + sum_j exp(P_c - P_j) k_j v_j^T
        total = P[:, -1:]                                # (b, 1, h, hd)
        sdecay = jnp.exp(total - P)                      # (b, c, h, hd)
        state = state * jnp.exp(total[:, 0])[..., None] + jnp.einsum(
            "bjhd,bjhe->bhde", kc * sdecay, vc)
        state = lc(state, ("batch", "heads", None, None))
        return state, y

    # checkpointed: the backward pass recomputes each chunk's (b, c, c, h, hd)
    # decay instead of keeping it for every chunk (~2 GiB per layer for
    # 2x2048 tokens at rwkv6-1.6b widths, more than a v5e can spare)
    state, ys = jax.lax.scan(jax.checkpoint(step), state0.astype(jnp.float32),
                             (r_c, k_c, v_c, w_c))
    y = ys.swapaxes(0, 1).reshape(b, nc * c, h, hd)[:, :s]
    return y.astype(r.dtype), state


def _decay_log(params: Dict, xw: jax.Array) -> jax.Array:
    """log w_t = -exp(w0 + lora(xw)) -> (b, s, d), strictly negative."""
    lora = jnp.einsum("bsd,dr->bsr", jnp.tanh(xw), params["w_lora_a"])
    ww = params["w0"].astype(jnp.float32) + jnp.einsum(
        "bsr,rd->bsd", lora, params["w_lora_b"]).astype(jnp.float32)
    return -jnp.exp(ww)


def rwkv_time_mix(params: Dict, cfg: ModelConfig, x: jax.Array,
                  prev: jax.Array, state0: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Train/prefill path. Returns (y, last_x, final_state)."""
    heads, hd = _dims(cfg)
    b, s, d = x.shape
    hx = ("batch", None, "heads", None)
    with jax.named_scope("time_mix"):
        xs = _shift(x, prev)
        mixed = _ddlerp(params, x, xs)
        r = lc(jnp.einsum("bsd,de->bse", mixed["r"], params["wr"]).reshape(b, s, heads, hd), hx)
        k = lc(jnp.einsum("bsd,de->bse", mixed["k"], params["wk"]).reshape(b, s, heads, hd), hx)
        v = lc(jnp.einsum("bsd,de->bse", mixed["v"], params["wv"]).reshape(b, s, heads, hd), hx)
        g = jax.nn.silu(jnp.einsum("bsd,de->bse", mixed["g"], params["wg"]))
        logw = lc(_decay_log(params, mixed["w"]).reshape(b, s, heads, hd), hx)
        u = params["u"].astype(jnp.float32).reshape(heads, hd)
    with jax.named_scope("wkv"):
        y, state = wkv_chunked(r, k, v, logw, u, state0)
    with jax.named_scope("time_mix"):
        y = lc(y, hx)
        y = rms_norm(y.reshape(b, s, d), params["ln_x"], cfg.norm_eps) * g
        out = jnp.einsum("bsd,de->bse", y, params["wo"])
    return out, x[:, -1:], state


def rwkv_time_decode(params: Dict, cfg: ModelConfig, x: jax.Array,
                     prev: jax.Array, state: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token recurrent step. x: (b,1,d); state: (..., b,h,hd,hd) fp32.

    Leading dims of ``state`` (the decode loop passes its layer's slice as
    (1, b,h,hd,hd)) ride through the read and the update unchanged."""
    heads, hd = _dims(cfg)
    b, _, d = x.shape
    with jax.named_scope("time_mix"):
        mixed = _ddlerp(params, x, prev)
        r = jnp.einsum("bsd,de->bse", mixed["r"], params["wr"]).reshape(b, heads, hd)
        k = jnp.einsum("bsd,de->bse", mixed["k"], params["wk"]).reshape(b, heads, hd)
        v = jnp.einsum("bsd,de->bse", mixed["v"], params["wv"]).reshape(b, heads, hd)
        g = jax.nn.silu(jnp.einsum("bsd,de->bse", mixed["g"], params["wg"]))
        logw = _decay_log(params, mixed["w"]).reshape(b, heads, hd)
        u = params["u"].astype(jnp.float32).reshape(heads, hd)
    with jax.named_scope("wkv"):
        rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
        y = jnp.einsum("bhd,...bhde->...bhe", rf, state) + jnp.einsum(
            "bhd,hd,bhd,bhe->bhe", rf, u, kf, vf)
        state = state * jnp.exp(logw)[..., None] + jnp.einsum("bhd,bhe->bhde", kf, vf)
    with jax.named_scope("time_mix"):
        y = y.reshape(b, 1, d).astype(x.dtype)
        y = rms_norm(y, params["ln_x"], cfg.norm_eps) * g
        return jnp.einsum("bsd,de->bse", y, params["wo"]), x, state


def rwkv_channel_mix(params: Dict, cfg: ModelConfig, x: jax.Array,
                     prev: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("channel_mix"):
        xs = _shift(x, prev)
        xk = x + (xs - x) * params["mu_k"].astype(x.dtype)
        xr = x + (xs - x) * params["mu_r"].astype(x.dtype)
        k = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk, params["wk"])))
        kv = jnp.einsum("bsf,fd->bsd", k, params["wv"])
        r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, params["wr"]))
        return r * kv, x[:, -1:]


def rwkv_channel_decode(params: Dict, cfg: ModelConfig, x: jax.Array,
                        prev: jax.Array) -> Tuple[jax.Array, jax.Array]:
    y, _ = rwkv_channel_mix(params, cfg, x, prev)
    return y, x


def rwkv_cache_shapes(cfg: ModelConfig, batch: int) -> Dict[str, Tuple[int, ...]]:
    heads, hd = _dims(cfg)
    return {
        "state": (batch, heads, hd, hd),
        "tm_prev": (batch, 1, cfg.d_model),
        "cm_prev": (batch, 1, cfg.d_model),
    }
