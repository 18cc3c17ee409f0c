"""Attention: GQA + RoPE + causal / sliding-window / cross, train & decode paths.

The pure-jnp path here is the *reference semantics*; the Pallas flash-attention
kernel in ``repro.kernels.flash_attention`` implements identical math with VMEM
tiling and is swapped in through ``repro.kernels.dispatch`` when the backend
supports it.

Attention's ops run under ``jax.named_scope("attention")``, and the decode
path's write of the new position into the cache under ``"kv_cache"``; the
scopes sit side by side, never one inside the other.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import lc
from repro.models.layers import ParamSpec, apply_rope, dense

NEG_INF = -2.3819763e38   # matches XLA's min bf16-representable fp32 mask


def attn_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    specs = {
        "wq": ParamSpec((d, h * hd), ("fsdp", "qkv")),
        "wk": ParamSpec((d, kv * hd), ("fsdp", "qkv")),
        "wv": ParamSpec((d, kv * hd), ("fsdp", "qkv")),
        "wo": ParamSpec((h * hd, d), ("qkv", "fsdp")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h * hd,), ("qkv",), init="zeros")
        specs["bk"] = ParamSpec((kv * hd,), ("qkv",), init="zeros")
        specs["bv"] = ParamSpec((kv * hd,), ("qkv",), init="zeros")
    return specs


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window, k_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """(q, k) additive mask bias in fp32.

    ``window`` may be a python int or a traced scalar (gemma3 switches
    local/global per layer inside the layer scan); <=0 disables the window.
    """
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    window = jnp.asarray(window, jnp.int32)
    dist = q_pos[:, None] - k_pos[None, :]
    ok &= (window <= 0) | (dist < window)
    if k_valid_len is not None:
        ok &= k_pos[None, :] < k_valid_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


FLASH_SEQ_THRESHOLD = 4096   # switch to query-chunked attention at/above this
FLASH_Q_BLOCK = 512


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, bias: Optional[jax.Array],
         softcap: float = 0.0) -> jax.Array:
    """q: (b, s, h, d); k/v: (b, t, kv, d). GQA via head grouping. fp32 softmax."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    group = h // kvh
    qg = q.reshape(b, s, kvh, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    if bias is not None:
        scores = scores + bias     # (s, t) broadcast over (b, k, g)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def chunked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 q_positions: jax.Array, k_positions: jax.Array,
                 causal: bool, window, softcap: float = 0.0,
                 q_block: int = FLASH_Q_BLOCK) -> jax.Array:
    """Query-block-chunked attention: peak memory O(S*q_block), not O(S^2).

    Each block's body is rematerialized in the backward pass (jax.checkpoint),
    so training at 32k+ context never materializes the full score matrix.
    Same math as :func:`sdpa` (full-row softmax per query block).
    """
    b, s, h, d = q.shape
    nb = max(s // q_block, 1)
    qb = s // nb
    q_c = q.reshape(b, nb, qb, h, d).swapaxes(0, 1)            # (nb, b, qb, h, d)
    qpos_c = q_positions.reshape(nb, qb)

    @jax.checkpoint
    def body(_, inp):
        qc, qpos = inp
        bias = _mask_bias(qpos, k_positions, causal=causal, window=window)
        return 0.0, sdpa(qc, k, v, bias, softcap)

    _, out = jax.lax.scan(body, 0.0, (q_c, qpos_c))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


@jax.named_scope("attention")
def attention(params: Dict, cfg: ModelConfig, x: jax.Array,
              positions: jax.Array, *, causal: bool = True,
              window: int = 0, kv_source: Optional[jax.Array] = None,
              use_rope: bool = True) -> jax.Array:
    """Full-sequence attention (train / prefill). kv_source != None => cross-attn."""
    b, s, d_model = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    src = x if kv_source is None else kv_source
    q = dense(x, params["wq"], params.get("bq"))
    k = dense(src, params["wk"], params.get("bk"))
    v = dense(src, params["wv"], params.get("bv"))
    q = lc(q, ("batch", "seq", "qkv")).reshape(b, s, h, hd)
    k = lc(k, ("batch", "seq", "qkv")).reshape(b, src.shape[1], kv, hd)
    v = lc(v, ("batch", "seq", "qkv")).reshape(b, src.shape[1], kv, hd)
    if use_rope and kv_source is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kv_source is None:
        k_pos = positions if positions.ndim == 1 else positions[0]
        if s >= FLASH_SEQ_THRESHOLD:
            out = chunked_sdpa(q, k, v, q_positions=k_pos, k_positions=k_pos,
                               causal=causal, window=window,
                               softcap=cfg.logit_softcap)
        else:
            bias = _mask_bias(k_pos, k_pos, causal=causal, window=window)
            out = sdpa(q, k, v, bias, cfg.logit_softcap)
    else:
        out = sdpa(q, k, v, None, cfg.logit_softcap)  # cross-attn: dense
    out = lc(out.reshape(b, s, h * hd), ("batch", "seq", "qkv"))
    return dense(out, params["wo"])


@jax.named_scope("attention")
def attention_prefill(params: Dict, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array, *, window: int = 0
                      ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Like :func:`attention` but also returns (k, v) for the KV cache."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = dense(x, params["wq"], params.get("bq")).reshape(b, s, h, hd)
    k = dense(x, params["wk"], params.get("bk")).reshape(b, s, kv, hd)
    v = dense(x, params["wv"], params.get("bv")).reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k_pos = positions if positions.ndim == 1 else positions[0]
    if s >= FLASH_SEQ_THRESHOLD:
        out = chunked_sdpa(q, k, v, q_positions=k_pos, k_positions=k_pos,
                           causal=True, window=window, softcap=cfg.logit_softcap)
    else:
        bias = _mask_bias(k_pos, k_pos, causal=True, window=window)
        out = sdpa(q, k, v, bias, cfg.logit_softcap)
    out = dense(out.reshape(b, s, h * hd), params["wo"])
    k = lc(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = lc(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    return out, (k, v)


def _decode_qkv(params: Dict, cfg: ModelConfig, x: jax.Array, pos: jax.Array):
    """The new token's roped q and its k/v, each (b, 1, heads, hd)."""
    b = x.shape[0]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    with jax.named_scope("attention"):
        q = dense(x, params["wq"], params.get("bq")).reshape(b, 1, h, hd)
        k_new = dense(x, params["wk"], params.get("bk")).reshape(b, 1, kvh, hd)
        v_new = dense(x, params["wv"], params.get("bv")).reshape(b, 1, kvh, hd)
        posv = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, posv, cfg.rope_theta)
        k_new = apply_rope(k_new, posv, cfg.rope_theta)
    return q, k_new, v_new


def _decode_attend(params: Dict, cfg: ModelConfig, q: jax.Array,
                   cache_k: jax.Array, cache_v: jax.Array, pos: jax.Array,
                   window) -> jax.Array:
    """The new token's attention over positions ``<= pos`` of one layer's
    (b, S, kv, hd) cache, projected out."""
    b, _, h, hd = q.shape
    with jax.named_scope("attention"):
        k_pos = jnp.arange(cache_k.shape[1], dtype=jnp.int32)
        q_pos = jnp.full((1,), pos, jnp.int32)
        bias = _mask_bias(q_pos, k_pos, causal=True, window=window,
                          k_valid_len=pos + 1)
        out = sdpa(q, cache_k, cache_v, bias, cfg.logit_softcap)
        return dense(out.reshape(b, 1, h * hd), params["wo"])


def attention_decode_stacked(params: Dict, cfg: ModelConfig, x: jax.Array,
                             cache_k: jax.Array, cache_v: jax.Array,
                             idx: jax.Array, pos: jax.Array, *, window: int = 0
                             ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode of layer ``idx`` against the stacked (L, b, S, kv, hd)
    cache, in place: the new position is written into the stack and the
    layer's K/V are read where they lie, never copied out and back.
    ``pos`` is the scalar index of the new token (same for the whole batch).
    Returns the updated stacked cache."""
    q, k_new, v_new = _decode_qkv(params, cfg, x, pos)
    with jax.named_scope("kv_cache"):
        at = (idx, 0, pos, 0, 0)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k_new[None].astype(cache_k.dtype), at)
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v_new[None].astype(cache_v.dtype), at)
    with jax.named_scope("attention"):
        ck = jax.lax.dynamic_index_in_dim(cache_k, idx, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cache_v, idx, 0, keepdims=False)
    out = _decode_attend(params, cfg, q, ck, cv, pos, window)
    return out, (cache_k, cache_v)
