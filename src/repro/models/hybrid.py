"""Zamba2-style hybrid LM: Mamba2 backbone + a single weight-SHARED attention
block applied every ``attn_every`` layers.

Structure (G = num_layers // attn_every groups, R = remainder mamba layers):

    for g in 0..G-1:   shared_attn_block(x)  ;  attn_every x mamba(x)
    then R trailing mamba layers

The shared block's weights are one set reused at every application point; each
application has its own KV cache (decode).  Simplifications vs the released
model (documented): no per-application LoRA on the shared block, standard
pre-norm residual wiring.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import MeshConfig, ModelConfig, ShapeConfig, ShardingConfig
from repro.distributed.sharding import lc
from repro.models import attention as attn
from repro.models import ssm
from repro.models.layers import (
    ParamSpec, abstract_params, axes_tree, init_params, lm_loss_from_hidden, pad_vocab,
    rms_norm, rms_norm_spec, softmax_cross_entropy, stack_specs, swiglu,
)
from repro.models.transformer import _remat


class HybridLM:
    def __init__(self, cfg: ModelConfig, sharding: ShardingConfig = ShardingConfig()):
        self.cfg = cfg
        self.sharding = sharding
        self.groups = cfg.num_layers // cfg.attn_every
        self.remainder = cfg.num_layers - self.groups * cfg.attn_every

    # ------------------------------------------------------------------ specs
    def _mamba_specs(self) -> Dict[str, Any]:
        return {"ln": rms_norm_spec(self.cfg.d_model),
                "mixer": ssm.ssm_param_specs(self.cfg)}

    def _shared_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "ln1": rms_norm_spec(cfg.d_model),
            "attn": attn.attn_param_specs(cfg),
            "ln2": rms_norm_spec(cfg.d_model),
            "ffn": {
                "w_gate": ParamSpec((cfg.d_model, cfg.d_ff), ("fsdp", "ffn")),
                "w_up": ParamSpec((cfg.d_model, cfg.d_ff), ("fsdp", "ffn")),
                "w_down": ParamSpec((cfg.d_ff, cfg.d_model), ("ffn", "fsdp")),
            },
        }

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs = {
            "embed": ParamSpec((pad_vocab(cfg.vocab_size), cfg.d_model),
                               (None, "embed_tbl"), init="embed", scale=0.02),
            "shared": self._shared_specs(),
            "groups": stack_specs(stack_specs(self._mamba_specs(), cfg.attn_every),
                                  self.groups),
            "ln_f": rms_norm_spec(cfg.d_model),
            "head": ParamSpec((cfg.d_model, pad_vocab(cfg.vocab_size)),
                              ("fsdp", "vocab")),
        }
        if self.remainder:
            specs["tail"] = stack_specs(self._mamba_specs(), self.remainder)
        return specs

    def init(self, key):
        return init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract(self):
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def axes(self):
        return axes_tree(self.param_specs())

    def logical_overrides(self, mesh_cfg: MeshConfig) -> Dict[str, Any]:
        m = mesh_cfg.axis_size("model")
        if self.cfg.num_kv_heads and self.cfg.num_kv_heads % m == 0:
            return {"kv_heads": "model", "head_dim": None}
        return {"kv_heads": None, "head_dim": "model"}

    # ---------------------------------------------------------------- blocks
    def _shared_block(self, p, x, positions):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        h = attn.attention(p["attn"], cfg, h, positions)
        x = x + h
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        h = swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"], p["ffn"]["w_down"])
        return x + h

    def _mamba_block(self, p, x):
        h = rms_norm(x, p["ln"], self.cfg.norm_eps)
        return lc(x + ssm.ssm_mixer(p["mixer"], self.cfg, h),
                  ("batch", "act_seq", "embed"))

    # ----------------------------------------------------------------- train
    def hidden(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(lc(params["embed"], (None, "embed_tbl")), tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        x = lc(x, ("batch", "act_seq", "embed"))
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        shared = params["shared"]

        def mamba_step(x, p_l):
            return self._mamba_block(p_l, x), None

        def group_step(x, p_group):
            x = self._shared_block(shared, x, positions)
            # nested remat: per-mamba-layer inside the group-level checkpoint,
            # so a group's bwd recompute holds one mamba layer's internals
            x, _ = jax.lax.scan(_remat(mamba_step, self.sharding.remat_policy),
                                x, p_group)
            return lc(x, ("batch", "act_seq", "embed")), None

        # remat at group granularity: the shared attention block's internals
        # are recomputed in bwd, not saved once per application point
        x, _ = jax.lax.scan(_remat(group_step, self.sharding.remat_policy),
                            x, params["groups"])
        if self.remainder:
            x, _ = jax.lax.scan(_remat(mamba_step, self.sharding.remat_policy),
                                x, params["tail"])
        return rms_norm(x, params["ln_f"], cfg.norm_eps)

    def forward(self, params, tokens):
        x = self.hidden(params, tokens)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return lc(logits, ("batch", "act_seq", "vocab"))

    def loss(self, params, batch):
        x = self.hidden(params, batch["tokens"])
        loss, ce = lm_loss_from_hidden(x, params["head"], batch["labels"],
                                       z_loss=1e-4)
        return loss, {"ce": ce}

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch):
        """Full-sequence prefill; returns last-token logits + decode cache."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = jnp.take(lc(params["embed"], (None, "embed_tbl")), tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        positions = jnp.arange(s, dtype=jnp.int32)
        shared = params["shared"]

        def mamba_prefill(x, p_l):
            # run the chunked mixer AND extract the final recurrent state
            h = rms_norm(x, p_l["ln"], cfg.norm_eps)
            d_inner, heads, headdim, n = ssm._dims(cfg)
            zxbcdt = jnp.einsum("bsd,de->bse", h, p_l["mixer"]["in_proj"])
            z, xs_, B, C, dt = ssm._split_proj(cfg, zxbcdt)
            xbc_raw = jnp.concatenate([xs_, B, C], axis=-1)
            xbc = ssm._causal_conv(xbc_raw, p_l["mixer"]["conv_w"],
                                   p_l["mixer"]["conv_b"])
            xs2, B2, C2 = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
            dtp = jax.nn.softplus(dt.astype(jnp.float32) +
                                  p_l["mixer"]["dt_bias"].astype(jnp.float32))
            A = -jnp.exp(p_l["mixer"]["a_log"].astype(jnp.float32))
            xh = xs2.reshape(b, s, heads, headdim)
            xdt = (xh.astype(jnp.float32) * dtp[..., None]).astype(x.dtype)
            state0 = jnp.zeros((b, heads, headdim, n), jnp.float32)
            y, state = ssm.ssd_chunked(xdt, dtp * A, B2, C2, state0)
            y = y + xh * p_l["mixer"]["d_skip"].astype(x.dtype)[None, None, :, None]
            y = y.reshape(b, s, d_inner)
            y = rms_norm(y * jax.nn.silu(z), p_l["mixer"]["norm"], cfg.norm_eps)
            out = x + jnp.einsum("bse,ed->bsd", y, p_l["mixer"]["out_proj"])
            conv_tail = xbc_raw[:, -(cfg.ssm_conv - 1):, :]
            return out, {"state": state, "conv": conv_tail}

        def group_prefill(x, p_group):
            h = rms_norm(x, shared["ln1"], cfg.norm_eps)
            h, (k, v) = attn.attention_prefill(shared["attn"], cfg, h, positions)
            x = x + h
            h = rms_norm(x, shared["ln2"], cfg.norm_eps)
            x = x + swiglu(h, shared["ffn"]["w_gate"], shared["ffn"]["w_up"],
                           shared["ffn"]["w_down"])
            x, mcaches = jax.lax.scan(mamba_prefill, x, p_group)
            return x, {"k": k, "v": v, "mamba": mcaches}

        x, caches = jax.lax.scan(group_prefill, x, params["groups"])
        tail_cache = None
        if self.remainder:
            x, tail_cache = jax.lax.scan(mamba_prefill, x, params["tail"])
        x = rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        cache = {"groups": caches, "tail": tail_cache,
                 "pos": jnp.asarray(s, jnp.int32)}
        return logits, cache

    def decode_step(self, params, cache, batch):
        """batch: {"token": (b, 1) int32}. Returns (logits, new cache).

        The stacked caches are the loops' CARRY, updated in place: the shared
        attention writes its new position into the stacked K/V, and a mamba
        layer reads its slice of ``state``/``conv`` where it lies and writes
        the new one back, its layer axes kept at size 1 from read to write
        (with a reshape between them the TPU compiler copies the slice out)."""
        cfg = self.cfg
        pos = cache["pos"]
        x = jnp.take(params["embed"], batch["token"], axis=0).astype(
            jnp.dtype(cfg.dtype))
        shared = params["shared"]

        def mamba_decode(lead):
            """The loop body whose j-th step updates layer ``lead + (j,)``."""
            def body(carry, inp):
                (x, mc_all), (p_l, j) = carry, inp
                ones = (1,) * (len(lead) + 1)
                at = lambda c: lead + (j,) + (0,) * (c.ndim - len(ones))
                mc = {k: jax.lax.dynamic_slice(c, at(c), ones + c.shape[len(ones):])
                      for k, c in mc_all.items()}
                h = rms_norm(x, p_l["ln"], cfg.norm_eps)
                h, mc = ssm.ssm_decode_step(p_l["mixer"], cfg, h, mc)
                mc_all = {k: jax.lax.dynamic_update_slice(c, mc[k], at(c))
                          for k, c in mc_all.items()}
                return (x + h, mc_all), None
            return body

        def group_decode(carry, inp):
            (x, ck_all, cv_all, mc_all), (p_group, g) = carry, inp
            h = rms_norm(x, shared["ln1"], cfg.norm_eps)
            h, (ck_all, cv_all) = attn.attention_decode_stacked(
                shared["attn"], cfg, h, ck_all, cv_all, g, pos)
            x = x + h
            h = rms_norm(x, shared["ln2"], cfg.norm_eps)
            x = x + swiglu(h, shared["ffn"]["w_gate"], shared["ffn"]["w_up"],
                           shared["ffn"]["w_down"])
            (x, mc_all), _ = jax.lax.scan(
                mamba_decode((g,)), (x, mc_all),
                (p_group, jnp.arange(cfg.attn_every, dtype=jnp.int32)))
            return (x, ck_all, cv_all, mc_all), None

        gc = cache["groups"]
        (x, ks, vs, mamba), _ = jax.lax.scan(
            group_decode, (x, gc["k"], gc["v"], gc["mamba"]),
            (params["groups"], jnp.arange(self.groups, dtype=jnp.int32)))
        tail_cache = cache["tail"]
        if self.remainder:
            (x, tail_cache), _ = jax.lax.scan(
                mamba_decode(()), (x, tail_cache),
                (params["tail"], jnp.arange(self.remainder, dtype=jnp.int32)))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return logits, {"groups": {"k": ks, "v": vs, "mamba": mamba},
                        "tail": tail_cache, "pos": pos + 1}

    # ------------------------------------------------------------------ specs
    def text_len(self, shape: ShapeConfig) -> int:
        return shape.seq_len

    def train_input_specs(self, shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return ({"tokens": tok, "labels": tok},
                {"tokens": ("batch", "seq"), "labels": ("batch", "seq")})

    def prefill_input_specs(self, shape: ShapeConfig):
        specs, axes = self.train_input_specs(shape)
        specs.pop("labels"), axes.pop("labels")
        return specs, axes

    def decode_state_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, S = shape.global_batch, shape.seq_len
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        d_inner, heads, headdim, n = ssm._dims(cfg)
        conv_ch = d_inner + 2 * n
        G, E = self.groups, cfg.attn_every
        f32, act = jnp.float32, jnp.dtype(cfg.dtype)
        mamba = {"state": jax.ShapeDtypeStruct((G, E, b, heads, headdim, n), f32),
                 "conv": jax.ShapeDtypeStruct((G, E, b, cfg.ssm_conv - 1, conv_ch), act)}
        mamba_axes = {"state": ("layers", "layers", "batch", "ssm_heads", None, "state"),
                      "conv": ("layers", "layers", "batch", None, "ffn")}
        cache = {"groups": {
                    "k": jax.ShapeDtypeStruct((G, b, S, kv, hd), act),
                    "v": jax.ShapeDtypeStruct((G, b, S, kv, hd), act),
                    "mamba": mamba},
                 "tail": None,
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        cache_axes = {"groups": {
                    "k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                    "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                    "mamba": mamba_axes},
                 "tail": None,
                 "pos": ()}
        if self.remainder:
            R = self.remainder
            cache["tail"] = {
                "state": jax.ShapeDtypeStruct((R, b, heads, headdim, n), f32),
                "conv": jax.ShapeDtypeStruct((R, b, cfg.ssm_conv - 1, conv_ch), act)}
            cache_axes["tail"] = {
                "state": ("layers", "batch", "ssm_heads", None, "state"),
                "conv": ("layers", "batch", None, "ffn")}
        tok = {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
        return cache, cache_axes, tok, {"token": ("batch", "seq")}
