"""Decoder-only transformer LM (families: dense, moe, vlm).

Layers are stacked on a leading axis and applied with ``lax.scan`` (+ optional
``jax.checkpoint``), which keeps compiled HLO size O(1) in depth — essential
for the 512-chip dry-runs — and gives the simulator a clean while-loop trip
count to scale per-layer cost by.

Each part of a step runs under one flat ``jax.named_scope`` (``embed``,
``norm``, ``attention`` and ``kv_cache`` from ``attention.py``, ``mlp``,
``head``), which names its ops in the HLO metadata, so a device trace can
charge op time to a part whatever numbers XLA gives its fusions.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MeshConfig, ModelConfig, ShapeConfig, ShardingConfig
from repro.distributed.sharding import lc
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import (
    ParamSpec, abstract_params, axes_tree, init_params, lm_loss_from_hidden, pad_vocab,
    rms_norm, rms_norm_spec, softmax_cross_entropy, stack_specs, swiglu,
)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)   # "full"


class DecoderLM:
    """Shared implementation for dense / moe / vlm decoder-only models."""

    def __init__(self, cfg: ModelConfig, sharding: ShardingConfig = ShardingConfig()):
        self.cfg = cfg
        self.sharding = sharding
        self.moe_capacity = 1.25      # train/prefill capacity factor (<=0: no-drop)

    # ------------------------------------------------------------------ specs
    def layer_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "ln1": rms_norm_spec(cfg.d_model),
            "attn": attn.attn_param_specs(cfg),
            "ln2": rms_norm_spec(cfg.d_model),
        }
        if cfg.family == "moe":
            specs["moe"] = moe_mod.moe_param_specs(cfg)
        else:
            specs["ffn"] = {
                "w_gate": ParamSpec((cfg.d_model, cfg.d_ff), ("fsdp", "ffn")),
                "w_up": ParamSpec((cfg.d_model, cfg.d_ff), ("fsdp", "ffn")),
                "w_down": ParamSpec((cfg.d_ff, cfg.d_model), ("ffn", "fsdp")),
            }
        return specs

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": ParamSpec((pad_vocab(cfg.vocab_size), cfg.d_model),
                               (None, "embed_tbl"), init="embed", scale=0.02),
            "layers": stack_specs(self.layer_specs(), cfg.num_layers),
            "ln_f": rms_norm_spec(cfg.d_model),
            "head": ParamSpec((cfg.d_model, pad_vocab(cfg.vocab_size)),
                              ("fsdp", "vocab")),
        }

    def init(self, key) -> Any:
        return init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract(self) -> Any:
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def axes(self) -> Any:
        return axes_tree(self.param_specs())

    def logical_overrides(self, mesh_cfg: MeshConfig) -> Dict[str, Any]:
        """Divisibility-aware cache sharding: prefer kv-head sharding, fall back
        to head-dim sharding when kv_heads doesn't divide the model axis."""
        m = mesh_cfg.axis_size("model")
        if self.cfg.num_kv_heads and self.cfg.num_kv_heads % m == 0:
            return {"kv_heads": "model", "head_dim": None}
        return {"kv_heads": None, "head_dim": "model"}

    # ---------------------------------------------------------------- embed
    def _embed(self, params, tokens, frontend_emb=None, seq_axis="act_seq"):
        with jax.named_scope("embed"):
            tbl = lc(params["embed"], (None, "embed_tbl"))
            x = jnp.take(tbl, tokens, axis=0).astype(jnp.dtype(self.cfg.dtype))
            if frontend_emb is not None:
                x = jnp.concatenate([frontend_emb.astype(x.dtype), x], axis=1)
            return lc(x, ("batch", seq_axis, "embed"))

    def _window_for(self, idx):
        cfg = self.cfg
        if cfg.global_every <= 0:
            return cfg.window_size
        is_global = (idx + 1) % cfg.global_every == 0
        return jnp.where(is_global, 0, cfg.window_size)

    # ---------------------------------------------------------------- train
    def hidden(self, params, tokens, frontend_emb=None):
        """Causal forward -> (final-norm hidden (b, s_total, d), moe aux)."""
        cfg = self.cfg
        x = self._embed(params, tokens, frontend_emb)
        s_total = x.shape[1]
        positions = jnp.arange(s_total, dtype=jnp.int32)

        def layer(carry, inp):
            x, aux = carry
            p_l, idx = inp
            window = self._window_for(idx)
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            h = attn.attention(p_l["attn"], cfg, h, positions, window=window)
            x = x + h
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                if cfg.family == "moe":
                    h, a = moe_mod.moe_ffn(p_l["moe"], cfg, h,
                                           capacity_factor=self.moe_capacity,
                                           gather_once=self.sharding.moe_gather_once)
                    aux = aux + a
                else:
                    h = swiglu(h, p_l["ffn"]["w_gate"], p_l["ffn"]["w_up"],
                               p_l["ffn"]["w_down"])
            x = lc(x + h, ("batch", "act_seq", "embed"))
            return (x, aux), None

        layer = _remat(layer, self.sharding.remat_policy)
        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, aux), _ = jax.lax.scan(layer, (x, jnp.zeros((), jnp.float32)),
                                   (params["layers"], idxs))
        with jax.named_scope("norm"):
            return rms_norm(x, params["ln_f"], cfg.norm_eps), aux

    def forward(self, params, tokens, frontend_emb=None):
        """Full logits (test/debug convenience; training uses chunked loss)."""
        x, aux = self.hidden(params, tokens, frontend_emb)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return lc(logits, ("batch", "act_seq", "vocab")), aux

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x, aux = self.hidden(params, batch["tokens"], batch.get("frontend_emb"))
        if cfg.frontend != "none":          # loss only on text positions
            x = x[:, cfg.frontend_seq:]
        loss, ce = lm_loss_from_hidden(x, params["head"], batch["labels"],
                                       z_loss=1e-4, mask=batch.get("loss_mask"))
        metrics = {"ce": ce, "aux_loss": aux}
        if cfg.family == "moe":
            loss = loss + 1e-2 * aux
        return loss, metrics

    # -------------------------------------------------------------- prefill
    def prefill(self, params, batch):
        """Returns (last-token logits, cache). Cache K/V: (L, b, S, kv, hd)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch.get("frontend_emb"))
        s_total = x.shape[1]
        positions = jnp.arange(s_total, dtype=jnp.int32)

        def layer(x, inp):
            p_l, idx = inp
            window = self._window_for(idx)
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            h, (k, v) = attn.attention_prefill(p_l["attn"], cfg, h, positions,
                                               window=window)
            x = x + h
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                if cfg.family == "moe":
                    h, _ = moe_mod.moe_ffn(p_l["moe"], cfg, h,
                                           capacity_factor=self.moe_capacity,
                                           gather_once=self.sharding.moe_gather_once)
                else:
                    h = swiglu(h, p_l["ffn"]["w_gate"], p_l["ffn"]["w_up"],
                               p_l["ffn"]["w_down"])
            return lc(x + h, ("batch", "act_seq", "embed")), (k, v)

        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        x, (ks, vs) = jax.lax.scan(layer, x, (params["layers"], idxs))
        with jax.named_scope("norm"):
            x = rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        cache = {"k": lc(ks, ("layers", "batch", "kv_seq", "kv_heads", "head_dim")),
                 "v": lc(vs, ("layers", "batch", "kv_seq", "kv_heads", "head_dim")),
                 "pos": jnp.asarray(s_total, jnp.int32)}
        return logits, cache

    # --------------------------------------------------------------- decode
    def decode_step(self, params, cache, batch):
        """batch: {"token": (b, 1) int32}. Returns (logits, new cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], batch["token"], axis=0).astype(
                jnp.dtype(self.cfg.dtype))
            x = lc(x, ("batch", "seq", "embed"))   # decode: seq dim is 1, unsharded

        def layer(carry, inp):
            # cache as CARRY, written one position per layer in place and read
            # where it lies: the while loop aliases carries, so the KV cache
            # exists ONCE in HBM (cache-as-xs/ys held 2x live copies -> OOM on
            # 32k decode cells), and no layer copies its slice out and back
            x, ck_all, cv_all = carry
            p_l, idx = inp
            window = self._window_for(idx)
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            h, (ck_all, cv_all) = attn.attention_decode_stacked(
                p_l["attn"], cfg, h, ck_all, cv_all, idx, pos, window=window)
            x = x + h
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                if cfg.family == "moe":
                    h, _ = moe_mod.moe_ffn(p_l["moe"], cfg, h, capacity_factor=0.0)
                else:
                    h = swiglu(h, p_l["ffn"]["w_gate"], p_l["ffn"]["w_up"],
                               p_l["ffn"]["w_down"])
            return (x + h, ck_all, cv_all), None

        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, ks, vs), _ = jax.lax.scan(layer, (x, cache["k"], cache["v"]),
                                      (params["layers"], idxs))
        with jax.named_scope("norm"):
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        new_cache = {"k": ks, "v": vs, "pos": pos + 1}
        return logits, new_cache

    # ----------------------------------------------------------------- specs
    def text_len(self, shape: ShapeConfig) -> int:
        if self.cfg.frontend != "none":
            return max(shape.seq_len - self.cfg.frontend_seq, 1)
        return shape.seq_len

    def train_input_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, s = shape.global_batch, self.text_len(shape)
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs = {"tokens": tok, "labels": tok}
        axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}
        if cfg.frontend != "none":
            specs["frontend_emb"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype))
            axes["frontend_emb"] = ("batch", "frontend_seq", "embed")
        return specs, axes

    def prefill_input_specs(self, shape: ShapeConfig):
        specs, axes = self.train_input_specs(shape)
        specs.pop("labels"), axes.pop("labels")
        return specs, axes

    def decode_state_specs(self, shape: ShapeConfig):
        """Abstract cache as produced by prefill at full sequence length."""
        cfg = self.cfg
        b, S = shape.global_batch, shape.seq_len
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        kv_sds = jax.ShapeDtypeStruct((cfg.num_layers, b, S, kv, hd),
                                      jnp.dtype(cfg.dtype))
        cache = {"k": kv_sds, "v": kv_sds,
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        cache_axes = {"k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                      "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                      "pos": ()}
        tok = {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
        tok_axes = {"token": ("batch", "seq")}
        return cache, cache_axes, tok, tok_axes
