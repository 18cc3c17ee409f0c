"""Encoder-decoder transformer (seamless-m4t backbone, family "audio"/"encdec").

The audio frontend is a STUB: the encoder consumes precomputed frame embeddings
(b, frontend_seq, d_model) supplied by ``input_specs`` — per the assignment
spec, only the transformer backbone is modeled.  Decoder = self-attn (causal) +
cross-attn over encoder outputs + classic 2-matrix FFN (relu), post-LN family
simplified to pre-RMSNorm (documented).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import MeshConfig, ModelConfig, ShapeConfig, ShardingConfig
from repro.distributed.sharding import lc
from repro.models import attention as attn
from repro.models.layers import (
    ParamSpec, abstract_params, axes_tree, dense, init_params,
    lm_loss_from_hidden, pad_vocab, rms_norm, rms_norm_spec, softmax_cross_entropy,
    stack_specs,
)
from repro.models.transformer import _remat


class EncDecLM:
    def __init__(self, cfg: ModelConfig, sharding: ShardingConfig = ShardingConfig()):
        self.cfg = cfg
        self.sharding = sharding

    # ------------------------------------------------------------------ specs
    def _ffn_specs(self):
        cfg = self.cfg
        return {
            "w_in": ParamSpec((cfg.d_model, cfg.d_ff), ("fsdp", "ffn")),
            "b_in": ParamSpec((cfg.d_ff,), ("ffn",), init="zeros"),
            "w_out": ParamSpec((cfg.d_ff, cfg.d_model), ("ffn", "fsdp")),
            "b_out": ParamSpec((cfg.d_model,), (None,), init="zeros"),
        }

    def enc_layer_specs(self):
        return {"ln1": rms_norm_spec(self.cfg.d_model),
                "attn": attn.attn_param_specs(self.cfg),
                "ln2": rms_norm_spec(self.cfg.d_model),
                "ffn": self._ffn_specs()}

    def dec_layer_specs(self):
        return {"ln1": rms_norm_spec(self.cfg.d_model),
                "self_attn": attn.attn_param_specs(self.cfg),
                "ln_x": rms_norm_spec(self.cfg.d_model),
                "cross_attn": attn.attn_param_specs(self.cfg),
                "ln2": rms_norm_spec(self.cfg.d_model),
                "ffn": self._ffn_specs()}

    def param_specs(self):
        cfg = self.cfg
        return {
            "embed": ParamSpec((pad_vocab(cfg.vocab_size), cfg.d_model),
                               (None, "embed_tbl"), init="embed", scale=0.02),
            "encoder": stack_specs(self.enc_layer_specs(), cfg.encoder_layers),
            "ln_enc": rms_norm_spec(cfg.d_model),
            "decoder": stack_specs(self.dec_layer_specs(), cfg.num_layers),
            "ln_f": rms_norm_spec(cfg.d_model),
            "head": ParamSpec((cfg.d_model, pad_vocab(cfg.vocab_size)),
                              ("fsdp", "vocab")),
        }

    def init(self, key):
        return init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract(self):
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def axes(self):
        return axes_tree(self.param_specs())

    def logical_overrides(self, mesh_cfg: MeshConfig) -> Dict[str, Any]:
        m = mesh_cfg.axis_size("model")
        if self.cfg.num_kv_heads % m == 0:
            return {"kv_heads": "model", "head_dim": None}
        return {"kv_heads": None, "head_dim": "model"}

    # --------------------------------------------------------------- encoder
    def encode(self, params, frontend_emb):
        cfg = self.cfg
        x = lc(frontend_emb.astype(jnp.dtype(cfg.dtype)),
               ("batch", "act_seq", "embed"))
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)

        def layer(x, p_l):
            h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            h = attn.attention(p_l["attn"], cfg, h, positions, causal=False)
            x = x + h
            h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            h = dense(jax.nn.relu(dense(h, p_l["ffn"]["w_in"], p_l["ffn"]["b_in"])),
                      p_l["ffn"]["w_out"], p_l["ffn"]["b_out"])
            return lc(x + h, ("batch", "act_seq", "embed")), None

        x, _ = jax.lax.scan(_remat(layer, self.sharding.remat_policy),
                            x, params["encoder"])
        return rms_norm(x, params["ln_enc"], cfg.norm_eps)

    # --------------------------------------------------------------- decoder
    def _dec_layer(self, p_l, x, enc_out, positions):
        cfg = self.cfg
        h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
        h = attn.attention(p_l["self_attn"], cfg, h, positions)
        x = x + h
        h = rms_norm(x, p_l["ln_x"], cfg.norm_eps)
        h = attn.attention(p_l["cross_attn"], cfg, h, positions,
                           kv_source=enc_out, causal=False)
        x = x + h
        h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
        h = dense(jax.nn.relu(dense(h, p_l["ffn"]["w_in"], p_l["ffn"]["b_in"])),
                  p_l["ffn"]["w_out"], p_l["ffn"]["b_out"])
        return x + h

    def hidden(self, params, tokens, frontend_emb):
        cfg = self.cfg
        enc_out = self.encode(params, frontend_emb)
        x = jnp.take(lc(params["embed"], (None, "embed_tbl")), tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)

        def layer(x, p_l):
            return lc(self._dec_layer(p_l, x, enc_out, positions),
                      ("batch", "act_seq", "embed")), None

        x, _ = jax.lax.scan(_remat(layer, self.sharding.remat_policy),
                            x, params["decoder"])
        return rms_norm(x, params["ln_f"], cfg.norm_eps)

    def forward(self, params, tokens, frontend_emb):
        x = self.hidden(params, tokens, frontend_emb)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return lc(logits, ("batch", "act_seq", "vocab"))

    def loss(self, params, batch):
        x = self.hidden(params, batch["tokens"], batch["frontend_emb"])
        loss, ce = lm_loss_from_hidden(x, params["head"], batch["labels"],
                                       z_loss=1e-4)
        return loss, {"ce": ce}

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch):
        """Encode + causal prefill of the decoder prompt; returns KV caches."""
        cfg = self.cfg
        enc_out = self.encode(params, batch["frontend_emb"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = jnp.take(lc(params["embed"], (None, "embed_tbl")), tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        positions = jnp.arange(s, dtype=jnp.int32)

        def layer(x, p_l):
            h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            h, (k, v) = attn.attention_prefill(p_l["self_attn"], cfg, h, positions)
            x = x + h
            h = rms_norm(x, p_l["ln_x"], cfg.norm_eps)
            h = attn.attention(p_l["cross_attn"], cfg, h, positions,
                               kv_source=enc_out, causal=False)
            x = x + h
            h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            h = dense(jax.nn.relu(dense(h, p_l["ffn"]["w_in"], p_l["ffn"]["b_in"])),
                      p_l["ffn"]["w_out"], p_l["ffn"]["b_out"])
            return x + h, (k, v)

        x, (ks, vs) = jax.lax.scan(layer, x, params["decoder"])
        x = rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        cache = {"k": ks, "v": vs, "enc_out": enc_out,
                 "pos": jnp.asarray(s, jnp.int32)}
        return logits, cache

    def decode_step(self, params, cache, batch):
        cfg = self.cfg
        pos = cache["pos"]
        enc_out = cache["enc_out"]
        x = jnp.take(params["embed"], batch["token"], axis=0).astype(
            jnp.dtype(cfg.dtype))
        positions = jnp.full((1,), pos, jnp.int32)

        def layer(carry, inp):
            x, ck_all, cv_all = carry       # cache carried: in-place aliasing
            p_l, idx = inp
            h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            h, (ck_all, cv_all) = attn.attention_decode_stacked(
                p_l["self_attn"], cfg, h, ck_all, cv_all, idx, pos)
            x = x + h
            h = rms_norm(x, p_l["ln_x"], cfg.norm_eps)
            h = attn.attention(p_l["cross_attn"], cfg, h, positions,
                               kv_source=enc_out, causal=False)
            x = x + h
            h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            h = dense(jax.nn.relu(dense(h, p_l["ffn"]["w_in"], p_l["ffn"]["b_in"])),
                      p_l["ffn"]["w_out"], p_l["ffn"]["b_out"])
            return (x + h, ck_all, cv_all), None

        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, ks, vs), _ = jax.lax.scan(layer, (x, cache["k"], cache["v"]),
                                      (params["decoder"], idxs))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return logits, {"k": ks, "v": vs, "enc_out": enc_out, "pos": pos + 1}

    # ------------------------------------------------------------------ specs
    def text_len(self, shape: ShapeConfig) -> int:
        return max(shape.seq_len - self.cfg.frontend_seq, 1)

    def train_input_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b = shape.global_batch
        s = self.text_len(shape)
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs = {"tokens": tok, "labels": tok,
                 "frontend_emb": jax.ShapeDtypeStruct(
                     (b, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype))}
        axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq"),
                "frontend_emb": ("batch", "frontend_seq", "embed")}
        return specs, axes

    def prefill_input_specs(self, shape: ShapeConfig):
        specs, axes = self.train_input_specs(shape)
        specs.pop("labels"), axes.pop("labels")
        return specs, axes

    def decode_state_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, S = shape.global_batch, self.text_len(shape)
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        act = jnp.dtype(cfg.dtype)
        kv_sds = jax.ShapeDtypeStruct((cfg.num_layers, b, S, kv, hd), act)
        cache = {"k": kv_sds, "v": kv_sds,
                 "enc_out": jax.ShapeDtypeStruct((b, cfg.frontend_seq, cfg.d_model), act),
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        cache_axes = {"k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                      "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
                      "enc_out": ("batch", "seq", "embed"),
                      "pos": ()}
        tok = {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
        return cache, cache_axes, tok, {"token": ("batch", "seq")}
