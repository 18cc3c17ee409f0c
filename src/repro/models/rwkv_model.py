"""RWKV6 LM (family "ssm"): attention-free, O(1)-state decode.

Decode carries the stacked caches (the float32 WKV state, the two
token-shift carries) through its layer loop and writes each layer's slice
in place, as the transformer does its KV cache: the state is most of the
bytes a decode step moves, and the loop then holds it once and never
copies it.

The embedding, the norms and the output head run under the flat
``jax.named_scope``s ``embed``, ``norm`` and ``head``; the blocks' own
scopes (``time_mix``, ``wkv``, ``channel_mix``) are in ``rwkv.py``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import MeshConfig, ModelConfig, ShapeConfig, ShardingConfig
from repro.distributed.sharding import lc
from repro.models import rwkv
from repro.models.layers import (
    ParamSpec, abstract_params, axes_tree, init_params, lm_loss_from_hidden, pad_vocab,
    rms_norm, rms_norm_spec, softmax_cross_entropy, stack_specs,
)
from repro.models.transformer import _remat


class RWKVLM:
    #: XLA options for a train step sharded over several TPU devices.  The
    #: TPU compiler (libtpu 0.0.34) fails a scheduling RET_CHECK ("not
    #: scheduled after its control predecessor") on the sharded step unless
    #: async collective fusion is off, which may expose its collectives.
    sharded_tpu_compiler_options = {
        "xla_tpu_enable_async_collective_fusion": "false"}

    def __init__(self, cfg: ModelConfig, sharding: ShardingConfig = ShardingConfig()):
        self.cfg = cfg
        self.sharding = sharding

    def layer_specs(self) -> Dict[str, Any]:
        return {
            "ln1": rms_norm_spec(self.cfg.d_model),
            "time": rwkv.rwkv_time_specs(self.cfg),
            "ln2": rms_norm_spec(self.cfg.d_model),
            "channel": rwkv.rwkv_channel_specs(self.cfg),
        }

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": ParamSpec((pad_vocab(cfg.vocab_size), cfg.d_model),
                               (None, "embed_tbl"), init="embed", scale=0.02),
            "ln_in": rms_norm_spec(cfg.d_model),
            "layers": stack_specs(self.layer_specs(), cfg.num_layers),
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
        return {}

    def _embed(self, params, tokens):
        """The tokens' rows of the table, then the input norm ``ln_in``."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            tbl = lc(params["embed"], (None, "embed_tbl"))
            x = jnp.take(tbl, tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        with jax.named_scope("norm"):
            return rms_norm(x, params["ln_in"], cfg.norm_eps)

    # ----------------------------------------------------------------- train
    def hidden(self, params, tokens):
        cfg = self.cfg
        b, s = tokens.shape
        heads, hd = rwkv._dims(cfg)
        x = self._embed(params, tokens)
        x = lc(x, ("batch", "act_seq", "embed"))
        zeros_prev = jnp.zeros((b, 1, cfg.d_model), x.dtype)
        state0 = jnp.zeros((b, heads, hd, hd), jnp.float32)

        def layer(x, p_l):
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            y, _, _ = rwkv.rwkv_time_mix(p_l["time"], cfg, h, zeros_prev, state0)
            x = x + y
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            y, _ = rwkv.rwkv_channel_mix(p_l["channel"], cfg, h, zeros_prev)
            return lc(x + y, ("batch", "act_seq", "embed")), None

        x, _ = jax.lax.scan(_remat(layer, self.sharding.remat_policy),
                            x, params["layers"])
        with jax.named_scope("norm"):
            return rms_norm(x, params["ln_f"], cfg.norm_eps)

    def forward(self, params, tokens):
        x = self.hidden(params, tokens)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return lc(logits, ("batch", "act_seq", "vocab"))

    def loss(self, params, batch):
        x = self.hidden(params, batch["tokens"])
        with jax.named_scope("head"):
            loss, ce = lm_loss_from_hidden(x, params["head"], batch["labels"],
                                           z_loss=1e-4)
        return loss, {"ce": ce}

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        heads, hd = rwkv._dims(cfg)
        x = self._embed(params, tokens)
        zeros_prev = jnp.zeros((b, 1, cfg.d_model), x.dtype)
        state0 = jnp.zeros((b, heads, hd, hd), jnp.float32)

        def layer(x, p_l):
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            y, tm_prev, state = rwkv.rwkv_time_mix(p_l["time"], cfg, h,
                                                   zeros_prev, state0)
            x = x + y
            with jax.named_scope("norm"):
                h2 = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            y, cm_prev = rwkv.rwkv_channel_mix(p_l["channel"], cfg, h2, zeros_prev)
            cache = {"state": state, "tm_prev": tm_prev, "cm_prev": cm_prev}
            return x + y, cache

        x, caches = jax.lax.scan(layer, x, params["layers"])
        with jax.named_scope("norm"):
            x = rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        caches["pos"] = jnp.asarray(s, jnp.int32)
        return logits, caches

    def decode_step(self, params, cache, batch):
        """batch: {"token": (b, 1) int32}. Returns (logits, new cache).

        The stacked caches are the layer loop's CARRY: layer ``idx`` reads
        its slice where it lies and writes the new one back in place.  The
        while loop aliases carries, so the float32 WKV state (3.2 GB at
        batch 256) exists once in HBM; as the scan's ``xs``/``ys`` every
        layer sliced it out, relaid it out and back, and wrote it into a
        second stack, and a whole-state copy followed the loop.  The state's
        slice keeps its layer axis of 1 from read to write: with a reshape
        between them, the TPU compiler copies the slice out before its
        in-place update."""
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed(params, batch["token"])

        def layer(carry, inp):
            x, st_all, tm_all, cm_all = carry
            p_l, idx = inp
            st = jax.lax.dynamic_slice_in_dim(st_all, idx, 1, 0)
            tm_prev = jax.lax.dynamic_index_in_dim(tm_all, idx, 0, keepdims=False)
            cm_prev = jax.lax.dynamic_index_in_dim(cm_all, idx, 0, keepdims=False)
            with jax.named_scope("norm"):
                h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
            y, tm_new, st_new = rwkv.rwkv_time_decode(p_l["time"], cfg, h,
                                                      tm_prev, st)
            x = x + y
            with jax.named_scope("norm"):
                h2 = rms_norm(x, p_l["ln2"], cfg.norm_eps)
            y, cm_new = rwkv.rwkv_channel_decode(p_l["channel"], cfg, h2, cm_prev)
            st_all = jax.lax.dynamic_update_slice_in_dim(st_all, st_new, idx, 0)
            tm_all = jax.lax.dynamic_update_index_in_dim(tm_all, tm_new, idx, 0)
            cm_all = jax.lax.dynamic_update_index_in_dim(cm_all, cm_new, idx, 0)
            return (x + y, st_all, tm_all, cm_all), None

        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, state, tm_prev, cm_prev), _ = jax.lax.scan(
            layer, (x, cache["state"], cache["tm_prev"], cache["cm_prev"]),
            (params["layers"], idxs))
        with jax.named_scope("norm"):
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return logits, {"state": state, "tm_prev": tm_prev, "cm_prev": cm_prev,
                        "pos": pos + 1}

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
        b = shape.global_batch
        heads, hd = rwkv._dims(cfg)
        L = cfg.num_layers
        act = jnp.dtype(cfg.dtype)
        cache = {
            "state": jax.ShapeDtypeStruct((L, b, heads, hd, hd), jnp.float32),
            "tm_prev": jax.ShapeDtypeStruct((L, b, 1, cfg.d_model), act),
            "cm_prev": jax.ShapeDtypeStruct((L, b, 1, cfg.d_model), act),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
        cache_axes = {
            "state": ("layers", "batch", "ssm_heads", None, None),
            "tm_prev": ("layers", "batch", None, "embed"),
            "cm_prev": ("layers", "batch", None, "embed"),
            "pos": (),
        }
        tok = {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
        return cache, cache_axes, tok, {"token": ("batch", "seq")}
