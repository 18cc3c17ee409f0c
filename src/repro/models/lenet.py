"""LeNet-5 — the paper's §IV correlation workload, with selectable conv algos."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import MeshConfig, ModelConfig, ShapeConfig, ShardingConfig
from repro.models.conv_algos import conv2d
from repro.models.layers import (
    ParamSpec, abstract_params, axes_tree, init_params, softmax_cross_entropy,
)


def _pool(x: jax.Array) -> jax.Array:
    """2x2 max pool."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


class LeNet:
    def __init__(self, cfg: ModelConfig, sharding: ShardingConfig = ShardingConfig(),
                 conv_algo: str = "gemm"):
        # im2col by default: the TPU compiler takes minutes over the weight
        # gradient of a native conv with one input channel at batch 256
        # (1 s at batch 16, 59 s at 64, described v5e)
        self.cfg = cfg
        self.conv_algo = conv_algo

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        k = cfg.conv_kernel
        c1, c2 = cfg.conv_channels
        hw = cfg.image_hw
        # conv1 SAME + pool, conv2 VALID + pool
        h2 = (hw // 2 - (k - 1)) // 2
        flat = h2 * h2 * c2
        f1, f2 = cfg.fc_dims
        return {
            "conv1": ParamSpec((k, k, cfg.image_c, c1), (None, None, "conv_in", "conv_out")),
            "b1": ParamSpec((c1,), ("conv_out",), init="zeros"),
            "conv2": ParamSpec((k, k, c1, c2), (None, None, "conv_in", "conv_out")),
            "b2": ParamSpec((c2,), ("conv_out",), init="zeros"),
            "fc1": ParamSpec((flat, f1), ("fsdp", "ffn")),
            "fb1": ParamSpec((f1,), ("ffn",), init="zeros"),
            "fc2": ParamSpec((f1, f2), ("ffn", "fsdp")),
            "fb2": ParamSpec((f2,), (None,), init="zeros"),
            "fc3": ParamSpec((f2, cfg.num_classes), ("fsdp", "classes")),
            "fb3": ParamSpec((cfg.num_classes,), ("classes",), init="zeros"),
        }

    def init(self, key):
        return init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract(self):
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def axes(self):
        return axes_tree(self.param_specs())

    def logical_overrides(self, mesh_cfg: MeshConfig) -> Dict[str, Any]:
        return {}

    def forward(self, params, images):
        x = images.astype(jnp.dtype(self.cfg.dtype))
        x = jax.nn.relu(conv2d(x, params["conv1"], self.conv_algo, "SAME") + params["b1"])
        x = _pool(x)
        x = jax.nn.relu(conv2d(x, params["conv2"], self.conv_algo, "VALID") + params["b2"])
        x = _pool(x)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"] + params["fb1"])
        x = jax.nn.relu(x @ params["fc2"] + params["fb2"])
        return x @ params["fc3"] + params["fb3"]

    def loss(self, params, batch):
        logits = self.forward(params, batch["images"])
        ce, _ = softmax_cross_entropy(logits, batch["labels"])
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
        return jnp.mean(ce), {"ce": jnp.mean(ce), "accuracy": acc}

    def text_len(self, shape: ShapeConfig) -> int:
        return 1

    def train_input_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b = shape.global_batch
        specs = {"images": jax.ShapeDtypeStruct((b, cfg.image_hw, cfg.image_hw,
                                                 cfg.image_c), jnp.float32),
                 "labels": jax.ShapeDtypeStruct((b,), jnp.int32)}
        axes = {"images": ("batch", "spatial", "spatial", "conv_in"),
                "labels": ("batch",)}
        return specs, axes
