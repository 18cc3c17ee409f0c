"""Parameter-spec system + common neural-net layers (pure JAX, no flax).

Every model declares its parameters as a nested dict of :class:`ParamSpec`
(shape + logical sharding axes + initializer).  From the spec tree we derive:

* ``init_params``      — materialized arrays (smoke tests / real training)
* ``abstract_params``  — ShapeDtypeStructs (dry-run: zero allocation)
* ``axes_tree``        — logical axes pytree -> NamedShardings via rules

so the 512-chip dry-run never allocates a single parameter.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _lc(x, axes):
    from repro.distributed.sharding import lc
    return lc(x, axes)


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed | fan_in
    scale: float = 1.0
    dtype: Optional[str] = None   # None -> model compute dtype

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def pad_vocab(v: int, multiple: int = 256) -> int:
    """Pad vocab to a shardable multiple (standard embedding-table padding)."""
    return ((v + multiple - 1) // multiple) * multiple


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


_SPEC_LEAF = dict(is_leaf=is_spec)


def _init_one(spec: ParamSpec, key, default_dtype) -> jax.Array:
    dtype = jnp.dtype(spec.dtype or default_dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, jnp.float32) * spec.scale).astype(dtype)
    if spec.init in ("normal", "fan_in"):
        fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
        if spec.init == "fan_in" and len(spec.shape) >= 2:
            fan_in = int(np.prod(spec.shape[:-1]))
        std = spec.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(specs: Any, key: jax.Array, dtype: str = "bfloat16") -> Any:
    leaves, treedef = jax.tree.flatten(specs, **_SPEC_LEAF)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(specs: Any, dtype: str = "bfloat16") -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or dtype)),
        specs, **_SPEC_LEAF)


def axes_tree(specs: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, **_SPEC_LEAF)


def spec_param_count(specs: Any, active_expert_frac: float = 1.0) -> int:
    """Analytic #params; expert-stacked tensors scaled by active fraction."""
    total = 0
    for s in jax.tree.leaves(specs, **_SPEC_LEAF):
        n = int(np.prod(s.shape))
        if "experts" in s.axes:
            n = int(n * active_expert_frac)
        total += n
    return total


def stack_specs(specs: Any, n: int, axis_name: str = "layers") -> Any:
    """Add a leading stacked-layer dimension to every spec (for lax.scan)."""
    return jax.tree.map(
        lambda s: dataclasses.replace(s, shape=(n,) + s.shape,
                                      axes=(axis_name,) + s.axes),
        specs, **_SPEC_LEAF)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * (1.0 + gamma.astype(jnp.float32)).astype(x.dtype)


def rms_norm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), (None,), init="zeros")   # gamma stored as (1+g)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    g = dense(x, w_gate)
    u = dense(x, w_up)
    return dense(jax.nn.silu(g) * u, w_down)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def lm_loss_from_hidden(x: jax.Array, head_w: jax.Array, labels: jax.Array,
                        *, z_loss: float = 0.0, chunk: int = 512,
                        mask: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Sequence-chunked LM loss: never materializes full (b, s, V) logits.

    Each chunk's logits are recomputed in the backward pass (jax.checkpoint on
    the chunk body), bounding loss memory at O(b * chunk * V) — essential for
    the 262k-vocab gemma3 heads.  Returns (loss_mean, ce_mean).
    """
    b, s, d = x.shape
    # largest divisor of s that is <= chunk (s may be 3840 etc.)
    c = next(cc for cc in range(min(chunk, s), 0, -1) if s % cc == 0)
    nb = s // c
    x_c = x.reshape(b, nb, c, d).swapaxes(0, 1)          # (nb, b, c, d)
    l_c = labels.reshape(b, nb, c).swapaxes(0, 1)
    m_c = (mask if mask is not None else jnp.ones((b, s), jnp.float32)
           ).reshape(b, nb, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, inp):
        xb, lb, mb = inp
        # constrain head at the use site: with_sharding_constraint transposes
        # to itself, so the bwd head-gradient accumulator stays sharded too
        hw = _lc(head_w, ("fsdp", "vocab"))
        logits = jnp.einsum("bcd,dv->bcv", xb, hw)
        ce, zl = softmax_cross_entropy(logits, lb, z_loss=z_loss)
        tot, ce_tot, cnt = carry
        return (tot + jnp.sum((ce + zl) * mb), ce_tot + jnp.sum(ce * mb),
                cnt + jnp.sum(mb)), None

    init = (jnp.zeros((), jnp.float32),) * 3
    (tot, ce_tot, cnt), _ = jax.lax.scan(body, init, (x_c, l_c, m_c))
    denom = jnp.maximum(cnt, 1.0)
    return tot / denom, ce_tot / denom


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          label_smoothing: float = 0.0,
                          z_loss: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """Token-level CE with optional z-loss. Returns (loss_sum, z_loss_sum)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    # gather-free true-logit extraction: fuses to iota+select+reduce and stays
    # sharded under GSPMD (take_along_axis would all-gather the vocab dim)
    v_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    true_logit = jnp.sum(
        jnp.where(v_iota == labels[..., None], logits, 0.0), axis=-1)
    ce = lse - true_logit
    if label_smoothing:
        ce = (1.0 - label_smoothing) * ce + label_smoothing * (
            lse - jnp.mean(logits, axis=-1))
    zl = z_loss * jnp.square(lse) if z_loss else jnp.zeros_like(lse)
    return ce, zl
