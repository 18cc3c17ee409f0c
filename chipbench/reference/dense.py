"""Plain float32 reference of a dense decoder-only LM with QKV bias (Qwen1.5),
and the seeded random weights that the serve cell hands the program.

The forward pass follows the published architecture: token embedding, then
per layer a pre-norm multi-head attention block with rotary positions
(rotate-half convention) and a pre-norm SwiGLU block, a final RMSNorm and an
untied output head.  It runs one layer at a time, in float32 with matmuls at
``HIGHEST`` precision, and imports nothing of the program.  Norm weights are
stored as an offset from one (``scale = 1 + g``), as the program stores them.

``quantize=True`` rounds both inputs of every matmul to float8 (e4m3, one
scale per tensor): the control, one precision step below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: rows of the vocabulary are padded to a multiple of this in the program's
#: embedding and head (ids past the published vocabulary are never used)
VOCAB_PAD = 256
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def param_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree by name, layers stacked on a leading axis."""
    d, h, kv, hd, f, L = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                          m["head_dim"], m["d_ff"], m["num_layers"])
    V = padded_vocab(m["vocab_size"])
    attn = {"wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
            "wo": (L, h * hd, d)}
    if m.get("qkv_bias"):
        attn.update(bq=(L, h * hd), bk=(L, kv * hd), bv=(L, kv * hd))
    return {"embed": (V, d), "head": (d, V), "ln_f": (d,),
            "layers": {"ln1": (L, d), "ln2": (L, d), "attn": attn,
                       "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                               "w_down": (L, f, d)}}}


def _draw(path: str, shape: Tuple[int, ...], key) -> jax.Array:
    """One leaf, keyed by its path ``a/b/leaf``: matrices N(0, 1/fan_in)
    over their contracted axis, the embedding, biases and norm offsets
    N(0, 0.02^2)."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    z = jax.random.normal(k, shape, jnp.float32)
    leaf = path.rsplit("/", 1)[-1]
    if leaf.startswith("w") or leaf == "head":
        return z / np.sqrt(shape[-2])
    return z * 0.02


def make_params(m: Dict[str, Any], seed: int) -> Any:
    """Every weight from ``seed``, on the device, in one jitted call, in the
    dtype the configuration serves (bfloat16)."""
    shapes = param_shapes(m)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    dtype = jnp.dtype(m["dtype"])

    def make(key):
        flat = [_draw("/".join(k.key for k in p), s, key).astype(dtype)
                for p, s in paths]
        return jax.tree.unflatten(
            jax.tree.structure(shapes, is_leaf=lambda x: isinstance(x, tuple)), flat)

    return jax.jit(make)(jax.random.key(seed))


# ---------------------------------------------------------------------------

def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(spec, a, b, quantize):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quantize:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (s, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(m, p, x, quantize: bool):
    """One decoder layer over x (b, s, d) float32, causal."""
    b, s, d = x.shape
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(s)
    a = p["attn"]
    y = _rms(x, p["ln1"], m["norm_eps"])
    q = _mm("bsd,de->bse", y, a["wq"], quantize)
    k = _mm("bsd,de->bse", y, a["wk"], quantize)
    v = _mm("bsd,de->bse", y, a["wv"], quantize)
    if "bq" in a:
        q, k, v = (t + a[n].astype(jnp.float32)
                   for t, n in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(b, s, h, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(b, s, kv, hd), pos, m["rope_theta"])
    v = v.reshape(b, s, kv, hd)
    k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
    scores = _mm("bqhd,bkhd->bhqk", q, k, quantize) / np.sqrt(hd)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, quantize)
    x = x + _mm("bse,ed->bsd", o.reshape(b, s, h * hd), a["wo"], quantize)
    f = p["ffn"]
    y = _rms(x, p["ln2"], m["norm_eps"])
    g = _mm("bsd,df->bsf", y, f["w_gate"], quantize)
    u = _mm("bsd,df->bsf", y, f["w_up"], quantize)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["w_down"], quantize)


def logits(m, params, tokens: jax.Array, first: int, quantize: bool = False):
    """Float32 logits over the published vocabulary at positions
    ``first`` .. end of ``tokens`` (b, s): ``[:, j]`` predicts token
    ``first + j + 1``."""
    @jax.jit
    def run_layer(stacked, i, x):
        p = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, False),
                         stacked)
        return layer(m, p, x, quantize)

    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(m["num_layers"]):
        x = run_layer(params["layers"], jnp.int32(i), x)

    @jax.jit
    def head(x, ln_f, w):
        y = _rms(x[:, first:], ln_f, m["norm_eps"])
        return _mm("bsd,dv->bsv", y, w[:, : m["vocab_size"]], quantize)

    return head(x, params["ln_f"], params["head"])


def token_gaps(ref_logits: jax.Array, tokens: jax.Array) -> np.ndarray:
    """How far below the reference's best each token's logit lies."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return np.asarray(best - got)
