"""Plain float32 reference of RWKV-6 ("Finch") serving: logits over whole
sequences, and the seeded random weights that the decode cell hands the
program.

The forward pass is ``reference/rwkv6.py``'s ``layer``, unedited, one layer
at a time: token shift with the five LoRA-mixed inputs, the decay LoRA
``log w_t = -exp(w0 + lora(x))``, the WKV recurrence token by token from a
zero state,

    y_t = r_t^T S_{t-1} + (r_t . (u * k_t)) v_t,   S_t = diag(w_t) S_{t-1} + k_t v_t^T,

the normalized and gated output and squared-ReLU channel mixing; then the
final norm and the output head, in blocks of positions.  Everything is
float32 with matmuls at ``HIGHEST`` precision, and nothing of the program
is imported.  It follows the program's equations, which depart from the
paper in the norms: RMSNorm (``scale = 1 + g``) where the paper has
LayerNorm, and one RMSNorm over the full width for ``ln_x`` where the paper
has a GroupNorm per head.

Two controls compute the same function one step below the configuration's
precision: ``quantize=True`` rounds both inputs of every matmul to float8
(e4m3, one scale per tensor), and ``bf16_state=True`` keeps the WKV state in
bfloat16, rounded after every token.
"""
from __future__ import annotations

import contextlib
import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import rwkv6

HIGHEST = rwkv6.HIGHEST
#: positions per block of the output head
HEAD_BLOCK = 256
#: the norm offsets' spread (``scale = 1 + g``)
NORM_STD = 0.1
#: the bonus ``u``'s spread
BONUS_STD = 0.5


# ---------------------------------------------------------------------------
# weights

def param_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree of shapes, layers stacked on a leading
    axis (``rwkv6.param_table``'s paths, nested)."""
    tree: Dict[str, Any] = {}
    for path, (shape, _, _) in rwkv6.param_table(m).items():
        *outer, leaf = path.split("/")
        node = tree
        for key in outer:
            node = node.setdefault(key, {})
        node[leaf] = shape
    return tree


def _w0(shape) -> jax.Array:
    """The RWKV-6 initialisation of the decay's bias: channel n of layer l
    of L gets -6 + 5 (n / (d - 1)) ** (0.7 + 1.3 l / (L - 1)), from -6 (a
    memory of hundreds of tokens) to -1 (a few tokens)."""
    L, d = shape
    n = jnp.arange(d, dtype=jnp.float32) / max(d - 1, 1)
    ratio = jnp.arange(L, dtype=jnp.float32)[:, None] / max(L - 1, 1)
    return -6.0 + 5.0 * n[None] ** (0.7 + 1.3 * ratio)


def _draw(path: str, shape, key) -> jax.Array:
    """One leaf, keyed by its path ``a/b/leaf``.  Every matrix is
    N(0, 1/fan_in) over its true input width (the axis it contracts; 1 for
    the embedding, a lookup of one-hot rows); norm offsets N(0, 0.1^2);
    token-shift mixes ``mu_*`` uniform on [0, 1); the bonus ``u``
    N(0, 0.5^2); ``w0`` as in the RWKV-6 initialisation."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "w0":
        return _w0(shape)
    if leaf.startswith("mu_"):
        return jax.random.uniform(k, shape, jnp.float32)
    z = jax.random.normal(k, shape, jnp.float32)
    if leaf == "embed":
        return z
    if leaf == "u":
        return z * BONUS_STD
    if leaf.startswith("ln"):
        return z * NORM_STD
    return z / np.sqrt(shape[-2])


def make_params(m: Dict[str, Any], seed: int) -> Any:
    """Every weight from ``seed``, on the device, in one jitted call, in the
    dtype the configuration serves (bfloat16)."""
    shapes = param_shapes(m)
    is_shape = lambda x: isinstance(x, tuple)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)[0]
    dtype = jnp.dtype(m["dtype"])

    def make(key):
        flat = [_draw("/".join(k.key for k in p), s, key).astype(dtype)
                for p, s in paths]
        return jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), flat)

    return jax.jit(make)(jax.random.key(seed))


# ---------------------------------------------------------------------------
# the forward pass

def _wkv_bf16_state(r, k, v, logw, u):
    """``rwkv6.wkv`` with the state rounded to bfloat16 after every token."""
    b, s, h, n = r.shape

    def token(S, inp):
        rt, kt, vt, wt = inp                                     # (b, h, n)
        y = jnp.einsum("bhi,bhij->bhj", rt, S, precision=HIGHEST)
        y = y + jnp.sum(rt * u * kt, -1, keepdims=True) * vt
        S = jnp.exp(wt)[..., None] * S + kt[..., None] * vt[..., None, :]
        # a round trip through bfloat16 is a no-op to XLA on the TPU, which
        # allows excess precision; reduce_precision always rounds
        return jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7), y

    seq = tuple(t.swapaxes(0, 1) for t in (r, k, v, logw))
    _, ys = jax.lax.scan(token, jnp.zeros((b, h, n, n), jnp.float32), seq)
    return ys.swapaxes(0, 1)


@contextlib.contextmanager
def _recurrence(bf16_state: bool):
    """``rwkv6.layer`` calls its module's ``wkv``: the state control swaps
    it while the layer is traced."""
    saved = rwkv6.wkv
    if bf16_state:
        rwkv6.wkv = _wkv_bf16_state
    try:
        yield
    finally:
        rwkv6.wkv = saved


def logits(m, params, tokens: jax.Array, first: int, quantize: bool = False,
           bf16_state: bool = False) -> jax.Array:
    """Float32 logits over the vocabulary at positions ``first`` .. end of
    ``tokens`` (b, s): ``[:, j]`` predicts token ``first + j + 1``."""
    eps = m["norm_eps"]

    @jax.jit
    def run_layer(stacked, i, x):
        # rwkv6.layer names the weights by path: ``ln1``, ``time/wr``, ...
        p = {"/".join(k.key for k in path): jax.lax.dynamic_index_in_dim(w, i, 0, False)
             for path, w in jax.tree_util.tree_flatten_with_path(stacked)[0]}
        return rwkv6.layer(m, p, x, quantize)

    @jax.jit
    def head(x, ln_f, w):
        return rwkv6._mm("bsd,dv->bsv", rwkv6._rms(x, ln_f, eps),
                         w[:, : m["vocab_size"]], quantize)

    x = rwkv6._rms(jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32),
                   params["ln_in"], eps)
    with _recurrence(bf16_state):
        for i in range(m["num_layers"]):
            x = run_layer(params["layers"], jnp.int32(i), x)
    s = tokens.shape[1]
    return jnp.concatenate(
        [head(x[:, a: min(a + HEAD_BLOCK, s)], params["ln_f"], params["head"])
         for a in range(first, s, HEAD_BLOCK)], axis=1)
