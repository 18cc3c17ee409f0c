"""Plain float32 reference of RWKV-6 ("Finch") language-model training: the
loss, its gradients and the AdamW steps that follow them.

It follows the equations the program states for its rwkv6 family, which
keep the paper's token shift, data-dependent decay and WKV recurrence and
use RMSNorm (``scale = 1 + g``) where the paper has LayerNorm and a
per-head GroupNorm.  The WKV recurrence runs one token at a time,

    y_t = r_t^T S_{t-1} + (r_t . (u * k_t)) v_t,   S_t = diag(w_t) S_{t-1} + k_t v_t^T,

with no chunked or parallel form.  Everything is float32 with matmuls at
``HIGHEST`` precision; ``quantize=True`` rounds both inputs of every matmul
to float8 (e4m3, one scale per tensor): the control, one precision step
below the bfloat16 the configuration states.  It imports nothing of the
program.

The first train state is redrawn from the seed by the same recipe the
program's initialisation follows: one key per parameter in sorted path
order, ``N(0, (scale / sqrt(fan_in))^2)`` with ``fan_in`` the leading axis
(the layer axis, for stacked parameters), cast to bfloat16.

To fit the chip, the layers are run one at a time and the rows are spread
over the devices; each layer's gradient and optimizer state live on one
device, round robin.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
MIX_RANK, DECAY_RANK, MIXES = 32, 64, 5
#: z-loss weight on the log-partition function (the program's default)
Z_LOSS = 1e-4
#: tokens per block of the recurrence that is recomputed in the backward pass
SCAN_BLOCK = 64
#: tokens per iteration of the recurrence's loop (the same arithmetic)
UNROLL = 8
#: positions per block of the output head's loss
HEAD_BLOCK = 256


# ---------------------------------------------------------------------------
# parameters

def param_table(m: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """path -> (shape, init, scale), layers stacked on a leading axis."""
    d, f, L, V = m["d_model"], m["d_ff"], m["num_layers"], m["vocab_size"]
    Z, N = "zeros", "normal"
    return {
        "embed": ((V, d), "embed", 0.02),
        "head": ((d, V), N, 1.0),
        "layers/channel/mu_k": ((L, d), Z, 1.0),
        "layers/channel/mu_r": ((L, d), Z, 1.0),
        "layers/channel/wk": ((L, d, f), N, 1.0),
        "layers/channel/wr": ((L, d, d), N, 1.0),
        "layers/channel/wv": ((L, f, d), N, 1.0),
        "layers/ln1": ((L, d), Z, 1.0),
        "layers/ln2": ((L, d), Z, 1.0),
        "layers/time/ln_x": ((L, d), Z, 1.0),
        "layers/time/lora_a": ((L, d, MIXES * MIX_RANK), N, 0.1),
        "layers/time/lora_b": ((L, MIXES, MIX_RANK, d), Z, 1.0),
        "layers/time/mu_base": ((L, MIXES, d), Z, 1.0),
        "layers/time/mu_x": ((L, d), Z, 1.0),
        "layers/time/u": ((L, d), Z, 1.0),
        "layers/time/w0": ((L, d), Z, 1.0),
        "layers/time/w_lora_a": ((L, d, DECAY_RANK), N, 0.1),
        "layers/time/w_lora_b": ((L, DECAY_RANK, d), Z, 1.0),
        "layers/time/wg": ((L, d, d), N, 1.0),
        "layers/time/wk": ((L, d, d), N, 1.0),
        "layers/time/wo": ((L, d, d), N, 1.0),
        "layers/time/wr": ((L, d, d), N, 1.0),
        "layers/time/wv": ((L, d, d), N, 1.0),
        "ln_f": ((d,), Z, 1.0),
        "ln_in": ((d,), Z, 1.0),
    }


def _draw(shape, init, scale, key):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    std = scale if init == "embed" else scale / np.sqrt(shape[0])
    return z * std


def init_draws(m: Dict[str, Any], key) -> Dict[str, jax.Array]:
    """The first master weights, by path: the float32 draws that the
    parameters round.  (Compiled for the TPU, the program's
    ``init_state`` keeps these unrounded draws as its master copy: XLA drops
    the round trip through bfloat16.)"""
    table = param_table(m)
    names = sorted(table)
    keys = jax.random.split(key, len(names))
    return {n: _draw(*table[n], k) for n, k in zip(names, keys)}


def init_leaves(m: Dict[str, Any], key) -> Dict[str, jax.Array]:
    """The first parameters, by path, in the configuration's dtype."""
    dtype = jnp.dtype(m["dtype"])
    return {n: a.astype(dtype) for n, a in init_draws(m, key).items()}


def groups_of(m: Dict[str, Any], leaves: Dict[str, jax.Array]) -> Dict[str, Dict]:
    """One group per layer, then the embedding, the head and the outer norms."""
    out = {f"layer{i}": {n[7:]: a[i] for n, a in leaves.items()
                         if n.startswith("layers/")}
           for i in range(m["num_layers"])}
    out["embed"] = {"embed": leaves["embed"]}
    out["head"] = {"head": leaves["head"]}
    out["norms"] = {"ln_f": leaves["ln_f"], "ln_in": leaves["ln_in"]}
    return out


def _block(n: int, most: int) -> int:
    return next(c for c in range(min(most, n), 0, -1) if n % c == 0)


# ---------------------------------------------------------------------------
# the model

def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


# a matmul input is rounded on the way in, and its gradient on the way back
_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def _mm(spec, a, b, quantize):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quantize:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g.astype(jnp.float32))


def _shift(x):
    """x_{t-1}, zero before the first token."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, logw, u):
    """The recurrence token by token.  r, k, v, logw: (b, s, h, n); u: (h, n).
    Returns y: (b, s, h, n)."""
    b, s, h, n = r.shape

    def token(S, inp):
        rt, kt, vt, wt = inp                                     # (b, h, n)
        y = jnp.einsum("bhi,bhij->bhj", rt, S, precision=HIGHEST)
        y = y + jnp.sum(rt * u * kt, -1, keepdims=True) * vt
        S = jnp.exp(wt)[..., None] * S + kt[..., None] * vt[..., None, :]
        return S, y

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(token, S, inp, unroll=UNROLL)

    c = _block(s, SCAN_BLOCK)
    seq = [t.swapaxes(0, 1).reshape(s // c, c, b, h, n) for t in (r, k, v, logw)]
    S0 = jnp.zeros((b, h, n, n), jnp.float32)
    _, ys = jax.lax.scan(block, S0, tuple(seq))
    return ys.reshape(s, b, h, n).swapaxes(0, 1)


def layer(m, p: Dict[str, jax.Array], x, quantize: bool):
    """One block over x (b, s, d): time mixing, then channel mixing."""
    b, s, d = x.shape
    n = m["rwkv_head_dim"]
    h, eps = d // n, m["norm_eps"]
    f32 = lambda name: p[name].astype(jnp.float32)

    y = _rms(x, p["ln1"], eps)
    dx = _shift(y) - y
    base = y + dx * f32("time/mu_x")
    lora = jnp.tanh(base)
    lora = _mm("bsd,dr->bsr", lora, p["time/lora_a"], quantize).reshape(b, s, MIXES, MIX_RANK)
    mix = f32("time/mu_base") + _mm("bsmr,mrd->bsmd", lora, p["time/lora_b"], quantize)
    xr, xk, xv, xw, xg = (y + dx * mix[:, :, i] for i in range(MIXES))
    r = _mm("bsd,de->bse", xr, p["time/wr"], quantize)
    k = _mm("bsd,de->bse", xk, p["time/wk"], quantize)
    v = _mm("bsd,de->bse", xv, p["time/wv"], quantize)
    g = jax.nn.silu(_mm("bsd,de->bse", xg, p["time/wg"], quantize))
    dw = _mm("bsr,rd->bsd", _mm("bsd,dr->bsr", jnp.tanh(xw), p["time/w_lora_a"], quantize),
             p["time/w_lora_b"], quantize)
    logw = -jnp.exp(f32("time/w0") + dw)
    heads = lambda t: t.reshape(b, s, h, n)
    o = wkv(heads(r), heads(k), heads(v), heads(logw), f32("time/u").reshape(h, n))
    o = _rms(o.reshape(b, s, d), p["time/ln_x"], eps) * g
    x = x + _mm("bsd,de->bse", o, p["time/wo"], quantize)

    y = _rms(x, p["ln2"], eps)
    dx = _shift(y) - y
    xk = y + dx * f32("channel/mu_k")
    xr = y + dx * f32("channel/mu_r")
    kk = jnp.square(jax.nn.relu(_mm("bsd,df->bsf", xk, p["channel/wk"], quantize)))
    kv = _mm("bsf,fd->bsd", kk, p["channel/wv"], quantize)
    rr = jax.nn.sigmoid(_mm("bsd,de->bse", xr, p["channel/wr"], quantize))
    return x + rr * kv


def head_loss(m, head, ln_f, x, labels, quantize: bool):
    """Sum over positions of cross-entropy plus the z-loss, and of the
    cross-entropy alone, in blocks of positions."""
    b, s, d = x.shape
    c = _block(s, HEAD_BLOCK)
    xs = x.reshape(b, s // c, c, d).swapaxes(0, 1)
    ls = labels.reshape(b, s // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def block(carry, inp):
        xb, lb = inp
        logits = _mm("bcd,dv->bcv", _rms(xb, ln_f, m["norm_eps"]), head, quantize)
        lse = jax.scipy.special.logsumexp(logits, -1)
        true = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        ce = lse - true
        tot, ce_tot = carry
        return (tot + jnp.sum(ce + Z_LOSS * lse * lse), ce_tot + jnp.sum(ce)), None

    (tot, ce_tot), _ = jax.lax.scan(block, (jnp.zeros(()), jnp.zeros(())), (xs, ls))
    return tot, ce_tot


# ---------------------------------------------------------------------------
# training

class Reference:
    """The reference's train state spread over ``devices`` and its steps.

    The forward pass uses bfloat16 copies of the float32 master weights, as
    in the configuration's mixed precision.  Each group of weights (a
    layer, the embedding, the head, the outer norms) lives on one device,
    round robin, with its optimizer state and gradient, and is copied to
    every device only while it is used; rows of a batch are spread over the
    devices."""

    def __init__(self, m: Dict[str, Any], train: Dict[str, float], seed: int,
                 devices=None, quantize: bool = False):
        self.m, self.train, self.quantize = m, train, quantize
        self.devices = list(devices or jax.devices())
        self.mesh = Mesh(np.asarray(self.devices), ("d",))
        self.replicated = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("d"))
        drawn = jax.jit(lambda key: groups_of(m, init_draws(m, key)),
                        out_shardings=self.replicated)(jax.random.key(seed))
        self.groups: List[str] = list(drawn)
        self.owner = {g: self.devices[i % len(self.devices)]
                      for i, g in enumerate(self.groups)}
        self.master = {g: jax.device_put(drawn[g], self.owner[g]) for g in self.groups}
        del drawn
        self._build()
        self.params = {g: self.to_bf16(self.master[g]) for g in self.groups}
        #: the first master weights, kept on the host
        self.first = {g: jax.device_get(self.master[g]) for g in self.groups}
        self.mom1 = {g: jax.tree.map(jnp.zeros_like, self.master[g]) for g in self.groups}
        self.mom2 = {g: jax.tree.map(jnp.zeros_like, self.master[g]) for g in self.groups}
        self.step_count = 0

    def _build(self):
        m, q = self.m, self.quantize
        rep, rows = self.replicated, self.rows
        eps = m["norm_eps"]

        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        def embed_fwd(tbl, ln_in, tokens):
            return _rms(jnp.take(tbl, tokens, axis=0).astype(jnp.float32), ln_in, eps)

        # gradients are taken with respect to float32 copies of the weights
        self.embed_fwd = jax.jit(embed_fwd, out_shardings=rows)
        self.embed_bwd = jax.jit(
            lambda tbl, ln_in, tokens, dx: jax.vjp(
                lambda a, b: embed_fwd(a, b, tokens), *f32((tbl, ln_in)))[1](dx),
            out_shardings=(rep, rep))
        self.layer_fwd = jax.jit(lambda p, x: layer(m, p, x, q), out_shardings=rows)
        self.layer_bwd = jax.jit(
            lambda p, x, dy: jax.vjp(lambda a, b: layer(m, a, b, q), f32(p), x)[1](dy),
            out_shardings=(rep, rows))
        self.head_fwd_bwd = jax.jit(
            lambda head, ln_f, x, labels, count: _head_grads(
                m, *f32((head, ln_f)), x, labels, count, q),
            out_shardings=((rep, rep), (rep, rep, rows)))
        tcfg = self.train

        def adam(master, m1, m2, g, scale, lr, t):
            def one(w, a, b, gg):
                gg = gg * scale
                a = tcfg["beta1"] * a + (1 - tcfg["beta1"]) * gg
                b = tcfg["beta2"] * b + (1 - tcfg["beta2"]) * gg * gg
                ah = a / (1 - tcfg["beta1"] ** t)
                bh = b / (1 - tcfg["beta2"] ** t)
                w = w - lr * (ah / (jnp.sqrt(bh) + tcfg["eps"]) + tcfg["weight_decay"] * w)
                return w, a, b
            out = jax.tree.map(one, master, m1, m2, g)
            pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                          is_leaf=lambda o: isinstance(o, tuple))
            return pick(0), pick(1), pick(2)

        self.adam = jax.jit(adam)
        self.sqnorms = jax.jit(lambda t: jax.tree.map(lambda a: jnp.sum(jnp.square(a)), t))
        self.to_bf16 = jax.jit(lambda t: jax.tree.map(
            lambda a: a.astype(jnp.dtype(m["dtype"])), t))

    def loss_and_grads(self, tokens: np.ndarray, labels: np.ndarray):
        """Mean loss over all positions and the gradient of every group,
        each on its owner device."""
        L = self.m["num_layers"]
        tok = jax.device_put(tokens, self.rows)
        lab = jax.device_put(labels, self.rows)
        count = float(tokens.size)
        shared = lambda g: jax.device_put(self.params[g], self.replicated)
        norms, table = shared("norms"), shared("embed")["embed"]
        xs = [self.embed_fwd(table, norms["ln_in"], tok)]
        for i in range(L):
            xs.append(self.layer_fwd(shared(f"layer{i}"), xs[-1]))
        (loss, ce), (d_head, d_lnf, dx) = self.head_fwd_bwd(
            shared("head")["head"], norms["ln_f"], xs.pop(), lab, count)
        grads = {"head": jax.device_put({"head": d_head}, self.owner["head"])}
        for i in reversed(range(L)):
            dp, dx = self.layer_bwd(shared(f"layer{i}"), xs.pop(), dx)
            grads[f"layer{i}"] = jax.device_put(dp, self.owner[f"layer{i}"])
        d_tbl, d_lnin = self.embed_bwd(table, norms["ln_in"], tok, dx)
        grads["embed"] = jax.device_put({"embed": d_tbl}, self.owner["embed"])
        grads["norms"] = jax.device_put({"ln_f": d_lnf, "ln_in": d_lnin}, self.owner["norms"])
        return float(loss), grads

    def leaf_sqnorms(self, tree_by_group) -> Dict[str, float]:
        """Squared norms by the program's parameter path (stacked layers
        summed over the layer axis)."""
        out: Dict[str, float] = {}
        for g, tree in tree_by_group.items():
            for name, v in jax.device_get(self.sqnorms(tree)).items():
                path = f"layers/{name}" if g.startswith("layer") else name
                out[path] = out.get(path, 0.0) + float(v)
        return out

    def step(self, tokens, labels):
        """One AdamW step as the configuration states it: global-norm
        clipping, bias-corrected moments, decoupled weight decay, linear
        warm-up.  Returns the loss and the clipped gradient's squared
        norms by path."""
        loss, grads = self.loss_and_grads(tokens, labels)
        sq = self.leaf_sqnorms(grads)
        gnorm = float(np.sqrt(sum(sq.values())))
        clip = self.train["grad_clip"]
        scale = min(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
        self.step_count += 1
        t = self.step_count
        if t >= self.train["warmup_steps"]:
            raise ValueError("the reference follows warm-up steps only")
        lr = self.train["learning_rate"] * t / self.train["warmup_steps"]
        for g in self.groups:
            self.master[g], self.mom1[g], self.mom2[g] = self.adam(
                self.master[g], self.mom1[g], self.mom2[g], grads[g],
                jnp.float32(scale), jnp.float32(lr), jnp.float32(t))
            self.params[g] = self.to_bf16(self.master[g])
        return loss, {k: v * scale * scale for k, v in sq.items()}

    def change_sqnorms(self) -> Dict[str, float]:
        """Squared norms by path of how far the master weights have moved."""
        diff = {g: jax.tree.map(lambda a, b: a - jnp.asarray(b, jnp.float32, device=a.device),
                                self.master[g], self.first[g]) for g in self.groups}
        return self.leaf_sqnorms(diff)


def _head_grads(m, head, ln_f, x, labels, count, quantize):
    def f(hd, lf, xx):
        tot, ce = head_loss(m, hd, lf, xx, labels, quantize)
        return tot / count, ce / count
    (loss, ce), vjp = jax.vjp(f, head, ln_f, x)
    return (loss, ce), vjp((jnp.ones(()), jnp.zeros(())))
