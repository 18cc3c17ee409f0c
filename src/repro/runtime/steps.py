"""Step builders: the single source of truth for train/prefill/decode step
functions, their abstract input specs and their shardings.

Used by four consumers with identical semantics:
  * smoke tests      — materialized params, no mesh
  * launch/dryrun.py — ShapeDtypeStructs + NamedShardings on 256/512-chip meshes
  * launch/train.py  — real training on whatever devices exist
  * repro.core       — the simulator captures these exact step functions
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import RunConfig
from repro.distributed.sharding import (
    axes_to_pspec, logical_rules, param_shardings, use_rules,
)
from repro.models import build_model
from repro.optim import (
    TrainState, abstract_state, adamw_update, init_state, state_axes,
    warmup_cosine,
)


@dataclass
class StepBundle:
    """Everything needed to lower/compile/run one step function."""
    fn: Callable
    abstract_inputs: Tuple[Any, ...]          # pytrees of ShapeDtypeStruct
    in_shardings: Optional[Tuple[Any, ...]]   # NamedShardings or Formats (None: unset)
    out_shardings: Optional[Any]
    donate_argnums: Tuple[int, ...] = ()
    compiler_options: Optional[Dict[str, str]] = None

    def lower(self, mesh: Optional[Mesh] = None):
        jitted = self.jit()
        if mesh is not None:
            with mesh:
                return jitted.lower(*self.abstract_inputs)
        return jitted.lower(*self.abstract_inputs)

    def jit(self):
        # The persistent compilation cache leaves metadata out of its key by
        # default: a hit could then return an executable built from other
        # source, whose ops carry that source's named scopes, and a device
        # trace would charge their time to stale names.
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        kw = {}
        if self.in_shardings is not None:
            kw["in_shardings"] = self.in_shardings
            kw["out_shardings"] = self.out_shardings
        return jax.jit(self.fn, donate_argnums=self.donate_argnums,
                       compiler_options=self.compiler_options, **kw)


def _ambient(fn: Callable, rules, mesh) -> Callable:
    @functools.wraps(fn)
    def wrapped(*args):
        with use_rules(rules, mesh):
            return fn(*args)
    return wrapped


def _rules(run_cfg: RunConfig, model):
    rules = logical_rules(run_cfg.mesh, run_cfg.sharding)
    rules.update(model.logical_overrides(run_cfg.mesh))
    mesh_cfg = run_cfg.mesh
    # batch divisibility: long_500k (batch=1) can't shard batch over data —
    # replicate batch and shard the caches' positions over data instead, the
    # one place that shards ``kv_seq``
    batch_ax = rules.get("batch")
    if batch_ax is not None:
        axes = (batch_ax,) if isinstance(batch_ax, str) else batch_ax
        div = 1
        for a in axes:
            div *= mesh_cfg.axis_size(a)
        if run_cfg.shape.global_batch % max(div, 1) != 0:
            rules["batch"] = None
            rules["kv_seq"] = "data"
    return rules


def _shard(axes_tree_: Any, rules, mesh: Optional[Mesh]):
    if mesh is None:
        return None
    return param_shardings(axes_tree_, rules, mesh)


def _replicated(tree: Any, mesh: Mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def train_bundle(run_cfg: RunConfig, mesh: Optional[Mesh] = None) -> StepBundle:
    model = build_model(run_cfg.model, run_cfg.sharding)
    rules = _rules(run_cfg, model)
    lr_fn = warmup_cosine(run_cfg.train)
    accum = max(run_cfg.train.accum_steps, 1)

    def grad_fn(params, mb):
        return jax.value_and_grad(
            lambda p: model.loss(p, mb), has_aux=True)(params)

    def train_step(state: TrainState, batch):
        if accum == 1:
            (loss, metrics), grads = grad_fn(state.params, batch)
        else:
            # microbatch gradient accumulation: activation memory scales with
            # global_batch/accum; grads accumulate in fp32 with param sharding
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)
            g0 = jax.tree.map(lambda p: (p * 0).astype(jnp.float32),
                              state.params)

            def body(carry, mb):
                g_acc, loss_acc = carry
                (loss, metrics), g = grad_fn(state.params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / accum, g_acc, g)
                return (g_acc, loss_acc + loss / accum), metrics

            (grads, loss), metrics_stack = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), micro)
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics_stack)
        new_state, opt_metrics = adamw_update(state, grads, run_cfg.train, lr_fn)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return new_state, metrics

    fn = _ambient(train_step, rules, mesh)
    state_sds = abstract_state(model.abstract())
    batch_sds, batch_axes = model.train_input_specs(run_cfg.shape)
    st_axes = state_axes(model.axes())

    in_sh = out_state_sh = out_sh = None
    options = None
    if mesh is not None:
        if mesh.size > 1 and mesh.devices.flat[0].platform == "tpu":
            # a model that the TPU compiler needs steered says so itself
            options = getattr(model, "sharded_tpu_compiler_options", None)
        state_sh = param_shardings(st_axes, rules, mesh)
        batch_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, axes_to_pspec(a, rules)), batch_axes,
            is_leaf=lambda x: isinstance(x, tuple))
        in_sh = (state_sh, batch_sh)
        # metrics subtree: replicated (pytree-prefix sharding)
        out_sh = (state_sh, NamedSharding(mesh, P()))
    return StepBundle(fn, (state_sds, batch_sds), in_sh, out_sh,
                      donate_argnums=(0,), compiler_options=options)


def init_train_state(run_cfg: RunConfig, key, mesh: Optional[Mesh] = None
                     ) -> TrainState:
    """Materialize an initial TrainState (optionally sharded onto a mesh)."""
    model = build_model(run_cfg.model, run_cfg.sharding)
    if mesh is None:
        return init_state(model.init(key))
    rules = _rules(run_cfg, model)
    st_axes = state_axes(model.axes())
    shardings = param_shardings(st_axes, rules, mesh)

    def make():
        return init_state(model.init(key))

    with mesh:
        return jax.jit(make, out_shardings=shardings)()


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------

def init_params(run_cfg: RunConfig, key) -> Any:
    """Materialize model parameters from ``key`` on the device, in one jit."""
    model = build_model(run_cfg.model, run_cfg.sharding)
    return jax.jit(model.init)(key)


def prefill_bundle(run_cfg: RunConfig, mesh: Optional[Mesh] = None) -> StepBundle:
    model = build_model(run_cfg.model, run_cfg.sharding)
    rules = _rules(run_cfg, model)

    def prefill_step(params, batch):
        return model.prefill(params, batch)

    fn = _ambient(prefill_step, rules, mesh)
    params_sds = model.abstract()
    batch_sds, batch_axes = model.prefill_input_specs(run_cfg.shape)
    in_sh = out_sh = None
    if mesh is not None:
        cache_sds, cache_axes, _, _ = model.decode_state_specs(run_cfg.shape)
        params_sh = param_shardings(model.axes(), rules, mesh)
        batch_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, axes_to_pspec(a, rules)), batch_axes,
            is_leaf=lambda x: isinstance(x, tuple))
        in_sh = (params_sh, batch_sh)
        logits_sh = NamedSharding(mesh, axes_to_pspec(("batch", None, "vocab"), rules))
        cache_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, axes_to_pspec(a, rules)), cache_axes,
            is_leaf=lambda x: isinstance(x, tuple))
        out_sh = (logits_sh, cache_sh)
    return StepBundle(fn, (params_sds, batch_sds), in_sh, out_sh)


def position_axes(cache_axes: Any) -> Any:
    """Which cache leaves grow with position, as the model declares them:
    for each leaf of the logical-axes tree that ``decode_state_specs`` gives,
    the index of its ``kv_seq`` axis, or ``None`` where it names none.  A
    leaf that grows is padded to the decode length along that axis and takes
    the layout the compiler chooses for the decode loop."""
    return jax.tree.map(lambda axes: axes.index("kv_seq") if "kv_seq" in axes else None,
                        cache_axes, is_leaf=lambda x: isinstance(x, tuple))


def decode_bundle(run_cfg: RunConfig, mesh: Optional[Mesh] = None) -> StepBundle:
    """One-token serve_step against a full-length cache (decode_* shapes).

    The cache's leaves that grow with position (``position_axes``) take the
    layout the compiler chooses for the decode loop (``Layout.AUTO``) on
    input and output, so that a step's cache feeds the next step as it is,
    with no relayout between steps.  A concrete array can only be passed
    to the step once it is compiled: ``lower(...).compile()``, then
    ``Compiled.input_formats`` says the layout to put the cache in
    (``Server`` does this once per shape).
    """
    model = build_model(run_cfg.model, run_cfg.sharding)
    rules = _rules(run_cfg, model)

    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch)

    fn = _ambient(decode_step, rules, mesh)
    params_sds = model.abstract()
    cache_sds, cache_axes, tok_sds, tok_axes = model.decode_state_specs(run_cfg.shape)
    params_sh = cache_sh = tok_sh = logits_sh = None
    if mesh is None:
        # leaves left None follow their argument: the single device's sharding
        cache_sh = jax.tree.map(lambda _: None, cache_sds)
    else:
        params_sh = param_shardings(model.axes(), rules, mesh)
        cache_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, axes_to_pspec(a, rules)), cache_axes,
            is_leaf=lambda x: isinstance(x, tuple))
        tok_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, axes_to_pspec(a, rules)), tok_axes,
            is_leaf=lambda x: isinstance(x, tuple))
        logits_sh = NamedSharding(mesh, axes_to_pspec(("batch", None, "vocab"), rules))
    cache_fmt = jax.tree.map(
        lambda axis, sh: sh if axis is None else Format(Layout.AUTO, sh),
        position_axes(cache_axes), cache_sh, is_leaf=lambda x: x is None)
    return StepBundle(fn, (params_sds, cache_sds, tok_sds),
                      (params_sh, cache_fmt, tok_sh), (logits_sh, cache_fmt),
                      donate_argnums=(1,))


def bundle_for(run_cfg: RunConfig, mesh: Optional[Mesh] = None) -> StepBundle:
    """Pick the step kind the shape dictates (train/prefill/decode)."""
    kind = run_cfg.shape.kind
    if kind == "train":
        return train_bundle(run_cfg, mesh)
    if kind == "prefill":
        return prefill_bundle(run_cfg, mesh)
    return decode_bundle(run_cfg, mesh)
