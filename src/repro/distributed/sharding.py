"""Logical-axis sharding rules (MaxText-style).

Models annotate parameters and activations with *logical* axis names
("batch", "qkv", "ffn", "experts", ...).  A rule table maps logical names to
mesh axes; the mapping depends on ShardingConfig (fsdp on/off, sequence
sharding, expert parallelism) and the pod role, so one model definition serves
every parallelism layout.

Inside a jit trace, :func:`lc` applies ``with_sharding_constraint`` using the
ambient rules+mesh installed by :func:`use_rules` (a context manager the step
builders use).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ShardingConfig

MeshAxes = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, MeshAxes]


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

def logical_rules(mesh_cfg: MeshConfig, sharding: ShardingConfig) -> Rules:
    """Build the logical->mesh mapping for one run."""
    pod_is_data = mesh_cfg.is_multi_pod and mesh_cfg.pod_role == "data"
    batch_axes: MeshAxes = ("pod", "data") if pod_is_data else "data"
    fsdp_axes: MeshAxes = "data" if sharding.fsdp else None

    rules: Rules = {
        # --- activation axes ---
        "batch": batch_axes,
        "seq": None,
        # Megatron-style sequence sharding of the residual stream between
        # blocks (AG on block entry / RS on block exit) — divides saved-for-
        # backward activation memory by the model-axis size
        "act_seq": "model" if sharding.sequence_sharding else None,
        "kv_seq": None,
        "embed": None,                # activation d_model dim stays replicated
        "qkv": "model",               # flattened heads*head_dim activation dim
        "heads": "model",
        "ffn": "model",
        "moe_ffn": "model" if not sharding.expert_parallel else None,
        "vocab": "model",
        "classes": None,
        # --- parameter-only axes ---
        "fsdp": fsdp_axes,            # weight input-dim shard (ZeRO-3 style)
        "embed_tbl": fsdp_axes if sharding.shard_embed_over == "data" else "model",
        "experts": "model" if sharding.expert_parallel else None,
        "exp_cap": "data",            # MoE capacity slots over the data axis
        "layers": None,
        "stages": "pod" if (mesh_cfg.is_multi_pod and mesh_cfg.pod_role == "pipeline") else None,
        # --- conv / misc ---
        "conv_in": None, "conv_out": None, "spatial": None,
        "state": None, "ssm_heads": "model", "frontend_seq": None,
    }
    rules.update(dict(sharding.extra_rules))
    # prune mesh axes that don't exist in this mesh (e.g. "pod" on single pod)
    def prune(ax: MeshAxes) -> MeshAxes:
        if ax is None:
            return None
        if isinstance(ax, str):
            return ax if ax in mesh_cfg.axis_names else None
        kept = tuple(a for a in ax if a in mesh_cfg.axis_names)
        return kept if kept else None
    return {k: prune(v) for k, v in rules.items()}


def axes_to_pspec(axes: Sequence[Optional[str]], rules: Rules) -> P:
    """Map a tuple of logical axis names (or None) to a PartitionSpec.

    A mesh axis may appear at most once in a PartitionSpec; later duplicates
    degrade to replication (standard logical-axis-rules behaviour).
    """
    used: set = set()
    out = []
    for name in axes:
        ax = rules.get(name) if name is not None else None
        if ax is None:
            out.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_t = tuple(a for a in ax_t if a not in used)
        if not ax_t:
            out.append(None)
            continue
        used.update(ax_t)
        out.append(ax_t[0] if len(ax_t) == 1 else ax_t)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(axes_tree: Any, rules: Rules, mesh: Mesh):
    """Map a pytree of logical-axes tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, axes_to_pspec(axes, rules)),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


# ---------------------------------------------------------------------------
# Ambient rules for activation constraints inside jit traces
# ---------------------------------------------------------------------------

class _Ambient(threading.local):
    rules: Optional[Rules] = None
    mesh: Optional[Mesh] = None


_AMBIENT = _Ambient()


@contextmanager
def use_rules(rules: Rules, mesh: Optional[Mesh] = None):
    prev = (_AMBIENT.rules, _AMBIENT.mesh)
    _AMBIENT.rules, _AMBIENT.mesh = rules, mesh
    try:
        yield
    finally:
        _AMBIENT.rules, _AMBIENT.mesh = prev


def rules_for() -> Optional[Rules]:
    return _AMBIENT.rules


def lc(x, axes: Sequence[Optional[str]]):
    """Apply a logical sharding constraint if rules are ambient, else no-op.

    Safe to call unconditionally from model code: in smoke tests (no mesh) it
    is the identity.
    """
    rules = _AMBIENT.rules
    if rules is None or _AMBIENT.mesh is None:
        return x   # constraints are meaningful only under an explicit mesh
    spec = axes_to_pspec(axes, rules)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(_AMBIENT.mesh, spec))
