"""Distributed-feature tests: int8 gradient compression and pipeline
parallelism.  Multi-device behavior runs in a subprocess with a forced
4-device host platform (the main test process keeps 1 device)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import dequantize_int8, quantize_int8

MULTIDEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from jax import shard_map
from jax.sharding import AxisType

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))

# --- int8 compressed mean vs exact mean ---
from repro.distributed.compression import compressed_psum_mean
xs = jax.random.normal(jax.random.key(0), (4, 64))       # one row per device
def local(x):
    return compressed_psum_mean(x, "data")
out = shard_map(local, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                check_vma=False)(xs)
exact = jnp.broadcast_to(jnp.mean(xs, axis=0, keepdims=True), xs.shape)
err = float(jnp.max(jnp.abs(out - exact)))
bound = float(jnp.max(jnp.abs(xs))) / 127.0
assert err <= bound + 1e-6, (err, bound)
print("compression_ok", err, bound)

# --- pipeline_apply == sequential stage application ---
from repro.distributed.pipeline import pipeline_apply
S, M, b, d = 4, 6, 2, 8
mesh_p = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
ws = jax.random.normal(jax.random.key(1), (S, d, d)) * 0.3
def stage_fn(w, x):
    return jnp.tanh(x @ w)
x = jax.random.normal(jax.random.key(2), (M, b, d))
out = pipeline_apply(stage_fn, ws, x, mesh=mesh_p, axis="pod")
ref = x
for s in range(S):
    ref = jax.vmap(lambda xm: stage_fn(ws[s], xm))(ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
print("pipeline_ok")

# --- pipeline is differentiable (permutes transpose to reverse ring) ---
g = jax.grad(lambda ws: jnp.sum(pipeline_apply(stage_fn, ws, x, mesh=mesh_p,
                                               axis="pod")))(ws)
assert g.shape == ws.shape and bool(jnp.all(jnp.isfinite(g)))
print("pipeline_grad_ok")
"""


def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.key(0), (256,)) * 3.0
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6
    assert q.dtype == jnp.int8


def test_multidevice_compression_and_pipeline():
    res = subprocess.run([sys.executable, "-c", MULTIDEV], capture_output=True,
                         text=True, timeout=300,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-2000:]
    assert "compression_ok" in res.stdout
    assert "pipeline_ok" in res.stdout
    assert "pipeline_grad_ok" in res.stdout
