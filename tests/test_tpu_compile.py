"""Compile the main path's kernels, the qwen1.5-4b decode step, the
rwkv6-1.6b serve steps and a zamba2-7b decode step for a described TPU v5e,
with no chip attached.

The TPU compiler refuses what interpret mode accepts (unaligned slices, too
much VMEM, dots with several batch dimensions) and programs that do not fit
the device's memory.  These tests run that compiler at real widths.  The
topology is described inside a fixture, so a worker that is not given this
file never loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import config as C
from repro.core.hlo_ir import parse_hlo_module
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.tiled_matmul.kernel import tiled_matmul
from repro.kernels.winograd.kernel import winograd_tiles
from repro.runtime.steps import decode_bundle, prefill_bundle, train_bundle

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_qwen_head_width(one_chip):
    cfg = C.get("qwen1.5-4b").full
    qkv = _sds((1, cfg.num_heads, 2048, cfg.resolved_head_dim), jnp.bfloat16,
               one_chip)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                 interpret=False), qkv, qkv, qkv)


def test_tiled_matmul_compiles_at_qwen_ffn_width(one_chip):
    cfg = C.get("qwen1.5-4b").full
    a = _sds((2048, cfg.d_model), jnp.bfloat16, one_chip)
    b = _sds((cfg.d_model, cfg.d_ff), jnp.bfloat16, one_chip)
    _compile(lambda a, b: tiled_matmul(a, b, interpret=False), a, b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_winograd_compiles(one_chip, dtype):
    tiles = _sds((8, 32, 4, 4, 32, 128), dtype, one_chip)
    u = _sds((4, 4, 128, 128), dtype, one_chip)
    _compile(lambda t, u: winograd_tiles(t, u, interpret=False), tiles, u)


def test_qwen_decode_step_fits_one_chip(one_chip):
    cfg = C.get("qwen1.5-4b").full
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", 288, 8, "decode"),
                     mesh=C.SMOKE_MESH)
    bundle = decode_bundle(rc)
    args = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                        bundle.abstract_inputs)
    compiled = jax.jit(bundle.fn, donate_argnums=bundle.donate_argnums
                       ).lower(*args).compile()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 7 * 2**30 < live < V5E_HBM_BYTES


def _parse_tpu_hlo(text):
    """The module, with the TPU's tiled layouts (``{4,1,3,2,0:T(8,128)}``)
    dropped so that the simulator's parser reads its instructions."""
    return parse_hlo_module(re.sub(r"\{[\d,]*:[^{}]*\}", "", text))


def _top_level(mod):
    """The module's computations that no fusion calls, by name."""
    fused = {m.group(1) for comp in mod.computations.values()
             for op in comp.ops if op.opcode == "fusion"
             for m in [re.search(r"calls=%?([\w.\-]+)", op.raw)]}
    return {name: comp for name, comp in mod.computations.items()
            if name not in fused}


def _large_kv_cache_ops(mod, min_elems, one_position):
    """Ops under the ``kv_cache`` scope whose output holds at least
    ``min_elems`` elements, less the in-place writes: a dynamic-update-slice
    (or a fusion whose root is one) whose update is ``one_position``
    elements.  A fusion counts once, its fused ops not at all."""
    def writes_one_position(comp, op):
        if op.opcode == "fusion":
            called = mod.comp(re.search(r"calls=%?([\w.\-]+)", op.raw).group(1))
            return writes_one_position(called, called.by_name[called.root])
        return (op.opcode == "dynamic-update-slice"
                and sum(s.elems for s in mod.op_shape(comp, op.operands[1]))
                == one_position)

    return [op.name for comp in _top_level(mod).values() for op in comp.ops
            if "/kv_cache/" in op.raw and op.out_elems >= min_elems
            and not writes_one_position(comp, op)]


def test_qwen_decode_holds_its_kv_cache_in_place(one_chip):
    """At the benchmark cell's shapes (batch 16, 512 positions) the decode
    step writes one position per layer into the stacked cache and reads the
    layer's K/V where they lie, in the layout the compiler chose for the
    loop: no layer copies its slice out or back, and the whole cache is
    never relaid out, at any step."""
    cfg = C.get("qwen1.5-4b").full
    b, S, kv, hd = 16, 512, cfg.num_kv_heads, cfg.resolved_head_dim
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", S, b, "decode"),
                     mesh=C.SMOKE_MESH)
    bundle = decode_bundle(rc)
    args = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                        bundle.abstract_inputs)
    compiled = bundle.jit().lower(*args).compile()
    mod = _parse_tpu_hlo(compiled.as_text())

    large = _large_kv_cache_ops(mod, b * S * kv * hd, b * kv * hd)
    assert len(large) == 0, f"{len(large)} slice-sized kv_cache ops: {large}"
    cache_dims = (cfg.num_layers, b, S, kv, hd)
    whole_copies = [op.name for comp in mod.computations.values()
                    for op in comp.ops
                    if op.opcode == "copy" and any(s.dims == cache_dims
                                                   for s in op.outputs)]
    assert len(whole_copies) == 0, f"whole-cache copies: {whole_copies}"
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    (_, cache_in, _), _ = compiled.input_formats
    _, cache_out = compiled.output_formats
    for leaf in ("k", "v"):
        assert cache_in[leaf] == cache_out[leaf]


def test_rwkv_decode_holds_its_state_in_place(rwkv_serve_step):
    """The rwkv6 decode cell's step (batch 256, float32 WKV state of
    (24, 256, 32, 64, 64)) updates each layer's slice of the stacked state
    where it lies: no op outside a fusion makes an array of a layer's slice
    (no slice copied out, relaid out or stacked anew), nothing copies the
    whole state, the state keeps one layout from input to output, and the
    step's temp holds no second state."""
    compiled = rwkv_serve_step("decode")
    mod = _parse_tpu_hlo(compiled.as_text())
    stack = (24, 256, 32, 64, 64)
    state_dims = {stack, stack[1:], (1,) + stack[1:]}
    made = [op for comp in _top_level(mod).values() for op in comp.ops
            if op.opcode not in ("parameter", "get-tuple-element", "tuple", "while")
            and any(s.dims in state_dims for s in op.outputs)]
    copies = [op.name for op in made if op.opcode == "copy"]
    assert not copies, f"state-sized copies: {copies}"
    slices = [op.name for op in made
              if any(s.dims in state_dims - {stack} for s in op.outputs)]
    assert not slices, f"ops that make a layer's state slice: {slices}"
    (_, cache_in, _), _ = compiled.input_formats
    _, cache_out = compiled.output_formats
    assert cache_in["state"] == cache_out["state"]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


def test_hybrid_decode_holds_its_state_in_place(one_chip):
    """zamba2-7b's decode step at smoke widths and batch 8, with two groups
    of three mamba layers and a tail of two, compiled for a described v5e:
    the shared attention's stacked K/V and the stacked mamba ``state`` and
    ``conv`` are updated where they lie.  No op outside a fusion makes a
    layer's state or K/V slice or a group's slice of a stack; an op that
    makes a whole stack is the loop, the compiler's move of it between
    memories or an in-place update (a fusion whose root is a
    dynamic-update-slice), never a copy; each cache leaf keeps one format
    from input to output.  A layer's conv slice is read out once a loop:
    the window's shift reads rows that the in-place write overwrites."""
    cfg = C.get("zamba2-7b").smoke.replace(num_layers=8, attn_every=3)
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", 64, 8, "decode"),
                     mesh=C.SMOKE_MESH)
    bundle = decode_bundle(rc)
    args = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                        bundle.abstract_inputs)
    compiled = bundle.jit().lower(*args).compile()
    mod = _parse_tpu_hlo(compiled.as_text())
    groups, tail = bundle.abstract_inputs[1]["groups"], bundle.abstract_inputs[1]["tail"]

    def layer(shape, lead):
        """A layer's slice of a stack with ``lead`` layer axes, its layer
        axes dropped or kept at size 1."""
        return {shape[lead:], (1,) + shape[lead:], (1, 1) + shape[lead:]}

    stacks = {x.shape for x in jax.tree.leaves((groups, tail))}
    slices = (layer(groups["k"].shape, 1) | layer(groups["mamba"]["state"].shape, 2)
              | layer(tail["state"].shape, 1)
              | {shape[1:] for shape in (groups["mamba"]["state"].shape,
                                         groups["mamba"]["conv"].shape)}
              | {(1,) + shape[1:] for shape in (groups["mamba"]["state"].shape,
                                                groups["mamba"]["conv"].shape)})
    conv_reads = layer(groups["mamba"]["conv"].shape, 2) | layer(tail["conv"].shape, 1)

    def in_place(op):
        if op.opcode != "fusion":
            return False
        called = mod.comp(re.search(r"calls=%?([\w.\-]+)", op.raw).group(1))
        return called.by_name[called.root].opcode == "dynamic-update-slice"

    made = [(op, tuple(s.dims)) for comp in _top_level(mod).values() for op in comp.ops
            if op.opcode not in ("parameter", "get-tuple-element", "tuple")
            for s in op.outputs]
    sliced = [op.name for op, dims in made if dims in slices]
    assert not sliced, f"ops that make a layer's or a group's slice: {sliced}"
    whole = [op.name for op, dims in made if dims in stacks and not in_place(op)
             and op.opcode not in ("while", "copy-start", "copy-done")]
    assert not whole, f"ops that make a whole stack anew: {whole}"
    reads = [op.name for op, dims in made if dims in conv_reads]
    assert len(reads) <= 2, f"conv slices read out: {reads}"
    (_, cache_in, _), _ = compiled.input_formats
    _, cache_out = compiled.output_formats
    assert cache_in == cache_out


def test_lenet_train_step_compiles(topo):
    rc = C.RunConfig(model=C.get("lenet").full, shape=C.TRAIN_4K,
                     mesh=C.MeshConfig((1, 1)))
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    train_bundle(rc, mesh).lower(mesh).compile()


def _sharded_train_bundle(topo, arch):
    """A 2-layer FSDP train step of ``arch`` over four described chips."""
    rc = C.RunConfig(model=C.get(arch).full.replace(num_layers=2),
                     shape=C.ShapeConfig("train", 128, 8, "train"),
                     mesh=C.MeshConfig((4, 1)))
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    return train_bundle(rc, mesh), mesh


def test_sharded_rwkv_train_step_compiles(topo):
    bundle, mesh = _sharded_train_bundle(topo, "rwkv6-1.6b")
    assert bundle.compiler_options is not None
    compiled = bundle.lower(mesh).compile()
    assert "all-gather" in compiled.as_text()


def test_sharded_dense_train_step_compiles_without_options(topo):
    bundle, mesh = _sharded_train_bundle(topo, "qwen1.5-4b")
    assert bundle.compiler_options is None
    compiled = bundle.lower(mesh).compile()
    assert "all-gather" in compiled.as_text()


@pytest.fixture(scope="module")
def rwkv_serve_step(one_chip):
    """The rwkv6-1.6b decode cell's steps at published widths, compiled once
    each: the prefill of 256 prompts of 256 tokens and the decode step at
    batch 256."""
    compiled = {}

    def get(kind):
        if kind not in compiled:
            cfg = C.get("rwkv6-1.6b").full
            rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", 256, 256, kind),
                             mesh=C.SMOKE_MESH)
            bundle = (prefill_bundle if kind == "prefill" else decode_bundle)(rc)
            args = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                                bundle.abstract_inputs)
            compiled[kind] = bundle.jit().lower(*args).compile()
        return compiled[kind]
    return get


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_rwkv_serve_steps_fit_one_chip(rwkv_serve_step, kind):
    """The rwkv6-1.6b decode cell's steps at published widths: the prefill
    of 256 prompts of 256 tokens and the decode step at batch 256, whose
    float32 WKV state is 3.2e9 bytes.  What each holds at once (arguments,
    outputs and temp, less what the outputs reuse) fits the chip."""
    mem = rwkv_serve_step(kind).memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 3 * 10**9          # the weights, at least
    assert live < V5E_HBM_BYTES


def test_rwkv_decode_step_keeps_its_state_in_float32(rwkv_serve_step):
    """The configuration's float32 WKV state, as the TPU compiler builds the
    decode step: every op of the ``wkv`` scope that makes an array of the
    state's shape makes float32, and a contraction there that the compiler
    puts on the MXU (where float32 operands at the default precision take
    one bfloat16 pass) asks for ``highest``.  Today the read r.S, the bonus
    and the update are float32 multiplies and sums on the vector unit."""
    state = "[256,32,64,64]"
    ops = [ln for ln in rwkv_serve_step("decode").as_text().splitlines()
           if "/wkv/" in ln and " = " in ln]
    shaped = [ln for ln in ops if state in ln.split(" = ", 1)[1].split("(", 1)[0]]
    assert shaped and all(re.search(r"= f32\[256,32,64,64\]", ln) for ln in shaped)
    mxu = [ln for ln in ops if re.search(r" (convolution|dot)\(", ln)]
    assert all("operand_precision={highest" in ln for ln in mxu), mxu[:1]
