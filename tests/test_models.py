"""Per-architecture smoke tests (reduced configs, deliverable f) +
prefill/decode consistency against the full forward."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import config as C
from repro.models import build_model, param_count
from repro.runtime.steps import position_axes
from conftest import tiny_lm_batch

ALL_ARCHS = list(C.list_archs())

# published sizes (±12% tolerance; frontend-stubbed archs count backbone only)
EXPECTED_PARAMS = {
    "llama3-8b": 8.0e9, "qwen1.5-4b": 4.0e9, "gemma3-12b": 12.2e9,
    "gemma3-27b": 27.4e9, "qwen3-moe-30b-a3b": 30.5e9, "dbrx-132b": 132e9,
    "zamba2-7b": 7.0e9, "rwkv6-1.6b": 1.6e9,
}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_loss(arch):
    """One forward/train-loss step on CPU: output shapes + no NaNs."""
    cfg = C.get(arch).smoke
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if cfg.family == "conv":
        batch = {
            "images": jax.random.normal(
                jax.random.key(1), (2, cfg.image_hw, cfg.image_hw, cfg.image_c)),
            "labels": jnp.zeros((2,), jnp.int32),
        }
    else:
        s = 32 - (cfg.frontend_seq if cfg.frontend != "none" else 0)
        batch = tiny_lm_batch(cfg, b=2, s=s)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert not bool(jnp.isnan(loss)), f"{arch}: NaN loss"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", sorted(EXPECTED_PARAMS))
def test_full_config_param_count(arch):
    """The exact pool configs must land near the published model sizes."""
    n = param_count(C.get(arch).full)
    expected = EXPECTED_PARAMS[arch]
    assert abs(n - expected) / expected < 0.12, f"{arch}: {n/1e9:.2f}B"


def pad_kv(model, cache, extra):
    """``cache`` with ``extra`` more positions on each leaf that grows with
    position: the runtime's rule, ``position_axes`` over the axes the model
    declares in ``decode_state_specs``."""
    _, axes, _, _ = model.decode_state_specs(C.ShapeConfig("serve", 1, 1, "decode"))

    def pad(x, axis):
        if axis is None:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, extra)
        return jnp.pad(x, widths)
    return jax.tree.map(pad, cache, position_axes(axes))


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-12b", "zamba2-7b",
                                  "rwkv6-1.6b", "seamless-m4t-large-v2",
                                  "internvl2-2b"])
def test_prefill_decode_matches_forward(arch):
    """Decoding token s-1 after prefilling s-1 tokens must equal the full
    causal forward's last-position logits."""
    cfg = C.get(arch).smoke
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    fe = None
    if cfg.frontend != "none":
        fe = jax.random.normal(jax.random.key(3),
                               (b, cfg.frontend_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family in ("encdec", "audio"):
        full = model.forward(params, tokens, fe)
    elif cfg.family in ("dense", "moe", "vlm"):
        full, _ = model.forward(params, tokens, fe)
    else:
        full = model.forward(params, tokens)

    pre_batch = {"tokens": tokens[:, :s - 1]}
    if fe is not None:
        pre_batch["frontend_emb"] = fe
    _, cache = jax.jit(model.prefill)(params, pre_batch)
    cache = pad_kv(model, cache, 4)
    logits_dec, new_cache = jax.jit(model.decode_step)(
        params, cache, {"token": tokens[:, s - 1:]})
    a = np.asarray(logits_dec[:, 0], np.float32)
    ref = np.asarray(full[:, -1], np.float32)
    rel = np.max(np.abs(a - ref)) / max(np.max(np.abs(ref)), 1e-6)
    # chunked-vs-recurrent reassociation allows small drift for SSM/hybrid
    tol = 0.05 if cfg.family in ("hybrid", "ssm") else 1e-3
    assert rel < tol, f"{arch}: decode/forward rel err {rel}"


def _rwkv_decode_step_as_scan_xs(model, params, cache, batch):
    """The rwkv6 decode step with its stacked caches as the layer scan's
    ``xs`` and the new caches as its ``ys``: the loop that carrying the
    caches in place replaced."""
    from repro.models import rwkv
    from repro.models.layers import rms_norm
    cfg = model.cfg
    x = model._embed(params, batch["token"])

    def layer(x, inp):
        p_l, st, tm_prev, cm_prev = inp
        h = rms_norm(x, p_l["ln1"], cfg.norm_eps)
        y, tm_new, st_new = rwkv.rwkv_time_decode(p_l["time"], cfg, h, tm_prev, st)
        x = x + y
        h2 = rms_norm(x, p_l["ln2"], cfg.norm_eps)
        y, cm_new = rwkv.rwkv_channel_decode(p_l["channel"], cfg, h2, cm_prev)
        return x + y, {"state": st_new, "tm_prev": tm_new, "cm_prev": cm_new}

    x, new = jax.lax.scan(layer, x, (params["layers"], cache["state"],
                                     cache["tm_prev"], cache["cm_prev"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    new["pos"] = cache["pos"] + 1
    return jnp.einsum("bsd,dv->bsv", x, params["head"]), new


def _hybrid_decode_step_as_scan_xs(model, params, cache, batch):
    """zamba2's decode step with its stacked caches as the layer scans'
    ``xs`` and the new caches as their ``ys``, the shared attention's K/V
    given one group's layer at a time: the loops that carrying the caches in
    place replaced."""
    from repro.models import attention as attn, ssm
    from repro.models.layers import rms_norm, swiglu
    cfg, shared, pos = model.cfg, params["shared"], cache["pos"]
    x = jnp.take(params["embed"], batch["token"], axis=0).astype(jnp.dtype(cfg.dtype))

    def mamba(x, inp):
        p_l, mc = inp
        h, mc = ssm.ssm_decode_step(p_l["mixer"], cfg,
                                    rms_norm(x, p_l["ln"], cfg.norm_eps), mc)
        return x + h, mc

    def group(x, inp):
        p_group, gc = inp
        h = rms_norm(x, shared["ln1"], cfg.norm_eps)
        h, (k, v) = attn.attention_decode_stacked(
            shared["attn"], cfg, h, gc["k"][None], gc["v"][None], 0, pos)
        x = x + h
        h = rms_norm(x, shared["ln2"], cfg.norm_eps)
        x = x + swiglu(h, shared["ffn"]["w_gate"], shared["ffn"]["w_up"],
                       shared["ffn"]["w_down"])
        x, mamba_caches = jax.lax.scan(mamba, x, (p_group, gc["mamba"]))
        return x, {"k": k[0], "v": v[0], "mamba": mamba_caches}

    x, groups = jax.lax.scan(group, x, (params["groups"], cache["groups"]))
    tail = cache["tail"]
    if model.remainder:
        x, tail = jax.lax.scan(mamba, x, (params["tail"], tail))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return (jnp.einsum("bsd,dv->bsv", x, params["head"]),
            {"groups": groups, "tail": tail, "pos": pos + 1})


def _assert_decode_matches(model, params, cache, tokens, start, old_step):
    """From ``start`` to the end of ``tokens``, the model's decode step
    gives the logits and caches of ``old_step``, in the same tree, shapes
    and dtypes."""
    new_step = jax.jit(model.decode_step)
    old_step = jax.jit(functools.partial(old_step, model))
    got = want = cache
    for t in range(start, tokens.shape[1]):
        batch = {"token": tokens[:, t:t + 1]}
        logits, got = new_step(params, got, batch)
        ref_logits, want = old_step(params, want, batch)
        assert (jax.tree.structure(got) == jax.tree.structure(want)
                == jax.tree.structure(cache))
        for a, b, c in zip(jax.tree.leaves((logits, got)),
                           jax.tree.leaves((ref_logits, want)),
                           jax.tree.leaves((ref_logits, cache))):
            assert a.shape == b.shape == c.shape and a.dtype == b.dtype == c.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-5, atol=1e-6)
    assert int(got["pos"]) == tokens.shape[1]
    return got


def test_rwkv_decode_in_place_matches_scan_over_caches():
    """rwkv6's decode step, which carries its stacked caches through the
    layer loop and writes each layer's slice in place, gives the logits and
    caches of the loop that took the caches as ``xs`` and gave them back as
    ``ys``, over three steps after a prefill, in the same tree, shapes and
    dtypes."""
    cfg = C.get("rwkv6-1.6b").smoke
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens[:, :9]})
    got = _assert_decode_matches(model, params, cache, tokens, 9,
                                 _rwkv_decode_step_as_scan_xs)
    assert got["state"].dtype == jnp.float32


@pytest.mark.parametrize("num_layers", [4, 5])   # 5: a mamba tail after the groups
def test_hybrid_decode_in_place_matches_scan_over_caches(num_layers):
    """zamba2's decode step, which carries the shared attention's stacked
    K/V and the stacked mamba ``state``/``conv`` through its loops and
    updates each layer's slice in place, gives the logits and caches of the
    loops that took the caches as ``xs`` and gave them back as ``ys``, over
    three steps after a prefill."""
    cfg = C.get("zamba2-7b").smoke.replace(num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens[:, :9]})
    got = _assert_decode_matches(model, params, pad_kv(model, cache, 3), tokens,
                                 9, _hybrid_decode_step_as_scan_xs)
    assert got["groups"]["mamba"]["state"].dtype == jnp.float32


def test_encdec_decode_steps_match_forward():
    """seamless-m4t's decode step, which writes each layer's new position
    into the stacked self-attention K/V in place, gives the full forward's
    logits at every position of three steps after a prefill: each step
    reads the positions the steps before it wrote."""
    cfg = C.get("seamless-m4t-large-v2").smoke
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    fe = jax.random.normal(jax.random.key(3), (b, cfg.frontend_seq, cfg.d_model),
                           jnp.bfloat16)
    full = model.forward(params, tokens, fe)
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens[:, :s - 3],
                                               "frontend_emb": fe})
    cache = pad_kv(model, cache, 3)
    step = jax.jit(model.decode_step)
    for t in range(s - 3, s):
        logits, cache = step(params, cache, {"token": tokens[:, t:t + 1]})
        a = np.asarray(logits[:, 0], np.float32)
        ref = np.asarray(full[:, t], np.float32)
        rel = np.max(np.abs(a - ref)) / max(np.max(np.abs(ref)), 1e-6)
        assert rel < 1e-3, f"position {t}: decode/forward rel err {rel}"


def test_moe_nodrop_consistency():
    """With no-drop capacity everywhere, MoE decode == forward exactly."""
    cfg = C.get("qwen3-moe-30b-a3b").smoke
    model = build_model(cfg)
    model.moe_capacity = 0.0
    params = model.init(jax.random.key(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    full, _ = model.forward(params, tokens)
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens[:, :s - 1]})
    cache = pad_kv(model, cache, 4)
    logits, _ = jax.jit(model.decode_step)(params, cache,
                                           {"token": tokens[:, s - 1:]})
    np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               rtol=2e-2, atol=1e-3)


@pytest.mark.parametrize("arch,window", [
    ("qwen1.5-4b", None),
    ("gemma3-12b", 4),           # local layers' window shorter than the run
    ("qwen3-moe-30b-a3b", None),
])
def test_server_generate_across_cache_growth_matches_forward(arch, window,
                                                             monkeypatch):
    """``Server.generate`` prefills 8 tokens, grows the cache into the
    decode step's compiled formats and decodes 8 more: every greedy token
    is the full-sequence forward's argmax at its position.  Routing drops
    no token, so that prefill and forward route alike."""
    from repro.models import moe
    from repro.runtime.server import Server
    from repro.runtime.steps import init_params

    monkeypatch.setattr(moe, "_capacity", lambda tokens, cfg, factor: tokens)
    cfg = C.get(arch).smoke
    if window is not None:
        cfg = cfg.replace(window_size=window)
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", 16, 2, "prefill"),
                     mesh=C.SMOKE_MESH)
    params = init_params(rc, jax.random.key(0))
    server = Server(rc, params, eos_token=-1)
    prompts = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    out = server.generate({"tokens": prompts}, max_new_tokens=8)

    seq = jnp.concatenate([prompts, jnp.asarray(out)], axis=1)
    full, _ = build_model(cfg).forward(params, seq[:, :-1])
    want = np.asarray(jnp.argmax(full[:, 7:, :cfg.vocab_size], axis=-1))
    np.testing.assert_array_equal(out, want)

    # a step's cache comes out in the formats the step takes it in
    _, cache = server._prefill(params, {"tokens": prompts})
    cache = server._grow_cache(cache, 8, 2)
    tok = {"token": prompts[:, :1]}
    (_, formats, _), _ = server._decode.compiled(params, cache, tok).input_formats
    grown = {leaf: cache[leaf].format for leaf in ("k", "v")}
    _, cache = server._decode(params, cache, tok)      # donates the grown cache
    for leaf in ("k", "v"):
        assert grown[leaf] == cache[leaf].format == formats[leaf]


#: the cache leaves that grow with position, by the path of their keys
GROWING_LEAVES = {
    "qwen1.5-4b": {"k", "v"},
    "zamba2-7b": {"groups/k", "groups/v"},
    "seamless-m4t-large-v2": {"k", "v"},          # not enc_out
    "rwkv6-1.6b": set(),
}


def _leaf_names(tree, keep):
    return {"/".join(p.key for p in path)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree) if keep(leaf)}


@pytest.mark.parametrize("arch", sorted(GROWING_LEAVES))
def test_cache_grows_where_the_model_declares_kv_seq(arch):
    """The leaves the runtime grows and gives the compiler's layout are
    exactly those whose declared axes name ``kv_seq``, and ``_grow_cache``
    pads only their position axis."""
    from jax.experimental.layout import Format, Layout
    from repro.runtime.server import Server
    from repro.runtime.steps import decode_bundle, init_params

    cfg = C.get(arch).smoke
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", 24, 2, "prefill"),
                     mesh=C.SMOKE_MESH)
    model = build_model(cfg)
    _, cache_axes, _, _ = model.decode_state_specs(rc.shape)
    positions = position_axes(cache_axes)
    declared = {"/".join(p.key for p in path)
                for path, axes in jax.tree_util.tree_flatten_with_path(
                    cache_axes, is_leaf=lambda x: isinstance(x, tuple))[0]
                if "kv_seq" in axes}
    assert declared == GROWING_LEAVES[arch]
    assert _leaf_names(positions, lambda a: a is not None) == declared
    fmts = decode_bundle(rc).in_shardings[1]
    auto = _leaf_names(fmts, lambda f: isinstance(f, Format) and f.layout == Layout.AUTO)
    assert auto == declared

    params = init_params(rc, jax.random.key(0))
    specs, _ = model.prefill_input_specs(rc.shape)
    batch = {k: (jax.random.randint(jax.random.key(1), s.shape, 0, cfg.vocab_size)
                 if k == "tokens" else jax.random.normal(jax.random.key(2), s.shape, s.dtype))
             for k, s in specs.items()}
    batch["tokens"] = batch["tokens"][:, :8]
    server = Server(rc, params, eos_token=-1)
    _, cache = server._prefill(params, batch)
    before = jax.tree.map(lambda x: x.shape, cache)
    grown = server._grow_cache(cache, 5, 2)

    def want(shape, axis):
        return shape if axis is None else shape[:axis] + (shape[axis] + 5,) + shape[axis + 1:]
    assert (jax.tree.map(lambda x: x.shape, grown)
            == jax.tree.map(want, before, positions, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.mark.parametrize("num_layers", [4, 5])   # 5: a mamba tail after the groups
def test_hybrid_server_generate_matches_a_greedy_decode_loop(num_layers):
    """zamba2-7b's ``Server.generate`` (8-token prompts, 8 new tokens), whose
    decode step carries the shared attention's stacked K/V and the mamba
    ``state``/``conv`` stacks in place, gives the greedy tokens of a plain
    loop of the jitted ``decode_step`` over the prefill cache padded with
    ``jnp.pad``."""
    from repro.runtime.server import Server
    from repro.runtime.steps import init_params

    cfg = C.get("zamba2-7b").smoke.replace(num_layers=num_layers)
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("serve", 16, 2, "prefill"),
                     mesh=C.SMOKE_MESH)
    params = init_params(rc, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    out = Server(rc, params, eos_token=-1).generate({"tokens": prompts},
                                                   max_new_tokens=8)

    model = build_model(cfg)
    greedy = lambda logits: jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)
    logits, cache = jax.jit(model.prefill)(params, {"tokens": prompts})
    cache = pad_kv(model, cache, 8)
    step = jax.jit(model.decode_step)
    want = [greedy(logits)]
    for _ in range(7):
        logits, cache = step(params, cache, {"token": want[-1][:, None]})
        want.append(greedy(logits))
    np.testing.assert_array_equal(out, np.stack(want, axis=1))
    assert int(cache["pos"]) == 15


def test_gemma_window_pattern():
    """gemma3 smoke: global layers attend beyond the window, local don't."""
    cfg = C.get("gemma3-12b").smoke  # window 16, global every 2
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    b, s = 1, 32
    tokens = jnp.zeros((b, s), jnp.int32)
    logits, _ = model.forward(params, tokens)
    assert logits.shape == (b, s, ((cfg.vocab_size + 255) // 256) * 256)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
