"""Per-architecture smoke tests (reduced configs, deliverable f) +
prefill/decode consistency against the full forward."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import config as C
from repro.models import build_model, param_count
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

    def pad_kv(c):
        if isinstance(c, dict):
            return {k: (jnp.pad(v, [(0, 0)] * 2 + [(0, 4)] + [(0, 0)] * 2)
                        if k in ("k", "v") and hasattr(v, "ndim") and v.ndim == 5
                        else pad_kv(v)) for k, v in c.items()}
        return c

    cache = pad_kv(cache)
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
    new_step = jax.jit(model.decode_step)
    old_step = jax.jit(functools.partial(_rwkv_decode_step_as_scan_xs, model))
    got = want = cache
    for t in range(9, 12):
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
    assert got["state"].dtype == jnp.float32 and int(got["pos"]) == 12


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
    cache = {k: (jnp.pad(v, [(0, 0)] * 2 + [(0, 4)] + [(0, 0)] * 2)
                 if k in ("k", "v") else v) for k, v in cache.items()}
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
