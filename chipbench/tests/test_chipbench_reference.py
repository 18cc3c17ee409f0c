"""The plain float32 references against the program at smoke widths on the
CPU: the same weights from the same seed, and the same function."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import dense, rwkv6
from chipbench.tests.test_chipbench_harness import QWEN_SMOKE, RWKV_SMOKE, smoke_cell


def _program(cell, **model):
    from repro import config as C
    from repro.models import build_model
    cfg = C.get(cell.config["arch"]).full.replace(**dict(cell.config["model"], **model))
    return cfg, build_model(cfg)


def test_dense_reference_matches_the_program_in_float32():
    cell = smoke_cell("qwen1.5-4b.decode-b16", QWEN_SMOKE)
    m = cell.config["model"]
    params = dense.make_params(m, 5)
    cfg, model = _program(cell, dtype="float32")
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0, m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = model.forward(jax.tree.map(lambda a: a.astype(jnp.float32), params),
                             tokens)[0][:, 7:, : m["vocab_size"]]
    got = dense.logits(m, params, tokens, 7)
    assert float(jnp.std(got)) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_rwkv_reference_draws_the_programs_first_weights():
    from repro.runtime.steps import init_train_state
    from repro import config as C
    cell = smoke_cell("rwkv6-1.6b.train-fsdp4", RWKV_SMOKE)
    m = cell.config["model"]
    cfg, model = _program(cell)
    rc = C.RunConfig(model=cfg, shape=C.ShapeConfig("t", 64, 2, "train"),
                     mesh=C.SMOKE_MESH)
    state = init_train_state(rc, jax.random.key(2**31 + 3))
    ours = rwkv6.init_leaves(m, jax.random.key(2**31 + 3))
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    theirs = {"/".join(k.key for k in p): v for p, v in flat}
    assert set(theirs) == set(ours)
    for name, v in theirs.items():
        assert v.dtype == ours[name].dtype
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)


def test_rwkv_reference_loss_and_gradients_match_the_program_in_float32():
    cell = smoke_cell("rwkv6-1.6b.train-fsdp4", RWKV_SMOKE)
    m = cell.config["model"]
    _, model = _program(cell, dtype="float32")
    leaves = rwkv6.init_leaves(dict(m, dtype="float32"), jax.random.key(7))
    params = {"embed": leaves["embed"], "head": leaves["head"], "ln_f": leaves["ln_f"],
              "ln_in": leaves["ln_in"], "layers": {}}
    for name, v in leaves.items():
        if name.startswith("layers/"):
            node = params["layers"]
            *outer, last = name.split("/")[1:]
            for key in outer:
                node = node.setdefault(key, {})
            node[last] = v
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, m["vocab_size"], (2, 64), dtype=np.int32)
    labels = rng.integers(0, m["vocab_size"], (2, 64), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        (want, _), want_g = jax.value_and_grad(model.loss, has_aux=True)(
            params, {"tokens": tokens, "labels": labels})
    ref = rwkv6.Reference(dict(m, dtype="float32"), {}, 7)
    got, grads = ref.loss_and_grads(tokens, labels)
    assert got == pytest.approx(float(want), rel=1e-5)
    want_sq = {"/".join(k.key for k in p): float(jnp.sum(jnp.square(v)))
               for p, v in jax.tree_util.tree_flatten_with_path(want_g)[0]}
    got_sq = ref.leaf_sqnorms(grads)
    for name, v in want_sq.items():
        assert got_sq[name] == pytest.approx(v, rel=1e-3, abs=1e-12), name


def _fp8(a):
    x = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 448.0
    return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale).astype(a.dtype)


@pytest.mark.parametrize("seed", [1, 2])
def test_serve_agreement_fails_with_the_program_in_float8(seed):
    """The program serving with float8 weights, one precision step below the
    configuration's bfloat16, reads above the limits; in bfloat16 it stays
    below them.  Four layers of width 128."""
    from repro import config as C
    from repro.runtime.server import Server
    from chipbench.drivers import serve
    cell = smoke_cell("qwen1.5-4b.decode-b16",
                      dict(QWEN_SMOKE, num_layers=4, d_model=128, head_dim=32,
                           d_ff=256, vocab_size=1024),
                      batch=4, prompt_len=16, new_tokens=24, check_sequences=4)
    m, limits = cell.config["model"], cell.traffic["limits"]
    rc = C.RunConfig(model=serve.model_config(cell),
                     shape=C.ShapeConfig("b", 40, 4, "prefill"), mesh=C.SMOKE_MESH)
    prompts = [serve.batch_prompts(np.random.default_rng(seed), 4, 16, m["vocab_size"])]
    read = {}
    for name, weights in (("bf16", lambda t: t), ("fp8", lambda t: jax.tree.map(_fp8, t))):
        server = Server(rc, weights(dense.make_params(m, seed)), eos_token=-1)
        outs = [server.generate({"tokens": jnp.asarray(prompts[0])}, max_new_tokens=24)]
        read[name] = serve.gap_readings(cell, seed, prompts, outs)["program"]
    assert all(read["bf16"][k] <= limits[k] for k in limits)
    assert any(read["fp8"][k] > limits[k] for k in limits)


def test_train_agreement_fails_with_the_reference_in_float8():
    """The control, the reference computed in float8 and put in the
    program's place, fails a limit that the bfloat16 program meets."""
    from chipbench.drivers import train
    from chipbench.tests.test_chipbench_harness import train_smoke
    cell = train_smoke()
    limits = cell.traffic["limits"]
    got = train.readings(cell, 2**31 + 11)
    assert all(got["program"][k] <= limits[k] for k in limits)
    assert any(got["control"][k] > limits[k] for k in limits)
