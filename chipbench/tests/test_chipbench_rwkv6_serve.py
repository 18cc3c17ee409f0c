"""The rwkv6 decode cell on the CPU at smoke widths: the program served
through ``Server.generate`` against the plain float32 reference
(``reference/rwkv6_serve.py``) on its drawn weights, the cell's check
against planted faults and the float8 control, its counts, and its readers
on a small trace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import catalog, counts_rwkv6_decode, program_trace, program_trace_rwkv6, trace
from chipbench.reference import rwkv6_serve
from chipbench.tests.test_chipbench_harness import RWKV_SMOKE, execute, smoke_cell
from chipbench.tests.test_chipbench_program_trace import PROGRAM, xspace

CELL = "rwkv6-1.6b.decode-long"
SEED = 2**31 + 7
#: twelve layers, so that the float8 control and the planted faults show at
#: the size of the cell's limits, which were read at 24 layers of width
#: 2048: at two layers every error is smaller
DEEP = dict(RWKV_SMOKE, num_layers=12, vocab_size=1024)


def serve_cell(model=RWKV_SMOKE, **traffic):
    return smoke_cell(CELL, model, **dict(
        dict(batch=2, prompt_len=16, new_tokens=12, batch_seconds=1.0,
             check_sequences=2), **traffic))


def driver():
    return catalog.load_driver("serve_rwkv6")


# ---------------------------------------------------------------------------
# weights

def test_drawn_weights_fill_the_programs_tree():
    from chipbench.drivers.serve import check_params, model_config
    cell = serve_cell()
    m = cell.config["model"]
    params = rwkv6_serve.make_params(m, SEED)
    check_params(params, model_config(cell))
    time, channel = params["layers"]["time"], params["layers"]["channel"]
    for leaf in (time["lora_b"], time["w_lora_b"], time["mu_base"], time["mu_x"],
                 time["u"], time["ln_x"], channel["mu_k"], channel["mu_r"]):
        assert float(jnp.mean(jnp.abs(leaf.astype(jnp.float32)))) > 0.05
    w0 = np.asarray(time["w0"], np.float32)
    assert w0.min() == pytest.approx(-6.0) and w0.max() == pytest.approx(-1.0, abs=0.01)
    # every layer spreads its channels' memories from hundreds of tokens to a few
    assert (w0[:, 0] < -5.9).all() and (w0[:, -1] > -1.01).all()


# ---------------------------------------------------------------------------
# the program against the reference

def _recording(fn, seen):
    def call(*args):
        out = fn(*args)
        seen.append(np.asarray(out[0][:, -1], np.float32))
        return out
    return call


def _weights_and_prompts(cell):
    m, tr = cell.config["model"], cell.traffic
    prompts = driver().batch_prompts(np.random.default_rng(tr["prompt_len"]), 2,
                                     tr["prompt_len"], m["vocab_size"])
    return rwkv6_serve.make_params(m, SEED), prompts


def _float32_served_logits(cell, params, prompts):
    """The logits ``Server.generate`` serves in float32 at every position,
    and the reference's over the same tokens."""
    m, P = cell.config["model"], cell.traffic["prompt_len"]
    f32 = dataclasses.replace(cell, config=dict(cell.config, model=dict(m, dtype="float32")))
    server = driver()._server(f32, jax.tree.map(lambda a: a.astype(jnp.float32), params))
    seen = []
    server._prefill = _recording(server._prefill, seen)
    server._decode = _recording(server._decode, seen)
    with jax.default_matmul_precision("highest"):
        out = server.generate({"tokens": jnp.asarray(prompts)},
                              max_new_tokens=cell.traffic["new_tokens"])
    got = np.stack(seen, 1)[..., : m["vocab_size"]]
    seq = jnp.asarray(np.concatenate([prompts, out], 1))
    return got, np.asarray(rwkv6_serve.logits(m, params, seq, P - 1)[:, :-1])


@pytest.mark.parametrize("prompt_len", [1, 63, 64, 129, 256])
def test_served_logits_agree_with_the_reference(prompt_len):
    """Prefill, then decode through the recurrent state: in float32 every
    position's logits equal the reference's full forward pass.  The
    tolerance is the reassociation of float32 sums (the prefill's chunked
    scan with its cumulative log decays against the reference's
    token-by-token recurrence; 2e-6 read here) through two layers, on
    logits of unit spread.  Served in bfloat16 through the same path, the cell's
    check reads within its limits."""
    cell = serve_cell(prompt_len=prompt_len, new_tokens=8)
    drv, params, prompts = driver(), *_weights_and_prompts(cell)
    got, want = _float32_served_logits(cell, params, prompts)
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4)

    served = [drv._server(cell, params).generate({"tokens": jnp.asarray(prompts)},
                                                 max_new_tokens=8)]
    checks = drv.compare(cell, SEED, [prompts], served)
    assert all(v <= limit for _, v, limit in checks), checks


def _state_rounded_to_bf16(monkeypatch):
    """The decode step keeps its WKV state in bfloat16, rounded every token."""
    from repro.models import rwkv
    real = rwkv.rwkv_time_decode

    def decode(*args):
        y, x, state = real(*args)
        return y, x, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)

    monkeypatch.setattr(rwkv, "rwkv_time_decode", decode)


def test_float32_logits_catch_a_bf16_state(monkeypatch):
    """The planted fault of a bfloat16 state: the float32 comparison above
    fails by far (its tolerance 1e-4; over 1e-3 here), where the cell's
    logit gaps cannot tell it from the bfloat16 program (PERF.md)."""
    cell = serve_cell(prompt_len=16, new_tokens=24)
    params, prompts = _weights_and_prompts(cell)
    _state_rounded_to_bf16(monkeypatch)
    got, want = _float32_served_logits(cell, params, prompts)
    assert np.abs(got - want).max() > 1e-3


@pytest.mark.parametrize("prompt_len", [1, 63, 64, 129, 256])
def test_chunked_prefill_leaves_the_recurrent_state(prompt_len):
    """The prefill's chunked scan (its tail chunk padded where 64 does not
    divide the length) leaves the state, and the token-shift carries, that
    decoding the same tokens one at a time does: float32, drawn weights,
    within float32 round-off (6e-7 of the largest entry read here)."""
    from repro.models import build_model
    from chipbench.drivers.serve import model_config
    cell = serve_cell()
    m = cell.config["model"]
    model = build_model(model_config(cell).replace(dtype="float32"))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), rwkv6_serve.make_params(m, SEED))
    tokens = jax.random.randint(jax.random.key(prompt_len), (2, prompt_len), 0,
                                m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(model.prefill)(params, {"tokens": tokens})
        _, got = jax.jit(model.prefill)(params, {"tokens": tokens[:, :1]})
        step = jax.jit(model.decode_step)
        for t in range(1, prompt_len):
            _, got = step(params, got, {"token": tokens[:, t:t + 1]})
    assert int(got["pos"]) == int(want["pos"]) == prompt_len
    for leaf in ("state", "tm_prev", "cm_prev"):
        scale = float(jnp.max(jnp.abs(want[leaf])))
        np.testing.assert_allclose(np.asarray(got[leaf]), np.asarray(want[leaf]),
                                   atol=1e-5 * scale, err_msg=leaf)


def test_traced_run_hands_its_readers_the_fused_scopes():
    """With the profiler asked for, the driver reads the compiled decode
    step's fused scopes (started and stopped by a stand-in tracer here)."""
    class Tracer:
        enabled = True
        start = stop = staticmethod(lambda: None)

    result = driver().run(serve_cell(), seed=SEED, seconds=1.0, tracer=Tracer(),
                          t0=0.0, memory_peak=lambda: 0)
    assert isinstance(result["counts"]["op_scopes"], dict)
    assert result["counts"]["decode_steps"] == 11


def test_window_step_is_the_decode_step_the_window_ran():
    """The traced run reads the executable that served the window, found by
    the window's shapes, without compiling another."""
    cell = serve_cell()
    drv, m, tr = driver(), cell.config["model"], cell.traffic
    params = rwkv6_serve.make_params(m, SEED)
    server = drv._server(cell, params)
    prompts = drv.batch_prompts(np.random.default_rng(0), tr["batch"], tr["prompt_len"],
                                m["vocab_size"])
    server.generate({"tokens": jnp.asarray(prompts)}, max_new_tokens=4)
    (ran,) = server._decode_step._compiled.values()
    assert drv._window_step(server, params, tr["batch"], tr["prompt_len"]) is ran
    assert len(server._decode_step._compiled) == 1


def test_driver_end_to_end(monkeypatch):
    line = execute(serve_cell(), monkeypatch, seed=SEED)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"decode_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert set(line["checks"]) == {"bad_batches", "max_gap", "mean_gap"}


# ---------------------------------------------------------------------------
# planted faults

def _without(name):
    """A time-mixing path that runs as if its weight ``name`` were zero."""
    from repro.models import rwkv

    def plant(monkeypatch):
        for fn in ("rwkv_time_mix", "rwkv_time_decode"):
            real = getattr(rwkv, fn)
            monkeypatch.setattr(rwkv, fn, lambda p, *a, real=real: real(
                dict(p, **{name: jnp.zeros_like(p[name])}), *a))
    return plant


def _state_dropped(monkeypatch):
    """Decode starts from a zero state, as if the prefill had not run."""
    from repro.models.rwkv_model import RWKVLM
    real = RWKVLM.prefill

    def prefill(self, params, batch):
        logits, cache = real(self, params, batch)
        return logits, dict(cache, state=jnp.zeros_like(cache["state"]))

    monkeypatch.setattr(RWKVLM, "prefill", prefill)


@pytest.mark.parametrize("plant", [_state_dropped, _without("u"), _without("w_lora_b")],
                         ids=["prefill state dropped", "bonus u left out",
                              "decay LoRA left out"])
def test_planted_fault_reads_incorrect(monkeypatch, plant):
    plant(monkeypatch)
    line = execute(serve_cell(DEEP, new_tokens=24), monkeypatch, seed=SEED)
    assert line["correct"] is False
    assert {"max_gap", "mean_gap"} & {k for k, c in line["checks"].items()
                                      if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("seed", [1, 2])
def test_float8_control_lies_beyond_the_limits(seed):
    cell = serve_cell(DEEP, new_tokens=24)
    limits = cell.traffic["limits"]
    got = driver().readings(cell, seed)
    assert set(got) == {"program", "control", "state_bf16"}
    assert all(got["program"][k] <= limits[k] for k in limits)
    assert any(got["control"][k] > limits[k] for k in limits)


# ---------------------------------------------------------------------------
# counts and readers

def test_full_width_decode_step_counts():
    m = catalog.load_cell(CELL).config["model"]
    # 24 layers x 256 sequences x 32 heads x 64 x 64 float32, read and written
    assert counts_rwkv6_decode.wkv_state_bytes(m, 256) == 6_442_450_944
    assert counts_rwkv6_decode.wkv_flops(m, 256) == 5 * 24 * 256 * 32 * 64 * 64
    assert counts_rwkv6_decode.decode_flops_per_token(m) == (
        2 * 1_465_503_744 + 5 * 24 * 32 * 64 * 64)
    # + 1,465,503,744 bf16 weights, the rows, two (24, 256, 2048) carries
    assert counts_rwkv6_decode.decode_step_bytes(m, 256) == (
        6_442_450_944 + 2 * 1_465_503_744 + 256 * 2048 * 2 + 4 * 24 * 256 * 2048 * 2)


RWKV_NAMES = {"/attention/": "/wkv/", "/mlp/": "/channel_mix/", "kv_cache": "time_mix"}


@pytest.fixture
def rwkv_trace(tmp_path, monkeypatch):
    """``two_chip_program_trace`` with rwkv6 scopes, left where run.py
    leaves a trace: ``wkv`` 3500 ns, ``time_mix`` 850, ``channel_mix`` 500,
    unscoped 2750, of a 10000 ns window (see its test module)."""
    text = PROGRAM.read_text()
    for old, new in RWKV_NAMES.items():
        text = text.replace(old, new)
    data = ProfileData.text_proto_to_serialized_xspace(text)
    out = tmp_path / "cell-1" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(data)
    monkeypatch.setattr(program_trace, "TRACES", tmp_path)
    return trace.reduce(trace.load_profile(ProfileData.from_serialized_xspace(data)))


def ns(seconds):
    return {k: round(v * 1e9, 6) for k, v in seconds.items()}


def test_rwkv6_scopes_in_the_program_reduction(rwkv_trace):
    r = program_trace_rwkv6.for_reduction(rwkv_trace)
    assert ns(r["scope_s"]) == {"wkv": 3500, "time_mix": 850, "channel_mix": 500,
                                "unscoped": 2750}
    # an unscoped op that the compiled step's fused ops charge to wkv:
    # while.1's self time, 3000 on chip 0, is 1500 on the average
    r = program_trace_rwkv6.for_reduction(rwkv_trace, {"jit(decode_step)/while.1": "wkv"})
    assert ns(r["scope_s"]) == {"wkv": 5000, "time_mix": 850, "channel_mix": 500,
                                "unscoped": 1250}


HLO = """\
%fused_update (param_0: f32[4,8], param_1: f32[4]) -> f32[1,4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  %mul.1 = f32[4,8]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(decode_step)/while/body/closed_call/wkv/mul"}
  ROOT %bitcast.2 = f32[1,4,8]{2,1,0} bitcast(%mul.1), metadata={op_name="jit(decode_step)/while/body/broadcast_in_dim"}
}

%fused_mixed (param_0: f32[4,8]) -> f32[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  %neg.1 = f32[4,8]{1,0} negate(%param_0), metadata={op_name="jit(decode_step)/while/body/closed_call/wkv/neg"}
  ROOT %exp.1 = f32[4,8]{1,0} exponential(%neg.1), metadata={op_name="jit(decode_step)/while/body/closed_call/time_mix/exp"}
}

%body (arg: (f32[4,8])) -> (f32[4,8]) {
  %fusion.241 = f32[1,4,8]{2,1,0} fusion(%a), kind=kLoop, calls=%fused_update, metadata={op_name="jit(decode_step)/while/body/broadcast_in_dim"}
  %fusion.7 = f32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_mixed, metadata={op_name="jit(decode_step)/while/body/dynamic_slice"}
  %copy.3 = f32[4,8]{0,1} copy(%a), metadata={op_name="jit(decode_step)/while/body/dynamic_update_slice"}
  %fusion.8 = f32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_mixed, metadata={op_name="jit(decode_step)/while/body/closed_call/norm/mul"}
}
"""


def test_fused_scopes_charge_an_unscoped_fusion_to_the_scope_fused_into_it():
    # fusion.7 fuses two scopes, copy.3 none, fusion.8 has its own
    assert program_trace_rwkv6.fused_scopes(HLO) == {"jit(decode_step)/fusion.241": "wkv"}


def test_readers_on_an_rwkv6_trace(rwkv_trace):
    peaks = catalog.peaks("TPU v5 lite")
    m = catalog.load_cell(CELL).config["model"]
    c = {"decode_bytes": 819e9 * 5e-6, "wkv_state_bytes": 819e9 * 1.4e-6,
         "wkv_flops": 1.0, "output_tokens": 64, "op_scopes": {}}
    ctx = {"trace": rwkv_trace, "counts": c, "peaks": peaks, "model": m}
    got = {name: r.read(ctx) for name, r in catalog.layer_metric_readers("serve_rwkv6").items()}
    # 2 x 1,465,503,744 parameters and 5 x 24 x 32 x 64^2 a token
    token_flops = 2 * 1_465_503_744 + 5 * 24 * 32 * 64 * 64
    # busy 7350 of 10000 ns; the state's least time 1400 ns of 3500 in wkv;
    # idle in serve.token 2000 ns, serve.prefill 300 ns
    assert got == pytest.approx({"idle_share.rwkv_decode": 26.5,
                                 "hbm_share.rwkv_decode": 50.0,
                                 "wkv_share.rwkv_decode": 35.0,
                                 "wkv_roofline.rwkv_decode": 40.0,
                                 "unscoped_share.rwkv_decode": 27.5,
                                 "token_loop_idle_share.rwkv_decode": 20.0,
                                 "prefill_share.rwkv_decode": 3.0,
                                 "mfu.rwkv_decode": 100.0 * token_flops * 64 / 1e-5
                                 / peaks["bf16_flops_per_s"]})


def test_scope_readers_read_nothing_on_a_decoder_trace(tmp_path, monkeypatch):
    data = xspace(PROGRAM)
    out = tmp_path / "cell-1" / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(data)
    monkeypatch.setattr(program_trace, "TRACES", tmp_path)
    reduced = trace.reduce(trace.load_profile(ProfileData.from_serialized_xspace(data)))
    ctx = {"trace": reduced, "counts": {"wkv_state_bytes": 1.0, "wkv_flops": 1.0},
           "peaks": catalog.peaks("TPU v5 lite")}
    readers = catalog.layer_metric_readers("serve_rwkv6")
    for name in ("wkv_share.rwkv_decode", "wkv_roofline.rwkv_decode"):
        assert readers[name].read(ctx) is None
