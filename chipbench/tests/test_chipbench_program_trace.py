"""The program reduction on a small two-chip trace, against numbers worked
out by hand (nanoseconds; the window is the bench.window span, 1000-11000).

``two_chip_program_trace.textproto`` is ``two_chip_trace.textproto`` with a
``tf_op`` stat on the device ops' metadata and ``serve.*`` host spans.
Scopes: chip 0 copy.1 ``kv_cache`` (200 in the window), fusion.1
``attention`` (2000), all-gather.2 ``unscoped`` (no stat, 1500), while.1
``unscoped`` (op_name ``jit(decode_step)/while``, self time 4000 - 1000 =
3000), fusion.2 ``mlp`` (1000), reduce-scatter.1 ``kv_cache`` (the last
component that is a scope wins and ``normalize`` is none, 1500).  Chip 1: fusion.1 ``attention`` 5000,
all-reduce.1 ``unscoped`` 1000.  Averaged over the chips: attention 3500,
kv_cache 850, mlp 500, unscoped 2750.  They add up to 250 more than the busy
7350, half of chip 0's 500 where all-gather.2 and fusion.1 overlap.

Host spans: bench.window 1000-11000, bench.step_dispatch 1000-1500 and
6000-6500, serve.prefill 1100-1400, serve.token 4000-10000 holding
serve.step 4000-4800 and serve.sample 4800-5000, and serve.fetch 8500-10000.
Idle: chip 0 1200-1500, 4500-5000, 9000-9500; chip 1 7000-11000.  By the
innermost span: serve.prefill 200 and bench.step_dispatch 100 (1200-1500),
serve.step 300 and serve.sample 200 (4500-5000), serve.fetch 500 + 1500,
serve.token 1500 (7000-8500), bench.window 1000 (10000-11000); halved over
the chips, 2650 in all.  Idle inside any serve.token: chip 0 500 + 500,
chip 1 3000, so 2000.
"""
import os
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import catalog, program_trace, trace

DATA = Path(__file__).parent / "data"
PROGRAM = DATA / "two_chip_program_trace.textproto"
PLAIN = DATA / "two_chip_trace.textproto"
READERS = ("kv_cache_share.decode", "token_loop_idle_share.decode",
           "prefill_share.decode")


def xspace(path):
    return ProfileData.text_proto_to_serialized_xspace(path.read_text())


@pytest.fixture(scope="module")
def reduced():
    return program_trace.reduce(program_trace.parse(xspace(PROGRAM)))


def ns(d):
    return {k: round(v * 1e9, 6) for k, v in d.items()}


def test_device_time_by_scope(reduced):
    assert ns(reduced["scope_s"]) == {"attention": 3500, "kv_cache": 850, "mlp": 500,
                                      "unscoped": 2750}


def test_idle_time_by_innermost_span(reduced):
    assert ns(reduced["idle_by_span_s"]) == {
        "serve.prefill": 100, "bench.step_dispatch": 50, "serve.step": 150,
        "serve.sample": 100, "serve.fetch": 1000, "serve.token": 750,
        "bench.window": 500}
    assert ns(reduced["idle_in_span_s"])["serve.token"] == 2000
    assert ns(reduced["idle_in_span_s"])["bench.window"] == 2650


def test_span_time_in_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(10000e-9)
    assert ns(reduced["span_s"]) == {
        "bench.window": 10000, "bench.step_dispatch": 1000, "serve.prefill": 300,
        "serve.token": 6000, "serve.step": 800, "serve.sample": 200,
        "serve.fetch": 1500}


def test_idle_gaps_named_by_program_span(reduced):
    gaps = [(name, round(s * 1e9)) for name, s in reduced["idle_gaps"]]
    assert gaps[0] == ("serve.fetch", 4000)
    assert sorted(gaps[1:3]) == [("serve.fetch", 500), ("serve.step", 500)]
    assert gaps[3:] == [("serve.prefill", 300)]


def test_the_benchmarks_own_reduction_reads_both_traces_alike():
    plain, program = (trace.reduce(trace.load_profile(ProfileData.from_serialized_xspace(
        xspace(p)))) for p in (PLAIN, PROGRAM))
    assert program == plain


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode_step)/while/body/closed_call/kv_cache/squeeze:", "kv_cache"),
    ("jit(f)/attention/kv_cache/dynamic_update_slice", "kv_cache"),
    ("jit(f)/normalize/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_is_the_last_scope_named_in_the_path(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


# ---------------------------------------------------------------------------
# the readers, fed as run.py feeds them

def traced_run(tmp_path, source, name="cell-1"):
    """A trace left where run.py leaves it, and trace.reduce's result for it."""
    data = xspace(source)
    out = tmp_path / name / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(data)
    return {"trace": trace.reduce(trace.load_profile(ProfileData.from_serialized_xspace(data)))}


def read_all(ctx, tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "TRACES", tmp_path)
    readers = catalog.layer_metric_readers("serve")
    return {name: readers[name].read(ctx) for name in READERS}


def test_readers_on_a_trace_of_the_program(tmp_path, monkeypatch):
    got = read_all(traced_run(tmp_path, PROGRAM), tmp_path, monkeypatch)
    assert got == pytest.approx({"kv_cache_share.decode": 8.5,
                                 "token_loop_idle_share.decode": 20.0,
                                 "prefill_share.decode": 3.0})


def test_readers_read_nothing_where_the_program_marks_nothing(tmp_path, monkeypatch):
    got = read_all(traced_run(tmp_path, PLAIN), tmp_path, monkeypatch)
    assert got == dict.fromkeys(READERS)


def test_readers_read_only_the_trace_the_harness_reduced(tmp_path, monkeypatch):
    ctx = traced_run(tmp_path, PROGRAM)
    assert read_all({"trace": None}, tmp_path, monkeypatch) == dict.fromkeys(READERS)
    other = dict(ctx["trace"], window_s=ctx["trace"]["window_s"] + 1e-3)
    assert read_all({"trace": other}, tmp_path, monkeypatch) == dict.fromkeys(READERS)
    # a newer trace from another run hides the one reduced
    newer = traced_run(tmp_path, PLAIN, name="cell-2")
    os.utime(next((tmp_path / "cell-2").rglob("*.xplane.pb")),
             ns=(2 ** 62, 2 ** 62))
    assert read_all(ctx, tmp_path, monkeypatch) == dict.fromkeys(READERS)
    assert newer["trace"]["window_s"] == ctx["trace"]["window_s"]


def test_readers_read_nothing_without_a_trace(tmp_path, monkeypatch):
    got = read_all({"trace": {"window_s": 1.0}}, tmp_path, monkeypatch)
    assert got == dict.fromkeys(READERS)


# ---------------------------------------------------------------------------
# per-token host times beside the benchmark's own clock

def test_token_times_fall_between_the_decode_calls():
    import jax
    from repro import config as C
    from repro.runtime.server import Server
    from repro.runtime.steps import init_params

    rc = C.RunConfig(model=C.get("qwen1.5-4b").smoke,
                     shape=C.ShapeConfig("serve", 16, 2, "prefill"), mesh=C.SMOKE_MESH)
    server = Server(rc, init_params(rc, jax.random.key(0)), eos_token=-1)
    clock = catalog.load_driver("serve").TokenClock(server)
    server.generate({"tokens": jax.numpy.zeros((2, 8), jax.numpy.int32)},
                    max_new_tokens=6)
    times, stamps = server.stats.token_times, clock.stamps
    assert len(times) == 6 and len(stamps) == 5
    assert times[0] < stamps[0]
    for k in range(5):
        assert stamps[k] < times[k + 1] < (stamps[k + 1] if k + 1 < 5 else np.inf)
