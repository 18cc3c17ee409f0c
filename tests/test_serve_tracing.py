"""Program spans on the profiler's clock and named scopes in the serve path,
on the CPU: ``TRACER.annotated`` writes ``TraceAnnotation`` events into a
``jax.profiler`` trace and records into its ring only when enabled;
``Server.generate`` lays out its ``serve.*`` spans token by token and stamps
each token's arrival on the host; the prefill and decode steps name their
ops by flat scopes in the compiled HLO's metadata."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import config as C
from repro.obs.trace import SpanTracer
from repro.runtime.server import Server
from repro.runtime.steps import StepBundle, decode_bundle, init_params, prefill_bundle

SCOPES = {"embed", "norm", "attention", "kv_cache", "mlp", "head"}
NEW_TOKENS = 5


def host_spans(trace_dir, prefix):
    """(start_ns, end_ns, name) of the trace's host events named ``prefix...``,
    outer before inner."""
    path = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.end_ns, e.name) for e in line.events
                        if e.name.startswith(prefix)]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def smoke_run_config(seq_len):
    return C.RunConfig(model=C.get("qwen1.5-4b").smoke,
                       shape=C.ShapeConfig("serve", seq_len, 2, "prefill"),
                       mesh=C.SMOKE_MESH)


@pytest.mark.parametrize("enabled", [False, True])
def test_annotated_spans_reach_the_profile_and_the_ring_when_enabled(tmp_path, enabled):
    tr = SpanTracer()
    if enabled:
        tr.enable()
    with jax.profiler.trace(str(tmp_path)):
        with tr.annotated("probe.outer", k=1):
            with tr.annotated("probe.inner"):
                pass
    (o0, o1, outer), (i0, i1, inner) = host_spans(tmp_path, "probe.")
    assert (outer, inner) == ("probe.outer", "probe.inner")
    assert o0 <= i0 <= i1 <= o1
    if enabled:
        by = {r.name: r for r in tr.records}
        assert by["probe.inner"].parent == "probe.outer"
        assert by["probe.outer"].attrs == {"k": 1}
    else:
        assert tr.records == []


def test_generate_lays_out_its_spans_token_by_token(tmp_path):
    rc = smoke_run_config(16)
    # eos -1 is never sampled: every sequence runs to its length
    server = Server(rc, init_params(rc, jax.random.key(0)), eos_token=-1)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    server.generate(batch, max_new_tokens=NEW_TOKENS)    # compiles
    with jax.profiler.trace(str(tmp_path)):
        server.generate(batch, max_new_tokens=NEW_TOKENS)
    spans = host_spans(tmp_path, "serve.")
    names = [n for _, _, n in spans]
    assert names.count("serve.generate") == 1 and names.count("serve.prefill") == 1
    (g0, g1, _), = [s for s in spans if s[2] == "serve.generate"]
    assert all(g0 <= a <= b <= g1 for a, b, _ in spans)
    tokens = [s for s in spans if s[2] == "serve.token"]
    assert len(tokens) == NEW_TOKENS - 1
    for t0, t1, _ in tokens:
        assert [n for a, b, n in spans if t0 <= a and b <= t1 and (a, b) != (t0, t1)
                ] == ["serve.step", "serve.sample", "serve.fetch"]
    times = server.stats.token_times
    assert len(times) == NEW_TOKENS and np.all(np.diff(times) > 0)


@pytest.mark.parametrize("bundle,seq_len,scopes", [
    (decode_bundle, 16, SCOPES),
    (prefill_bundle, 8, SCOPES - {"kv_cache"}),
])
def test_steps_name_their_ops_by_flat_scopes(bundle, seq_len, scopes):
    text = bundle(smoke_run_config(seq_len)).lower().compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    held = [[p for p in name.split("/") if p in SCOPES] for name in op_names]
    assert all(len(h) <= 1 for h in held), "scopes nest"
    assert {h[0] for h in held if h} == scopes


def test_step_programs_are_cached_with_their_scopes(tmp_path):
    """A cached executable of the same ops under other scopes is not reused."""
    from jax.experimental.compilation_cache import compilation_cache

    def step(x):
        return jnp.sin(x) * 2

    def scoped(x):
        with jax.named_scope("mlp"):
            return jnp.sin(x) * 2

    scoped.__name__ = step.__name__
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    saved = {k: jax.config.values[k] for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        texts = [StepBundle(fn, (x,), None, None).lower().compile().as_text()
                 for fn in (step, scoped)]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert any(tmp_path.iterdir())
    assert "mlp" not in texts[0] and "mlp" in texts[1]
