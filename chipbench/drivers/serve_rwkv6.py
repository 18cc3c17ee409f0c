"""Closed-loop batch generation of rwkv6 through the program's
``Server.generate``: ``serve.py``'s loop, with the weights and the float32
reference of ``reference/rwkv6_serve.py``.

Set-up makes the weights from the seed, builds the ``Server`` and runs one
warm-up batch at the window's batch and prompt length with ``WARM_TOKENS``
new tokens: the recurrent decode step's shapes do not depend on how many
tokens a sequence holds, so that compiles or loads every program the window
uses.  The window, its per-token clock and the check after it are
``serve.py``'s: every served token's logit must lie close to the
reference's best at its position, over a seeded sample of the finished
sequences.  A traced run also hands its readers the scopes of the decode
step's fused ops (``program_trace_rwkv6.fused_scopes``).
"""
from __future__ import annotations

import sys
import time
from typing import Any, Dict

import numpy as np

from chipbench.drivers.serve import (
    TokenClock, batch_prompts, check_params, free, model_config, sample, serve_window,
)

KIND = "serve_rwkv6"
WARM_TOKENS = 4


def _server(cell, params):
    from repro import config as C
    from repro.runtime.server import Server
    tr = cell.traffic
    rc = C.RunConfig(model=model_config(cell),
                     shape=C.ShapeConfig("bench", tr["prompt_len"] + tr["new_tokens"],
                                         tr["batch"], "prefill"),
                     mesh=C.SMOKE_MESH)
    # eos -1 is never sampled: every sequence runs to its length
    return Server(rc, params, eos_token=-1)


def _window_step(server, params, batch: int, prompt_len: int):
    """The decode step compiled for the window's shapes: its cache is the
    prefill's, which ``Server`` passes on unchanged (no leaf grows with the
    length), and its token batch (batch, 1)."""
    import jax
    import jax.numpy as jnp
    tokens = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    _, cache = jax.eval_shape(server.model.prefill, params, {"tokens": tokens})
    token = {"token": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
    return server._decode_step.compiled(params, cache, token)


def run(cell, *, seed: int, seconds: float, tracer, t0: float,
        memory_peak) -> Dict[str, Any]:
    import jax
    from chipbench import counts_rwkv6_decode as counts, program_trace_rwkv6
    from chipbench.reference import rwkv6_serve

    m, tr = cell.config["model"], cell.traffic
    B, P, N = tr["batch"], tr["prompt_len"], tr["new_tokens"]
    rng = np.random.default_rng(seed)
    n_batches = max(1, round(seconds / tr["batch_seconds"]))
    warm = batch_prompts(rng, B, P, m["vocab_size"])
    prompts = [batch_prompts(rng, B, P, m["vocab_size"]) for _ in range(n_batches)]

    params = rwkv6_serve.make_params(m, seed)
    check_params(params, model_config(cell))
    server = _server(cell, params)
    clock = TokenClock(server)
    server.generate({"tokens": jax.numpy.asarray(warm)}, max_new_tokens=WARM_TOKENS)

    traced = min(1, n_batches - 1) if tracer.enabled else -1
    outs, gaps, t_start, t_end, traced_s = serve_window(
        server, prompts, N, clock, tracer, traced)
    peak = memory_peak()
    op_scopes = {}
    if tracer.enabled:
        op_scopes = program_trace_rwkv6.fused_scopes(_window_step(server, params, B, P).as_text())
    del server
    free(params)

    bad = [o.shape != (B, N) or o.min() < 0 or o.max() >= m["vocab_size"]
           for o in outs]
    checks = [("bad_batches", float(sum(bad)), 0.0)]
    if not any(bad):
        t = time.perf_counter()
        checks += compare(cell, seed, prompts, outs)
        print(f"reference_s {time.perf_counter() - t}", file=sys.stderr)
    steps = N - 1
    return {
        "metrics": {
            "decode_tokens_per_s": (B * N * n_batches / (t_end - t_start), "tokens/s"),
            "itl_p95_ms": (float(np.percentile(gaps, 95)) * 1e3, "ms"),
            "setup_s": (t_start - t0, "s"),
        },
        "attempted": B * n_batches,
        "failed": B * int(sum(bad)),
        "checks": checks,
        "memory_peak_bytes": peak,
        "window": (t_start, t_end),
        "counts": {"decode_steps": steps, "output_tokens": B * N,
                   "decode_bytes": steps * counts.decode_step_bytes(m, B),
                   "wkv_state_bytes": steps * counts.wkv_state_bytes(m, B),
                   "wkv_flops": steps * counts.wkv_flops(m, B),
                   "host_window_s": traced_s, "op_scopes": op_scopes},
    }


def gap_readings(cell, seed: int, prompts, outs, control: bool = False):
    """The float32 reference over a seeded sample of served sequences: the
    widest and the mean gap between a served token's logit and the best
    logit at its position.  With ``control``, also the same readings for
    the tokens that each control of the reference puts first: ``control``
    (float8 matmuls) and ``state_bf16`` (the WKV state in bfloat16)."""
    import jax.numpy as jnp
    from chipbench.reference import dense, rwkv6_serve

    m, tr = cell.config["model"], cell.traffic
    P = tr["prompt_len"]
    picks = sample(seed, len(outs), tr["batch"], tr["check_sequences"])
    seq = jnp.asarray(np.stack([np.concatenate([prompts[b][r], outs[b][r]])
                                for b, r in picks]))
    params = rwkv6_serve.make_params(m, seed)

    # the whole sequence, so that the reference's blocks of positions fit
    # it; the last position predicts nothing that was served
    def ref(**kw):
        return rwkv6_serve.logits(m, params, seq, P - 1, **kw)[:, :-1]

    best = ref()
    gaps = {"program": dense.token_gaps(best, seq[:, P:])}
    if control:
        gaps["control"] = dense.token_gaps(best, jnp.argmax(ref(quantize=True), -1))
        gaps["state_bf16"] = dense.token_gaps(best, jnp.argmax(ref(bf16_state=True), -1))
    free(params)
    return {k: {"max_gap": float(g.max()), "mean_gap": float(g.mean())}
            for k, g in gaps.items()}


def compare(cell, seed: int, prompts, outs):
    got = gap_readings(cell, seed, prompts, outs)["program"]
    limits = cell.traffic["limits"]
    return [(k, got[k], limits[k]) for k in ("max_gap", "mean_gap")]


def readings(cell, seed: int) -> Dict[str, Any]:
    """The program's and the controls' readings on one seed: one batch of
    the cell's shapes through ``Server.generate``, no measured window."""
    import jax
    from chipbench.reference import rwkv6_serve

    m, tr = cell.config["model"], cell.traffic
    B, P = tr["batch"], tr["prompt_len"]
    rng = np.random.default_rng(seed)
    batch_prompts(rng, B, P, m["vocab_size"])            # the warm-up batch
    prompts = [batch_prompts(rng, B, P, m["vocab_size"])]
    params = rwkv6_serve.make_params(m, seed)
    server = _server(cell, params)
    outs = [server.generate({"tokens": jax.numpy.asarray(prompts[0])},
                            max_new_tokens=tr["new_tokens"])]
    del server
    free(params)
    return gap_readings(cell, seed, prompts, outs, control=True)
