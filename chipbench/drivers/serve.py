"""Closed-loop batch generation through the program's ``Server.generate``.

Set-up makes the weights from the seed (``reference/dense.py``), builds the
``Server`` and runs one warm-up batch at the window's shapes, so that every
program the window uses is compiled or loaded from the cache.  The window
then generates a fixed number of batches, as many as fill ``--seconds`` at
the batch time the workload file records, each from its own seeded prompts.
``Server`` exposes no per-token times, so the driver wraps the instance's
jitted ``_decode`` with a timestamp: ``generate`` syncs on every token, so
consecutive calls are one whole token apart.

Once the window has closed and the memory peak is read, the program's
weights are freed and the float32 reference reruns a seeded sample of the
finished sequences: every served token's logit must lie close to the
reference's best at its position.
"""
from __future__ import annotations

import contextlib
import sys
import time
from typing import Any, Dict, List

import numpy as np

KIND = "serve"
SPAN_GENERATE = "bench.generate"
SPAN_DECODE = "bench.decode"


def model_config(cell):
    from repro import config as C
    return C.get(cell.config["arch"]).full.replace(**cell.config["model"])


def batch_prompts(rng: np.random.Generator, batch: int, length: int,
                  vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=(batch, length), dtype=np.int32)


class TokenClock:
    """Wraps a server's jitted decode call with a host timestamp per call."""

    def __init__(self, server):
        import jax
        if not callable(getattr(server, "_decode", None)):
            raise AttributeError("Server has no jitted _decode to time")
        self._decode = server._decode
        self.stamps: List[float] = []

        def timed(*args):
            self.stamps.append(time.perf_counter())
            with jax.profiler.TraceAnnotation(SPAN_DECODE):
                return self._decode(*args)

        server._decode = timed


def check_params(params, model_cfg):
    """The seeded weights must match the program's parameter layout."""
    import jax
    from repro.models import build_model
    want = build_model(model_cfg).abstract()
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError(f"seeded weights {got} do not match the program's {want}")


def free(tree):
    import jax
    for leaf in jax.tree.leaves(tree):
        leaf.delete()


def serve_window(server, prompts: List[np.ndarray], new_tokens: int, clock,
                 tracer, traced: int):
    """Generate every batch, tracing batch ``traced``; returns the outputs,
    the per-token gaps, the window's ends and the traced batch's seconds."""
    import jax
    outs, gaps, traced_s = [], [], None
    t_start = time.perf_counter()
    for i, p in enumerate(prompts):
        tracing = i == traced
        if tracing:
            tracer.start()
        clock.stamps.clear()
        b0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation("bench.window") if tracing
              else contextlib.nullcontext()):
            with jax.profiler.TraceAnnotation(SPAN_GENERATE):
                out = server.generate({"tokens": jax.numpy.asarray(p)},
                                      max_new_tokens=new_tokens)
        b1 = time.perf_counter()
        if tracing:
            tracer.stop()
            traced_s = b1 - b0
        outs.append(out)
        # token k is on the host when decode call k starts; the last at b1
        gaps.extend(np.diff(np.asarray(clock.stamps + [b1])))
    return outs, np.asarray(gaps), t_start, time.perf_counter(), traced_s


def run(cell, *, seed: int, seconds: float, tracer, t0: float,
        memory_peak) -> Dict[str, Any]:
    import jax
    from repro import config as C
    from repro.runtime.server import Server
    from chipbench import counts
    from chipbench.reference import dense

    m, tr = cell.config["model"], cell.traffic
    B, P, N = tr["batch"], tr["prompt_len"], tr["new_tokens"]
    model_cfg = model_config(cell)
    rc = C.RunConfig(model=model_cfg, shape=C.ShapeConfig("bench", P + N, B, "prefill"),
                     mesh=C.SMOKE_MESH)
    rng = np.random.default_rng(seed)
    n_batches = max(1, round(seconds / tr["batch_seconds"]))
    warm = batch_prompts(rng, B, P, m["vocab_size"])
    prompts = [batch_prompts(rng, B, P, m["vocab_size"]) for _ in range(n_batches)]

    params = dense.make_params(m, seed)
    check_params(params, model_cfg)
    # eos -1 is never sampled: every sequence runs to its length
    server = Server(rc, params, eos_token=-1)
    clock = TokenClock(server)
    server.generate({"tokens": jax.numpy.asarray(warm)}, max_new_tokens=N)

    traced = min(1, n_batches - 1) if tracer.enabled else -1
    outs, gaps, t_start, t_end, traced_s = serve_window(
        server, prompts, N, clock, tracer, traced)
    peak = memory_peak()
    del server
    free(params)

    tokens = B * N * n_batches
    bad = [o.shape != (B, N) or o.min() < 0 or o.max() >= m["vocab_size"]
           for o in outs]
    checks = [("bad_batches", float(sum(bad)), 0.0)]
    if not any(bad):
        t = time.perf_counter()
        checks += compare(cell, seed, prompts, outs)
        print(f"reference_s {time.perf_counter() - t}", file=sys.stderr)
    filled = sum(counts.decode_step_bytes(m, B, P + k) for k in range(1, N))
    return {
        "metrics": {
            "decode_tokens_per_s": (tokens / (t_end - t_start), "tokens/s"),
            "itl_p95_ms": (float(np.percentile(gaps, 95)) * 1e3, "ms"),
            "setup_s": (t_start - t0, "s"),
        },
        "attempted": B * n_batches,
        "failed": B * int(sum(bad)),
        "checks": checks,
        "memory_peak_bytes": peak,
        "window": (t_start, t_end),
        "counts": {"decode_steps": N - 1, "output_tokens": B * N,
                   "decode_bytes": filled, "host_window_s": traced_s},
    }


def sample(seed: int, n_batches: int, batch: int, k: int):
    """A seeded sample of (batch, row) among all sequences of the window."""
    rng = np.random.default_rng([seed, 1])
    picks = rng.choice(n_batches * batch, size=min(k, n_batches * batch),
                       replace=False)
    return [divmod(int(i), batch) for i in sorted(picks)]


def gap_readings(cell, seed: int, prompts, outs, control: bool = False):
    """The float32 reference over a seeded sample of served sequences: the
    widest and the mean gap between a served token's logit and the best
    logit at its position.  With ``control``, also the same readings for
    the tokens that the float8 reference puts first at each position."""
    import jax.numpy as jnp
    from chipbench.reference import dense

    m, tr = cell.config["model"], cell.traffic
    P = tr["prompt_len"]
    picks = sample(seed, len(outs), tr["batch"], tr["check_sequences"])
    seq = np.stack([np.concatenate([prompts[b][r], outs[b][r]]) for b, r in picks])
    params = dense.make_params(m, seed)
    context = jnp.asarray(seq[:, :-1])
    ref = dense.logits(m, params, context, P - 1)
    gaps = {"program": dense.token_gaps(ref, jnp.asarray(seq[:, P:]))}
    if control:
        low = dense.logits(m, params, context, P - 1, quantize=True)
        gaps["control"] = dense.token_gaps(ref, jnp.argmax(low, -1))
    free(params)
    return {k: {"max_gap": float(g.max()), "mean_gap": float(g.mean())}
            for k, g in gaps.items()}


def compare(cell, seed: int, prompts, outs):
    got = gap_readings(cell, seed, prompts, outs)["program"]
    limits = cell.traffic["limits"]
    return [(k, got[k], limits[k]) for k in ("max_gap", "mean_gap")]


def readings(cell, seed: int) -> Dict[str, Any]:
    """The program's and the control's readings on one seed: one batch of
    the cell's shapes through ``Server.generate``, no measured window."""
    import jax
    from repro import config as C
    from repro.runtime.server import Server
    from chipbench.reference import dense

    m, tr = cell.config["model"], cell.traffic
    B, P, N = tr["batch"], tr["prompt_len"], tr["new_tokens"]
    rc = C.RunConfig(model=model_config(cell),
                     shape=C.ShapeConfig("bench", P + N, B, "prefill"),
                     mesh=C.SMOKE_MESH)
    rng = np.random.default_rng(seed)
    batch_prompts(rng, B, P, m["vocab_size"])            # the warm-up batch
    prompts = [batch_prompts(rng, B, P, m["vocab_size"])]
    params = dense.make_params(m, seed)
    server = Server(rc, params, eos_token=-1)
    outs = [server.generate({"tokens": jax.numpy.asarray(prompts[0])},
                            max_new_tokens=N)]
    del server
    free(params)
    return gap_readings(cell, seed, prompts, outs, control=True)
