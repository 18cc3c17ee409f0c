"""Batched serving loop: continuous decode over a KV/state cache.

``Server`` owns jitted prefill/decode step functions for one RunConfig and
exposes ``generate``: prefill a batch of prompts, then greedy/temperature
decode for N tokens.  Slot-based batching (a finished sequence's slot can be
refilled) is modeled by the per-slot ``done`` mask.  The decode step takes
the KV cache in the layout its compiled program chose, and ``generate``
grows the prefill cache into that layout once, before the first step.

``generate`` opens its spans with ``TRACER.annotated``, so a
``jax.profiler`` trace shows them on the device's clock: ``serve.generate``
around the call, ``serve.prefill`` around the prefill and cache growth up
to the logits being ready, and for every token ``serve.token`` holding
``serve.step`` (the decode dispatch), ``serve.sample`` and ``serve.fetch``
(the wait for the token on the host).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import RunConfig
from repro.models import build_model
from repro.obs.trace import TRACER
from repro.runtime.steps import decode_bundle, position_axes, prefill_bundle


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_out: int = 0
    #: ``time.perf_counter()`` at which each token of the last ``generate``
    #: call reached the host, the first one after prefill included
    token_times: List[float] = field(default_factory=list)

    @property
    def decode_tok_per_s(self) -> float:
        return self.tokens_out / self.decode_s if self.decode_s else 0.0


@contextlib.contextmanager
def _uncached_compiles():
    """Compile without JAX's persistent compilation cache.  An executable
    read back from that cache (jax 0.9.0 on a TPU v5e) labels each output
    in a layout of the compiler's choosing with the default layout instead;
    the step that reads that output then refuses it."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        tree)


class CompiledPerShape:
    """A jitted step whose arguments take the formats its compiled program
    chose (``Layout.AUTO``): compiled once per argument shapes, outside the
    persistent cache, and called as compiled, so an argument in another
    layout is refused, not copied."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._compiled: Dict[Any, Any] = {}

    def compiled(self, *args):
        """The executable for these arguments' shapes (arrays or
        ``ShapeDtypeStruct``s), compiled on first use."""
        key = tuple((x.shape, x.dtype) for x in jax.tree.leaves(args))
        exe = self._compiled.get(key)
        if exe is None:
            with _uncached_compiles():
                exe = self._jitted.lower(*_abstract(args)).compile()
            self._compiled[key] = exe
        return exe

    def __call__(self, *args):
        return self.compiled(*args)(*args)


def _pad_positions(x: jax.Array, axis: int, extra: int) -> jax.Array:
    """A stacked cache leaf with ``extra`` more positions along ``axis``,
    grown a layer (its leading axis) at a time: a change of layout on the
    way then needs one layer's room, not the whole leaf's."""
    widths = [(0, 0)] * (x.ndim - 1)
    widths[axis - 1] = (0, extra)
    return jax.lax.map(lambda xl: jnp.pad(xl, widths), x)


class Server:
    def __init__(self, run_cfg: RunConfig, params: Any, mesh=None,
                 eos_token: int = 0, temperature: float = 0.0):
        self.run_cfg = run_cfg
        self.model = build_model(run_cfg.model, run_cfg.sharding)
        self.params = params
        self.eos = eos_token
        self.temperature = temperature
        self._prefill = prefill_bundle(run_cfg, mesh).jit()
        # the growth asks ``_decode_step`` for the formats; ``_decode`` is
        # what the token loop calls, which a caller may wrap
        self._decode = self._decode_step = CompiledPerShape(
            decode_bundle(run_cfg, mesh).jit())
        self._pads: Dict[Any, Any] = {}
        self._positions = position_axes(
            self.model.decode_state_specs(run_cfg.shape)[1])
        self.stats = ServeStats()

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        logits = logits[:, -1, :self.run_cfg.model.vocab_size].astype(jnp.float32)
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.temperature).astype(jnp.int32)

    def _grow_cache(self, cache, extra: int, batch: int):
        """Grow the prefill cache's position axes by ``extra``, each leaf
        straight into the format the decode step takes it in: the cache's
        one relayout, after which every step takes the previous one's cache
        as it is.  Each prefill leaf is freed once grown, so that the last
        leaf grows beside the rest of the grown cache and nothing more."""
        grown = jax.tree.map(
            lambda x, axis: x if axis is None
            else jax.eval_shape(lambda y: _pad_positions(y, axis, extra), x),
            cache, self._positions)
        tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        exe = self._decode_step.compiled(self.params, grown, {"token": tok})
        (_, formats, _), _ = exe.input_formats
        if exe.output_formats[1] != formats:
            raise RuntimeError(
                f"the decode step takes its cache in {formats} but returns it "
                f"in {exe.output_formats[1]}: each step would relayout it")

        def grow(leaf, axis, fmt):
            if axis is None:
                return leaf
            key = (leaf.shape, leaf.dtype, axis, extra, fmt)
            pad = self._pads.get(key)
            if pad is None:
                with _uncached_compiles():
                    pad = jax.jit(_pad_positions, static_argnums=(1, 2),
                                  out_shardings=fmt).lower(leaf, axis, extra).compile()
                self._pads[key] = pad
            # not donated, as XLA cannot reuse a buffer smaller than the
            # output: freed by hand once the pad is done, before the next
            # leaf's output is allocated
            out = jax.block_until_ready(pad(leaf))
            leaf.delete()
            return out

        return jax.tree.map(grow, cache, self._positions, formats)

    def generate(self, batch: Dict[str, Any], max_new_tokens: int = 16,
                 seed: int = 0) -> np.ndarray:
        """Prefill the prompt batch, then decode up to max_new_tokens."""
        with TRACER.annotated("serve.generate"):
            return self._generate(batch, max_new_tokens, seed)

    def _generate(self, batch, max_new_tokens: int, seed: int) -> np.ndarray:
        times = self.stats.token_times = []
        t0 = time.perf_counter()
        with TRACER.annotated("serve.prefill"):
            logits, cache = self._prefill(self.params, batch)
            cache = self._grow_cache(cache, max_new_tokens, logits.shape[0])
            jax.block_until_ready(logits)
        self.stats.prefill_s += time.perf_counter() - t0

        key = jax.random.key(seed)
        with TRACER.annotated("serve.sample"):
            tok = self._sample(logits, key)
        with TRACER.annotated("serve.fetch"):
            out = [np.asarray(tok)]
        times.append(time.perf_counter())
        done = np.zeros(tok.shape[0], bool)
        t0 = time.perf_counter()
        for i in range(max_new_tokens - 1):
            with TRACER.annotated("serve.token"):
                with TRACER.annotated("serve.step"):
                    logits, cache = self._decode(self.params, cache,
                                                 {"token": tok[:, None]})
                with TRACER.annotated("serve.sample"):
                    key, sub = jax.random.split(key)
                    tok = self._sample(logits, sub)
                with TRACER.annotated("serve.fetch"):
                    arr = np.asarray(tok)
                times.append(time.perf_counter())
            done |= arr == self.eos
            out.append(arr)
            self.stats.tokens_out += int((~done).sum())
            if done.all():
                break
        jax.block_until_ready(tok)
        self.stats.decode_s += time.perf_counter() - t0
        return np.stack(out, axis=1)
