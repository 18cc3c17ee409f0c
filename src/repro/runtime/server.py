"""Batched serving loop: continuous decode over a KV/state cache.

``Server`` owns jitted prefill/decode step functions for one RunConfig and
exposes ``generate``: prefill a batch of prompts, then greedy/temperature
decode for N tokens.  Slot-based batching (a finished sequence's slot can be
refilled) is modeled by the per-slot ``done`` mask.

``generate`` opens its spans with ``TRACER.annotated``, so a
``jax.profiler`` trace shows them on the device's clock: ``serve.generate``
around the call, ``serve.prefill`` around the prefill and cache growth up
to the logits being ready, and for every token ``serve.token`` holding
``serve.step`` (the decode dispatch), ``serve.sample`` and ``serve.fetch``
(the wait for the token on the host).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import RunConfig
from repro.models import build_model
from repro.obs.trace import TRACER
from repro.runtime.steps import decode_bundle, prefill_bundle


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


class Server:
    def __init__(self, run_cfg: RunConfig, params: Any, mesh=None,
                 eos_token: int = 0, temperature: float = 0.0):
        self.run_cfg = run_cfg
        self.model = build_model(run_cfg.model, run_cfg.sharding)
        self.params = params
        self.eos = eos_token
        self.temperature = temperature
        self._prefill = prefill_bundle(run_cfg, mesh).jit()
        self._decode = decode_bundle(run_cfg, mesh).jit()
        self.stats = ServeStats()

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        logits = logits[:, -1, :self.run_cfg.model.vocab_size].astype(jnp.float32)
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.temperature).astype(jnp.int32)

    @staticmethod
    def _grow_cache(cache, extra: int):
        """Pad KV caches (rank-5 leaves named k/v) so decode has capacity for
        ``extra`` new positions; O(1) recurrent states need no growth."""
        if isinstance(cache, dict):
            out = {}
            for key, v in cache.items():
                if key in ("k", "v") and hasattr(v, "ndim") and v.ndim == 5:
                    pad = [(0, 0)] * 5
                    pad[2] = (0, extra)
                    out[key] = jnp.pad(v, pad)
                else:
                    out[key] = Server._grow_cache(v, extra)
            return out
        return cache

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
            cache = self._grow_cache(cache, max_new_tokens)
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
