"""Serving launcher: initializes params from a seed and serves one batch of
requests from a random prompt stream.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke
"""
import argparse
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import config as C
from repro.config import ModelConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.server import Server
from repro.runtime.steps import init_params


def build_server(model_cfg: ModelConfig, *, batch: int, prompt_len: int,
                 max_new: int, seed: int = 0,
                 temperature: float = 0.0) -> Server:
    """A one-device ``Server`` whose parameters are made from ``seed``."""
    shape = C.ShapeConfig("serve", prompt_len + max_new, batch, "prefill")
    rc = C.RunConfig(model=model_cfg, shape=shape, mesh=C.SMOKE_MESH)
    params = init_params(rc, jax.random.key(seed))
    return Server(rc, params, temperature=temperature)


def random_prompts(model_cfg: ModelConfig, *, batch: int, prompt_len: int,
                   seed: int = 1) -> Dict[str, Any]:
    """Uniform random prompt tokens (+ frontend embeddings for stub models)."""
    out = {"tokens": jax.random.randint(jax.random.key(seed),
                                        (batch, prompt_len), 0,
                                        model_cfg.vocab_size)}
    if model_cfg.frontend != "none":
        out["frontend_emb"] = jax.random.normal(
            jax.random.key(seed + 1),
            (batch, model_cfg.frontend_seq, model_cfg.d_model), jnp.bfloat16)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    entry = C.get(args.arch)
    model_cfg = entry.smoke if args.smoke else entry.full
    server = build_server(model_cfg, batch=args.batch,
                          prompt_len=args.prompt_len, max_new=args.max_new,
                          temperature=0.7)
    batch = random_prompts(model_cfg, batch=args.batch,
                           prompt_len=args.prompt_len)
    out = server.generate(batch, max_new_tokens=args.max_new)
    print(f"generated {out.shape} tokens; "
          f"decode {server.stats.decode_tok_per_s:.1f} tok/s")


if __name__ == "__main__":
    main()
