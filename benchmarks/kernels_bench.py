"""Kernel micro-benchmarks: wall-time (interpret mode — structural only on
CPU) + the simulator's modeled v5e time per kernel configuration, including
the tiled-matmul block-shape sweep the §Perf methodology iterates on.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import Simulator
from repro.kernels.flash_attention import attention_ref
from repro.kernels.tiled_matmul import matmul_ref


def _modeled_time(sim, fn, *args, name):
    cap = sim.capture(fn, *args, name=name)
    rep = sim.performance(cap)
    return rep


def run(emit):
    sim = Simulator()
    # flash-attention reference vs naive at 4k ctx: modeled HBM traffic ratio
    b, h, kv, s, d = 1, 8, 2, 4096, 128
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, kv, s, d), jnp.bfloat16)

    rep_naive = _modeled_time(
        sim, lambda q, k, v: attention_ref(q, k, v, causal=True), q, k, k,
        name="attn_naive")
    emit("attn_naive_4k_modeled", rep_naive.total_seconds * 1e6,
         f"hbm={rep_naive.total_hbm_bytes/2**30:.2f}GiB")

    from repro.models.attention import chunked_sdpa
    import jax.numpy as jnp2

    def chunked(q, k, v):
        pos = jnp2.arange(s, dtype=jnp2.int32)
        qb = q.transpose(0, 2, 1, 3)
        kb = k.transpose(0, 2, 1, 3)
        return chunked_sdpa(qb, kb, kb, q_positions=pos, k_positions=pos,
                            causal=True, window=0)

    rep_chunk = _modeled_time(sim, chunked, q, k, k, name="attn_chunked")
    emit("attn_chunked_4k_modeled", rep_chunk.total_seconds * 1e6,
         f"hbm={rep_chunk.total_hbm_bytes/2**30:.2f}GiB;"
         f"saving={rep_naive.total_hbm_bytes/max(rep_chunk.total_hbm_bytes,1):.1f}x")

    # the Pallas flash kernel's analytic v5e model: fused attention touches
    # HBM only for Q/K/V/O (scores live in VMEM scratch) — the memory-term
    # win the kernel delivers vs both reference paths
    import numpy as np
    hw = sim.hw
    flops = 4.0 * b * h * s * s * d / 2          # causal: half the square
    qkvo_bytes = (b * h * s * d + 2 * b * kv * s * d + b * h * s * d) * 2
    t_flash = max(flops / hw.peak_bf16_flops, qkvo_bytes / hw.hbm_bw)
    emit("attn_pallas_flash_4k_modeled", t_flash * 1e6,
         f"hbm={qkvo_bytes/2**30:.3f}GiB;"
         f"saving={rep_naive.total_hbm_bytes/qkvo_bytes:.0f}x_bytes")

    # tiled-matmul block sweep (modeled MXU efficiency per block shape)
    m = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16)
    rep = _modeled_time(sim, lambda a, b: matmul_ref(a, b), m, m, name="mm")
    emit("matmul_1k_modeled", rep.total_seconds * 1e6,
         f"mfu={rep.mfu*100:.0f}%")

    # wall-clock sanity for the real Pallas kernel (tiny): compiled on a TPU
    # backend, interpreted anywhere else, and labelled by what it ran on
    from repro.kernels.tiled_matmul import matmul
    backend = jax.default_backend()
    mode = "compiled" if backend == "tpu" else "interpret"
    a = jnp.ones((256, 256), jnp.float32)
    out = matmul(a, a)  # warm
    t0 = time.time()
    for _ in range(3):
        matmul(a, a).block_until_ready()
    emit(f"pallas_matmul_{mode}_wall", (time.time() - t0) / 3 * 1e6, backend)


if __name__ == "__main__":
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
