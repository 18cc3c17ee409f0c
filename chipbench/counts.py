"""Operations and bytes that the algorithm needs, from a configuration's
shapes alone.  Every share of a peak divides by these, never by what the
program happens to compute."""
from __future__ import annotations

from typing import Any, Dict

BF16_BYTES = 2
#: RWKV-6 token-shift and decay low-rank widths (arXiv:2404.05892, 1.6B)
RWKV_MIX_RANK, RWKV_DECAY_RANK, RWKV_MIXES = 32, 64, 5


def dense_layer_params(m: Dict[str, Any]) -> int:
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if m.get("qkv_bias"):
        attn += h * hd + 2 * kv * hd
    return attn + 3 * d * f + 2 * d          # SwiGLU and two norms


def dense_nonembedding_params(m: Dict[str, Any]) -> int:
    """Every parameter a token passes through a matmul or norm with: the
    layers, the final norm and the output head (not the embedding table)."""
    return (m["num_layers"] * dense_layer_params(m) + m["d_model"]
            + m["d_model"] * m["vocab_size"])


def dense_decode_flops_per_token(m: Dict[str, Any]) -> float:
    return 2.0 * dense_nonembedding_params(m)


def kv_bytes_per_token(m: Dict[str, Any]) -> int:
    return 2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * BF16_BYTES


def decode_step_bytes(m: Dict[str, Any], batch: int, filled: int) -> float:
    """Bytes one decode step must read: every non-embedding weight once, the
    batch's embedding rows, and the ``filled`` cache positions of each row
    (not the cache's capacity)."""
    weights = dense_nonembedding_params(m) * BF16_BYTES
    rows = batch * m["d_model"] * BF16_BYTES
    return weights + rows + batch * filled * kv_bytes_per_token(m)


def rwkv6_layer_params(m: Dict[str, Any]) -> int:
    d, f = m["d_model"], m["d_ff"]
    time = (5 * d * d                                   # r, k, v, g, o
            + d * RWKV_MIXES * RWKV_MIX_RANK + RWKV_MIXES * RWKV_MIX_RANK * d
            + 2 * d * RWKV_DECAY_RANK
            + RWKV_MIXES * d + 4 * d)                  # mu_base, mu_x, w0, u, ln_x
    channel = 2 * d * f + d * d + 2 * d
    return time + channel + 2 * d


def rwkv6_nonembedding_params(m: Dict[str, Any]) -> int:
    return (m["num_layers"] * rwkv6_layer_params(m) + 2 * m["d_model"]
            + m["d_model"] * m["vocab_size"])


def rwkv6_wkv_flops_per_token(m: Dict[str, Any]) -> float:
    """The WKV recurrence a token requires, forward and backward: per head a
    (hd x hd) state update k v^T and a read-out r^T S, 2 hd^2 each, and the
    backward pass twice the forward."""
    hd = m["rwkv_head_dim"]
    heads = m["d_model"] // hd
    return 3.0 * m["num_layers"] * heads * 4 * hd * hd


def rwkv6_train_flops_per_token(m: Dict[str, Any]) -> float:
    """6 N for the matmuls, forward and backward, plus the WKV scan.  What
    the program recomputes under its checkpoints does not count."""
    return 6.0 * rwkv6_nonembedding_params(m) + rwkv6_wkv_flops_per_token(m)
