"""Bytes and operations one rwkv6 decode step needs, from a configuration's
shapes alone (``counts.py`` for the parameters).  The WKV state is float32
whatever the configuration's dtype, one (hd x hd) matrix per head, layer and
sequence, read and written once a step."""
from __future__ import annotations

from typing import Any, Dict

from chipbench import counts

STATE_BYTES = 4                       # float32


def _state_elems(m: Dict[str, Any], batch: int) -> int:
    """Elements of the batch's WKV state: L x B x H x hd x hd."""
    hd = m["rwkv_head_dim"]
    return m["num_layers"] * batch * (m["d_model"] // hd) * hd * hd


def wkv_state_bytes(m: Dict[str, Any], batch: int) -> int:
    """The state a step must read and write: 2 B L H hd^2 x 4 bytes."""
    return 2 * _state_elems(m, batch) * STATE_BYTES


def wkv_flops(m: Dict[str, Any], batch: int) -> int:
    """The recurrence of one step: per head, sequence and layer the read
    r^T S (2 hd^2), the decay (hd^2), the update k v^T (hd^2) and its sum
    (hd^2), 5 hd^2 in all; the bonus is O(hd)."""
    return 5 * _state_elems(m, batch)


def decode_flops_per_token(m: Dict[str, Any]) -> int:
    """The operations one sequence's token requires: 2 x every non-embedding
    parameter and the recurrence of its state (``wkv_flops``)."""
    return 2 * counts.rwkv6_nonembedding_params(m) + wkv_flops(m, 1)


def decode_step_bytes(m: Dict[str, Any], batch: int) -> int:
    """Bytes one decode step must move: every non-embedding weight once,
    the batch's embedding rows, the WKV state read and written, and the two
    token-shift carries (L, B, d) read and written."""
    act = counts.BF16_BYTES
    weights = counts.rwkv6_nonembedding_params(m) * act
    rows = batch * m["d_model"] * act
    shifts = 2 * 2 * m["num_layers"] * batch * m["d_model"] * act
    return weights + rows + shifts + wkv_state_bytes(m, batch)
