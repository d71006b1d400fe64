"""Operations that causal (windowed) flash attention needs for one call,
from the shapes alone: QK^T and PV forward (2 matrix products), and five in
the backward pass (the scores again, dV, dP, dQ, dK), each 2 x keys-seen x
head_dim a query and head."""

from typing import Tuple


def keys_seen(seq: int, window: int = 0) -> float:
    """Summed over the queries of one sequence."""
    if window and seq > window:
        return window * (window + 1) / 2 + (seq - window) * window
    return seq * (seq + 1) / 2


def forward_flops(batch: int, seq: int, n_heads: int, head_dim: int,
                  window: int = 0) -> float:
    return 2 * 2.0 * batch * n_heads * head_dim * keys_seen(seq, window)


def backward_flops(batch: int, seq: int, n_heads: int, head_dim: int,
                   window: int = 0) -> float:
    return 2.5 * forward_flops(batch, seq, n_heads, head_dim, window)


def io_bytes(batch: int, seq: int, n_heads: int, n_kv_heads: int,
             head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """(forward, backward) bytes: Q, K, V in and O out; backward reads
    those and dO and writes dQ, dK, dV."""
    q = batch * seq * n_heads * head_dim * itemsize
    kv = 2 * batch * seq * n_kv_heads * head_dim * itemsize
    return 2.0 * q + kv, 4.0 * q + 2.0 * kv
