"""Operations and bytes that paged attention needs for one call, from the
shapes alone (one layer).

``seqs``: for every sequence in the ragged batch (new tokens ``n``, context
after the call ``ctx``). The least the kernel must move is each live
context's K and V once, plus Q in and the output out; the least it must
compute is QK^T and PV for every new token against the keys it may see
(causal inside the new chunk, clipped to the sliding window).
"""

from typing import Iterable, Tuple


def ops_and_bytes(seqs: Iterable[Tuple[int, int]], n_heads: int,
                  n_kv_heads: int, head_dim: int, window: int = 0,
                  kv_bytes: int = 2, act_bytes: int = 2) -> Tuple[float, float]:
    flops = moved = 0.0
    for n, ctx in seqs:
        flops += 4.0 * n_heads * head_dim * _keys_seen(ctx - n, ctx - 1,
                                                       window)
        live = min(ctx, window + n - 1) if window else ctx
        moved += 2.0 * live * n_kv_heads * head_dim * kv_bytes
        moved += 2.0 * n * n_heads * head_dim * act_bytes
    return flops, moved


def _keys_seen(first: int, last: int, window: int) -> float:
    """Summed over the queries at positions first..last: the query at
    position p sees p + 1 keys, or ``window`` where that is fewer."""
    count = lambda a, b: (a + 1 + b + 1) * (b - a + 1) / 2.0 if b >= a else 0.0
    if not window:
        return count(first, last)
    full = max(first, window)              # from here on, a whole window
    return count(first, min(last, window - 1)) \
        + max(0, last - full + 1) * float(window)
