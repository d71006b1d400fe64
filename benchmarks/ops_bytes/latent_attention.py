"""Operations and bytes that latent attention (MLA in its absorbed form)
needs for the decode tokens of one call, from the shapes alone (one layer).

Every query head attends over ONE shared row a token: ``row`` values (the
normed latent and the rotated key: 512 + 64), the weighted sum over the
first ``v_dim`` of them. ``ctx_rows`` is the sum of the live contexts, which
for sequences that each decode one token is the count of (query, key)
pairs. The least the kernel must move is each such row once, plus the
queries in ([heads, row] a token) and the results out ([heads, v_dim]); the
least it must compute is a multiply-add a head for each value of the score
and of the weighted sum: ``2 * heads * (row + v_dim)`` a pair. The row is
counted at its ``row`` values, 1,152 B in bfloat16, whatever the layout
pads it to: a leaf padded to 640 moves 1,280 B a row, so a kernel at the
memory's peak reads a share of 90%.
"""

from typing import Tuple


def ops_and_bytes(ctx_rows: int, n_tokens: int, n_heads: int, row: int,
                  v_dim: int, row_bytes: int = 2, act_bytes: int = 2
                  ) -> Tuple[float, float]:
    flops = 2.0 * ctx_rows * n_heads * (row + v_dim)
    moved = float(ctx_rows) * row * row_bytes \
        + float(n_tokens) * n_heads * (row + v_dim) * act_bytes
    return flops, moved
