"""Operations and bytes the Mamba-2 one-token step needs for one call, from
the shapes alone (one layer).

``n_seqs`` sequences each decode one token. The least the step must move is
each such sequence's float32 state [H, P, N] read once and written once,
plus its rows: x and the output y (P a head), B and C (N a group), the step
dt and the decay (one a head), all float32. The least it must compute, from
``S <- a S + (dt x) B^T; y = S C + D x``, is a state element's multiply for
the decay, a multiply-add for the outer product and a multiply-add for
``S C``: 5 H P N, and dt x and D x beside them: 3 H P. A slot that decodes
nothing moves nothing.
"""

from typing import Tuple


def ops_and_bytes(n_seqs: int, n_heads: int, head_dim: int, n_groups: int,
                  state_dim: int, state_bytes: int = 4, act_bytes: int = 4
                  ) -> Tuple[float, float]:
    state = n_heads * head_dim * state_dim
    flops = float(n_seqs) * (5 * state + 3 * n_heads * head_dim)
    moved = n_seqs * (2.0 * state * state_bytes
                      + (2 * n_heads * head_dim + 2 * n_groups * state_dim
                         + 2 * n_heads) * act_bytes)
    return flops, moved
