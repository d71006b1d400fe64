"""Operations and bytes the gated delta rule's one-token step needs for one
call, from the shapes alone (one layer).

``n_seqs`` sequences each decode one token. The least the step must move is
each such sequence's float32 state [H, dk, dv] read once and written once,
plus q, k (dk a head), v and the output (dv a head) and the two gates, all
float32; the least it must compute, from ``S <- alpha S; u = beta (v - S^T
k); S <- S + k u^T; o = S^T q``, is one multiply for the decay, a
multiply-add a state element for each of S^T k, k u^T and S^T q: 7 dk dv a
head a token. A slot that decodes nothing moves nothing.
"""

from typing import Tuple


def ops_and_bytes(n_seqs: int, n_heads: int, k_dim: int, v_dim: int,
                  state_bytes: int = 4, act_bytes: int = 4
                  ) -> Tuple[float, float]:
    state = n_heads * k_dim * v_dim
    flops = 7.0 * n_seqs * state
    moved = n_seqs * (2.0 * state * state_bytes
                      + n_heads * (2 * k_dim + 2 * v_dim + 2) * act_bytes)
    return flops, moved
