"""Gated delta-rule linear attention ("Gated Delta Networks", Yang, Kautz,
Hatamizadeh 2024): the token mixer of a recurrent layer, once.

For one head, token ``t``, input ``x_t`` (``K`` = ``conv_kernel``)::

    q~, k~, v~ = x W_q, x W_k, x W_v
    q, k, v = silu(conv_K(q~)), silu(conv_K(k~)), silu(conv_K(v~))   causal, depthwise
    q = l2norm(q) * dk^-0.5;  k = l2norm(k)
    beta = sigmoid(x W_b) (x 2 with neg_eigval);  log alpha = -exp(A_log) * softplus(x W_a + dt_bias)
    S <- alpha S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q      S [dk, dv], float32
    y = concat_heads(rmsnorm_dv(o) * silu(x W_z)) W_o

The recurrence exists in three forms over the same mathematics:

* :func:`delta_step`: one token a sequence, batched (a decode lane), in
  XLA. Off the TPU it is what the ragged step runs, over the whole state
  leaf; everywhere it is what :func:`delta_recurrent` scans and the oracle
  of the kernel below;
* :func:`delta_chunk`: ``CHUNK`` tokens of one sequence at once, the WY form
  (the strictly-lower system ``(I + A) U = beta (V - exp(G) K S0)`` solved
  by one triangular solve), from a state and leaving one behind;
* :func:`delta_recurrent`: ``lax.scan`` of the step over tokens, the oracle
  the tests hold the other two to.

On the TPU the step is a Pallas call a layer,
``ops/pallas/gated_delta.py::delta_step_slots``: the same float32
products over the slots that decode one token this step and no others,
each slot's state read once and written once, the leaf aliased in and out.

:func:`mix_dense` ([b, s, d], what ``Transformer.apply`` and training run)
scans chunks; :func:`mix_ragged` (a flat batch of lanes from many sequences,
what ``RaggedInferenceEngine``'s step runs) gives each single-lane run the
step and cuts longer runs into chunk-sized pieces, reading the slot's state
and convolution rows from the pool leaves before a run and leaving them
behind after it. Both call the same projections, gates and output. Which
form of the step :func:`mix_ragged` takes is the engine's
``attention_path``, handed down: ``pallas`` (the chip) and
``pallas_interpret`` (CPU tests) the kernel, ``gather`` the XLA form; the
chunk loop behind it is the same on every path.

Everything between the projections and the output product is float32; the
state products that decide the answer ask for full float32 passes on the
MXU (``Precision.HIGHEST``), which costs nothing at these sizes.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: tokens a piece of the chunked form covers
CHUNK = 64


def l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


# ----------------------------------------------------------------------
# the recurrence
def delta_step(q, k, v, g, beta, state):
    """One token a row. q, k [..., H, dk]; v [..., H, dv]; g (log alpha),
    beta [..., H]; state [..., H, dk, dv], all float32. Returns (o, state).
    Elementwise products and sums: each state is read twice and written
    once, and nothing is rounded below float32."""
    a = jnp.exp(g)[..., None]
    ks = jnp.sum(k[..., None] * state, -2) * a            # (alpha S)^T k
    qs = jnp.sum(q[..., None] * state, -2) * a
    u = beta[..., None] * (v - ks)
    new = a[..., None] * state + k[..., None] * u[..., None, :]
    return qs + jnp.sum(q * k, -1, keepdims=True) * u, new


def delta_recurrent(q, k, v, g, beta, state):
    """The token recurrence over [s, H, *] from ``state``: (o [s, H, dv],
    state)."""
    def one(s, x):
        o, s = delta_step(*x, s)
        return s, o

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    return o, state


def delta_chunk(q, k, v, g, beta, state):
    """One piece of one sequence in the WY form. q, k [C, H, dk]; v
    [C, H, dv]; g, beta [C, H]; state [H, dk, dv]. A lane that is not live
    comes with g = beta = 0 and k = 0 and then changes nothing. Returns
    (o [C, H, dv], state)."""
    C = q.shape[0]
    G = jnp.cumsum(g, 0).T                                # [H, C]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    decay = jnp.where(i >= j,
                      jnp.exp(jnp.where(i >= j, G[:, :, None] - G[:, None, :],
                                        0.0)), 0.0)       # exp(G_i - G_j)
    bT = beta.T                                           # [H, C]
    kk = jnp.einsum("ihd,jhd->hij", k, k, precision=HI)
    A = jnp.where(i > j, bT[:, :, None] * decay * kk, 0.0)
    kh, vh, qh = (jnp.swapaxes(a, 0, 1) for a in (k, v, q))   # [H, C, *]
    rhs = jnp.concatenate([bT[..., None] * vh,
                           (bT * jnp.exp(G))[..., None] * kh], -1)
    sol = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    dv = v.shape[-1]
    u = sol[..., :dv] - jnp.einsum("hck,hkv->hcv", sol[..., dv:], state,
                                   precision=HI)
    qk = jnp.einsum("ihd,jhd->hij", q, k, precision=HI) * decay
    o = jnp.exp(G)[..., None] * jnp.einsum("hck,hkv->hcv", qh, state,
                                           precision=HI) \
        + jnp.einsum("hij,hjv->hiv", qk, u, precision=HI)
    last = G[:, -1]                                       # [H]
    kd = kh * jnp.exp(last[:, None] - G)[..., None]
    new = jnp.exp(last)[:, None, None] * state \
        + jnp.einsum("hck,hcv->hkv", kd, u, precision=HI)
    return jnp.swapaxes(o, 0, 1), new


def delta_chunked(q, k, v, g, beta, state):
    """A whole sequence [s, H, *] from ``state``, a scan over its chunks
    (the tail padded with lanes that are not live)."""
    s = q.shape[0]
    pad = (-s) % CHUNK
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) \
        .reshape((-1, CHUNK) + a.shape[1:])

    def one(st, x):
        o, st = delta_chunk(*x, st)
        return st, o

    state, o = jax.lax.scan(one, state, tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape((-1,) + o.shape[2:])[:s], state


# ----------------------------------------------------------------------
# projections, gates, convolution, output: shared by both layouts
def _dims(c) -> Tuple[int, int, int, int]:
    return (c.linear_n_k_heads, c.linear_n_v_heads, c.linear_k_dim,
            c.linear_v_dim)


def state_shapes(c) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Per sequence: (recurrent state [Hv, dk, dv] float32, convolution
    rows [K - 1, channels] in the compute type)."""
    hk, hv, dk, dv = _dims(c)
    return (hv, dk, dv), (c.linear_conv_kernel - 1, 2 * hk * dk + hv * dv)


def _project(x, lp, c):
    """x [..., d] -> (pre-convolution q|k|v [..., channels], log alpha
    [..., Hv], beta [..., Hv], output gate [..., Hv * dv])."""
    qkv = jnp.concatenate([x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]], -1)
    g = -jnp.exp(lp["A_log"].astype(F32)) * jax.nn.softplus(
        (x @ lp["w_a"]).astype(F32) + lp["dt_bias"].astype(F32))
    beta = jax.nn.sigmoid((x @ lp["w_beta"]).astype(F32))
    if c.linear_neg_eigval:
        beta = 2.0 * beta
    return qkv, g, beta, x @ lp["w_z"]


def _heads(qkv, c):
    """Convolved q|k|v (float32) -> silu, heads, norms: q, k [..., Hv, dk],
    v [..., Hv, dv]; fewer key heads than value heads are repeated."""
    hk, hv, dk, dv = _dims(c)
    qkv = jax.nn.silu(qkv)
    lead = qkv.shape[:-1]
    q = qkv[..., :hk * dk].reshape(lead + (hk, dk))
    k = qkv[..., hk * dk:2 * hk * dk].reshape(lead + (hk, dk))
    v = qkv[..., 2 * hk * dk:].reshape(lead + (hv, dv))
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    if hv != hk:
        q, k = (jnp.repeat(a, hv // hk, axis=-2) for a in (q, k))
    return q, k, v


def _output(o, z, lp, c, dtype):
    """o [..., Hv, dv] float32, z [..., Hv * dv] -> y [..., d]."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.norm_eps) \
        * lp["o_norm_w"].astype(F32)
    o = o * jax.nn.silu(z.astype(F32)).reshape(o.shape)
    return o.reshape(o.shape[:-2] + (-1,)).astype(dtype) @ lp["wo"]


def mix_dense(x, lp: Dict[str, Any], c):
    """x [b, s, d] -> y [b, s, d]: every sequence from a zero state."""
    K = c.linear_conv_kernel
    hv, dk, dv = state_shapes(c)[0]
    qkv, g, beta, z = _project(x, lp, c)
    with jax.named_scope("conv"):
        w = lp["conv_w"].astype(F32)                      # [K, channels]
        s = x.shape[1]
        padded = jnp.pad(qkv.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
        qkv = sum(w[i] * padded[:, i:i + s] for i in range(K))
    q, k, v = _heads(qkv, c)
    with jax.named_scope("delta_chunk"):
        o, _ = jax.vmap(delta_chunked)(
            q, k, v, g, beta, jnp.zeros((x.shape[0], hv, dk, dv), F32))
    return _output(o, z, lp, c, x.dtype)


# ----------------------------------------------------------------------
# the ragged layout
class Runs(NamedTuple):
    """What one step's lanes say of its runs (a sequence's lanes are
    adjacent, and no sequence has two runs), the same for every layer.
    Slot ``n_slots`` is the sink of lanes that belong to no sequence."""

    slot: Any        # [T] the lane's slot, the sink for a lane not live
    off: Any         # [T] the lane's index inside its run
    fresh: Any       # [T] its run starts a sequence (position 0)
    first: Any       # [S + 1] a slot's first lane (T where it has none)
    last: Any        # [S + 1] its last lane (0 where it has none)
    length: Any      # [S + 1] lanes of its run; the sink's 0
    slot_fresh: Any  # [S + 1] its run starts at position 0
    n_pieces: Any    # [] pieces of the runs longer than one lane
    piece_slot: Any  # [P] each piece's slot
    piece_at: Any    # [P] its first lane
    piece_n: Any     # [P] its live lanes (1..chunk)
    piece_first: Any  # [P] it is its run's first piece
    steps: Any       # [4, N] the runs of one lane, live entries first: an
    #                  entry's slot, its lane, whether its run starts a
    #                  sequence, and (every column) the count of live
    #                  entries; an entry past it names the last live slot
    #                  again (the sink where none is live). N = min(S, T)


def runs_of(slots, positions, n_slots: int, chunk: int = CHUNK) -> Runs:
    """slots [T] (-1 = not live), positions [T] -> :class:`Runs`, the runs
    longer than one lane cut into pieces of ``chunk`` lanes (the delta
    rule's ``CHUNK``; ``ops/mamba2.py`` asks for its own)."""
    T = slots.shape[0]
    lane = jnp.arange(T, dtype=jnp.int32)
    slot = jnp.where(slots >= 0, slots, n_slots).astype(jnp.int32)
    start = jnp.concatenate([jnp.ones((1,), bool), slot[1:] != slot[:-1]])
    begin = jax.lax.cummax(jnp.where(start, lane, 0))
    off = lane - begin
    fresh = positions[begin] == 0
    first = jnp.full((n_slots + 1,), T, jnp.int32).at[slot].min(lane)
    last = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].max(lane)
    live = (first < T) & (jnp.arange(n_slots + 1) < n_slots)
    length = jnp.where(live, last - first + 1, 0)
    slot_fresh = positions[jnp.minimum(first, T - 1)] == 0
    # pieces: every run longer than one lane, cut at multiples of chunk
    per = jnp.where(length > 1, -(-length // chunk), 0)
    ends = jnp.cumsum(per)
    P = T // chunk + n_slots
    p = jnp.arange(P, dtype=jnp.int32)
    piece_slot = jnp.minimum(jnp.searchsorted(ends, p, side="right"),
                             n_slots).astype(jnp.int32)
    nth = p - (ends - per)[piece_slot]
    piece_at = first[piece_slot] + nth * chunk
    piece_n = jnp.clip(length[piece_slot] - nth * chunk, 0, chunk)
    # the single-lane runs, compacted in slot order (all compares at once,
    # as ``work_list``): what the step kernel's grid walks
    count = jnp.cumsum((length == 1).astype(jnp.int32))
    n = count[-1]
    e = jnp.arange(min(n_slots, T), dtype=jnp.int32)
    step_slot = jnp.searchsorted(count, jnp.minimum(e, n - 1) + 1,
                                 side="left", method="compare_all")
    step_slot = jnp.where(n > 0, step_slot, n_slots).astype(jnp.int32)
    steps = jnp.stack([step_slot, jnp.minimum(first[step_slot], T - 1),
                       slot_fresh[step_slot].astype(jnp.int32),
                       jnp.broadcast_to(n, e.shape)])
    return Runs(slot, off, fresh, first, last, length, slot_fresh, ends[-1],
                piece_slot, piece_at, piece_n, nth == 0, steps)


def conv_ragged(x, w, rows, runs: Runs, bias=None, base=None):
    """Causal depthwise convolution over lanes. x [T, ch] float32; w
    [K, ch]; rows [S + 1, K - 1, ch]: each slot's last K - 1 inputs, oldest
    first; ``bias`` [ch] where the layer has one (Mamba-2's). A lane nearer
    than a tap to its run's start reads the slot's rows (zeros where the
    run starts a sequence). Under a rolled stack the leaf holds a run of
    S + 1 slots a period and ``base`` (traced) is where this period's
    starts. Returns (y [T, ch], rows with every run's last K - 1 inputs
    left behind)."""
    K = w.shape[0]
    slot = runs.slot if base is None else runs.slot + base
    taps = [x]
    for j in range(1, K):
        kept = rows[slot, jnp.clip(K - 1 - j + runs.off, 0, K - 2)]
        kept = jnp.where(runs.fresh[:, None], 0.0, kept.astype(F32))
        taps.append(jnp.where((runs.off >= j)[:, None],
                              jnp.roll(x, j, axis=0), kept))
    y = sum(w[K - 1 - j] * taps[j] for j in range(K))
    if bias is not None:
        y = y + bias
    left = jnp.stack([taps[j][runs.last] for j in range(K - 2, -1, -1)], 1)
    live = (runs.length > 0)[:, None, None]
    if base is None:
        return y, jnp.where(live, left.astype(rows.dtype), rows)
    own = jax.lax.dynamic_slice_in_dim(rows, base, left.shape[0], 0)
    return y, jax.lax.dynamic_update_slice_in_dim(
        rows, jnp.where(live, left.astype(rows.dtype), own), base, 0)


def delta_ragged(q, k, v, g, beta, state, runs: Runs, path: str = "gather"):
    """The recurrence over lanes. q, k [T, H, dk]; v [T, H, dv]; g, beta
    [T, H]; state [S + 1, H, dk, dv] float32. ``path`` is the engine's
    ``attention_path``: which form the runs of one lane take (module
    docstring). Returns (o [T, H, dv], state)."""
    T = q.shape[0]
    with jax.named_scope("delta_step"):
        if path == "gather":
            # the step in XLA, a slot a row, over the whole leaf (a slot
            # without a run of one lane keeps its state)
            one = runs.length == 1
            lane = jnp.minimum(runs.first, T - 1)
            old = jnp.where((one & runs.slot_fresh)[:, None, None, None], 0.0,
                            state)
            o1, new = delta_step(q[lane], k[lane], v[lane], g[lane],
                                 beta[lane], old)
            state = jnp.where(one[:, None, None, None], new, state)
        else:
            # the kernel, over the slots that have such a run alone
            from .pallas.gated_delta import delta_step_slots

            lane = runs.steps[1]
            one = jnp.arange(lane.shape[0]) < runs.steps[3]
            o1, state = delta_step_slots(
                q[lane], k[lane], v[lane], g[lane], beta[lane], state,
                runs.steps, interpret=path == "pallas_interpret")
        # lanes CHUNK past the end take the pieces' overhang and the rows
        # of slots (the kernel's: of entries) that decode nothing
        out = jnp.zeros((T + CHUNK,) + o1.shape[1:], F32) \
            .at[jnp.where(one, lane, T)].set(o1)
    with jax.named_scope("delta_chunk"):
        pad = lambda a: jnp.pad(a, ((0, CHUNK),) + ((0, 0),) * (a.ndim - 1))
        qp, kp, vp, gp, bp = map(pad, (q, k, v, g, beta))
        cut = lambda a, at: jax.lax.dynamic_slice_in_dim(a, at, CHUNK, 0)

        def piece(carry):
            p, state, out = carry
            slot, at = runs.piece_slot[p], runs.piece_at[p]
            live = jnp.arange(CHUNK) < runs.piece_n[p]
            gate = lambda a: jnp.where(
                live.reshape((-1,) + (1,) * (a.ndim - 1)), cut(a, at), 0.0)
            s0 = jnp.where(runs.piece_first[p] & runs.slot_fresh[slot], 0.0,
                           state[slot])
            o, s1 = delta_chunk(cut(qp, at), gate(kp), cut(vp, at), gate(gp),
                                gate(bp), s0)
            o = jnp.where(live[:, None, None], o, cut(out, at))
            return (p + 1, state.at[slot].set(s1),
                    jax.lax.dynamic_update_slice_in_dim(out, o, at, 0))

        _, state, out = jax.lax.while_loop(
            lambda carry: carry[0] < runs.n_pieces, piece,
            (jnp.zeros((), jnp.int32), state, out))
    return out[:T], state


def mix_ragged(x, lp: Dict[str, Any], c, state, rows, runs: Runs,
               path: str = "gather"):
    """x [T, d] -> (y [T, d], state, rows): the lanes of one step through
    one recurrent layer and its two pool leaves; ``path`` as
    :func:`delta_ragged` takes it."""
    qkv, g, beta, z = _project(x, lp, c)
    with jax.named_scope("conv"):
        qkv, rows = conv_ragged(qkv.astype(F32), lp["conv_w"].astype(F32),
                                rows, runs)
    q, k, v = _heads(qkv, c)
    o, state = delta_ragged(q, k, v, g, beta, state, runs, path)
    return _output(o, z, lp, c, x.dtype), state, rows
