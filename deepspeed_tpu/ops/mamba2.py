"""Mamba-2 state-space mixer ("Transformers are SSMs", Dao and Gu 2024), as
``transformers``' ``GraniteMoeHybridMambaLayer`` has it: the token mixer of
a ``"mamba"`` layer, once.

For token ``t``, input ``u_t`` (``K`` = ``mamba_d_conv``, ``H`` heads of
``P`` channels, ``G`` groups of ``N`` state channels)::

    [z, xBC, dt~] = u W_in                       z [H P], xBC [H P + 2 G N], dt~ [H]
    xBC = silu(conv_K(xBC) + b_conv)             causal, depthwise
    [x, B, C] = xBC                              x [H, P]; B, C [G, N], a group's shared by H / G heads
    dt = softplus(dt~ + dt_bias);  a = exp(-exp(A_log) dt)          a head
    S <- a S + (dt x) B^T;  y = S C + D x        S [P, N] a head, float32
    o = (rmsnorm_w(y * silu(z)) over all H P channels) W_out

The recurrence exists in three forms over the same mathematics:

* :func:`ssd_step`: one token a row, batched, in XLA: elementwise products
  and sums in float32. Off the TPU it is what the ragged step runs, over a
  period's whole run of slots (a slot without a run of one lane keeps its
  state); everywhere it is what :func:`ssd_recurrent` scans and the oracle
  of the kernel below;
* :func:`ssd_chunk`: a piece of one sequence at once, the SSD form (the
  masked ``(C B^T) * decay`` product inside the piece, the state's part
  beside it), from a state and leaving one behind;
* :func:`ssd_recurrent`: ``lax.scan`` of the step over tokens, the oracle
  the tests hold the other two to.

:func:`mix_dense` ([b, s, d], what ``Transformer.apply`` runs) scans pieces
of ``mamba_chunk`` tokens; :func:`mix_ragged` (a flat batch of lanes from
many sequences, what ``RaggedInferenceEngine``'s step runs) gives each
single-lane run the step and cuts longer runs into pieces, reading the
slot's state and convolution rows from the pool leaves before a run and
leaving them behind after it. The runs and the convolution over them are
``ops/gated_delta.py``'s (:func:`~.gated_delta.runs_of`,
:func:`~.gated_delta.conv_ragged`): one schedule for both recurrent kinds.

On the TPU the step is a Pallas call a layer,
``ops/pallas/mamba2.py::ssd_step_slots``: the same float32 products over
the slots that decode one token this step and no others, each slot's state
read once and written once where it lies in the leaf (a rolled stack's
too: the period's ``base`` is a scalar the kernel's index map adds), the
leaf aliased in and out. Which form :func:`mix_ragged` takes is the
engine's ``attention_path``, handed down as ``ops/gated_delta.py`` takes
it: ``pallas`` (the chip) and ``pallas_interpret`` (CPU tests) the kernel,
``gather`` the XLA form; the chunk loop behind it is the same on every
path.

Everything between the input projection and the output product is float32;
the state products ask for full float32 passes on the MXU
(``Precision.HIGHEST``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .gated_delta import Runs, conv_ragged

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# the recurrence
def ssd_step(x, B, C, dt, g, D, state):
    """One token a row. x [..., H, P]; B, C [..., H, N] (a group's, a copy
    a head); dt, g (log a) [..., H]; D [H]; state [..., H, P, N], all
    float32. Returns (y [..., H, P], state): each state is read once and
    written once, and nothing is rounded below float32."""
    new = jnp.exp(g)[..., None, None] * state \
        + (dt[..., None] * x)[..., None] * B[..., None, :]
    return jnp.sum(new * C[..., None, :], -1) + D[:, None] * x, new


def ssd_recurrent(x, B, C, dt, g, D, state):
    """The token recurrence over [s, ...] from ``state``: (y [s, H, P],
    state)."""
    def one(s, xs):
        y, s = ssd_step(*xs, D, s)
        return s, y

    state, y = jax.lax.scan(one, state, (x, B, C, dt, g))
    return y, state


def ssd_chunk(x, B, C, dt, g, D, state):
    """One piece of one sequence in the SSD form. x [L, H, P]; B, C
    [L, G, N] (groups as they come: one ``C B^T`` a group serves its
    heads); dt, g [L, H]; D [H]; state [H, P, N]. A lane that is not live
    comes with dt = g = 0 and then changes nothing. Returns (y [L, H, P],
    state)."""
    L, H, P = x.shape
    n_groups, N = B.shape[1:]
    rep = H // n_groups
    cum = jnp.cumsum(g, 0)                                # [L, H]
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    ct = cum.T                                            # [H, L]
    decay = jnp.where(i >= j,
                      jnp.exp(jnp.where(i >= j, ct[:, :, None] - ct[:, None, :],
                                        0.0)), 0.0)       # a_(j+1) .. a_i
    cb = jnp.einsum("ign,jgn->gij", C, B, precision=HI)   # [G, L, L]
    xdt = (x * dt[..., None]).reshape(L, n_groups, rep, P)
    m = decay.reshape(n_groups, rep, L, L) * cb[:, None]
    y = jnp.einsum("grij,jgrp->igrp", m, xdt, precision=HI)
    s0 = state.reshape(n_groups, rep, P, N)
    y = y + jnp.exp(cum).reshape(L, n_groups, rep, 1) \
        * jnp.einsum("ign,grpn->igrp", C, s0, precision=HI)
    last = cum[-1]                                        # [H]
    kept = xdt * jnp.exp(last[None] - cum).reshape(L, n_groups, rep, 1)
    new = jnp.exp(last)[:, None, None] * state \
        + jnp.einsum("jgrp,jgn->grpn", kept, B,
                     precision=HI).reshape(H, P, N)
    return y.reshape(L, H, P) + D[:, None] * x, new


def ssd_chunked(x, B, C, dt, g, D, state, chunk: int):
    """A whole sequence [s, ...] from ``state``, a scan over its pieces of
    ``chunk`` tokens (the tail padded with lanes that are not live)."""
    s = x.shape[0]
    pad = (-s) % chunk
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) \
        .reshape((-1, chunk) + a.shape[1:])

    def one(st, xs):
        y, st = ssd_chunk(*xs, D, st)
        return st, y

    state, y = jax.lax.scan(one, state, tuple(map(cut, (x, B, C, dt, g))))
    return y.reshape((-1,) + y.shape[2:])[:s], state


# ----------------------------------------------------------------------
# projection, gates, split, output: shared by both layouts
def _dims(c) -> Tuple[int, int, int, int]:
    return c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups, c.mamba_d_state


def conv_channels(c) -> int:
    H, P, n_groups, N = _dims(c)
    return H * P + 2 * n_groups * N


def state_shapes(c) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Per sequence: (state [H, P, N] float32, convolution rows
    [K - 1, channels] in the compute type)."""
    H, P, _, N = _dims(c)
    return (H, P, N), (c.mamba_d_conv - 1, conv_channels(c))


def _project(u, lp, c):
    """u [..., d] -> (output gate z [..., H P], pre-convolution x|B|C
    [..., channels], dt [..., H] and log a [..., H], float32)."""
    H, P = _dims(c)[:2]
    ch = conv_channels(c)
    zxd = u @ lp["w_in"]
    dt = jax.nn.softplus(zxd[..., H * P + ch:].astype(F32)
                         + lp["dt_bias"].astype(F32))
    return zxd[..., :H * P], zxd[..., H * P:H * P + ch], dt, \
        -jnp.exp(lp["A_log"].astype(F32)) * dt


def _split(xBC, c):
    """Convolved x|B|C with its bias (float32) -> silu, heads and groups:
    x [..., H, P]; B, C [..., G, N]."""
    H, P, n_groups, N = _dims(c)
    xBC = jax.nn.silu(xBC)
    lead = xBC.shape[:-1]
    return (xBC[..., :H * P].reshape(lead + (H, P)),
            xBC[..., H * P:H * P + n_groups * N].reshape(lead + (n_groups, N)),
            xBC[..., H * P + n_groups * N:].reshape(lead + (n_groups, N)))


def _output(y, z, lp, c, dtype):
    """y [..., H, P] float32, z [..., H P] -> o [..., d]: the gate first,
    then one RMSNorm over all H P channels."""
    y = y.reshape(y.shape[:-2] + (-1,)) * jax.nn.silu(z.astype(F32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + c.norm_eps)
    return (y.astype(dtype) * lp["ssm_norm_w"].astype(dtype)) @ lp["w_out"]


def mix_dense(u, lp: Dict[str, Any], c):
    """u [b, s, d] -> o [b, s, d]: every sequence from a zero state."""
    K = c.mamba_d_conv
    z, xBC, dt, g = _project(u, lp, c)
    with jax.named_scope("conv"):
        w = lp["conv_w"].astype(F32)                      # [K, channels]
        s = u.shape[1]
        padded = jnp.pad(xBC.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
        xBC = sum(w[i] * padded[:, i:i + s] for i in range(K)) \
            + lp["conv_b"].astype(F32)
    x, B, C = _split(xBC, c)
    D = lp["D"].astype(F32)
    with jax.named_scope("ssd_chunk"):
        zeros = jnp.zeros((u.shape[0],) + state_shapes(c)[0], F32)
        y, _ = jax.vmap(lambda *a: ssd_chunked(*a[:5], D, a[5],
                                               c.mamba_chunk))(
            x, B, C, dt, g, zeros)
    return _output(y, z, lp, c, u.dtype)


# ----------------------------------------------------------------------
# the ragged layout
def piece_lanes(c, lanes: int) -> int:
    """Lanes a piece of the chunked form covers in a step of ``lanes``:
    ``mamba_chunk``, and no more than the step has."""
    return min(c.mamba_chunk, lanes)


def ssd_ragged(x, B, C, dt, g, D, state, runs: Runs, chunk: int, base=None,
               path: str = "gather"):
    """The recurrence over lanes. x [T, H, P]; B, C [T, G, N]; dt, g
    [T, H]; state [S + 1, H, P, N] float32; ``runs`` cut into pieces of
    ``chunk`` lanes. Under a rolled stack the leaf holds a run of S + 1
    slots a period and ``base`` (traced) is where this period's starts: the
    leaf is read and written in place there. ``path`` is the engine's
    ``attention_path``: which form the runs of one lane take (module
    docstring). Returns (y [T, H, P], state)."""
    T, H = x.shape[:2]
    with jax.named_scope("ssd_step"):
        if path == "gather":
            # the step in XLA, a slot a row, over the period's whole run of
            # slots (a slot without a run of one lane keeps its state)
            rep = H // B.shape[1]
            one = runs.length == 1
            lane = jnp.minimum(runs.first, T - 1)
            own = state if base is None else jax.lax.dynamic_slice_in_dim(
                state, base, one.shape[0], 0)
            old = jnp.where((one & runs.slot_fresh)[:, None, None, None],
                            0.0, own)
            heads = lambda a: jnp.repeat(a[lane], rep, axis=1)
            y1, new = ssd_step(x[lane], heads(B), heads(C), dt[lane],
                               g[lane], D, old)
            own = jnp.where(one[:, None, None, None], new, own)
            state = own if base is None else \
                jax.lax.dynamic_update_slice_in_dim(state, own, base, 0)
        else:
            # the kernel, over the slots that have such a run alone, where
            # they lie in the leaf
            from .pallas.mamba2 import ssd_step_slots

            lane = runs.steps[1]
            one = jnp.arange(lane.shape[0]) < runs.steps[3]
            y1, state = ssd_step_slots(
                x[lane], B[lane], C[lane], dt[lane], g[lane], D, state,
                runs.steps, base, interpret=path == "pallas_interpret")
        # lanes ``chunk`` past the end take the pieces' overhang and the
        # rows of slots (the kernel's: of entries) that decode nothing
        out = jnp.zeros((T + chunk,) + y1.shape[1:], F32) \
            .at[jnp.where(one, lane, T)].set(y1)
    with jax.named_scope("ssd_chunk"):
        pad = lambda a: jnp.pad(a, ((0, chunk),) + ((0, 0),) * (a.ndim - 1))
        xp, Bp, Cp, dp, gp = map(pad, (x, B, C, dt, g))
        cut = lambda a, at: jax.lax.dynamic_slice_in_dim(a, at, chunk, 0)

        def piece(carry):
            p, state, out = carry
            slot, at = runs.piece_slot[p], runs.piece_at[p]
            live = jnp.arange(chunk) < runs.piece_n[p]
            gate = lambda a: jnp.where(live[:, None], cut(a, at), 0.0)
            held = slot if base is None else slot + base
            s0 = jnp.where(runs.piece_first[p] & runs.slot_fresh[slot], 0.0,
                           state[held])
            y, s1 = ssd_chunk(cut(xp, at), cut(Bp, at), cut(Cp, at),
                              gate(dp), gate(gp), D, s0)
            y = jnp.where(live[:, None, None], y, cut(out, at))
            return (p + 1, state.at[held].set(s1),
                    jax.lax.dynamic_update_slice_in_dim(out, y, at, 0))

        _, state, out = jax.lax.while_loop(
            lambda carry: carry[0] < runs.n_pieces, piece,
            (jnp.zeros((), jnp.int32), state, out))
    return out[:T], state


def mix_ragged(u, lp: Dict[str, Any], c, state, rows, runs: Runs, base=None,
               path: str = "gather"):
    """u [T, d] -> (o [T, d], state, rows): the lanes of one step through
    one Mamba layer and its two pool leaves. ``runs`` is
    ``gated_delta.runs_of(..., chunk=piece_lanes(c, T))``; ``base`` and
    ``path`` as :func:`ssd_ragged` takes them."""
    z, xBC, dt, g = _project(u, lp, c)
    with jax.named_scope("conv"):
        xBC, rows = conv_ragged(xBC.astype(F32), lp["conv_w"].astype(F32),
                                rows, runs, lp["conv_b"].astype(F32), base)
    x, B, C = _split(xBC, c)
    y, state = ssd_ragged(x, B, C, dt, g, lp["D"].astype(F32), state, runs,
                          piece_lanes(c, u.shape[0]), base, path)
    return _output(y, z, lp, c, u.dtype), state, rows
