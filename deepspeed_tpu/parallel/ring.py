"""Ring attention (context parallelism) via collective-permute.

The reference has NO ring/blockwise context parallelism (SURVEY.md §2.2:
Ulysses is its only long-context mechanism) — this is a beyond-parity
capability. Blockwise attention with online softmax: K/V shards rotate
around the ``seq`` mesh axis with ``jax.lax.ppermute`` (riding the ICI
ring) while each device keeps its query shard resident, so sequence length
scales with the number of devices without ever materializing full-sequence
K/V — and without Ulysses' n_heads % P constraint.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _block_attn(q, k, v, q_off, k_off, causal, scale):
    """Partial attention of a q block vs one k/v block with global-position
    causal masking. Returns (unnormalized out, running max m, running sum l).
    q: [b, sq, h, d] k/v: [b, sk, h, d]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_off + jnp.arange(q.shape[1])
        k_pos = k_off + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                          # [b, h, q]
    # guard fully-masked rows (no valid key yet): exp(-inf - -inf) -> nan
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])               # [b, h, q, k]
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)                               # [b, h, q]
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(jnp.float32), m_safe, l


def _combine(acc_out, acc_m, acc_l, out, m, l):
    """Online-softmax merge of two partial attention results."""
    new_m = jnp.maximum(acc_m, m)
    a = jnp.exp(acc_m - new_m)
    b = jnp.exp(m - new_m)
    new_l = acc_l * a + l * b
    new_out = acc_out * a.transpose(0, 2, 1)[..., None] + out * b.transpose(0, 2, 1)[..., None]
    return new_out, new_m, new_l


def ring_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                   scale: Optional[float] = None):
    """Call INSIDE shard_map. q/k/v: local shards [b, s/P, h, d] where the
    global sequence is contiguously sharded over ``axis_name``."""
    P_ = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if k.shape[2] != h:  # GQA: broadcast kv heads once, locally
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)

    q_off = my * s_local
    acc_out = jnp.zeros((b, s_local, h, d), jnp.float32)
    acc_m = jnp.full((b, h, s_local), -jnp.inf, jnp.float32)
    acc_l = jnp.zeros((b, h, s_local), jnp.float32)
    perm = [(i, (i + 1) % P_) for i in range(P_)]

    def body(i, carry):
        acc_out, acc_m, acc_l, kk, vv = carry
        src = (my - i) % P_          # which shard currently holds
        k_off = src * s_local
        out, m, l = _block_attn(q, kk, vv, q_off, k_off, causal, scale)
        # first block initializes the accumulator (acc_m = -inf everywhere)
        acc_out, acc_m, acc_l = _combine(acc_out, acc_m, acc_l, out, m, l)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return acc_out, acc_m, acc_l, kk, vv

    acc_out, acc_m, acc_l, _, _ = jax.lax.fori_loop(
        0, P_, body, (acc_out, acc_m, acc_l, k, v))
    denom = jnp.maximum(acc_l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc_out / denom).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, axis_name: str = "seq",
                           causal: bool = True, batch_axes=None,
                           head_axes=None):
    """Global-array wrapper: q/k/v [b, s, h, d] sharded over ``axis_name``
    on the seq dim; runs ring attention under shard_map. ``batch_axes`` /
    ``head_axes`` must name the activations' existing batch/head sharding
    so the shard_map boundary doesn't force a replicate-then-reshard."""
    spec = P(batch_axes, axis_name, head_axes, None)

    def inner(q, k, v):
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

    return jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)(q, k, v)
