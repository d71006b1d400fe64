"""Ulysses-style sequence parallelism.

Capability parity with the reference's DeepSpeed-Ulysses
(``deepspeed/sequence/layer.py`` — ``DistributedAttention`` wrapping any
local attention with ``_SeqAllToAll``: inputs sharded ``[s/P, b, h]`` are
all-to-all'd to ``[s, b, h/P]`` so attention runs with full sequence but
sharded heads, then transformed back; SURVEY.md §5.7). TPU-native form:
the all-to-all rides the ``seq`` mesh axis via ``jax.lax.all_to_all``
inside ``shard_map``, composing with the batch sharding the engine already
applies ([b/data, s/seq, ...]).

The reference's ``seq_parallel_communication_data_type`` knob
(runtime/config.py:795) maps to ``comm_dtype`` below.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import dot_product_attention


def _a2a(x, axis_name: str, split_axis: int, concat_axis: int):
    """tiled all-to-all: scatter ``split_axis``, gather ``concat_axis``."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ulysses_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                      attn_fn: Optional[Callable] = None, comm_dtype=None):
    """Head-scattering attention for seq-sharded inputs.

    Call INSIDE shard_map where q/k/v are local shards [b, s/P, h, d].
    All-to-all swaps seq-sharding for head-sharding ([b, s, h/P, d]),
    runs full-sequence attention on the local heads, and swaps back.
    Requires n_heads % P == 0 (same constraint as the reference,
    sequence/layer.py head-count divisibility).
    """
    attn_fn = attn_fn or partial(dot_product_attention, causal=causal)
    orig_dtype = q.dtype
    if comm_dtype is not None:
        q, k, v = (t.astype(comm_dtype) for t in (q, k, v))
    # GQA: when the local kv-head count doesn't divide the seq axis (e.g.
    # TP already sharded kv heads down to 1), repeat each kv head just
    # enough to scatter — numerics-identical, it's the GQA broadcast done
    # before the a2a instead of inside attention (reference Ulysses does
    # the same for GQA models, sequence/layer.py head-repeat path)
    P_ = jax.lax.axis_size(axis_name)
    kvh = k.shape[2]
    if kvh % P_ != 0:
        r = P_ // math.gcd(kvh, P_)
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    # [b, s/P, h, d] -> [b, s, h/P, d]
    q, k, v = (_a2a(t, axis_name, split_axis=2, concat_axis=1) for t in (q, k, v))
    if comm_dtype is not None:
        q, k, v = (t.astype(orig_dtype) for t in (q, k, v))
    out = attn_fn(q, k, v)
    if comm_dtype is not None:
        out = out.astype(comm_dtype)
    # [b, s, h/P, d] -> [b, s/P, h, d]
    out = _a2a(out, axis_name, split_axis=1, concat_axis=2)
    return out.astype(orig_dtype)


class DistributedAttention:
    """Module-level parity with the reference's
    ``deepspeed.sequence.layer.DistributedAttention`` (layer.py:61): wraps a
    local attention callable; __call__ takes seq-sharded global arrays and
    runs the a2a dance under shard_map on the given mesh."""

    def __init__(self, local_attention: Callable, mesh: Mesh,
                 scatter_idx: int = 2, gather_idx: int = 1,
                 axis_name: str = "seq", comm_dtype=None,
                 batch_axes=None, head_axes=None):
        self.local_attn = local_attention
        self.mesh = mesh
        self.axis_name = axis_name
        self.comm_dtype = comm_dtype
        # batch/head axes must NAME the activations' existing sharding
        # (batch over the data axes, heads over 'model' under TP) — a spec
        # of None on a sharded dim forces GSPMD to replicate-then-reshard
        # at the shard_map boundary ("involuntary full rematerialization")
        self.batch_axes = batch_axes
        self.head_axes = head_axes
        # scatter/gather idx kept for API parity; fixed [b, s, h, d] layout

    def __call__(self, q, k, v, causal: bool = True):
        spec = P(self.batch_axes, self.axis_name, self.head_axes, None)

        def inner(q, k, v):
            return ulysses_attention(
                q, k, v, axis_name=self.axis_name, causal=causal,
                attn_fn=partial(self.local_attn, causal=causal),
                comm_dtype=self.comm_dtype)

        return jax.shard_map(
            inner, mesh=self.mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)(q, k, v)
