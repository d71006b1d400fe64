"""Pluggable kernel backends for the compressed-collectives facade
(docs/communication.md, "Kernel backends").

The facade (``comm/compressed.py``) made ZeRO-3 collectives cheap on the
wire; this seam makes them cheap in TIME by fusing the compression
bracket into the adjacent matmul and moving overlap from per-layer
fill/drain windows to per-tile pipelining. A backend implements three
fused compute–collective entry points whose semantics are *defined* by
the :class:`XlaCollectiveBackend`'s unfused composition of facade ops —
the fused :class:`PallasFusedBackend` must be bit-exact to it at the
same ``QuantSpec`` (and to dense with compression off), which the
interpret-mode parity suite (tests/test_fused_collectives.py) and the
``run_tests.sh`` fused gate enforce:

* ``all_gather_matmul`` — ``h @ all_gather(w_shard, dim)``: the Pallas
  backend runs a ring, dequantize+multiplying tile *i*
  (:func:`~deepspeed_tpu.ops.pallas.fused_collectives.dequant_matmul`)
  while tile *i+1*'s shard is in flight (``ring_permute`` issued before
  the kernel consumes). Bit-exactness holds because the gather dim is a
  NON-contraction dim of the matmul — each tile is an independent
  column slice of the product, so no fp32 accumulation is reordered.
  Contraction-dim shards take the fallback.
* ``matmul_reduce_scatter`` — the grad-producing matmul whose epilogue
  blockwise-quantizes the wire payload in-kernel
  (:func:`~...fused_collectives.matmul_quantize`), feeding the same
  ``quantized_chunk_exchange`` the facade reduction uses.
* ``matmul_all_reduce`` — the serving-decode MLP down-projection: the
  partial matmul's epilogue produces the (optionally quantized) chunks
  of a deterministic rank-ordered chunked all-reduce
  (``chunked_all_reduce``), so the decode all-reduce stops being pure
  exposed latency after the matmul.

Everything that cannot fuse (contraction-dim gathers, non-2D operands,
indivisible blocks, hierarchical inner hops) delegates to the fallback
backend and is metered through the existing ``comm/facade/fallbacks``
counter; engaged fusions count under ``comm/facade/fused``. Ledger
note: the fused all-gather books the same per-collective summary row as
the facade (so wire-ratio joins work across backends) plus per-hop
``<op>_ring`` rows for the physical ring traffic — per-op totals remain
comparable, and nothing sums across the two op names.

Backends contain no raw ``jax.lax`` collectives — every wire-moving
step routes through ``comm.compressed`` (the dslint ``comm-facade``
rule covers these modules too).
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from ..ops.quantizer import pack_int4, quantize_blockwise
from . import compressed as cc
from .comm import record_collective


def _note_fused(op: str) -> None:
    from ..telemetry.registry import get_registry

    # trace-time static, like the facade's fallback counter: whether a
    # call fuses is a shape/config property of the traced program
    get_registry().counter("comm/facade/fused").inc()
    get_registry().counter(f"comm/facade/fused/{op}").inc()


class CollectiveBackend:
    """Protocol for the facade's kernel-backend seam. Subclasses must be
    usable inside a shard_map manual region (same contract as the facade
    functions they compose)."""

    name = "base"

    def all_gather_matmul(self, h: jnp.ndarray, w_shard: jnp.ndarray,
                          axis_name: str, *, dim: int = 1,
                          qspec: Optional[cc.QuantSpec] = None,
                          out_dtype=None, op: str = "qwz_all_gather",
                          stats: Optional[List[jnp.ndarray]] = None
                          ) -> jnp.ndarray:
        """``h [m, k] @ merge(all_gather(w_shard, dim))`` in fp32
        accumulation; ``dim`` is w's gathered dimension."""
        raise NotImplementedError

    def matmul_reduce_scatter(self, h: jnp.ndarray, g: jnp.ndarray, *,
                              outer_axis: str, outer_world: int,
                              inner_axis: Optional[str] = None,
                              inner_world: int = 1,
                              qspec: Optional[cc.QuantSpec] = None,
                              min_quant_size: int = 0,
                              stats: Optional[List[jnp.ndarray]] = None
                              ) -> jnp.ndarray:
        """Mean over the ZeRO group of the local weight gradient
        ``h.T @ g`` (``h [m, k]``, ``g [m, n]`` -> ``[k, n]``), moved
        through the hierarchical quantized reduction."""
        raise NotImplementedError

    def matmul_all_reduce(self, x: jnp.ndarray, w_shard: jnp.ndarray,
                          axis_name: str, *,
                          qspec: Optional[cc.QuantSpec] = None,
                          out_dtype=None,
                          op: str = "decode_mlp_all_reduce",
                          stats: Optional[List[jnp.ndarray]] = None
                          ) -> jnp.ndarray:
        """Sum over ``axis_name`` of the partial products
        ``x [m, k_shard] @ w_shard [k_shard, n]`` — the TP decode MLP
        down-projection — via the deterministic rank-ordered chunked
        all-reduce."""
        raise NotImplementedError


class XlaCollectiveBackend(CollectiveBackend):
    """The default backend: the unfused composition of facade collectives
    and XLA matmuls. This is the semantic REFERENCE for the seam — the
    parity suite asserts the fused backend against it bit-for-bit."""

    name = "xla"

    def all_gather_matmul(self, h, w_shard, axis_name, *, dim=1, qspec=None,
                          out_dtype=None, op="qwz_all_gather", stats=None):
        w_full = cc.quantized_all_gather(w_shard, axis_name, dim=dim,
                                         qspec=qspec, op=op, stats=stats)
        y = jax.lax.dot_general(h, w_full, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return y.astype(out_dtype or h.dtype)

    def matmul_reduce_scatter(self, h, g, *, outer_axis, outer_world,
                              inner_axis=None, inner_world=1, qspec=None,
                              min_quant_size=0, stats=None):
        dw = jax.lax.dot_general(h, g, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        out = cc.hierarchical_pmean(
            dw.reshape(-1), outer_axis=outer_axis, outer_world=outer_world,
            inner_axis=inner_axis, inner_world=inner_world, qspec=qspec,
            min_quant_size=min_quant_size, stats=stats)
        return out.reshape(dw.shape)

    def matmul_all_reduce(self, x, w_shard, axis_name, *, qspec=None,
                          out_dtype=None, op="decode_mlp_all_reduce",
                          stats=None):
        y = jax.lax.dot_general(x, w_shard, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = cc.chunked_all_reduce(y, axis_name, qspec=qspec, op=op,
                                    reduce="sum", stats=stats)
        return out.astype(out_dtype or x.dtype)


class PallasFusedBackend(CollectiveBackend):
    """Fused compute–collective kernels (ops/pallas/fused_collectives.py)
    where shapes allow, the unfused backend otherwise. ``interpret``
    runs the kernels in Pallas interpret mode (the CPU testing path,
    like ops/pallas/flash_attention.py)."""

    name = "pallas"

    def __init__(self, fallback: Optional[CollectiveBackend] = None,
                 interpret: bool = False):
        self.fallback = fallback or XlaCollectiveBackend()
        self.interpret = interpret

    # -- fusability predicates (shape/config properties, trace-static) --
    def _gather_fusable(self, h, w_shard, dim, world) -> bool:
        # dim == 1 keeps the gather on a NON-contraction dim of h @ w:
        # each arriving tile is an independent column slice of the
        # product, so the fp32 accumulation order matches the unfused
        # matmul bit-for-bit. A dim-0 (contraction) shard would split
        # the accumulation across tiles — not bit-exact — so it falls
        # back instead. Mixed-dtype operands fall back too: the XLA
        # reference feeds the weight at ITS dtype into the dot, and a
        # ring tile cast to h's dtype would silently diverge.
        return (world > 1 and h.ndim == 2 and w_shard.ndim == 2
                and dim == 1 and h.shape[1] == w_shard.shape[0]
                and h.dtype == w_shard.dtype)

    def all_gather_matmul(self, h, w_shard, axis_name, *, dim=1, qspec=None,
                          out_dtype=None, op="qwz_all_gather", stats=None):
        from ..ops.pallas.fused_collectives import (dequant_matmul,
                                                    matmul_pallas)
        world = jax.lax.axis_size(axis_name)
        if world <= 1:
            return self.fallback.all_gather_matmul(
                h, w_shard, axis_name, dim=dim, qspec=qspec,
                out_dtype=out_dtype, op=op, stats=stats)
        if not self._gather_fusable(h, w_shard, dim, world):
            # structural fusion fallback the facade itself won't meter
            cc._note_fallback(op)
            return self.fallback.all_gather_matmul(
                h, w_shard, axis_name, dim=dim, qspec=qspec,
                out_dtype=out_dtype, op=op, stats=stats)
        quantized = qspec is not None and qspec.divides(w_shard.size)
        if qspec is not None and not quantized:
            # indivisible shard: the facade's dense fallback meters this
            return self.fallback.all_gather_matmul(
                h, w_shard, axis_name, dim=dim, qspec=qspec,
                out_dtype=out_dtype, op=op, stats=stats)
        _note_fused(op)
        out_dtype = out_dtype or h.dtype
        m = h.shape[0]
        k, b = w_shard.shape
        logical = cc._nbytes(w_shard)
        me = jax.lax.axis_index(axis_name)
        out = jnp.zeros((m, world * b), jnp.float32)
        if quantized:
            # same per-collective summary row as the unfused facade, so
            # per-op ledger totals stay comparable across backends
            record_collective(op, logical, qspec.wire_nbytes(w_shard.size),
                              axis_name, world)
            flat = w_shard.reshape(-1).astype(jnp.float32)
            q, s, _ = quantize_blockwise(flat, bits=qspec.bits,
                                         block=qspec.block,
                                         manual_sharding=True)
            if stats is not None:
                from ..ops.quantizer import dequantize_blockwise

                deq = dequantize_blockwise(q, s, block=qspec.block,
                                           manual_sharding=True)
                stats.append(cc._rel_err(flat, deq))
            cur = (pack_int4(q) if qspec.bits == 4 else q, s)
        else:
            record_collective(op, logical, logical, axis_name, world)
            cur = (w_shard,)
        for step in range(world):
            nxt = None
            if step + 1 < world:
                # tile i+1's shard goes on the wire BEFORE tile i's
                # dequant+matmul kernel consumes anything — the per-tile
                # overlap the coarse block schedule cannot express
                nxt = tuple(
                    cc.ring_permute(t, axis_name, world=world,
                                    op=f"{op}_ring") for t in cur)
            if quantized:
                # dequantize at the shard's dtype — exactly what the
                # facade's merged w_full would carry into the matmul
                y = dequant_matmul(h, cur[0], cur[1], bits=qspec.bits,
                                   block=qspec.block, b=b,
                                   w_dtype=w_shard.dtype,
                                   interpret=self.interpret)
            else:
                # same dtype as h (checked by _gather_fusable) — exactly
                # the operand the XLA reference's dot consumes
                y = matmul_pallas(h, cur[0], interpret=self.interpret)
            r = jax.lax.rem(me - step + world, world)
            out = jax.lax.dynamic_update_slice(out, y, (0, r * b))
            cur = nxt
        return out.astype(out_dtype)

    def matmul_reduce_scatter(self, h, g, *, outer_axis, outer_world,
                              inner_axis=None, inner_world=1, qspec=None,
                              min_quant_size=0, stats=None):
        from ..ops.pallas.fused_collectives import matmul_quantize

        numel = h.shape[-1] * g.shape[-1] if h.ndim == 2 and g.ndim == 2 \
            else 0
        fusable = (h.ndim == 2 and g.ndim == 2 and h.shape[0] == g.shape[0]
                   and outer_world > 1 and qspec is not None
                   and inner_world <= 1
                   and numel >= max(min_quant_size, 1)
                   and qspec.divides(numel, outer_world))
        if not fusable:
            if (qspec is not None and inner_world > 1 and outer_world > 1
                    and h.ndim == 2 and g.ndim == 2):
                # hierarchical meshes keep the dense inner hop, which
                # must run BEFORE quantization — nothing to fuse into
                # the epilogue; the facade won't meter this itself
                cc._note_fallback("qgz_inter_reduce_scatter")
            return self.fallback.matmul_reduce_scatter(
                h, g, outer_axis=outer_axis, outer_world=outer_world,
                inner_axis=inner_axis, inner_world=inner_world, qspec=qspec,
                min_quant_size=min_quant_size, stats=stats)
        _note_fused("qgz_inter_reduce_scatter")
        payload, s = matmul_quantize(h, g, bits=qspec.bits,
                                     block=qspec.block, trans_a=True,
                                     interpret=self.interpret)
        out = cc.quantized_chunk_exchange(
            payload, s, n=numel, axis_name=outer_axis, world=outer_world,
            qspec=qspec, op_prefix="qgz_inter", reduce="mean", stats=stats)
        return out.reshape(h.shape[1], g.shape[1])

    def matmul_all_reduce(self, x, w_shard, axis_name, *, qspec=None,
                          out_dtype=None, op="decode_mlp_all_reduce",
                          stats=None):
        from ..ops.pallas.fused_collectives import (matmul_pallas,
                                                    matmul_quantize)
        world = jax.lax.axis_size(axis_name)
        if not (x.ndim == 2 and w_shard.ndim == 2
                and x.shape[1] == w_shard.shape[0]):
            cc._note_fallback(op)
            return self.fallback.matmul_all_reduce(
                x, w_shard, axis_name, qspec=qspec, out_dtype=out_dtype,
                op=op, stats=stats)
        out_dtype = out_dtype or x.dtype
        n = x.shape[0] * w_shard.shape[1]
        if (world > 1 and qspec is not None and qspec.divides(n, world)):
            _note_fused(op)
            payload, s = matmul_quantize(x, w_shard, bits=qspec.bits,
                                         block=qspec.block, trans_a=False,
                                         interpret=self.interpret)
            out = cc.quantized_chunk_exchange(
                payload, s, n=n, axis_name=axis_name, world=world,
                qspec=qspec, op_prefix=op, reduce="sum", stats=stats)
            return out.reshape(x.shape[0], w_shard.shape[1]).astype(out_dtype)
        # dense (or indivisible, which chunked_all_reduce meters): the
        # partial matmul still fuses; the exchange is the shared
        # deterministic facade path, so XLA/Pallas stay bit-identical
        if world > 1:
            _note_fused(op)
        y = matmul_pallas(x, w_shard, interpret=self.interpret)
        out = cc.chunked_all_reduce(y, axis_name, qspec=qspec, op=op,
                                    reduce="sum", stats=stats)
        return out.astype(out_dtype)


def resolve_backend(name: Optional[str] = "auto", *,
                    interpret: Optional[bool] = None) -> CollectiveBackend:
    """Resolve a ``kernel_backend`` config value. ``"auto"`` picks the
    fused Pallas backend on TPU and the XLA backend elsewhere;
    ``"pallas"`` off-TPU runs the kernels in interpret mode (the CPU
    evidence-lane / testing configuration)."""
    from ..ops.attention import _on_tpu

    if name in (None, "auto"):
        name = "pallas" if _on_tpu() else "xla"
    if name == "xla":
        return XlaCollectiveBackend()
    if name == "pallas":
        on_tpu = _on_tpu()
        return PallasFusedBackend(
            interpret=(not on_tpu) if interpret is None else interpret)
    raise ValueError(f"unknown kernel backend {name!r} "
                     f"(expected 'auto', 'xla' or 'pallas')")
