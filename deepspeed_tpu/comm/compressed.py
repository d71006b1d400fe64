"""Compression-aware collective facade: the shipped large-mesh ZeRO-3
communication path (docs/communication.md).

ZeRO++ (arxiv 2306.10209) cuts ZeRO-3 wire volume ~4x with three legs —
qwZ (blockwise-int8 weight all-gather), hpZ (secondary weight shard kept
inside the fast-ICI slice so per-layer gathers never cross the slow
links), qgZ (hierarchical int4/int8 gradient reduce-scatter: dense fp
inside the node, quantized across) — and T3 (arxiv 2401.16677) hides
most of what remains by fusing the per-block collectives into the
adjacent blocks' compute. This module is where both live for this repo:

* every ZeRO-3 hot-path collective the engine issues goes through a
  facade function here (the dslint ``comm-facade`` rule keeps raw
  ``jax.lax`` collectives out of ``parallel/zero.py`` /
  ``runtime/engine.py``);
* each facade call records a **bytes-on-wire ledger** entry with the
  CommsLogger — logical payload (what the uncompressed path would move)
  vs wire payload (quantized ints + scales) — so the compression claims
  are evidence, not configuration;
* each quantized collective carries a deterministic **error bound**
  (symmetric blockwise quant: per-element error <= scale/2, i.e. rel
  error vs the block absmax <= ``QuantSpec.rel_error_bound``) and an
  optional traced error-stats channel the engine folds into StepStats;
* anything that cannot be compressed (indivisible block, tiny leaf,
  axis of size 1, compression disabled) takes a **clean fallback** to
  the uncompressed collective, recorded in the same ledger with
  wire == logical and counted in ``comm/facade/fallbacks``.

The int4 wire format really is 4-bit on the wire: payloads are
nibble-packed (:func:`~deepspeed_tpu.ops.quantizer.pack_int4`) before
the collective, so the program moves half the elements — the ledger
reports what the compiled HLO actually transfers.

Reference surface: runtime/zero/stage3.py quantized all-gather/
reduce-scatter paths, utils/groups.py:356 (secondary groups),
blogs/zeropp/README.md positioning (quantize across the slow hop, stay
dense inside the node).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.quantizer import (dequantize_blockwise, pack_int4,
                             quantize_blockwise, quantized_nbytes,
                             unpack_int4)
from .comm import record_collective


@dataclass(frozen=True)
class QuantSpec:
    """One quantized hop: bit width + block size of the symmetric
    blockwise quantization bracketing the collective."""

    bits: int = 8
    block: int = 256

    def __post_init__(self):
        if self.bits not in (4, 8):
            raise ValueError(f"QuantSpec.bits must be 4 or 8, got {self.bits}")
        if self.block <= 0 or self.block % 2:
            raise ValueError(f"QuantSpec.block must be positive and even, "
                             f"got {self.block}")

    @property
    def qmax(self) -> float:
        return 2.0 ** (self.bits - 1) - 1

    @property
    def rel_error_bound(self) -> float:
        """Deterministic per-element error bound relative to the block
        absmax: |x - deq(q(x))| <= scale/2 = absmax / (2*qmax)."""
        return 0.5 / self.qmax

    def wire_nbytes(self, numel: int) -> int:
        return quantized_nbytes(numel, self.bits, self.block)

    def divides(self, numel: int, world: int = 1) -> bool:
        """Whether ``numel`` elements can take this quantized hop across
        ``world`` ranks: chunking + blocking must come out even. (int4's
        pair-packing needs an even per-rank count, which block % 2 == 0
        — enforced at construction — already guarantees.)"""
        return numel > 0 and numel % (self.block * max(world, 1)) == 0


def _nbytes(x: Any) -> int:
    return int(np.prod(x.shape or (1,))) * jnp.dtype(x.dtype).itemsize


def _note_fallback(op: str) -> None:
    from ..telemetry.registry import get_registry

    # trace-time static: whether a collective falls back is a shape/config
    # property, so this counts once per traced program — the same
    # deliberate trace-time-counter pattern as the engine's _trace_counts
    get_registry().counter("comm/facade/fallbacks").inc()
    get_registry().counter(f"comm/facade/fallbacks/{op}").inc()


def _rel_err(x: jnp.ndarray, deq: jnp.ndarray) -> jnp.ndarray:
    """Traced max relative quantization error of one round trip, scaled
    to the tensor absmax (the documented bound is per-block; per-tensor
    is strictly looser, so bound violations still trip)."""
    denom = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    return jnp.max(jnp.abs(deq - x.astype(deq.dtype))) / denom


def _quant_roundtrip(x: jnp.ndarray, spec: QuantSpec,
                     dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """(q int8, scales, deq) of a flat tensor — the pack/unpack bracket
    every quantized hop pays."""
    q, s, _ = quantize_blockwise(x, bits=spec.bits, block=spec.block,
                                 manual_sharding=True)
    deq = dequantize_blockwise(q, s, block=spec.block, dtype=dtype,
                               manual_sharding=True)
    return q, s, deq


def _merge_gathered(full: jnp.ndarray, world: int, shape: Tuple[int, ...],
                    dim: int) -> jnp.ndarray:
    """[world, *shape] -> shape with dim scaled by world, rank-major along
    ``dim`` (the tiled all_gather layout)."""
    out = jnp.moveaxis(full, 0, dim)
    return out.reshape(shape[:dim] + (world * shape[dim],) + shape[dim + 1:])


# ----------------------------------------------------------------------
# weight all-gather (qwZ)

def quantized_all_gather(x: jnp.ndarray, axis_name: str, *, dim: int = 0,
                         qspec: Optional[QuantSpec] = None,
                         op: str = "qwz_all_gather",
                         out_dtype=None,
                         stats: Optional[List[jnp.ndarray]] = None
                         ) -> jnp.ndarray:
    """All-gather ``x`` along mesh axis ``axis_name`` concatenating on
    ``dim``. With a ``qspec``, the wire carries blockwise-quantized ints
    (+ fp32 scales) — the qwZ leg; without one (or when the shard can't
    block-divide) the dense gather runs and the ledger books wire ==
    logical. Must run inside a shard_map region where ``axis_name`` is
    manual. ``stats`` (optional list) receives the traced max relative
    quantization error of the local round trip."""
    world = jax.lax.axis_size(axis_name)
    if world <= 1:
        return x if out_dtype is None else x.astype(out_dtype)
    out_dtype = out_dtype or x.dtype
    logical = _nbytes(x)
    if qspec is None or not qspec.divides(x.size):
        if qspec is not None:
            _note_fallback(op)
        record_collective(op, logical, logical, axis_name, world)
        y = jax.lax.all_gather(x, axis_name, axis=dim, tiled=True)
        return y.astype(out_dtype)
    record_collective(op, logical, qspec.wire_nbytes(x.size), axis_name,
                      world)
    flat = x.reshape(-1).astype(jnp.float32)
    q, s, _ = quantize_blockwise(flat, bits=qspec.bits, block=qspec.block,
                                 manual_sharding=True)
    if stats is not None:
        deq = dequantize_blockwise(q, s, block=qspec.block,
                                   manual_sharding=True)
        stats.append(_rel_err(flat, deq))
    payload = pack_int4(q) if qspec.bits == 4 else q
    p_all = jax.lax.all_gather(payload, axis_name)            # [world, ...]
    s_all = jax.lax.all_gather(s, axis_name)                  # [world, n/block]
    q_all = (unpack_int4(p_all) if qspec.bits == 4
             else p_all.reshape(-1))
    deq_all = dequantize_blockwise(q_all, s_all.reshape(-1),
                                   block=qspec.block, dtype=out_dtype,
                                   manual_sharding=True)
    full = deq_all.reshape((world,) + x.shape)
    return _merge_gathered(full, world, x.shape, dim)


def gather_param_leaf(x: jnp.ndarray, spec, *,
                      outer_axes: Sequence[str] = ("data",),
                      qspec: Optional[QuantSpec] = None,
                      out_dtype=None,
                      stats: Optional[List[jnp.ndarray]] = None
                      ) -> jnp.ndarray:
    """Reassemble a full parameter leaf from its ZeRO-3 shard inside a
    manual shard_map region: per sharded dim, the inner (fast-ICI, hpZ)
    hops gather dense while hops crossing ``outer_axes`` move quantized
    payloads (qwZ). Minor axes of a tuple entry gather first so rank
    order composes like the GSPMD layout."""
    for d, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in reversed(axes):
            if ax in outer_axes:
                x = quantized_all_gather(x, ax, dim=d, qspec=qspec,
                                         op="qwz_all_gather",
                                         out_dtype=out_dtype, stats=stats)
            else:
                x = quantized_all_gather(x, ax, dim=d, qspec=None,
                                         op="hpz_all_gather",
                                         out_dtype=out_dtype)
    return x if out_dtype is None else x.astype(out_dtype)


def ste_quant_gather(x: jnp.ndarray, sharding, qspec: QuantSpec, dtype):
    """qwZ on the GSPMD (auto-sharded) path: fake-quantize through int8
    with the int8 tensor carrying the gather placement, so the compiler-
    inserted all-gather moves 1 byte/element. Straight-through estimator:
    the forward gathers quantized values, the backward passes the
    cotangent through unchanged — differentiating through round() would
    zero the gradient for all but the per-block argmax elements,
    silently freezing every quantized weight. (Moved from the engine's
    inline ste_quant; the facade records the wire ledger.)

    NB wire accounting: on this GSPMD path the gathered tensor is the
    int8 STORAGE format whatever the nominal bit width — nibble-packing
    would break the sharding-constraint trick — so the ledger books
    1 byte/element (+ scales) even for bits=4. True 4-bit wire needs the
    shard_map facade path (quantized_all_gather), which really packs."""
    record_collective("qwz_all_gather", _nbytes(x),
                      quantized_nbytes(x.size, 8, qspec.block))

    def primal(v):
        q, s, _ = quantize_blockwise(v, bits=qspec.bits, block=qspec.block)
        q = jax.lax.with_sharding_constraint(q, sharding)
        return dequantize_blockwise(q, s, block=qspec.block,
                                    dtype=dtype).reshape(v.shape)

    fq = jax.custom_vjp(primal)
    fq.defvjp(lambda v: (primal(v), None), lambda _, g: (g,))
    return fq(x)


# ----------------------------------------------------------------------
# gradient reduction (qgZ): hierarchical two-hop mean

def pmean(x: jnp.ndarray, axes) -> jnp.ndarray:
    """Dense mean-reduce over one or more mesh axes (losses, tiny
    tensors). Ledger-recorded as a plain all_reduce."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    record_collective("all_reduce", _nbytes(x), _nbytes(x),
                      axes[0])
    return jax.lax.pmean(x, axes)


def pmax(x: jnp.ndarray, axes) -> jnp.ndarray:
    """Dense max-reduce over one or more mesh axes (error-stat scalars:
    a per-rank local max is NOT replicated until reduced — declaring it
    so would hand the host an arbitrary shard's value)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    record_collective("all_reduce", _nbytes(x), _nbytes(x),
                      axes[0])
    return jax.lax.pmax(x, axes)


def quantized_chunk_exchange(payload: jnp.ndarray, s: jnp.ndarray, *,
                             n: int, axis_name: str, world: int,
                             qspec: QuantSpec, op_prefix: str,
                             reduce: str = "mean",
                             stats: Optional[List[jnp.ndarray]] = None
                             ) -> jnp.ndarray:
    """The two quantized wire hops of a chunked reduction, operating on
    an ALREADY-quantized flat payload (wire format: nibble-packed for
    int4) with its fp32 block scales: all_to_all chunk exchange (the
    reduce-scatter hop), dense reduce of the dequantized chunk in FIXED
    rank order (axis 0 of the all_to_all result is the source rank, so
    the accumulation order is deterministic and identical on every
    rank), re-quantize, all_gather (the broadcast hop): the cross-slice
    hops of :func:`hierarchical_pmean`. ``n`` is the logical element
    count (payload is packed for int4); ``reduce`` picks mean
    (gradients) or sum."""
    record_collective(f"{op_prefix}_reduce_scatter", n * 4,
                      qspec.wire_nbytes(n), axis_name, world)
    p_recv = jax.lax.all_to_all(payload.reshape(world, -1), axis_name,
                                0, 0, tiled=False)
    s_recv = jax.lax.all_to_all(s.reshape(world, -1), axis_name,
                                0, 0, tiled=False)
    chunk_n = n // world
    q_recv = (unpack_int4(p_recv) if qspec.bits == 4
              else p_recv.reshape(-1))
    vals = dequantize_blockwise(q_recv, s_recv.reshape(-1),
                                block=qspec.block, manual_sharding=True)
    vals = vals.reshape(world, chunk_n)
    chunk = (jnp.mean(vals, axis=0) if reduce == "mean"
             else jnp.sum(vals, axis=0))
    # broadcast hop: re-quantized reduced chunk, gathered by everyone
    record_collective(f"{op_prefix}_all_gather", chunk_n * 4,
                      qspec.wire_nbytes(chunk_n), axis_name, world)
    q2, s2, _ = quantize_blockwise(chunk, bits=qspec.bits, block=qspec.block,
                                   manual_sharding=True)
    if stats is not None:
        deq2 = dequantize_blockwise(q2, s2, block=qspec.block,
                                    manual_sharding=True)
        stats.append(_rel_err(chunk, deq2))
    payload2 = pack_int4(q2) if qspec.bits == 4 else q2
    p_all = jax.lax.all_gather(payload2, axis_name)
    s_all = jax.lax.all_gather(s2, axis_name)
    q_all = (unpack_int4(p_all) if qspec.bits == 4
             else p_all.reshape(-1))
    return dequantize_blockwise(q_all, s_all.reshape(-1), block=qspec.block,
                                manual_sharding=True).reshape(n)


def _quantized_pmean_1hop(x: jnp.ndarray, axis_name: str, world: int,
                          qspec: QuantSpec, op_prefix: str,
                          stats: Optional[List[jnp.ndarray]]) -> jnp.ndarray:
    """Quantized mean over one (slow) axis: quantize the local
    contribution, then the shared chunk exchange
    (:func:`quantized_chunk_exchange`) — both hops move quantized
    payloads, the qgZ wire shape. x: flat [n], n divisible by
    world*block (caller-checked)."""
    q, s, _ = quantize_blockwise(x, bits=qspec.bits, block=qspec.block,
                                 manual_sharding=True)
    if stats is not None:
        deq = dequantize_blockwise(q, s, block=qspec.block,
                                   manual_sharding=True)
        stats.append(_rel_err(x, deq))
    payload = pack_int4(q) if qspec.bits == 4 else q
    return quantized_chunk_exchange(
        payload, s, n=x.size, axis_name=axis_name, world=world, qspec=qspec,
        op_prefix=op_prefix, reduce="mean", stats=stats).reshape(x.shape)


def hierarchical_pmean(x: jnp.ndarray, *, outer_axis: str,
                       outer_world: int,
                       inner_axis: Optional[str] = None,
                       inner_world: int = 1,
                       qspec: Optional[QuantSpec] = None,
                       min_quant_size: int = 0,
                       stats: Optional[List[jnp.ndarray]] = None
                       ) -> jnp.ndarray:
    """Hierarchical gradient mean (qgZ). The shape that actually saves
    slow-link wire is *chunked*: reduce-SCATTER across the inner
    (fast-ICI) slice first so each inner rank holds a 1/inner_world fp
    chunk, run the quantized int8/int4 exchange across the outer
    (inter-slice) axis on that chunk only, then all-gather the reduced
    chunks back across the inner slice — inter-slice traffic is
    1/inner_world of the tensor per rank, matching ZeRO++'s hierarchy
    (an inner pmean followed by a full-tensor outer exchange would move
    inner_world x more across exactly the links compression exists to
    relieve). Degenerates cleanly: size-1 hops vanish, and
    indivisible/tiny tensors take the dense mean (inner pmean + dense
    outer pmean; ledger wire == logical, fallback counted). Must run
    inside a shard_map region where the named axes are manual."""
    hier = inner_axis is not None and inner_world > 1
    chunkable = x.size % max(inner_world, 1) == 0
    quantizable = (outer_world > 1 and qspec is not None
                   and x.size >= max(min_quant_size, 1)
                   and (not hier or chunkable)
                   and qspec.divides(x.size // (inner_world if hier else 1),
                                     outer_world))
    if not quantizable:
        y = x
        if hier:
            record_collective("qgz_intra_reduce", _nbytes(y), _nbytes(y),
                              inner_axis, inner_world)
            y = jax.lax.pmean(y, inner_axis)
        if outer_world <= 1:
            return y
        if qspec is not None:
            # counter op matches the ledger row the fallback records, so
            # comm/facade/fallbacks/<op> joins against comm/<op>/* rows
            _note_fallback("qgz_inter_reduce_dense")
        record_collective("qgz_inter_reduce_dense", _nbytes(y), _nbytes(y),
                          outer_axis, outer_world)
        return jax.lax.pmean(y, outer_axis)
    y = x
    if hier:
        # fast-ICI hop 1: fp reduce-scatter — each inner rank owns the
        # mean of its 1/inner_world chunk
        record_collective("qgz_intra_reduce_scatter", _nbytes(y), _nbytes(y),
                          inner_axis, inner_world)
        y = jax.lax.psum_scatter(y.reshape(-1), inner_axis,
                                 tiled=True) / inner_world
    # slow hop: quantized chunk-exchange mean across the outer axis
    y = _quantized_pmean_1hop(y.reshape(-1), outer_axis, outer_world, qspec,
                              "qgz_inter", stats)
    if hier:
        # fast-ICI hop 2: rebuild the full reduced tensor from the chunks
        record_collective("qgz_intra_all_gather", _nbytes(y), _nbytes(y),
                          inner_axis, inner_world)
        y = jax.lax.all_gather(y, inner_axis, axis=0, tiled=True)
    return y.reshape(x.shape)


def tree_hierarchical_pmean(grads: Any, *, outer_axis: str,
                            outer_world: int,
                            inner_axis: Optional[str] = None,
                            inner_world: int = 1,
                            qspec: Optional[QuantSpec] = None,
                            stats: Optional[List[jnp.ndarray]] = None
                            ) -> Any:
    """Leaf-wise :func:`hierarchical_pmean` over a gradient pytree; each
    leaf is flattened to fp32 for the reduction (the engine's gradient
    dtype discipline) and restored to its shape."""
    min_size = 4 * outer_world * (qspec.block if qspec else 1)

    def leaf(g):
        flat = g.reshape(-1).astype(jnp.float32)
        return hierarchical_pmean(
            flat, outer_axis=outer_axis, outer_world=outer_world,
            inner_axis=inner_axis, inner_world=inner_world,
            qspec=qspec, min_quant_size=min_size, stats=stats,
        ).reshape(g.shape)

    return jax.tree_util.tree_map(leaf, grads)


# ----------------------------------------------------------------------
# T3-style exposure model (shared by the NORTHSTAR projection, the
# MULTICHIP comm lane and the quant-comm smoke gate)

def modeled_exposure(*, param_bytes: float, grad_bytes: float,
                     n_blocks: int, compute_s: float, link_bps: float,
                     world: int,
                     weight_qspec: Optional[QuantSpec] = None,
                     grad_qspec: Optional[QuantSpec] = None,
                     weight_itemsize: int = 2,
                     grad_itemsize: int = 4) -> Dict[str, float]:
    """Analytic exposed-comm model for the staged ZeRO-3 schedule.

    Per step, ZeRO-3 moves the parameter set through TWO all-gathers
    (forward + backward re-gather) and the gradient set through ONE
    reduce-scatter, each split into ``n_blocks`` per-block collectives.
    The staged schedule (parallel/zero.py Zero3BlockSchedule) issues
    block i+1's gather before block i's compute and defers block i+1's
    reduce behind block i's backward, so only the pipeline fill/drain
    collectives plus any per-block excess (comm outrunning the block's
    compute window) stay exposed:

        serial_s     = (2*W + G) * (world-1)/world / bw
        overlapped_s = fill + drain + sum_i max(0, c_block_i - t_block_i)

    with the forward window ``compute_s/3 / n_blocks`` per block and the
    backward window ``2*compute_s/3 / n_blocks`` (fwd:bwd FLOP ratio
    1:2). Compression scales the wire volume by the quantized ratio
    before the division. All quantities are per-chip step time."""
    frac = (world - 1) / world if world > 1 else 0.0
    numel_w = param_bytes / weight_itemsize
    numel_g = grad_bytes / grad_itemsize
    w_wire = (weight_qspec.wire_nbytes(int(numel_w))
              if weight_qspec else param_bytes)
    g_wire = (grad_qspec.wire_nbytes(int(numel_g))
              if grad_qspec else grad_bytes)
    serial_dense = (2 * param_bytes + grad_bytes) * frac / link_bps
    serial_comp = (2 * w_wire + g_wire) * frac / link_bps
    # per-block comm vs the compute window it hides behind
    c_gather = w_wire * frac / link_bps / n_blocks
    c_reduce = g_wire * frac / link_bps / n_blocks
    t_fwd = compute_s / 3.0 / n_blocks
    t_bwd = 2.0 * compute_s / 3.0 / n_blocks
    fwd_exposed = (c_gather
                   + (n_blocks - 1) * max(0.0, c_gather - t_fwd))
    bwd_exposed = (c_gather + c_reduce                       # fill + drain
                   + (n_blocks - 1) * max(0.0, c_gather + c_reduce - t_bwd))
    overlapped = fwd_exposed + bwd_exposed
    return {
        "serial_dense_s": serial_dense,
        "serial_compressed_s": serial_comp,
        "overlapped_compressed_s": overlapped,
        "exposure_reduction_vs_serial": (1.0 - overlapped / serial_dense
                                         if serial_dense > 0 else 0.0),
        "weight_wire_ratio": param_bytes / w_wire if w_wire else 1.0,
        "grad_wire_ratio": grad_bytes / g_wire if g_wire else 1.0,
        "n_blocks": float(n_blocks),
    }
