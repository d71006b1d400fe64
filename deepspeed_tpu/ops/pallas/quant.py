"""Pallas blockwise int8 quantize/dequantize kernels.

SURVEY §2.4 parity target: the reference's CUDA quantizer suite
(``csrc/quantization/{quantize.cu,dequantize.cu,pt_binding.cpp}`` — fused
absmax + scale + pack at memory bandwidth). The XLA path in
``ops/quantizer.py`` stays the reference semantics (and the fallback);
these kernels fuse the scale reduction and the pack/unpack into single
VMEM passes so the qwZ/qgZ bracket cost is one HBM read + one write.

Layout: values as [rows, block] with ``block`` a lane multiple (256
default = 2 lanes); scales are emitted lane-replicated [rows, 128] (the
same Mosaic constraint trick as the flash kernel's LSE) and sliced to
[rows] by the wrapper. int8 tiles are (32, 128)-aligned, so ``rows`` is
processed in multiples of 32 per grid step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_TILE = 256          # rows per grid step (multiple of 32 for int8 tiles)


def _row_tile(rows: int) -> int:
    """Largest tile in {256,128,64,32} dividing ``rows`` (int8 tiles are
    (32,128)-aligned, so rows must be a multiple of 32 — the dispatch
    guard enforces that)."""
    for t in (ROW_TILE, 128, 64, 32):
        if rows % t == 0:
            return t
    raise AssertionError(f"rows {rows} not a multiple of 32")


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)                    # [R, block]
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, (x.shape[0], LANES))


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                    # [R, block]
    scale = s_ref[...][:, :1]                             # [R, 1]
    o_ref[...] = (q * scale).astype(o_ref.dtype)


def quantize_blockwise_pallas(x: jnp.ndarray, bits: int = 8,
                              block: int = 256, interpret: bool = False
                              ) -> Tuple[jnp.ndarray, jnp.ndarray, None]:
    """Fused symmetric blockwise quantization (signature-compatible with
    ops.quantizer.quantize_blockwise for the symmetric case)."""
    assert bits in (4, 8)
    qmax = 2.0 ** (bits - 1) - 1
    flat = x.reshape(-1)
    n = flat.shape[0]
    assert n % block == 0, f"size {n} not divisible by block {block}"
    rows = n // block
    row_tile = _row_tile(rows)
    xb = flat.reshape(rows, block)

    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(rows // row_tile,),
        in_specs=[pl.BlockSpec((row_tile, block), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((row_tile, block), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, block), jnp.int8),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(xb)
    return q.reshape(x.shape), s[:, 0], None


def dequantize_blockwise_pallas(q: jnp.ndarray, scale: jnp.ndarray,
                                zero=None, block: int = 256,
                                dtype=jnp.float32,
                                interpret: bool = False) -> jnp.ndarray:
    assert zero is None, "pallas path is symmetric-only"
    flat = q.reshape(-1)
    rows = flat.shape[0] // block
    row_tile = _row_tile(rows)
    qb = flat.reshape(rows, block)
    sb = jnp.broadcast_to(scale[:, None], (rows, LANES))

    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // row_tile,),
        in_specs=[
            pl.BlockSpec((row_tile, block), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((row_tile, block), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, block), dtype),
        interpret=interpret,
    )(qb, sb)
    return out.reshape(q.shape).astype(dtype)


def use_pallas_quant(numel: int, block: int,
                     manual_sharding: bool = False) -> bool:
    """Dispatch guard: TPU + lane-aligned block + whole row tiles.
    DST_NO_PALLAS_QUANT=1 pins the XLA path (microbench A/B lever).

    On multi-device PROCESSES the auto path yields to jnp: GSPMD-auto
    call sites (engine ste_quant, inference weight loads) would bake a
    replicated pallas_call into the trace (the flash-attention hazard —
    transformer._local_flash). ``manual_sharding=True`` is the opt-in for
    callers already inside a shard_map manual region (compressed.py
    collectives), where the kernel is device-local and safe. The check
    uses jax.devices() (not the topology singleton) so it cannot be
    defeated by trace-before-initialize ordering."""
    import os

    from ..attention import _on_tpu

    if os.environ.get("DST_NO_PALLAS_QUANT") == "1":
        return False
    if not _on_tpu():
        return False
    if not manual_sharding:
        import jax

        if len(jax.devices()) > 1:
            return False
    if block % LANES or numel % block:
        return False
    rows = numel // block
    return rows % 32 == 0
