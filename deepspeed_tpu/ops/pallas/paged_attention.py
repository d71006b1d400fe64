"""Paged-attention decode kernel (Pallas TPU) with scalar-prefetched block
tables and multi-page chunks.

Reference surface: FastGen's ragged kernels
(``deepspeed/inference/v2/kernels/ragged_ops/`` — blocked flash over a
paged KV cache, with host-built "atoms" describing each sequence's pages).
TPU-first redesign: the block table is a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``) and every grid step's pages are DMA'd
straight from the pool in HBM by the Pallas pipeline — no [T, ctx] gather
materialization (the jnp fallback in ``inference/ragged.py`` does exactly
that and is correctness-only).

Layout contract (chosen for TPU tiling):
  q:        [T, hq, hd]                 one token per ragged lane
  k_pool:   [n_pages, hkv, block, hd]   (block, hd) minor = native tiles
  v_pool:   [n_pages, hkv, block, hd]
  tables:   [T, max_pages] int32        per-token page list
  positions:[T] int32                   absolute position of each token
Output:     [T, hq, hd]

Grid: (T, n_chunks) where a chunk is ``pages_per_chunk`` pages. The KV
pools enter as 2*ppc separate BlockSpec inputs — one [hkv, block, hd]
page slot each, whose index maps pick that slot's page id out of the
prefetched table — so the standard Pallas pipeline double-buffers the
scattered page fetches (manual ``make_async_copy`` cannot: Mosaic rejects
any hand-rolled DMA whose lane dim is under 128, i.e. every hd=64 pool).
In-kernel the ppc page blocks concatenate along the row dim into one
[hkv, ppc*block, hd] tile per chunk, so each grid step runs one big
batched MXU matmul instead of ppc tiny ones. Online softmax in VMEM
scratch (flash-2 style, as ops/pallas/flash_attention.py) over
[hkv*group, ...] row tiles. Chunks past a token's context are skipped
compute-side via ``pl.when`` AND their page indices clamp to the last
live page — Pallas elides the copy when an input's block index repeats,
so dead chunks cost (almost) no DMA either. An earlier revision used a
(T, max_pages) grid with one page per step; at 64 seqs x 64 pages that is
4096 sequential grid steps of ~32 KB each and ran DMA-latency bound,
~0.8x the XLA gather path. This formulation replaces it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _dot(a, b, dims):
    """MXU matmul, fp32 accumulation, at the operands' own precision.
    Pinned rather than left to ``jax_default_matmul_precision``: under
    "highest" every dot here would ask for an fp32 contract precision, and
    Mosaic refuses that on bf16 operands ("Bad lhs type")."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)


class Int4KVKernelUnsupported(NotImplementedError):
    """The compiled paged kernel cannot read an int4 KV pool."""

    def __init__(self):
        super().__init__(
            "kv_quant='int4' is not supported by the compiled Pallas paged-"
            "attention kernel: unpacking two nibbles per byte interleaves "
            "the lane (head_dim) axis in VMEM, and the v5e compiler spends "
            "minutes on it before failing with RESOURCE_EXHAUSTED (vmem, "
            "allocating on stack) even at T=16 (ROADMAP.md S4). Use "
            "kv_quant='int8' or 'none' on TPU; int4 runs in interpret mode "
            "and on the gather path only.")


def _kernel(*refs,
            scale: float, block: int, hkv: int, group: int, ppc: int,
            num_scalars: int, window: int = 0, kv_bits: int = 0):
    # scalar-prefetch refs lead; positions is always the last of them.
    # kv_bits > 0 = quantized pool: 2*ppc extra per-page SCALE inputs
    # follow the payload pages, and the payload dequantizes in VMEM
    # right after the concat (the "dequant inside the kernel read path")
    pos_ref = refs[num_scalars - 1]
    q_ref, *rest = refs[num_scalars:]
    krefs, vrefs = rest[:ppc], rest[ppc:2 * ppc]
    n_in = 2 * ppc + (2 * ppc if kv_bits else 0)
    ksrefs = rest[2 * ppc:3 * ppc] if kv_bits else ()
    vsrefs = rest[3 * ppc:4 * ppc] if kv_bits else ()
    o_ref = rest[n_in]
    m_scr, l_scr, acc_scr = rest[n_in + 1:]
    t, c = pl.program_id(0), pl.program_id(1)
    nchunks = pl.num_programs(1)
    span = ppc * block

    @pl.when(c == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[t]
    run = c * span <= pos  # chunk holds at least one visible row
    if window > 0:
        # banded: rows <= pos - window are invisible; skip chunks whose
        # whole span lies below the band
        run = jnp.logical_and(run, (c + 1) * span - 1 > pos - window)

    @pl.when(run)
    def _step():
        q = q_ref[0]                                 # [hkv, group, hd] bf16
        k = jnp.concatenate([kr[0] for kr in krefs], axis=1)
        v = jnp.concatenate([vr[0] for vr in vrefs], axis=1)
        if kv_bits:
            # quantized pages: unpack (int4) + per-row scale in VMEM; the
            # matmuls below then run in fp32 (q is cast to match). The
            # nibble layout lives in ONE place (ops/quantizer) — pure
            # jnp, so it traces inside the kernel body too
            from ...ops.quantizer import unpack_kv_int4

            ks = jnp.concatenate([r[0] for r in ksrefs], axis=1)  # [hkv, span]
            vs = jnp.concatenate([r[0] for r in vsrefs], axis=1)
            if kv_bits == 4:
                k = unpack_kv_int4(k)
                v = unpack_kv_int4(v)
            k = k.astype(jnp.float32) * ks[..., None]
            v = v.astype(jnp.float32) * vs[..., None]
            q = q.astype(jnp.float32)
        # batched-over-heads MXU matmul: [hkv, group, span]
        s = _dot(q, k, (((2,), (2,)), ((0,), (0,)))) * scale
        s = s.reshape(hkv * group, span)
        row_pos = c * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        visible = row_pos <= pos
        if window > 0:
            visible = jnp.logical_and(visible, row_pos > pos - window)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)                      # [hkv*group, span]
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(l_scr[:, :1] * corr +
                                    jnp.sum(pr, axis=-1, keepdims=True),
                                    l_scr.shape)
        pv = _dot(pr.reshape(hkv, group, span).astype(v.dtype), v,
                  (((2,), (1,)), ((0,), (0,))))      # [hkv, group, hd]
        acc_scr[:] = acc_scr[:] * corr + pv.reshape(hkv * group, -1)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(c == nchunks - 1)
    def _final():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)         # fully-masked lane guard
        o_ref[0] = (acc_scr[:] / l_safe).reshape(o_ref.shape[1:]) \
            .astype(o_ref.dtype)


def _check_quant_geometry(k_pool, hd: int, kv_bits: int) -> None:
    """Fail loudly on a kv_bits/payload mismatch: an int4 nibble-packed
    pool read with the default ``kv_bits=8`` would dequantize to
    shape-valid garbage (hd//2 channels silently re-folded by the
    downstream reshape), not an error."""
    if kv_bits == 4:
        if k_pool.dtype != jnp.uint8 or k_pool.shape[-1] * 2 != hd:
            raise ValueError(
                f"kv_bits=4 expects a nibble-packed uint8 pool "
                f"[..., hd//2={hd // 2}], got {k_pool.dtype} "
                f"[..., {k_pool.shape[-1]}] — pass the kv_bits the pool "
                f"was quantized with")
    elif kv_bits == 8:
        if k_pool.dtype != jnp.int8 or k_pool.shape[-1] != hd:
            raise ValueError(
                f"kv_bits=8 expects an int8 pool [..., hd={hd}], got "
                f"{k_pool.dtype} [..., {k_pool.shape[-1]}] — pass the "
                f"kv_bits the pool was quantized with")
    else:
        raise ValueError(f"kv_bits must be 4 or 8 with scales, got {kv_bits}")


def paged_attention(q, k_pool, v_pool, tables, positions, *,
                    seq_slots=None, scale=None,
                    pages_per_chunk: int | None = None,
                    live_pages: int | None = None,
                    window: int = 0,
                    k_scale=None, v_scale=None, kv_bits: int = 8,
                    interpret: bool = False):
    """Decode attention over a paged KV pool. See module docstring for the
    layout contract. Causal by construction: token t sees pool rows with
    position <= positions[t] along its own page list.

    ``tables`` is per-token [T, max_pages] by default. For ragged batches
    where many tokens share a sequence (SplitFuse prefill chunks), pass
    per-sequence tables [n_seqs, max_pages] plus ``seq_slots`` [T] mapping
    each token to its table row — the prefetched scalars then stay
    O(n_seqs * max_pages) instead of O(T * max_pages), which must fit SMEM
    (a [4096, 128] per-token table is 2 MB and does not).

    ``live_pages`` (static) bounds the page walk: the grid only visits
    ceil(live_pages / ppc) chunks per token. Dead chunks are pl.when-skipped
    anyway, but their ~us of grid overhead dominates short-context decode
    over a long max_context table (caller guarantees every
    positions[t] < live_pages * block; rows beyond are silently ignored).

    ``window`` > 0 (static) bands attention to the trailing ``window``
    positions (Mistral/Qwen2 sliding-window serving): chunks wholly below
    the band are pl.when-skipped AND their page DMA indices clamp to the
    band's first live page, so repeated block indices dedup the copies —
    compute and traffic are O(window), not O(context).

    ``k_scale``/``v_scale`` [n_pages, hkv, block] switch the pools to
    quantized storage (``ops/quantizer.quantize_kv``; int8 payload, or
    nibble-packed uint8 [..., hd//2] at ``kv_bits=4``): scales ride the
    same per-page BlockSpec pipeline as the payloads (half/quarter the
    page DMA bytes vs an fp pool) and the payload dequantizes in VMEM
    right before the QK^T matmul. The int8 variant compiles for the v5e
    (tests/test_tpu_compile.py); the int4 variant does not and raises
    :class:`Int4KVKernelUnsupported` outside interpret mode."""
    T, hq, hd = q.shape
    n_pages, hkv, block, _ = k_pool.shape
    quant = k_scale is not None
    if quant:
        _check_quant_geometry(k_pool, hd, kv_bits)
        if kv_bits == 4 and not interpret:
            raise Int4KVKernelUnsupported()
    max_pages = tables.shape[1]
    group = hq // hkv
    assert hq % hkv == 0
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    walk_pages = max_pages if live_pages is None \
        else max(1, min(live_pages, max_pages))
    if pages_per_chunk is None:
        pages_per_chunk = max(1, min(walk_pages, 256 // block))
    ppc = min(pages_per_chunk, walk_pages)
    nchunks = -(-walk_pages // ppc)

    qg = q.reshape(T, hkv, group, hd)
    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    if seq_slots is None:
        scalars = (tables, positions)
    else:
        scalars = (tables, seq_slots.astype(jnp.int32), positions)

    def row_of(t, s):
        return t if seq_slots is None else s[1][t]

    def q_index(t, c, *s):
        return (t, 0, 0, 0)

    def page_index(i):
        def index(t, c, *s):
            # past-the-end slots re-use the last live page's index: Pallas
            # skips the copy when the block index repeats, so dead chunks
            # cost no DMA — and the table read never strays off the row.
            # With a window, below-band slots clamp UP to the band's first
            # live page for the same dedup effect.
            tbl, pos = s[0], s[-1]
            j = jnp.minimum(c * ppc + i, max_pages - 1)
            j = jnp.minimum(j, pos[t] // block)
            if window > 0:
                lo = jnp.maximum(pos[t] - (window - 1), 0) // block
                j = jnp.maximum(j, lo)
            return (tbl[row_of(t, s), j], 0, 0, 0)
        return index

    def page_index3(i):
        # the scale leaves are [n_pages, hkv, block] (no channel dim):
        # same page pick as the payload, one fewer trailing zero
        idx4 = page_index(i)

        def index(t, c, *s):
            return idx4(t, c, *s)[:3]
        return index

    hd_p = k_pool.shape[-1]               # packed channel dim (= hd unless int4)
    page_spec = lambda i: pl.BlockSpec((1, hkv, block, hd_p), page_index(i))
    scale_spec = lambda i: pl.BlockSpec((1, hkv, block), page_index3(i))
    in_specs = [pl.BlockSpec((1, hkv, group, hd), q_index)] \
        + [page_spec(i) for i in range(ppc)] * 2
    operands = [qg, *([k_pool] * ppc), *([v_pool] * ppc)]
    if quant:
        in_specs += [scale_spec(i) for i in range(ppc)] * 2
        operands += [*([k_scale] * ppc), *([v_scale] * ppc)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(T, nchunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, group, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((hkv * group, LANES), jnp.float32),
            pltpu.VMEM((hkv * group, LANES), jnp.float32),
            pltpu.VMEM((hkv * group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, hkv=hkv,
                          group=group, ppc=ppc, num_scalars=len(scalars),
                          window=int(window),  # dslint: disable=host-sync -- window is a static Python int kernel parameter, never a tracer
                          kv_bits=int(kv_bits) if quant else 0),  # dslint: disable=host-sync -- kv_bits is a static Python int kernel parameter, never a tracer
        out_shape=jax.ShapeDtypeStruct((T, hkv, group, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*scalars, *operands)
    return out.reshape(T, hq, hd)


def write_kv_rows(leaf, page, row, new):
    """Write one new row per ragged lane into a pool leaf, in place and in
    the layout the kernel above reads.

    ``leaf`` is a payload leaf [n_pages, hkv, block, hd] (``new`` [T, hkv,
    hd]) or a ``kv_quant`` scale leaf [n_pages, hkv, block] (``new`` [T,
    hkv]); ``page``/``row`` [T] say where lane t's row lands. The KV-head
    axis is an *index* of the scatter beside page and row, so the update
    window is ``hd`` alone (empty for a scale leaf), already minor-most.
    Left as a window (``leaf.at[page, :, row]``, same values, same
    places) XLA:TPU's layout assignment transposes the whole leaf to
    [pages, block, hkv, hd] to make the (hkv, hd) window contiguous and
    back again for the kernel, which pins the default layout: two
    pool-sized copies a leaf a tick to write T rows
    (``tests/test_tpu_compile.py`` guards the compiled step)."""
    heads = jnp.arange(leaf.shape[1], dtype=page.dtype)
    return leaf.at[page[:, None], heads[None, :], row[:, None]].set(
        new.astype(leaf.dtype))


def paged_attention_reference(q, k_pool, v_pool, tables, positions, *,
                              scale=None, window: int = 0,
                              k_scale=None, v_scale=None, kv_bits: int = 8):
    """jnp reference (gather-based) with identical semantics — the numerics
    oracle for the kernel and the off-TPU fallback formulation.
    ``window`` > 0 bands attention to the trailing ``window`` positions
    (sliding-window serving: k > pos - window).

    ``k_scale``/``v_scale`` [n_pages, hkv, block] switch the pools to
    quantized storage (``ops/quantizer.quantize_kv``): int8 payloads —
    or, at ``kv_bits=4``, nibble-packed uint8 [..., hd//2] — are
    dequantized AFTER the per-token page gather (only pages actually
    read pay the dequant, mirroring the kernel's in-VMEM dequant)."""
    from ..quantizer import dequantize_kv

    T, hq, hd = q.shape
    n_pages, hkv, block, _ = k_pool.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    group = hq // hkv
    if k_scale is not None:
        _check_quant_geometry(k_pool, hd, kv_bits)
        # gather first ([T, max_pages, hkv, block, hd_p]), then dequant
        # page payloads with their per-row scales ([T, max_pages, hkv,
        # block] broadcast over hd)
        k_pages = dequantize_kv(k_pool[tables], k_scale[tables],
                                bits=kv_bits)
        v_pages = dequantize_kv(v_pool[tables], v_scale[tables],
                                bits=kv_bits)
        keys = k_pages.transpose(0, 2, 1, 3, 4).reshape(
            T, hkv, -1, hd).transpose(0, 2, 1, 3)
        vals = v_pages.transpose(0, 2, 1, 3, 4).reshape(
            T, hkv, -1, hd).transpose(0, 2, 1, 3)
        keys = jnp.repeat(keys, group, axis=2)
        vals = jnp.repeat(vals, group, axis=2)
        logits = jnp.einsum("thd,tkhd->thk", q.astype(jnp.float32),
                            keys) * scale
        kv_pos = jnp.arange(keys.shape[1])[None, :]
        visible = kv_pos <= positions[:, None]
        if window > 0:
            visible = visible & (kv_pos > positions[:, None] - window)
        logits = jnp.where(visible[:, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("thk,tkhd->thd", probs, vals).astype(q.dtype)
    # [T, max_pages, hkv, block, hd] -> [T, ctx, hkv, hd]
    keys = k_pool[tables].transpose(0, 2, 1, 3, 4).reshape(
        T, hkv, -1, hd).transpose(0, 2, 1, 3)
    vals = v_pool[tables].transpose(0, 2, 1, 3, 4).reshape(
        T, hkv, -1, hd).transpose(0, 2, 1, 3)
    keys = jnp.repeat(keys, group, axis=2)
    vals = jnp.repeat(vals, group, axis=2)
    logits = jnp.einsum("thd,tkhd->thk", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) * scale
    kv_pos = jnp.arange(keys.shape[1])[None, :]
    visible = kv_pos <= positions[:, None]
    if window > 0:
        visible = visible & (kv_pos > positions[:, None] - window)
    logits = jnp.where(visible[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("thk,tkhd->thd", probs,
                      vals.astype(jnp.float32)).astype(q.dtype)
