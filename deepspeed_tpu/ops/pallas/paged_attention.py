"""Paged attention over a ragged batch of query lanes (Pallas TPU): a grid
over work. One grid step is a query tile — a stretch of one sequence's
lanes — and the KV chunks that tile's own context reaches.

Reference surface: FastGen's ragged kernels
(``deepspeed/inference/v2/kernels/ragged_ops/`` — blocked flash over a
paged KV cache, with host-built "atoms" describing each sequence's pages).
TPU-first redesign: the block tables and the step's list of tiles are
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), the pool stays
in HBM and a tile's pages are copied from it chunk by chunk, two chunks in
flight — no [T, ctx] gather materialization (the jnp fallback in
``inference/ragged.py`` does exactly that and is correctness-only).

Layout contract (chosen for TPU tiling):
  q:        [T, hq, hd]                 one token per ragged lane
  k_pool:   [n_pages, hkv, block, hd]   (block, hd) minor = native tiles
  v_pool:   [n_pages, hkv, block, hd]   (hd < 128: heads share a row, below)
  tables:   [n_seqs, max_pages] int32   a sequence's page list
  seq_slots:[T] int32                   a lane's row of tables; < 0: no lane
  positions:[T] int32                   absolute position of each token
Output:     [T, hq, hd]

Lanes of one sequence are contiguous in the flat batch, at consecutive
positions (``ops/ragged_host.build_batch``). :func:`work_list` cuts them
into tiles inside the jitted step, once a step, from ``seq_slots`` and
``positions`` alone: a tile ends where the sequence does and at every
multiple of ``Tq = query_tile(T)`` lanes, so it lies inside one block of
``Tq`` lanes and the q and output blocks ride the ordinary pipeline (tiles
of one block are consecutive steps; the block is fetched and written back
once). A decode lane, a speculative run of k + 1 lanes and a prompt chunk
are the 1-row, (k+1)-row and Tq-row cases of the same kernel. The grid is
``ceil(T / Tq) + n_seqs`` steps, the most tiles such a batch can make;
steps past the last tile do nothing and name the last block again.

A tile walks the 256-token chunks from the one its first row's window
reaches (0 without a window) to the one that holds its last row: a loop
with the tile's own trip count, whatever the page bucket. The chunk's
pages ([hkv, block, hd] slabs, K and V) land side by side in a
[hkv, 256, hd] VMEM buffer, so a chunk is one batched MXU matmul of the
tile's ``group * Tq`` rows (the GQA group folded into the rows) against
it, with the causal mask and the window's band by ``pos0 + row``. Under
block diffusion (``attn_block`` > 1, a power of two) the mask is causal
over blocks of that many positions: a query at position p sees keys up to
``p | (attn_block - 1)``, its own block whole, and a tile's last chunk is
the one that holds its last row's block; with ``attn_block`` 1 not one
operation differs. Online
softmax in VMEM scratch (flash-2 style, as ops/pallas/flash_attention.py).
bf16 operands, fp32 softmax and accumulation.

Mosaic refuses a hand-rolled copy of a slab under 128 lanes wide. A head
size under 128 that divides it (64: Granite) therefore shares a row:
:func:`heads_a_row` KV heads lie side by side in the 128 lanes of one row
of the pool, ``[n_pages, hkv / pack, block, pack * hd]`` (KV head ``pack *
r + j`` in lanes ``j * hd ..`` of row ``r``), which is what the step's new
rows ``[T, hkv, hd]`` are when read as ``[T, hkv / pack, pack * hd]``. The
wrapper hands the kernel each query head in its KV head's lanes of a
128-lane row, zeros in the others, with the row's ``pack`` GQA groups as
one folded group, and takes each head's lanes of the output: scores and
weighted sums are exact (the padding multiplies by zero), the MXU does
``pack`` times the work of a kernel that its page copies bound, and no
kernel body differs (PERF.md, PR 50). :func:`paged_attention` reads the
packing off the pool's row against the query's, so nothing is passed.

A pool that still has a leaf under 128 lanes wide — a quantized pool's
[.., block] scale rows at the usual 16-token pages, a head geometry that
does not fill whole rows (one KV head of 64; heads a device under a model
axis that are no multiple of ``pack``) — keeps the earlier grid, (T,
chunks of the page bucket) with every page a BlockSpec input
(:func:`_lane_grid`): about 2.4 us a step whether the chunk is live or
not, so its time follows lanes x bucket, not the work. The choice is made
on those shapes alone. PERF.md (PR 29) has both grids' timings.

Latent attention (MLA in its absorbed form) is multi-query attention of
every query head over ONE shared row a token, the weighted sum taken over
the row's first ``v_dim`` values: :func:`latent_attention`, the same grid
over the same list of tiles with one pool leaf [n_pages, 1, block, row] for
both operands (a chunk is copied once, not twice) and the heads, not a GQA
group, folded into a tile's rows. The two kernels share the walk over a
tile's chunks: :func:`_page_copies`, :func:`_walk_chunks`,
:func:`_fold_scores`.

The step's new K/V rows reach the pool through :func:`write_kv_pages`,
one Pallas call a layer over the same list of tiles, which returns both
leaves written in place (PERF.md, PR 38); :func:`write_kv_rows`, an XLA
scatter a leaf, is the form off the TPU, under tensor parallelism and for
a quantized pool, and the tests' oracle.
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


def _dequantize(q, k, v, ks, vs, kv_bits: int):
    """Quantized pages in VMEM: unpack (int4) + per-row scale ([hkv, span]);
    the matmuls then run in fp32 (q is cast to match). The nibble layout
    lives in ONE place (ops/quantizer) — pure jnp, so it traces inside a
    kernel body too."""
    from ...ops.quantizer import unpack_kv_int4

    if kv_bits == 4:
        k, v = unpack_kv_int4(k), unpack_kv_int4(v)
    return (q.astype(jnp.float32), k.astype(jnp.float32) * ks[..., None],
            v.astype(jnp.float32) * vs[..., None])


def _lane_kernel(*refs,
            scale: float, block: int, hkv: int, group: int, ppc: int,
            num_scalars: int, window: int = 0, kv_bits: int = 0,
            attn_block: int = 1):
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
    if attn_block > 1:       # the last key this lane sees: its block's
        pos = pos | (attn_block - 1)
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
            ks = jnp.concatenate([r[0] for r in ksrefs], axis=1)  # [hkv, span]
            vs = jnp.concatenate([r[0] for r in vsrefs], axis=1)
            q, k, v = _dequantize(q, k, v, ks, vs, kv_bits)
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


def query_tile(T: int) -> int:
    """Query lanes a grid step serves, from the lane bucket ``T`` alone."""
    return 16 if T < 256 else 32 if T < 1024 else 64


def chunk_pages(block: int) -> int:
    """Pages a KV chunk holds: 256 tokens' worth."""
    return max(1, 256 // block)


def heads_a_row(n_kv_heads: int, head_dim: int) -> int:
    """KV heads that share one 128-lane row of a payload leaf (module
    docstring): ``128 / head_dim`` where the head size divides 128 and the
    heads (those of one device, under a model axis) fill whole rows,
    else 1, the leaf a head a row."""
    pack = LANES // head_dim if head_dim and LANES % head_dim == 0 else 1
    return pack if n_kv_heads % pack == 0 else 1


def unpack_heads(pages, head_dim: int):
    """Pages of a payload leaf ``[.., rows, block, pack * hd]`` as
    ``[.., rows * pack, block, hd]``, a KV head a row: what the oracles
    read (the kernel reads the rows as they lie)."""
    *lead, rows, block, width = pages.shape
    pack = max(1, width // head_dim)
    if pack == 1:
        return pages
    return pages.reshape(*lead, rows, block, pack, head_dim) \
        .swapaxes(-3, -2).reshape(*lead, rows * pack, block, head_dim)


def share_rows(pages, rows: int):
    """The inverse of :func:`unpack_heads`: pages ``[.., hkv, block, hd]``
    laid out over ``rows`` rows a page, ``hkv / rows`` heads side by side
    in each."""
    *lead, hkv, block, hd = pages.shape
    pack = hkv // rows
    if pack == 1:
        return pages
    return pages.reshape(*lead, rows, pack, block, hd) \
        .swapaxes(-3, -2).reshape(*lead, rows, block, pack * hd)


def _rows_of(new, leaf):
    """The step's new rows ``[T, hkv, ..]`` as ``leaf`` holds a token's:
    heads that share a row of the leaf are neighbours in ``new`` already."""
    return new.reshape(new.shape[:1] + leaf.shape[1:2] + leaf.shape[3:])


def work_list(slots, positions, n_seqs: int, tile_rows: int | None = None):
    """The step's query tiles, built on the device from what the step is
    handed: slots [T] (< 0 = lane not live), positions [T] -> int32
    [5, n_tiles_max], one column a tile, live tiles first and in lane order:

      0  the tile's block of ``tile_rows`` lanes (``lane // tile_rows``)
      1  its first row inside that block
      2  its rows (0 = no tile: the block is the last live tile's again)
      3  its sequence's row of ``tables``
      4  its first row's position

    A tile is a stretch of lanes of one sequence at consecutive positions
    that lies inside one block: a new one starts where the slot changes,
    where the position does not follow, and at every multiple of
    ``tile_rows``. A sequence's lanes are contiguous in the flat batch
    (``ops/ragged_host.build_batch``), so there are at most
    ``ceil(T / tile_rows) + n_seqs`` tiles, the static bound; a batch that
    breaks that contract loses its last tiles."""
    T = slots.shape[0]
    tq = tile_rows or query_tile(T)
    nt = -(-T // tq) + n_seqs
    slots = slots.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    lane = jnp.arange(T, dtype=jnp.int32)
    active = slots >= 0
    follows = jnp.concatenate([
        jnp.zeros((1,), bool),
        (slots[1:] == slots[:-1]) & (positions[1:] == positions[:-1] + 1)])
    start = active & ((lane % tq == 0) | ~follows)
    count = jnp.cumsum(start.astype(jnp.int32))
    n_tiles = count[-1]
    t = jnp.arange(nt, dtype=jnp.int32)
    live = t < n_tiles
    # the t-th tile starts where the count of starts reaches t + 1 (all
    # compares at once: a binary search is a loop of a dozen tiny programs)
    first = jnp.searchsorted(count, t + 1, side="left",
                             method="compare_all").astype(jnp.int32)
    at = jnp.minimum(first, T - 1)
    # ... and ends before the next lane that starts a tile or is not live
    brk = jax.lax.cummin(jnp.where(start | ~active, lane, T), reverse=True)
    brk = jnp.concatenate([brk[1:], jnp.full((1,), T, jnp.int32)])
    at = jnp.where(live, at, at[jnp.maximum(n_tiles - 1, 0)])
    zero = lambda a: jnp.where(live, a, 0)
    return jnp.stack([at // tq, zero(at % tq), zero(brk[at] - at),
                      zero(slots[at]), zero(positions[at])])


def _check_attn_block(attn_block: int, block: int, window: int) -> None:
    if attn_block > 1 and (attn_block & (attn_block - 1) or block % attn_block
                           or window > 0):
        raise ValueError(
            f"attn_block {attn_block} must be a power of two that divides "
            f"the page size {block}, without a window (got {window})")


def tile_counts(runs, tile_rows: int, block: int, attn_block: int = 1
                ) -> tuple:
    """(tiles, KV steps, pages written) :func:`work_list` and the two
    kernels make of a packed batch, on the host: ``runs`` is (lanes, first
    position) of each sequence in batch order. A KV step is one chunk of
    one tile of the paged kernel (counted without a window); a page
    written is one page slab, K and V, that :func:`write_kv_pages` moves
    for a tile (a page two tiles share counts for each). ``attn_block``:
    the paged kernel's, whose last chunk holds the last row's block."""
    span = chunk_pages(block) * block
    tiles = steps = pages = lane = 0
    for take, pos in runs:
        while take > 0:
            n = min(take, tile_rows - lane % tile_rows)
            tiles += 1
            steps += ((pos + n - 1) | (attn_block - 1)) // span + 1
            pages += (pos + n - 1) // block - pos // block + 1
            lane, pos, take = lane + n, pos + n, take - n
    return tiles, steps, pages


def _page_copies(tbl_ref, slot, c, buf, ppc: int, block: int, lo_page,
                 hi_page, slabs, sem, act):
    """``act`` on each page copy of KV chunk ``c`` of the sequence at row
    ``slot`` of the tables into buffer ``buf``, whose semaphore they
    signal; c None: same-shaped copies, to wait on. ``slabs(src, j, at)``:
    the (source, destination) refs of pool page ``src`` as the chunk's
    page ``j``, whose rows are ``at``; ``lo_page`` None: no lower clamp
    (one scalar operation fewer a copy, and issuing the copies is what
    bounds a walk over slabs this small). A loop, not ``ppc`` unrolled
    copies: tracing the descriptors is most of what lowering a step
    program costs."""
    def page(j, carry):
        src = 0
        if c is not None:
            # pages past the tile's last (or below the window's band)
            # repeat a live one: their rows are masked by position
            nth = c * ppc + j
            src = tbl_ref[slot, jnp.minimum(nth, hi_page) if lo_page is None
                          else jnp.clip(nth, lo_page, hi_page)]
        at = pl.ds(pl.multiple_of(j * block, block), block)
        for pool, dst in slabs(src, j, at):
            act(pltpu.make_async_copy(pool, dst, sem.at[buf]))
        return carry

    jax.lax.fori_loop(0, ppc, page, 0)


def _walk_chunks(c_lo, c_hi, copies, fold):
    """A tile's KV chunks ``c_lo`` (None: 0, no window) .. ``c_hi``, two in
    flight: ``copies(c, buf, act)`` are chunk c's page copies into buffer
    ``buf``, chunk c + 1's start before chunk c's are waited on, and
    ``fold(c, buf)`` folds the chunk that has landed into the running
    softmax."""
    start = lambda d: d.start()
    first = 0 if c_lo is None else c_lo
    copies(first, 0, start)

    def chunk(c, carry):
        buf = (c if c_lo is None else c - c_lo) % 2

        @pl.when(c < c_hi)
        def _next():
            copies(c + 1, 1 - buf, start)

        copies(None, buf, lambda d: d.wait())
        fold(c, buf)
        return carry

    jax.lax.fori_loop(first, c_hi + 1, chunk, 0)


def _fold_scores(s, weighted, m_scr, l_scr, acc_scr, at=...):
    """One chunk's masked scores ``s`` [.., rows, span] folded into the
    online softmax (flash-2 style) the three scratches hold at ``at``, an
    index of their rows (all of them unless given): the running max and
    sum, one value a row across 128 lanes, and the weighted sum of values,
    to which ``weighted(probabilities)`` adds the chunk's."""
    one = (at, slice(0, 1))
    m_prev = m_scr[one]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pr = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    lanes = m_new.shape[:-1] + (LANES,)
    l_scr[at] = jnp.broadcast_to(
        l_scr[one] * corr + jnp.sum(pr, axis=-1, keepdims=True), lanes)
    acc_scr[at] = acc_scr[at] * corr + weighted(pr)
    m_scr[at] = jnp.broadcast_to(m_new, lanes)


def _tile_kernel(work_ref, tbl_ref, q_ref, *rest, scale: float, block: int,
                 ppc: int, tq: int, window: int, kv_bits: int,
                 attn_block: int = 1):
    """One grid step = one query tile; its KV chunks in a loop whose trip
    count is the tile's own, pages copied by hand from the pool in HBM,
    two chunks in flight. Nothing here knows the caller's page bucket: a
    table read is held inside the table by its own width."""
    if kv_bits:
        k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, \
            m_scr, l_scr, acc_scr = rest
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest
    hkv, group, _, hd = q_ref.shape
    rows = group * tq
    span = ppc * block
    i = pl.program_id(0)
    r0, n = work_ref[1, i], work_ref[2, i]
    slot, pos0 = work_ref[3, i], work_ref[4, i]
    last = pos0 + n - 1
    if attn_block > 1:       # the last row sees its block to the end
        last = last | (attn_block - 1)
    low = jnp.maximum(pos0 - (window - 1), 0) if window > 0 else 0
    lo_page = low // block
    # (a no-op under the caller's contract, positions inside the table)
    hi_page = jnp.minimum(last // block, tbl_ref.shape[1] - 1)
    c_lo, c_hi = low // span, last // span          # the tile's chunks

    def copies(c, buf, act):
        def slabs(src, j, at):
            pairs = [(k_hbm.at[src], kbuf.at[buf, :, at, :]),
                     (v_hbm.at[src], vbuf.at[buf, :, at, :])]
            if kv_bits:
                pairs += [(ks_hbm.at[src], ksbuf.at[buf, j]),
                          (vs_hbm.at[src], vsbuf.at[buf, j])]
            return pairs

        _page_copies(tbl_ref, slot, c, buf, ppc, block, lo_page, hi_page,
                     slabs, sem, act)

    def fold(c, buf):
        q = q_ref[...].reshape(hkv, rows, hd)        # row = g * tq + r
        k, v = kbuf[buf], vbuf[buf]                  # [hkv, span, hd]
        if kv_bits:
            ks = jnp.concatenate([ksbuf[buf, j] for j in range(ppc)], axis=1)
            vs = jnp.concatenate([vsbuf[buf, j] for j in range(ppc)], axis=1)
            q, k, v = _dequantize(q, k, v, ks, vs, kv_bits)
        s = _dot(q, k, (((2,), (2,)), ((0,), (0,)))) * scale  # [hkv, rows, span]
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0) % tq
        key = c * span + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        qpos = pos0 + (r - r0)
        seen_to = qpos | (attn_block - 1) if attn_block > 1 else qpos
        visible = (r >= r0) & (r < r0 + n) & (key <= seen_to)
        if window > 0:
            visible = visible & (key > qpos - window)
        _fold_scores(
            jnp.where(visible[None], s, NEG_INF),
            lambda pr: _dot(pr.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,)))),
            m_scr, l_scr, acc_scr)

    @pl.when(n > 0)
    def _tile():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _walk_chunks(c_lo, c_hi, copies, fold)
        # every row of a tile sees its own key, so l > 0 there; the
        # block's other rows belong to other tiles and keep what they hold
        out = (acc_scr[...] / l_scr[:, :, :1]).reshape(hkv, group, tq, hd)
        r = jax.lax.broadcasted_iota(jnp.int32, (tq, hd), 0)
        mine = (r >= r0) & (r < r0 + n)
        o_ref[...] = jnp.where(mine[None, None], out.astype(o_ref.dtype),
                               o_ref[...])


# jitted on its own: the layers of a step call it with the same shapes, so
# the kernel is traced, and lowered for Mosaic, once a program and not once
# a layer (tracing a chunk's 64 copy descriptors is most of what a step
# program's lowering costs)
@functools.partial(jax.jit, static_argnames=(
    "scale", "ppc", "tq", "window", "kv_bits", "interpret", "attn_block"))
def _tiled(q, k_pool, v_pool, tables, positions, slots, work, *, scale, ppc,
           tq, window, k_scale, v_scale, kv_bits, interpret, attn_block=1):
    """The grid over query tiles (module docstring). A tile's chunks are
    its own positions', so no page bucket is a key of this program."""
    T, hq, hd = q.shape
    _, hkv, block, hd_p = k_pool.shape
    group = hq // hkv
    if work is None:
        work = work_list(slots, positions, tables.shape[0], tq)
    pad = (-T) % tq
    # [hkv, group, T, hd]: a tile's rows, group by group, are the rows of
    # one matmul against the chunk's keys
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(T + pad, hkv, group, hd).transpose(1, 2, 0, 3)
    quant = k_scale is not None
    span = ppc * block
    rows = group * tq
    q_spec = pl.BlockSpec((hkv, group, tq, hd),
                          lambda i, work, tbl: (0, 0, work[0, i], 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quant else [])
    scratch = [pltpu.VMEM((2, hkv, span, hd_p), k_pool.dtype),
               pltpu.VMEM((2, hkv, span, hd_p), v_pool.dtype)]
    if quant:
        scratch += [pltpu.VMEM((2, ppc, hkv, block), k_scale.dtype),
                    pltpu.VMEM((2, ppc, hkv, block), v_scale.dtype)]
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hkv, rows, LANES), jnp.float32),
                pltpu.VMEM((hkv, rows, LANES), jnp.float32),
                pltpu.VMEM((hkv, rows, hd), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_tile_kernel, scale=scale, block=block, ppc=ppc,
                          tq=tq, window=window, kv_bits=kv_bits if quant else 0,
                          attn_block=attn_block),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(work.shape[1],),
            in_specs=[q_spec] + [hbm] * len(pools), out_specs=q_spec,
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        # the name the profiler's trace (and the benchmark's breakdown)
        # knows the kernel by
        name="paged_attention",
        interpret=interpret,
    )(work, tables, qg, *pools)
    out = out.transpose(2, 0, 1, 3)[:T].reshape(T, hq, hd)
    # a lane that is not live belongs to no tile: nothing wrote its row
    return jnp.where((slots >= 0)[:, None, None], out, 0)


def tiled_grid(*leaves) -> bool:
    """Whether :func:`paged_attention` takes the grid over query tiles for
    a pool with these leaves (``k_pool``, and a quantized pool's
    ``k_scale``; anything with a ``shape``). Mosaic refuses a
    hand-rolled copy of a slab under 128 lanes wide, so that grid takes
    pools whose every leaf has whole lanes: a payload row of head_dim 128
    or 256, or of :func:`heads_a_row` smaller heads side by side
    (``inference/kv_cache.pool_leaves`` lays eight heads of 64 out as four
    rows of 128), and a quantized pool's scale rows [.., block] where a
    page is that long. What keeps the lane grid: a quantized pool at the
    usual 16-token pages (its scale rows are 16 wide), and a head geometry
    that does not fill whole rows of 128 or does not divide over the model
    axis in whole rows. A caller that keys its programs by the page bucket
    (``inference/ragged.py``) asks here whether the bucket is read at all."""
    return all(a.shape[-1] % LANES == 0 for a in leaves)


def paged_attention(q, k_pool, v_pool, tables, positions, *,
                    seq_slots=None, work=None, scale=None,
                    pages_per_chunk: int | None = None,
                    live_pages: int | None = None,
                    window: int = 0,
                    k_scale=None, v_scale=None, kv_bits: int = 8,
                    tile_rows: int | None = None,
                    interpret: bool = False, attn_block: int = 1):
    """Attention of ragged query lanes over a paged KV pool. See module
    docstring for the layout contract. Causal by construction: token t sees
    pool rows with position <= positions[t] along its own page list.

    ``tables`` is per-token [T, max_pages] by default. For ragged batches
    where many tokens share a sequence (SplitFuse prefill chunks), pass
    per-sequence tables [n_seqs, max_pages] plus ``seq_slots`` [T] mapping
    each token to its table row; a slot < 0 marks a lane that is not live
    (its output row is zeros). The tables are prefetched scalars and must
    fit SMEM (a [4096, 128] per-token table is 2 MB and does not).

    ``work`` is :func:`work_list` of the same slots and positions, for a
    caller with many layers to build it once a step; built here if absent.
    ``tile_rows`` overrides :func:`query_tile` (tests and tuning only).

    ``live_pages`` (static): caller guarantees every positions[t] <
    live_pages * block; pages beyond are never read. It makes the lane
    grid, whose steps are lanes x chunks of that many pages; the grid over
    query tiles (:func:`tiled_grid`) walks a tile's own chunks and does
    not read it, so its program is the same whatever the bucket.

    ``window`` > 0 (static) bands attention to the trailing ``window``
    positions (Mistral/Qwen2 sliding-window serving): a tile starts at
    the chunk its first row's band reaches, so compute and traffic are
    O(window), not O(context).

    ``attn_block`` > 1 (static, a power of two that divides ``block``):
    causal over blocks of that many positions, token t sees rows up to
    ``positions[t] | (attn_block - 1)`` (module docstring); the caller has
    written the whole block's rows. Not with a window.

    ``k_scale``/``v_scale`` [n_pages, hkv, block] switch the pools to
    quantized storage (``ops/quantizer.quantize_kv``; int8 payload, or
    nibble-packed uint8 [..., hd//2] at ``kv_bits=4``): a page's scales are
    copied beside its payload (half/quarter the bytes of an fp pool) and
    the payload dequantizes in VMEM right before the QK^T matmul. The int8
    variant compiles for the v5e (tests/test_tpu_compile.py); the int4
    variant does not and raises :class:`Int4KVKernelUnsupported` outside
    interpret mode."""
    T, hq, hd = q.shape
    n_pages, rows, block, width = k_pool.shape
    quant = k_scale is not None
    if quant:
        _check_quant_geometry(k_pool, hd, kv_bits)
        if kv_bits == 4 and not interpret:
            raise Int4KVKernelUnsupported()
    # KV heads a row of the pool holds (heads_a_row), read off the row
    pack = 1 if quant else max(1, width // hd)
    max_pages = tables.shape[1]
    assert hq % (rows * pack) == 0
    _check_attn_block(attn_block, block, window)
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    common = dict(scale=scale, window=int(window),  # dslint: disable=host-sync -- window is a static Python int kernel parameter, never a tracer
                  k_scale=k_scale, v_scale=v_scale,
                  kv_bits=int(kv_bits),  # dslint: disable=host-sync -- kv_bits is a static Python int kernel parameter, never a tracer
                  interpret=interpret)
    if attn_block > 1:     # a key of the jitted kernel only where it is used
        common["attn_block"] = int(attn_block)  # dslint: disable=host-sync -- attn_block is a static Python int kernel parameter, never a tracer
    if tiled_grid(k_pool, *((k_scale,) if quant else ())):
        slots = jnp.arange(T, dtype=jnp.int32) if seq_slots is None \
            else seq_slots.astype(jnp.int32)
        if pack > 1:
            # each head's values in its KV head's lanes of a whole row and
            # zeros in the others: [T, rows, pack (KV head), group, pack
            # (its lanes), hd]. The kernel sees ``rows`` KV heads of 128
            # with a GQA group of pack * group
            own = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
            q = (q.reshape(T, rows, pack, -1, 1, hd) * own) \
                .reshape(T, hq, width)
        out = _tiled(q, k_pool, v_pool, tables, positions, slots, work,
                     ppc=pages_per_chunk or chunk_pages(block),
                     tq=tile_rows or query_tile(T), **common)
        if pack > 1:
            out = out.reshape(T, rows, pack, -1, pack, hd)
            out = jnp.stack([out[:, :, j, :, j] for j in range(pack)], 2) \
                .reshape(T, hq, hd)
        return out
    walk_pages = max_pages if live_pages is None \
        else max(1, min(live_pages, max_pages))
    return _lane_grid(q, k_pool, v_pool, tables, positions,
                      None if seq_slots is None
                      else jnp.maximum(seq_slots, 0).astype(jnp.int32),
                      ppc=min(pages_per_chunk or chunk_pages(block),
                              walk_pages),
                      walk_pages=walk_pages, **common)


def _lane_grid(q, k_pool, v_pool, tables, positions, seq_slots, *, scale, ppc,
               walk_pages, window, k_scale, v_scale, kv_bits, interpret,
               attn_block=1):
    """The grid (lanes, chunks of the page bucket): every page a BlockSpec
    input, for pools with a leaf under 128 lanes wide."""
    T, hq, hd = q.shape
    n_pages, hkv, block, _ = k_pool.shape
    quant = k_scale is not None
    max_pages = tables.shape[1]
    group = hq // hkv
    nchunks = -(-walk_pages // ppc)

    qg = q.reshape(T, hkv, group, hd)
    if seq_slots is None:
        scalars = (tables, positions)
    else:
        scalars = (tables, seq_slots, positions)
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
            # (a block of attn_block positions lies inside one page)
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
        functools.partial(_lane_kernel, scale=scale, block=block, hkv=hkv,
                          group=group, ppc=ppc, num_scalars=len(scalars),
                          window=window,
                          kv_bits=kv_bits if quant else 0,
                          attn_block=attn_block),
        out_shape=jax.ShapeDtypeStruct((T, hkv, group, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*scalars, *operands)
    return out.reshape(T, hq, hd)


def write_kv_rows(leaf, page, row, new):
    """Write one new row per ragged lane into a pool leaf, in place and in
    the layout the kernel above reads: an XLA scatter. The step's form off
    the TPU, under tensor parallelism (GSPMD partitions it by the head
    index) and for a quantized pool's leaves, and the oracle
    :func:`write_kv_pages` is held to; it costs an index a (lane, head)
    whatever is live (71 ns each on a v5e: PERF.md, PR 38).

    ``leaf`` is a payload leaf [n_pages, hkv, block, hd] (``new`` [T, hkv,
    hd]; heads that share a row of the leaf, :func:`heads_a_row`, are one
    index) or a ``kv_quant`` scale leaf [n_pages, hkv, block] (``new`` [T,
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
        _rows_of(new, leaf).astype(leaf.dtype))


# tiles the writer keeps in flight: a tile's page reads are started this
# many tiles, less one, ahead of the tile that changes them (2, 4 and 8
# read the same on the v5e within 7%: PERF.md, PR 38)
WRITE_DEPTH = 4


def _write_kernel(work_ref, tbl_ref, nk_ref, nv_ref, k_in, v_in, k_out, v_out,
                  kbuf, vbuf, plan, rsem, wsem, *, tq: int):
    """One call = every live tile of the step's work list, in a loop with
    the list's own trip count: a tile's page slabs come from the leaf in
    HBM into a ring of ``WRITE_DEPTH`` buffers, the tile's rows go in, the
    slabs go back. Reads run ``WRITE_DEPTH - 1`` tiles ahead of the tile
    that changes them; a buffer is read into again once its write-back has
    landed. Little code on purpose: tracing and lowering it is paid once a
    step program, at every start of a server."""
    del k_in, v_in          # the outputs are the same buffers (aliased)
    depth = WRITE_DEPTH
    _, hkv, block, hd = k_out.shape
    nt = work_ref.shape[1]
    limit = tbl_ref.shape[1] * block      # the context's end
    i32, lax = jnp.int32, jax.lax

    def lay_out(i, carry):
        """Tile i's plan, once a call: its rows inside the context (rows at
        or past its end, the tail of ``decode_steps``, write nothing), its
        first page, its pages, and whether its first page is the tile
        before's last (a tile boundary inside a page): that page is not
        read from HBM, where the earlier tile's rows have not landed; the
        earlier tile hands its slab on in VMEM."""
        live, slot_was, last_was = carry
        slot, pos0 = work_ref[3, i], work_ref[4, i]
        n = lax.max(lax.min(work_ref[2, i], limit - pos0), 0)
        pg0 = lax.div(pos0, i32(block))
        npg = lax.select(n > 0, lax.div(pos0 + n - 1, i32(block)) - pg0 + 1,
                         i32(0))
        plan[0, i], plan[1, i], plan[2, i] = n, pg0, npg
        plan[3, i] = ((npg > 0) & (slot == slot_was)
                      & (pg0 == last_was)).astype(i32)
        return (live + (work_ref[2, i] > 0).astype(i32), slot,
                lax.select(npg > 0, pg0 + npg - 1, i32(-1)))

    n_live, _, _ = lax.fori_loop(0, nt, lay_out, (i32(0), i32(-1), i32(-1)))

    def pages(i, out: bool, act):
        """``act`` on the copy of each of tile i's pages, K and V: pool ->
        its buffer, or (``out``) back. A loop, as ``_tile_kernel.copies``."""
        slot, pg0, buf = work_ref[3, i], plan[1, i], lax.rem(i, i32(depth))

        def page(j, carry):
            at = tbl_ref[slot, pg0 + j]
            for pool, vm in ((k_out, kbuf), (v_out, vbuf)):
                ends = (vm.at[buf, j], pool.at[at]) if out \
                    else (pool.at[at], vm.at[buf, j])
                act(pltpu.make_async_copy(*ends,
                                          (wsem if out else rsem).at[buf]))
            return carry

        lax.fori_loop(0 if out else plan[3, i], plan[2, i], page, 0)

    start, wait = (lambda d: d.start()), (lambda d: d.wait())

    def put_rows(i):
        r0, n, pg0, npg = work_ref[1, i], plan[0, i], plan[1, i], plan[2, i]
        buf = lax.rem(i, i32(depth))
        lanes = pl.ds(pl.multiple_of(work_ref[0, i] * tq, tq), tq)
        first = pg0 * block - work_ref[4, i] + r0

        def page(j, carry):
            # the page's row p holds the tile's row p + d
            d = first + j * block
            r = d + lax.broadcasted_iota(i32, (hkv, block, hd), 1)
            mine = (r >= r0) & (r < r0 + n)
            for new, vm in ((nk_ref, kbuf), (nv_ref, vbuf)):
                # a sublane rotation is a 32-bit operation: through float32
                # and back, which keeps every bit of a 16-bit float
                rows = new[:, lanes, :].astype(jnp.float32)
                if block > tq:      # a page longer than a tile
                    rows = jnp.concatenate(
                        [rows, jnp.zeros((hkv, block - tq, hd), rows.dtype)], 1)
                rows = pltpu.roll(rows, (-d) % max(tq, block), 1)[:, :block, :]
                vm[buf, j] = lax.select(mine, rows.astype(vm.dtype), vm[buf, j])
            return carry

        lax.fori_loop(0, npg, page, 0)

        @pl.when(plan[3, jnp.minimum(i + 1, nt - 1)] > 0)
        def _hand_on():     # only a live tile's flag is ever set
            nxt = lax.rem(i + 1, i32(depth))
            kbuf[nxt, 0] = kbuf[buf, npg - 1]
            vbuf[nxt, 0] = vbuf[buf, npg - 1]

    lax.fori_loop(0, lax.min(n_live, i32(depth - 1)),
                  lambda i, c: pages(i, False, start) or c, 0)

    def one(i, carry):
        @pl.when(i > 0)
        def _landed():          # frees the buffer the next read takes
            pages(i - 1, True, wait)

        @pl.when(i < n_live)    # the last round only waits
        def _tile():
            @pl.when(i + depth - 1 < n_live)
            def _ahead():
                pages(i + depth - 1, False, start)

            pages(i, False, wait)
            put_rows(i)
            pages(i, True, start)

        return carry

    lax.fori_loop(0, n_live + 1, one, 0)


# jitted on its own, as ``_tiled`` and for its reason
@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def _write_pages(k_leaf, v_leaf, new_k, new_v, tables, work, *, tq, interpret):
    T, hkv, hd = new_k.shape
    block = k_leaf.shape[2]
    pad = (-T) % tq
    # [hkv, T, hd]: a tile's rows of one head are the sublanes of one slab
    rows = lambda a: jnp.pad(a.astype(k_leaf.dtype), ((0, pad), (0, 0), (0, 0))) \
        .transpose(1, 0, 2)
    # a tile's rows, wherever its first lies in a page, reach this many
    # pages at most (tq / block + 1 where a page is no longer than a tile)
    slabs = (WRITE_DEPTH, (tq + block - 2) // block + 1, hkv, block, hd)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_write_kernel, tq=tq),
        out_shape=(jax.ShapeDtypeStruct(k_leaf.shape, k_leaf.dtype),
                   jax.ShapeDtypeStruct(v_leaf.shape, v_leaf.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[vmem, vmem, hbm, hbm], out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.VMEM(slabs, k_leaf.dtype),
                            pltpu.VMEM(slabs, v_leaf.dtype),
                            pltpu.SMEM((4, work.shape[1]), jnp.int32),
                            pltpu.SemaphoreType.DMA((WRITE_DEPTH,)),
                            pltpu.SemaphoreType.DMA((WRITE_DEPTH,))]),
        # both leaves are written where they lie: operands 4 and 5 (after
        # the two prefetched scalars and the new rows) are results 0 and 1
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="write_kv_pages",
        interpret=interpret,
    )(work, tables, rows(new_k), rows(new_v), k_leaf, v_leaf)


def write_kv_pages(k_leaf, v_leaf, new_k, new_v, tables, work, *,
                   tile_rows: int | None = None, interpret: bool = False):
    """Write the step's new K and V rows into one layer's two payload
    leaves [n_pages, hkv, block, hd], in place and in the layout the kernel
    above reads: ONE Pallas call returns both leaves, over the same
    ``work`` list (:func:`work_list`) the paged kernel walks.

    ``new_k`` / ``new_v`` [T, hkv, hd] hold a row a lane (heads that share
    a 128-lane row of the leaf, :func:`heads_a_row`, are written as the
    one row they are); lane t's row lands in page
    ``tables[slot, pos // block]`` at row ``pos % block``. A
    tile is a stretch of one sequence's lanes at consecutive positions, so
    its rows land in at most ``tile_rows / block + 1`` pages of that
    sequence: the call brings each such page's [hkv, block, hd] slab (one
    contiguous piece of the leaf) into VMEM, puts the tile's rows in and
    sends it back, :data:`WRITE_DEPTH` tiles in flight. A bf16 row shares
    its 32-bit words with its neighbour in the leaf's tiled layout, so the
    slab, not the row, is what a copy can move. What the call costs follows
    the live tiles alone: a lane that is not live belongs to no tile, and a
    lane at or past the context's end (``tables.shape[1] * block``: the
    tail of ``decode_steps``) writes nothing, where the scatter
    (:func:`write_kv_rows`) pays one index a (lane, head) whatever is live
    and sends those lanes to a sink page.

    ``tables`` is the pass's own for a looped stack (``block_tables + t *
    stride``). ``tile_rows`` must be what ``work`` was cut with."""
    T = new_k.shape[0]
    return _write_pages(k_leaf, v_leaf, _rows_of(new_k, k_leaf),
                        _rows_of(new_v, v_leaf), tables.astype(jnp.int32), work,
                        tq=tile_rows or query_tile(T), interpret=interpret)


# ----------------------------------------------------------------------
# latent attention: every head over one shared row a token

#: query lanes a tile of the latent kernel holds, whatever the lane bucket:
#: with 64 heads folded into its rows a tile of 16 lanes is a 1024-row
#: matmul against a chunk, past the ridge already, and a decode tile takes
#: the branch of its one live lane (64 rows)
LATENT_TILE = 16


def _latent_kernel(work_ref, tbl_ref, q_ref, pool, o_ref, buf, sem, m_scr,
                   l_scr, acc_scr, *, scale: float, block: int, ppc: int,
                   tq: int, v_dim: int):
    """One grid step = one query tile of ``tq`` lanes x ``h`` heads against
    its own context's chunks of one leaf, two chunks in flight, as
    ``_tile_kernel``. q and the output are [lanes, heads, values] blocks,
    the heads the sublanes, so a lane is a leading index: a tile of one
    live lane (a decode token) multiplies that lane's ``h`` rows alone,
    any other all ``tq * h`` under the mask. V is the chunk's first
    ``v_dim`` lanes."""
    _, h, w = q_ref.shape
    span = ppc * block
    i = pl.program_id(0)
    r0, n = work_ref[1, i], work_ref[2, i]
    slot, pos0 = work_ref[3, i], work_ref[4, i]
    last = pos0 + n - 1
    hi_page = jnp.minimum(last // block, tbl_ref.shape[1] - 1)
    c_hi = last // span

    def copies(c, b, act):
        _page_copies(tbl_ref, slot, c, b, ppc, block, None, hi_page,
                     lambda src, j, at: [(pool.at[src, 0], buf.at[b, at, :])],
                     sem, act)

    def walk(rows: int, q_of, visible_of):
        """The tile's chunks against ``rows`` query rows (the first
        ``rows`` of each scratch)."""
        at = slice(0, rows)
        m_scr[:rows] = jnp.full((rows, LANES), NEG_INF, jnp.float32)
        l_scr[:rows] = jnp.zeros((rows, LANES), jnp.float32)
        acc_scr[:rows] = jnp.zeros((rows, v_dim), jnp.float32)

        def fold(c, b):
            kv = buf[b]                                   # [span, w]
            s = _dot(q_of(), kv, (((1,), (1,)), ((), ()))) * scale
            key = c * span + jax.lax.broadcasted_iota(
                jnp.int32, (rows, span), 1)
            _fold_scores(
                jnp.where(visible_of(key), s, NEG_INF),
                lambda pr: _dot(pr.astype(kv.dtype), kv[:, :v_dim],
                                (((1,), (0,)), ((), ()))),
                m_scr, l_scr, acc_scr, at)

        _walk_chunks(None, c_hi, copies, fold)
        return acc_scr[:rows] / l_scr[:rows, :1]

    @pl.when(n == 1)
    def _one_lane():
        out = walk(h, lambda: q_ref[r0], lambda key: key <= pos0)
        o_ref[r0] = out.astype(o_ref.dtype)

    @pl.when(n > 1)
    def _tile():
        rows = tq * h

        def visible(key):
            r = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0) // h
            return (r >= r0) & (r < r0 + n) & (key <= pos0 + (r - r0))

        out = walk(rows, lambda: q_ref[...].reshape(rows, w), visible)
        r = jax.lax.broadcasted_iota(jnp.int32, (tq, h, v_dim), 0)
        mine = (r >= r0) & (r < r0 + n)
        o_ref[...] = jnp.where(
            mine, out.reshape(tq, h, v_dim).astype(o_ref.dtype), o_ref[...])


# jitted on its own, as ``_tiled`` and for its reason
@functools.partial(jax.jit, static_argnames=(
    "scale", "ppc", "tq", "v_dim", "interpret"))
def _latent_tiled(q, pool, tables, slots, work, *, scale, ppc, tq, v_dim,
                  interpret):
    T, h, w = q.shape
    block = pool.shape[2]
    pad = (-T) % tq
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    span = ppc * block
    rows = tq * h
    spec = lambda width: pl.BlockSpec(
        (tq, h, width), lambda i, work, tbl: (work[0, i], 0, 0))
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, block=block, ppc=ppc,
                          tq=tq, v_dim=v_dim),
        out_shape=jax.ShapeDtypeStruct((T + pad, h, v_dim), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(work.shape[1],),
            in_specs=[spec(w), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec(v_dim),
            scratch_shapes=[pltpu.VMEM((2, span, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((rows, LANES), jnp.float32),
                            pltpu.VMEM((rows, LANES), jnp.float32),
                            pltpu.VMEM((rows, v_dim), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="latent_attention",
        interpret=interpret,
    )(work, tables, qp, pool)
    # a lane that is not live belongs to no tile: nothing wrote its row
    return jnp.where((slots >= 0)[:, None, None], out[:T], 0)


def latent_attention(q, pool, tables, positions, seq_slots, work=None, *,
                     scale: float, v_dim: int,
                     pages_per_chunk: int | None = None,
                     interpret: bool = False):
    """Attention of ragged query lanes, every head over one shared row a
    token (MLA in its absorbed form), causal along each lane's page list.

      q:     [T, h, row]            a head's absorbed query beside its
                                    rotated part, zeros where the row's pad
      pool:  [n_pages, 1, block, row]   a token's latent, rotated key, pad
      tables [n_seqs, max_pages], seq_slots [T] (< 0: no lane), positions [T]

    Scores are over the whole row, the weighted sum over its first
    ``v_dim`` values: returns [T, h, v_dim]. ``row`` and ``v_dim`` are
    whole lanes of 128. ``work``: :func:`work_list` of the same slots and
    positions cut with :data:`LATENT_TILE`; built here if absent."""
    T, h, w = q.shape
    assert pool.shape[1] == 1 and pool.shape[3] == w, (q.shape, pool.shape)
    assert w % LANES == 0 and v_dim % LANES == 0 and v_dim <= w
    slots = seq_slots.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    if work is None:
        work = work_list(slots, positions, tables.shape[0], LATENT_TILE)
    return _latent_tiled(q, pool, tables, slots, work, scale=scale,
                         ppc=pages_per_chunk or chunk_pages(pool.shape[2]),
                         tq=LATENT_TILE, v_dim=v_dim, interpret=interpret)


def latent_attention_reference(q, pool, tables, positions, *, scale: float,
                               v_dim: int):
    """jnp reference (gather-based) of :func:`latent_attention`, with
    per-token ``tables`` [T, max_pages]: the oracle and the form off the
    TPU."""
    T, h, w = q.shape
    rows = pool[tables][:, :, 0].reshape(T, -1, w).astype(jnp.float32)
    logits = jnp.einsum("thw,tkw->thk", q.astype(jnp.float32), rows) * scale
    visible = jnp.arange(rows.shape[1])[None, :] <= positions[:, None]
    logits = jnp.where(visible[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("thk,tkv->thv", probs,
                      rows[..., :v_dim]).astype(q.dtype)


def paged_attention_reference(q, k_pool, v_pool, tables, positions, *,
                              scale=None, window: int = 0,
                              k_scale=None, v_scale=None, kv_bits: int = 8,
                              attn_block: int = 1):
    """jnp reference (gather-based) with identical semantics — the numerics
    oracle for the kernel and the off-TPU fallback formulation.
    ``window`` > 0 bands attention to the trailing ``window`` positions
    (sliding-window serving: k > pos - window). ``attn_block`` > 1: a lane
    sees rows up to ``pos | (attn_block - 1)`` (block diffusion).

    ``k_scale``/``v_scale`` [n_pages, hkv, block] switch the pools to
    quantized storage (``ops/quantizer.quantize_kv``): int8 payloads —
    or, at ``kv_bits=4``, nibble-packed uint8 [..., hd//2] — are
    dequantized AFTER the per-token page gather (only pages actually
    read pay the dequant, mirroring the kernel's in-VMEM dequant)."""
    from ..quantizer import dequantize_kv

    T, hq, hd = q.shape
    n_pages, hkv, block, width = k_pool.shape
    if k_scale is None:          # heads that share a row of the pool
        hkv *= max(1, width // hd)
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    group = hq // hkv
    _check_attn_block(attn_block, block, window)
    seen_to = positions | (attn_block - 1) if attn_block > 1 else positions
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
        visible = kv_pos <= seen_to[:, None]
        if window > 0:
            visible = visible & (kv_pos > positions[:, None] - window)
        logits = jnp.where(visible[:, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("thk,tkhd->thd", probs, vals).astype(q.dtype)
    # [T, max_pages, hkv, block, hd] -> [T, ctx, hkv, hd]
    keys = unpack_heads(k_pool[tables], hd).transpose(0, 2, 1, 3, 4).reshape(
        T, hkv, -1, hd).transpose(0, 2, 1, 3)
    vals = unpack_heads(v_pool[tables], hd).transpose(0, 2, 1, 3, 4).reshape(
        T, hkv, -1, hd).transpose(0, 2, 1, 3)
    keys = jnp.repeat(keys, group, axis=2)
    vals = jnp.repeat(vals, group, axis=2)
    logits = jnp.einsum("thd,tkhd->thk", q.astype(jnp.float32),
                        keys.astype(jnp.float32)) * scale
    kv_pos = jnp.arange(keys.shape[1])[None, :]
    visible = kv_pos <= seen_to[:, None]
    if window > 0:
        visible = visible & (kv_pos > positions[:, None] - window)
    logits = jnp.where(visible[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("thk,tkhd->thd", probs,
                      vals.astype(jnp.float32)).astype(q.dtype)
