"""Pallas paged-attention kernel: interpret-mode numerics vs the jnp
reference oracle and vs dense attention on an equivalent layout.

Reference surface: FastGen ragged kernels
(inference/v2/kernels/ragged_ops/blocked_flash) — VERDICT round-1 missing
item #7.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference)


def _random_paged(rng, T, hq, hkv, hd, n_pages, block, max_pages, dtype):
    q = jnp.asarray(rng.standard_normal((T, hq, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, block, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, block, hd)), dtype)
    # distinct pages per token row (simulate per-sequence tables)
    tables = jnp.asarray(
        rng.permutation(n_pages)[: T * max_pages].reshape(T, max_pages)
        if n_pages >= T * max_pages else
        rng.integers(0, n_pages, (T, max_pages)), jnp.int32)
    positions = jnp.asarray(
        rng.integers(0, max_pages * block, (T,)), jnp.int32)
    return q, kp, vp, tables, positions


@pytest.mark.parametrize("hq,hkv,hd,block", [
    (8, 8, 64, 16), (8, 2, 64, 16), (4, 1, 128, 16), (8, 4, 64, 32)])
def test_paged_kernel_matches_reference(hq, hkv, hd, block):
    rng = np.random.default_rng(0)
    T, n_pages, max_pages = 8, 64, 4
    q, kp, vp, tables, positions = _random_paged(
        rng, T, hq, hkv, hd, n_pages, block, max_pages, jnp.float32)
    ref = paged_attention_reference(q, kp, vp, tables, positions)
    got = paged_attention(q, kp, vp, tables, positions, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_bf16():
    rng = np.random.default_rng(1)
    q, kp, vp, tables, positions = _random_paged(
        rng, 16, 8, 4, 64, 128, 16, 4, jnp.bfloat16)
    ref = paged_attention_reference(q, kp, vp, tables, positions)
    got = paged_attention(q, kp, vp, tables, positions, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_paged_matches_dense_decode():
    """A single sequence laid out across pages == dense causal attention on
    the contiguous KV for the last-token decode."""
    rng = np.random.default_rng(2)
    hq, hkv, hd, block, ctx = 8, 4, 64, 16, 96  # 6 pages
    n_pages = 8
    kv_flat = rng.standard_normal((2, ctx, hkv, hd)).astype(np.float32)
    q_last = rng.standard_normal((1, hq, hd)).astype(np.float32)

    pages = list(rng.permutation(n_pages)[:6])
    kp = np.zeros((n_pages, hkv, block, hd), np.float32)
    vp = np.zeros_like(kp)
    for i, pg in enumerate(pages):
        kp[pg] = kv_flat[0, i * block:(i + 1) * block].transpose(1, 0, 2)
        vp[pg] = kv_flat[1, i * block:(i + 1) * block].transpose(1, 0, 2)
    tables = np.asarray([pages], np.int32)
    positions = np.asarray([ctx - 1], np.int32)

    got = paged_attention(jnp.asarray(q_last), jnp.asarray(kp),
                          jnp.asarray(vp), jnp.asarray(tables),
                          jnp.asarray(positions), interpret=True)
    ref = dot_product_attention(
        jnp.asarray(q_last[None]), jnp.asarray(kv_flat[0][None]),
        jnp.asarray(kv_flat[1][None]), causal=True)[0, -1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_positions_mask_tail():
    """Rows beyond a token's position must not contribute: perturbing them
    leaves the output unchanged."""
    rng = np.random.default_rng(3)
    q, kp, vp, tables, positions = _random_paged(
        rng, 4, 4, 4, 64, 32, 16, 4, jnp.float32)
    positions = jnp.asarray([5, 20, 40, 63], jnp.int32)
    base = paged_attention(q, kp, vp, tables, positions, interpret=True)
    # poison every pool row, then rewrite only the visible prefix rows
    kp2 = kp + 100.0
    vp2 = vp - 100.0
    for t in range(4):
        pos = int(positions[t])
        for p in range(pos // 16 + 1):
            pg = int(tables[t, p])
            upto = min(16, pos + 1 - p * 16)
            kp2 = kp2.at[pg, :, :upto].set(kp[pg, :, :upto])
            vp2 = vp2.at[pg, :, :upto].set(vp[pg, :, :upto])
    got = paged_attention(q, kp2, vp2, tables, positions, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_paged_seq_slots_indirection():
    """Per-seq tables + seq_slots must match the expanded per-token path —
    the SplitFuse configuration, where many ragged tokens share a sequence
    and the per-token [T, max_pages] table would not fit SMEM."""
    rng = np.random.default_rng(7)
    S, toks_per_seq, hq, hkv, hd, block, max_pages = 3, 5, 4, 2, 64, 16, 4
    n_pages = S * max_pages + 1
    T = S * toks_per_seq
    q = jnp.asarray(rng.standard_normal((T, hq, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, block, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, block, hd)), jnp.float32)
    seq_tables = jnp.asarray(
        rng.permutation(n_pages - 1)[: S * max_pages].reshape(S, max_pages),
        jnp.int32)
    seq_slots = jnp.repeat(jnp.arange(S, dtype=jnp.int32), toks_per_seq)
    # consecutive positions per sequence, as a prefill chunk would carry
    positions = jnp.concatenate([
        jnp.arange(toks_per_seq, dtype=jnp.int32) + 7 * (s + 1)
        for s in range(S)])
    via_slots = paged_attention(q, kp, vp, seq_tables, positions,
                                seq_slots=seq_slots, interpret=True)
    expanded = paged_attention(q, kp, vp, seq_tables[seq_slots], positions,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(via_slots), np.asarray(expanded),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_windowed_interpret():
    """Banded (sliding-window) paged kernel vs the banded gather
    reference, interpret mode — below-band chunks must be skipped without
    perturbing the online softmax."""
    rng = np.random.default_rng(7)
    T, hq, hkv, hd, blk, mp = 6, 8, 4, 64, 16, 8
    n_pages = T * mp + 1
    q = jnp.asarray(rng.standard_normal((T, hq, hd)), jnp.float32)
    kpool = jnp.asarray(rng.standard_normal((n_pages, hkv, blk, hd)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((n_pages, hkv, blk, hd)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(T * mp).reshape(T, mp), jnp.int32)
    pos = jnp.asarray([3, 17, 40, 63, 100, 127], jnp.int32)
    for w in (16, 33, 128):
        got = paged_attention(q, kpool, vpool, tbl, pos, window=w,
                              interpret=True)
        want = paged_attention_reference(q, kpool, vpool, tbl, pos, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# ----------------------------------------------------------------------
# quantized pools (kv_quant): dequantize inside the read path
# ----------------------------------------------------------------------

def _quantize_pools(kp, vp, bits):
    from deepspeed_tpu.ops.quantizer import quantize_kv

    qk, sk = quantize_kv(kp, bits)
    qv, sv = quantize_kv(vp, bits)
    return qk, qv, sk, sv


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_kernel_quantized_matches_reference(bits):
    """Quantized-pool kernel (interpret mode) vs the quantized gather
    reference: identical dequant arithmetic, so they agree to fp
    tolerance."""
    rng = np.random.default_rng(11)
    T, hq, hkv, hd, block, mp = 8, 8, 4, 64, 4, 4
    n_pages = T * mp
    q, kp, vp, tables, positions = _random_paged(
        rng, T, hq, hkv, hd, n_pages, block, mp, jnp.float32)
    qk, qv, sk, sv = _quantize_pools(kp, vp, bits)
    ref = paged_attention_reference(q, qk, qv, tables, positions,
                                    k_scale=sk, v_scale=sv, kv_bits=bits)
    got = paged_attention(q, qk, qv, tables, positions,
                          k_scale=sk, v_scale=sv, kv_bits=bits,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_quantized_close_to_fp():
    """int8-quantized attention tracks the fp pool within the
    accumulated scale/2 rounding (sanity on the end-to-end error, not a
    bit-exactness claim)."""
    rng = np.random.default_rng(12)
    T, hq, hkv, hd, block, mp = 4, 8, 4, 64, 8, 4
    n_pages = T * mp
    q, kp, vp, tables, positions = _random_paged(
        rng, T, hq, hkv, hd, n_pages, block, mp, jnp.float32)
    qk, qv, sk, sv = _quantize_pools(kp, vp, 8)
    fp = paged_attention_reference(q, kp, vp, tables, positions)
    quant = paged_attention_reference(q, qk, qv, tables, positions,
                                      k_scale=sk, v_scale=sv, kv_bits=8)
    np.testing.assert_allclose(np.asarray(quant), np.asarray(fp),
                               rtol=0.15, atol=0.05)


def test_paged_quantized_int4_packed_shape():
    """int4 payloads are REALLY nibble-packed: the pool leaf carries
    hd//2 uint8 channels, and the kernel unpacks them to the fp result
    the unpacked reference computes."""
    rng = np.random.default_rng(13)
    T, hq, hkv, hd, block, mp = 4, 4, 2, 64, 4, 4
    n_pages = T * mp
    q, kp, vp, tables, positions = _random_paged(
        rng, T, hq, hkv, hd, n_pages, block, mp, jnp.float32)
    qk, qv, sk, sv = _quantize_pools(kp, vp, 4)
    assert qk.shape[-1] == hd // 2 and qk.dtype == jnp.uint8
    got = paged_attention(q, qk, qv, tables, positions,
                          k_scale=sk, v_scale=sv, kv_bits=4,
                          interpret=True)
    ref = paged_attention_reference(q, qk, qv, tables, positions,
                                    k_scale=sk, v_scale=sv, kv_bits=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# the grid over query tiles (head_dim a whole number of 128-lane tiles):
# one grid step a (stretch of one sequence's lanes, that stretch's chunks)
# ----------------------------------------------------------------------
from deepspeed_tpu.ops.pallas import paged_attention as pa

TQ = 8          # tile rows in these tests (the engine's come from query_tile)
PPC = 2         # pages a chunk: 32-token chunks, so short contexts span many


def _ragged_batch(runs, T, n_seqs, *, hq=4, hkv=1, hd=128, block=16,
                  max_pages=12, seed=0, dtype=jnp.float32):
    """runs: (slot, first position, lanes) in batch order; lanes past the
    last run are not live. Per-sequence tables over distinct pages."""
    rng = np.random.default_rng(seed)
    slots = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    c = 0
    for s, p0, n in runs:
        slots[c:c + n] = s
        pos[c:c + n] = np.arange(p0, p0 + n)
        c += n
    n_pages = n_seqs * max_pages
    q = jnp.asarray(rng.standard_normal((T, hq, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((n_pages + 1, hkv, block, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages + 1, hkv, block, hd)), dtype)
    tables = jnp.asarray(rng.permutation(n_pages).reshape(n_seqs, max_pages),
                         jnp.int32)
    return q, kp, vp, tables, slots, pos


def _share_rows(pool):
    """A payload leaf [n, hkv, block, hd] as ``kv_cache.pool_leaves`` lays
    heads under 128 wide out: ``128 / hd`` heads side by side in one row
    of 128 lanes, [n, hkv / pack, block, 128]."""
    n, hkv, block, hd = pool.shape
    pack = pa.heads_a_row(hkv, hd)
    assert pack > 1, (hkv, hd)
    shared = pool.reshape(n, hkv // pack, pack, block, hd) \
        .transpose(0, 1, 3, 2, 4).reshape(n, hkv // pack, block, pack * hd)
    np.testing.assert_array_equal(np.asarray(pa.unpack_heads(shared, hd)),
                                  np.asarray(pool))
    return shared


def _check_tiled(runs, T, n_seqs, *, window=0, tol=2e-5, quant=None,
                 attn_block=1, shared=False, **kw):
    """``shared``: the kernel reads the pool with its heads side by side
    in rows of 128 lanes; the oracle reads the leaf a head a row."""
    q, kp, vp, tables, slots, pos = _ragged_batch(runs, T, n_seqs, **kw)
    scales = {"attn_block": attn_block} if attn_block > 1 else {}
    if quant:
        kp, vp, sk, sv = _quantize_pools(kp, vp, quant)
        scales = dict(k_scale=sk, v_scale=sv, kv_bits=quant)
    pools = (_share_rows(kp), _share_rows(vp)) if shared else (kp, vp)
    got = np.asarray(paged_attention(
        q, *pools, tables, jnp.asarray(pos), seq_slots=jnp.asarray(slots),
        window=window, tile_rows=TQ, pages_per_chunk=PPC, interpret=True,
        **scales))
    assert pa.tiled_grid(pools[0], *((scales["k_scale"],) if quant else ()))
    live = slots >= 0
    assert np.isfinite(got).all()
    assert not got[~live].any()            # lanes of no sequence: zeros
    if live.any():
        args = (tables[slots[live]], jnp.asarray(pos[live]))
        want = np.asarray(paged_attention_reference(
            q[live], kp, vp, *args, window=window, **scales))
        np.testing.assert_allclose(got[live], want, rtol=tol, atol=tol)
        if shared:  # the oracle unpacks the same leaf: bit for bit
            np.testing.assert_array_equal(want, np.asarray(
                paged_attention_reference(q[live], *pools, *args,
                                          window=window, **scales)))
    return pa.work_list(jnp.asarray(slots), jnp.asarray(pos), n_seqs, TQ)


HEAD64_LANES = {
    "decode": [(3, 150, 1), (0, 31, 1), (5, 64, 1), (2, 17, 1), (4, 0, 1)],
    # a prompt chunk crossing three pages and a chunk's edge, behind a
    # decode lane and a speculative run
    "chunk_crossing_pages": [(1, 90, 1), (5, 64, 4), (2, 21, 37)],
}


@pytest.mark.parametrize("attn_block", [1, 4])
@pytest.mark.parametrize("group,hkv", [(4, 2), (1, 4), (4, 8)],
                         ids=["gqa4", "mha", "granite"])
@pytest.mark.parametrize("lanes", sorted(HEAD64_LANES))
def test_tiled_head_size_64_two_heads_a_row(lanes, group, hkv, attn_block):
    """Head size 64 on the tiled grid: two KV heads a 128-lane row of the
    pool, each query head in its KV head's half of a row and zeros in the
    other, the pair's two groups one folded group. Exact against the
    oracle over the leaf a head a row."""
    _check_tiled(HEAD64_LANES[lanes], 64, 6, hq=group * hkv, hkv=hkv, hd=64,
                 attn_block=attn_block, shared=True)


@pytest.mark.parametrize("hd,hkv", [(32, 4), (16, 8)])
def test_tiled_smaller_heads_fill_a_row(hd, hkv):
    """128 / head_dim heads a row: four of 32, eight of 16."""
    _check_tiled(HEAD64_LANES["chunk_crossing_pages"], 64, 6, hq=2 * hkv,
                 hkv=hkv, hd=hd, shared=True)


@pytest.mark.parametrize("window", [0, 33])
def test_tiled_head_size_64_bf16_and_window(window):
    _check_tiled([(1, 77, 1), (0, 0, 20), (2, 130, 9)], 32, 3, hq=8, hkv=2,
                 hd=64, dtype=jnp.bfloat16, tol=2e-2, window=window,
                 shared=True)


@pytest.mark.parametrize("length", [1, TQ - 1, TQ, TQ + 1, 3 * TQ + 5])
@pytest.mark.parametrize("lead", [0, 3], ids=["aligned", "offset"])
def test_tiled_runs_straddle_tile_edges(length, lead):
    """A run of every length around a tile's, behind ``lead`` decode lanes
    so that it starts on a block's edge or inside a block."""
    runs = [(1 + i, 20 + 7 * i, 1) for i in range(lead)] + [(0, 5, length)]
    _check_tiled(runs, 48, 4)


@pytest.mark.parametrize("group,hkv", [(4, 2), (1, 3)], ids=["gqa4", "mha"])
def test_tiled_decode_lanes_and_a_prompt_chunk(group, hkv):
    """Decode lanes, a (k + 1)-lane speculative run and a prompt chunk in
    one batch, slots out of slot order, inactive lanes behind them."""
    runs = [(3, 150, 1), (0, 31, 1), (5, 64, 4), (2, 17, 37), (4, 0, 1)]
    _check_tiled(runs, 64, 6, hq=group * hkv, hkv=hkv)


@pytest.mark.parametrize("first", [0, 21, 40, 95],
                         ids=["position0", "mid_page", "mid_chunk",
                              "chunk_last_row"])
def test_tiled_run_start_positions(first):
    _check_tiled([(1, first, 19)], 32, 2)


def test_tiled_all_inactive_batch():
    """The warm-up's batch: no lane live, so no tile; zeros come back."""
    work = _check_tiled([], 32, 4)
    assert not np.asarray(work)[2].any()


@pytest.mark.parametrize("window", [16, 33, 64, 4096],
                         ids=["w16", "w33", "w64", "not_binding"])
def test_tiled_window(window):
    """The band by ``pos0 + i`` inside a tile: a tile starts at the chunk
    its first row's band reaches, later rows see nothing of it."""
    runs = [(0, 170, 1), (2, 100, 21), (1, 3, 9)]
    _check_tiled(runs, 40, 3, window=window)


@pytest.mark.parametrize("window", [0, 48])
def test_tiled_int8_kv(window):
    """Quantized pools ride the tiles where the scale rows are whole lanes
    (block 128); at block 16 they keep the lane grid (tests above)."""
    runs = [(1, 300, 1), (0, 120, 11)]
    _check_tiled(runs, 16, 2, block=128, max_pages=4, quant=8, window=window)


def test_tiled_bf16():
    runs = [(1, 77, 1), (0, 0, 20), (2, 130, 9)]
    _check_tiled(runs, 32, 3, dtype=jnp.bfloat16, tol=2e-2, hq=8, hkv=2)


def test_tiled_work_list_at_its_bound():
    """Runs that start off every block edge and cross every one: the most
    tiles a legal batch makes, one under the static bound (lane 0 is both
    a run's start and a block's)."""
    runs = [(0, 9, 5), (1, 40, 6), (2, 0, 10), (3, 100, 11)]
    T, n_seqs = 32, 4
    work = np.asarray(_check_tiled(runs, T, n_seqs))
    assert work.shape == (5, T // TQ + n_seqs)
    assert (work[2] > 0).sum() == T // TQ + n_seqs - 1
    assert work[2].sum() == T


@pytest.mark.parametrize("window,attn_block", [(0, 1), (24, 1), (0, 4)],
                         ids=["causal", "window24", "attn_block4"])
def test_tiled_clamp_is_the_tables_width_not_a_bucket(window, attn_block):
    """The tiled kernel holds a table read inside the row by the table's
    own width. Until PR 47 it clamped at the caller's live-page bucket,
    which made seven programs of one: under the caller's contract (every
    position under ``live_pages * block``) neither clamp binds, so the
    result over 12-page tables is bit for bit the result over the same
    tables cut to the bucket's 4 pages (whose width IS the old clamp),
    whatever ``live_pages`` says, and ``live_pages`` is no key of the
    jitted kernel."""
    runs = [(0, 57, 1), (2, 40, 21), (1, 3, 9), (3, 0, 5)]   # all under 64
    q, kp, vp, tables, slots, pos = _ragged_batch(runs, 40, 4)
    kw = dict(seq_slots=jnp.asarray(slots), window=window, tile_rows=TQ,
              pages_per_chunk=PPC, interpret=True)
    if attn_block > 1:
        kw["attn_block"] = attn_block
    run = lambda tb, **more: np.asarray(paged_attention(
        q, kp, vp, tb, jnp.asarray(pos), **kw, **more))
    cut = run(tables[:, :4])
    whole = run(tables)
    programs = pa._tiled._cache_size()
    np.testing.assert_array_equal(whole, cut)
    for bucket in (4, 8, 12):
        np.testing.assert_array_equal(run(tables, live_pages=bucket), cut)
    assert pa._tiled._cache_size() == programs
    live = slots >= 0
    more = {"attn_block": attn_block} if attn_block > 1 else {}
    want = paged_attention_reference(
        q[live], kp, vp, tables[slots[live]], jnp.asarray(pos[live]),
        window=window, **more)
    np.testing.assert_allclose(whole[live], np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("seed", range(4))
def test_tile_counts_agree_with_the_work_list(seed):
    """``ragged.put``'s host counters (q_tiles, kv_steps; write_tiles,
    write_pages) count what the device builds."""
    rng = np.random.default_rng(seed)
    T, n_seqs, block = 64, 8, 16
    takes = rng.integers(1, 14, n_seqs)
    firsts = rng.integers(0, 300, n_seqs)
    runs = [(int(s), int(p), int(n)) for s, p, n in
            zip(rng.permutation(n_seqs), firsts, takes)]
    _, _, _, _, slots, pos = _ragged_batch(runs, T, n_seqs, max_pages=1)
    work = np.asarray(pa.work_list(jnp.asarray(slots), jnp.asarray(pos),
                                   n_seqs, TQ))
    live = work[2] > 0
    span = pa.chunk_pages(block) * block
    last = work[4] + work[2] - 1
    steps = (last // span + 1)[live].sum()
    pages = (last // block - work[4] // block + 1)[live].sum()
    assert pa.tile_counts([(n, p) for _, p, n in runs], TQ, block) \
        == (live.sum(), steps, pages)


@pytest.mark.parametrize("hd,pool,grid", [
    (64, "shared_rows", "_tiled"), (128, "a_head_a_row", "_tiled"),
    (64, "a_head_a_row", "_lane_grid"), (128, "int8", "_lane_grid")])
def test_grid_is_chosen_on_head_dim(monkeypatch, hd, pool, grid):
    """Mosaic refuses a hand-rolled copy under 128 lanes: two heads of 64
    side by side in a row are a slab it takes, and so the tiled grid's; a
    leaf of 64-wide rows (one KV head of 64 cannot fill a row) and a
    quantized pool, whose scale rows are a page's 16 tokens wide, keep the
    (lane, chunk) grid. Chosen on the pool's shapes alone."""
    called = []
    for name in ("_lane_grid", "_tiled"):
        real = getattr(pa, name)
        monkeypatch.setattr(pa, name, lambda *a, _n=name, _f=real, **k:
                            called.append(_n) or _f(*a, **k))
    rng = np.random.default_rng(5)
    q, kp, vp, tables, positions = _random_paged(
        rng, 4, 4, 2, hd, 16, 16, 4, jnp.float32)
    ref = paged_attention_reference(q, kp, vp, tables, positions)
    scales, tol = {}, 2e-5
    if pool == "shared_rows":
        kp, vp = _share_rows(kp), _share_rows(vp)
    elif pool == "int8":
        kp, vp, sk, sv = _quantize_pools(kp, vp, 8)
        scales, tol = dict(k_scale=sk, v_scale=sv, kv_bits=8), 0.1
    got = paged_attention(q, kp, vp, tables, positions, interpret=True,
                          **scales)
    assert called == [grid]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)


# ----------------------------------------------------------------------
# the row writer: one call writes K and V over the step's live tiles
# ----------------------------------------------------------------------
WRITER_LANES = {
    # decode lanes of several sequences, slots out of order, at a page's
    # first and last row; dead lanes behind them
    "decode": [(3, 150, 1), (0, 31, 1), (5, 64, 1), (2, 17, 1), (4, 0, 1)],
    # a chunk of one sequence crossing three pages from a row inside a page
    "chunk_three_pages": [(1, 21, 30)],
    # tile boundaries (every 16 lanes) that fall inside a page: the page
    # is handed from one tile to the next, behind two decode lanes
    "tile_edge_in_page": [(0, 7, 1), (2, 90, 1), (1, 37, 41)],
    # a run that starts on a page's edge and fills whole pages
    "aligned_chunk": [(1, 32, 32)],
    # a lane at, and lanes past, the context's end (the tail of
    # decode_steps): they write nothing, the sink page included
    "past_max_context": [(0, 190, 5), (1, 192, 1), (2, 250, 1), (3, 5, 1)],
    "no_live_lane": [],
}


def _check_writer(runs, T, n_seqs, *, hkv, max_pages, pass_offset=0,
                  block=16, hd=128):
    """``write_kv_pages`` (interpret mode) against ``write_kv_rows`` on a
    bf16 pool that held other values: every page but the sink bit-equal, K
    and V; the sink page, which only the scatter writes, as it was.
    ``hd`` under 128: the leaves hold their heads side by side in rows of
    128 lanes, and the scatter over them writes what the scatter over the
    leaves a head a row does."""
    _, kp, vp, tables, slots, pos = _ragged_batch(
        runs, T, n_seqs, hkv=hkv, hd=hd, max_pages=max_pages, block=block,
        dtype=jnp.bfloat16)
    apart = (kp, vp)
    if hd < 128:
        kp, vp = _share_rows(kp), _share_rows(vp)
    rng = np.random.default_rng(3)
    pad = lambda a: jnp.concatenate(      # pages of the earlier passes
        [jnp.asarray(rng.standard_normal((pass_offset,) + a.shape[1:]),
                     a.dtype), a])
    kp, vp, tables = pad(kp), pad(vp), tables + pass_offset
    sink = kp.shape[0] - 1
    nk = jnp.asarray(rng.standard_normal((T, hkv, hd)), jnp.bfloat16)
    nv = jnp.asarray(rng.standard_normal((T, hkv, hd)), jnp.bfloat16)
    slots, pos = jnp.asarray(slots), jnp.asarray(pos)
    live = (slots >= 0) & (pos < max_pages * block)
    page = jnp.where(live, tables[jnp.maximum(slots, 0),
                                  jnp.minimum(pos // block, max_pages - 1)],
                     sink)
    got = pa.write_kv_pages(kp, vp, nk, nv, tables,
                            pa.work_list(slots, pos, n_seqs), interpret=True)
    bits = lambda a: np.asarray(a).view(np.uint16)
    for g, was, new in zip(got, (kp, vp), (nk, nv)):
        want = pa.write_kv_rows(was, page, pos % block, new)
        np.testing.assert_array_equal(bits(g)[:sink], bits(want)[:sink])
        np.testing.assert_array_equal(bits(g)[sink], bits(was)[sink])
        assert (bits(g) != bits(was)).any() == bool(live.any())
    if hd < 128 and not pass_offset:
        for was, new, shared in zip(apart, (nk, nv), (kp, vp)):
            np.testing.assert_array_equal(
                bits(_share_rows(pa.write_kv_rows(was, page, pos % block, new))),
                bits(pa.write_kv_rows(shared, page, pos % block, new)))


@pytest.mark.parametrize("pass_offset", [0, 97], ids=["pass0", "pass_offset"])
@pytest.mark.parametrize("lanes", sorted(WRITER_LANES))
@pytest.mark.parametrize("hkv", [8, 16, 30])
def test_writer_lands_rows_where_the_scatter_did(hkv, lanes, pass_offset):
    """At the three cells' KV-head counts (Mistral 8, Ouro 16, Olmo 30;
    head_dim 128) and 64 lanes (tiles of 16 rows). ``pass_offset``: a
    looped stack's later pass, whose tables are the first pass's plus a
    stride."""
    _check_writer(WRITER_LANES[lanes], 64, 6, hkv=hkv, max_pages=12,
                  pass_offset=pass_offset)


@pytest.mark.parametrize("lanes", sorted(WRITER_LANES))
def test_writer_on_heads_that_share_a_row(lanes):
    """Granite's leaf: eight KV heads of 64 as four rows of 128 lanes. The
    step's rows [T, 8, 64] are [T, 4, 128] for nothing."""
    _check_writer(WRITER_LANES[lanes], 64, 6, hkv=8, hd=64, max_pages=12)


@pytest.mark.parametrize("T,block,runs", [
    (256, 16, [(0, 3, 200), (1, 77, 1), (2, 300, 1)]),
    (1024, 16, [(2, 130, 1), (0, 37, 900), (1, 5, 70)]),
    (64, 32, [(2, 130, 1), (0, 25, 40), (1, 63, 2)])],
    ids=["tile32", "tile64", "page32"])
def test_writer_at_the_wider_tiles(T, block, runs):
    """The lane buckets whose tiles are 32 and 64 rows: three and five
    pages a tile, the first handed on from the tile before; and pages
    longer than a tile, which a tile still straddles."""
    _check_writer(runs, T, 4, hkv=2, max_pages=1024 // block, block=block)
