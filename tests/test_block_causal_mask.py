"""The block-causal mask of block diffusion (``attn_block`` > 1): a query at
position p sees keys up to ``p | (attn_block - 1)``. One rule in three
places, each against a dense mask: ``dot_product_attention`` (the model's
whole-sequence path), ``paged_attention_reference`` (the XLA path of the
ragged step) and the Pallas paged kernel in interpret mode (both grids, a
ragged batch of prompt chunks and blocks). With ``attn_block`` 1 every one
gives today's output bit for bit."""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference, tile_counts)


def dense(q, k, v, seen):
    """softmax(q k^T / sqrt(d)) v under ``seen`` [sq, skv], per head, GQA
    by repetition; q [sq, hq, d], k / v [skv, hkv, d], float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("block", [1, 2, 4, 8])
def test_whole_sequence_mask(block):
    rng = np.random.default_rng(block)
    s, hq, hkv, d = 21, 4, 2, 16
    q = rng.standard_normal((1, s, hq, d)).astype(np.float32)
    k = rng.standard_normal((1, s, hkv, d)).astype(np.float32)
    v = rng.standard_normal((1, s, hkv, d)).astype(np.float32)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    want = dense(q[0], k[0], v[0], j // block <= i // block)
    got = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), attn_block=block)[0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    if block == 1:
        plain = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v))[0]
        assert np.array_equal(np.asarray(plain), np.asarray(got))


def ragged_case(rng, hq, hkv, hd, page, runs, n_pages=48, max_pages=8):
    """A packed batch: ``runs`` = (lanes, first position) a sequence, each
    with a page list of its own and its context's K/V in the pool."""
    T = sum(n for n, _ in runs)
    q = rng.standard_normal((T, hq, hd)).astype(np.float32)
    kp = rng.standard_normal((n_pages, hkv, page, hd)).astype(np.float32)
    vp = rng.standard_normal((n_pages, hkv, page, hd)).astype(np.float32)
    ids = rng.permutation(n_pages)[: len(runs) * max_pages]
    tables = ids.reshape(len(runs), max_pages).astype(np.int32)
    slots = np.concatenate([np.full(n, s) for s, (n, _) in enumerate(runs)])
    pos = np.concatenate([np.arange(p, p + n) for n, p in runs])
    return q, kp, vp, tables, slots.astype(np.int32), pos.astype(np.int32)


def dense_paged(q, kp, vp, tables, slots, pos, block):
    out = np.zeros(q.shape, np.float64)
    page = kp.shape[2]
    for t in range(q.shape[0]):
        ctx = tables.shape[1] * page
        k = kp[tables[slots[t]]].transpose(0, 2, 1, 3).reshape(ctx, *kp.shape[1::2])
        v = vp[tables[slots[t]]].transpose(0, 2, 1, 3).reshape(ctx, *vp.shape[1::2])
        seen = (np.arange(ctx) <= (pos[t] | (block - 1)))[None, :]
        out[t] = dense(q[t:t + 1], k, v, seen)[0]
    return out


# a prompt chunk from 0, a folded pass (8 lanes) deep in a context, a
# block under way at a page's last block, a chunk that crosses pages
RUNS = [(12, 0), (8, 40), (4, 60), (20, 4)]


@pytest.mark.parametrize("hd", [128, 64], ids=["tile_grid", "lane_grid"])
@pytest.mark.parametrize("block", [1, 4])
def test_paged_paths_against_a_dense_mask(hd, block):
    rng = np.random.default_rng(7)
    q, kp, vp, tables, slots, pos = ragged_case(rng, 4, 2, hd, 16, RUNS)
    want = dense_paged(q, kp, vp, tables, slots, pos, block)
    dev = [jnp.asarray(a) for a in (q, kp, vp)]
    kw = {"attn_block": block}
    xla = paged_attention_reference(*dev, jnp.asarray(tables[slots]),
                                    jnp.asarray(pos), **kw)
    np.testing.assert_allclose(np.asarray(xla), want, rtol=2e-5, atol=2e-5)
    kernel = paged_attention(*dev, jnp.asarray(tables), jnp.asarray(pos),
                             seq_slots=jnp.asarray(slots), interpret=True,
                             tile_rows=16, **kw)
    np.testing.assert_allclose(np.asarray(kernel), want, rtol=2e-5, atol=2e-5)
    if block == 1:      # no argument and attn_block 1: bit for bit
        assert np.array_equal(np.asarray(paged_attention_reference(
            *dev, jnp.asarray(tables[slots]), jnp.asarray(pos))),
            np.asarray(xla))
        assert np.array_equal(np.asarray(paged_attention(
            *dev, jnp.asarray(tables), jnp.asarray(pos),
            seq_slots=jnp.asarray(slots), interpret=True, tile_rows=16)),
            np.asarray(kernel))
    else:               # and the blocks do see ahead: not the causal output
        causal = dense_paged(q, kp, vp, tables, slots, pos, 1)
        assert np.abs(causal - want).max() > 1e-2


def test_a_tile_that_ends_inside_a_block_reads_the_blocks_chunk():
    """A tile's last row sees its block to the end: where that end lies in
    the next 256-token chunk, the walk reaches it (tile_counts counts it)."""
    rng = np.random.default_rng(9)
    # one sequence, lanes at positions 250..257: with tiles of 6 rows the
    # first tile ends at 255, inside the block 252..255; the second starts
    # at 256 in the next chunk
    runs = [(6, 250), (2, 256)]
    q, kp, vp, tables, slots, pos = ragged_case(rng, 2, 1, 128, 16,
                                                [(8, 250)], max_pages=20)
    want = dense_paged(q, kp, vp, tables, slots, pos, 4)
    got = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(tables), jnp.asarray(pos),
                          seq_slots=jnp.asarray(slots), interpret=True,
                          tile_rows=16, attn_block=4)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # lanes 253..254 alone: causal, chunk 0 only; by blocks, still chunk 0;
    # lanes 253..256: the last row's block ends at 259, chunk 1 either way
    assert tile_counts([(2, 253)], 16, 16)[1] == 1
    assert tile_counts([(2, 253)], 16, 16, 4)[1] == 1
    assert tile_counts([(2, 254)], 16, 16, 4)[1] == 1
    assert tile_counts([(3, 252)], 16, 16, 8)[1] == 1     # 254 | 7 = 255
    assert tile_counts([(3, 253)], 16, 16, 8)[1] == 1
    assert tile_counts([(4, 249)], 16, 16, 8)[1] == 1     # 252 | 7 = 255
    assert tile_counts([(4, 253)], 16, 16, 4)[1] == 2 \
        == tile_counts([(4, 253)], 16, 16)[1]
    assert tile_counts(runs, 16, 16, 4) == tile_counts(runs, 16, 16)


def test_refused_shapes():
    rng = np.random.default_rng(1)
    q, kp, vp, tables, slots, pos = ragged_case(rng, 2, 1, 128, 16, [(4, 0)])
    args = [jnp.asarray(a) for a in (q, kp, vp, tables[slots], pos)]
    with pytest.raises(ValueError, match="power of two"):
        paged_attention_reference(*args, attn_block=3)
    with pytest.raises(ValueError, match="without a window"):
        paged_attention_reference(*args, attn_block=4, window=8)
    with pytest.raises(ValueError, match="divides the page size"):
        paged_attention_reference(*args, attn_block=32)
