"""The cache's one owner (inference/kv_cache.py) and the attention block's
one projection (Transformer._qkv): the pool's description against the pool
it builds, the capacity questions the scheduler asks, and the seams the
serving layer may not reach past."""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import Llama
from deepspeed_tpu.models.transformer import Transformer
from deepspeed_tpu.serving import Request
from deepspeed_tpu.serving.scheduler import CapacityView

REPO = Path(__file__).resolve().parent.parent


def _llama(**kw):
    kw = dict(dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                   vocab_size=128, max_seq_len=128, use_flash=False,
                   remat=False), **kw)
    return Llama("tiny", **kw)


def _cfg(**kw):
    kw = dict(dict(token_budget=32, max_seqs=4, kv_block_size=8,
                   n_kv_blocks=24, max_context=64, dtype=jnp.float32), **kw)
    return RaggedConfig(**kw)


def test_bias_and_qk_norm_serve_the_logits_they_train():
    """A model with both a q/k/v bias and QK-norm: the ragged step (prefill,
    then decode through the pages) gives Transformer.apply's logits. The
    two orders (bias then norm, norm then bias) differed until the
    projection was written once; the biases and norm weights are made
    random because the init's zeros and ones hide the order."""
    model = _llama(qkv_bias=True, qk_norm=True)
    params = model.init(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    for name in ("bq", "bk", "bv", "q_norm_w", "k_norm_w"):
        leaf = params["layers"][name]
        params["layers"][name] = leaf + 0.5 * jax.random.normal(
            next(keys), leaf.shape, leaf.dtype)
    tokens = np.random.default_rng(0).integers(1, 128, (12,)).tolist()
    want = np.asarray(model.apply(params, jnp.asarray([tokens])))[0]

    eng = RaggedInferenceEngine(model, _cfg(), params=params)
    got = [eng.put([1], [tokens[:9]])[0]]                     # prefill
    got += [eng.put([1], [[t]])[0] for t in tokens[9:]]       # decode
    np.testing.assert_allclose(np.stack(got), want[8:], rtol=2e-4, atol=2e-4)


def _hybrid():
    from deepspeed_tpu.models.transformer import TransformerConfig

    return Transformer(TransformerConfig(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab_size=128, max_seq_len=128, activation="silu_glu",
        tie_embeddings=False, use_flash=False, remat=False, qk_norm=True,
        branch_norm=True,
        layer_types=("linear", "linear", "linear", "full"),
        linear_n_k_heads=2, linear_n_v_heads=4, linear_k_dim=8,
        linear_v_dim=16))


@pytest.mark.parametrize("kind", ["dense", "int8", "int4", "hybrid"])
def test_the_bytes_are_counted_from_the_leaves_that_are_built(kind):
    """kv_page_bytes a page (the sink page too) plus state_pool_bytes (the
    sink slot in it) is every byte of the pool the constructor built, and
    every leaf has the shape, dtype and count its description says."""
    model = _hybrid() if kind == "hybrid" else _llama()
    cfg = _cfg(kv_quant=kind if kind.startswith("int") else "none")
    eng = RaggedInferenceEngine(model, cfg)
    pool, kinds = eng.kv_pool, kv_cache.pool_leaves(model.config, cfg)
    assert isinstance(pool, kv_cache.KVPool)
    for field in pool._fields:
        leaves, want = getattr(pool, field), getattr(kinds, field)
        assert len(leaves) == want.n, field
        assert all(a.shape == want.shape and a.dtype == want.dtype
                   for a in leaves), field
    assert bool(pool.k_scale) == kind.startswith("int")
    assert bool(pool.state) == bool(pool.conv_rows) == (kind == "hybrid")
    built = sum(a.nbytes for a in jax.tree_util.tree_leaves(pool))
    assert built == (kv_cache.kv_page_bytes(model.config, cfg)
                     * (cfg.n_kv_blocks + 1)
                     + kv_cache.state_pool_bytes(model.config, cfg))
    assert (kv_cache.state_pool_bytes(model.config, cfg) > 0) \
        == (kind == "hybrid")


def test_pages_moved_out_and_in_are_the_same_pages():
    """PageMoves' gather / write / copy over the named leaves of an
    int8 pool: payload and scales land together, bit for bit, and nothing
    else in the pool changes."""
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(kv_quant="int8"))
    eng.put([1], [list(range(1, 20))])                    # three pages
    src = eng.seqs[1].blocks
    moves = kv_cache.PageMoves(model.config)
    pages = moves.gather(eng.kv_pool, src)
    assert [a.shape[:2] for a in pages] == [(2, 3)] * 4
    before = jax.tree_util.tree_map(np.asarray, eng.kv_pool)
    dst = eng.allocator.allocate(3)
    eng.kv_pool = moves.write(eng.kv_pool, dst, pages, eng.max_pages)
    eng.kv_pool = moves.copy(eng.kv_pool, src[0], dst[2])
    again = moves.gather(eng.kv_pool, dst)
    for a, b in zip(pages, again):
        np.testing.assert_array_equal(a[:, :2], b[:, :2])
        np.testing.assert_array_equal(a[:, 0], b[:, 2])
    untouched = [p for p in range(eng.config.n_kv_blocks) if p not in dst]
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(eng.kv_pool)):
        np.testing.assert_array_equal(a[untouched], np.asarray(b)[untouched])
    eng.allocator.release(dst)


def test_would_free_counts_what_an_eviction_really_frees():
    """A sequence sharing its first pages with the prefix cache AND with
    another live sequence: evicting it frees only the pages no other live
    sequence holds (cache-only-held pages count, admission reclaims them).
    The scheduler's CapacityView, which SLOPolicy preempts on, gives the
    cache's answer."""
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(enable_prefix_cache=True))
    shared = list(range(1, 17))                            # two full pages
    eng.put([1], [shared + [40, 41, 42]])
    eng.flush([1])                                         # published
    eng.put([2], [shared + [50, 51, 52, 53]])              # adopts 2, owns 1
    eng.put([3], [shared + list(range(60, 72))])           # adopts 2, owns 2
    a, b = eng.seqs[2], eng.seqs[3]
    assert a.blocks[:2] == b.blocks[:2]
    assert eng.allocator.refcount(a.blocks[0]) == 4        # 2 cache levels + a + b
    # the shared pages stay held by the other live sequence: not credited
    assert eng.cache.evictable_blocks(a.blocks) == 1
    assert eng.cache.evictable_blocks(b.blocks) == 2
    view = CapacityView(eng, reserve_output=False)
    assert view.evictable_blocks(a) == 1 and view.evictable_blocks(b) == 2
    assert view.free_slots == eng.cache.free_slots == 2
    eng.flush([3])
    # now only the cache shares them: evicting seq 2 frees all three
    assert eng.cache.evictable_blocks(a.blocks) == 3
    free = eng.allocator.free_blocks
    assert eng.cache.available_blocks() > free             # cache-only pages
    req = Request(uid=9, prompt=[1] * 8, max_new_tokens=4)
    assert view.blocks_short(req) == max(
        0, eng.blocks_needed(8) - eng.cache.available_blocks())
    eng.flush([2])
    kv_cache.assert_block_balance(eng)


@pytest.mark.parametrize("module", ["scheduler", "server"])
def test_serving_asks_the_cache_and_reaches_past_nothing(module):
    """serving/scheduler.py and serving/server.py name no underscore
    attribute of the engine, its cache, the allocator or the prefix cache:
    capacity is asked through KVLedger's methods."""
    src = (REPO / "deepspeed_tpu" / "serving" / f"{module}.py").read_text()
    reach = re.findall(
        r"\b(?:engine|eng|cache|prefix_cache|allocator|alloc)\._[a-z]\w*",
        src)
    assert not reach, reach
    assert "cache.free_slots" in src


def test_the_engine_imports_neither_the_v1_engine_nor_a_positional_pool():
    """inference/ragged.py is the engine alone: no import of
    inference/engine.py, no integer-indexed pool, the projection matmul
    nowhere but the model (and the linear layer's own)."""
    pkg = REPO / "deepspeed_tpu"
    ragged = (pkg / "inference" / "ragged.py").read_text()
    assert not re.search(r"from \.engine import|inference\.engine", ragged)
    assert "DST_RAGGED_FORCE_GATHER" not in ragged
    for path in pkg.rglob("*.py"):
        text = path.read_text()
        assert not re.search(r"\b(?:kv_pool|pools)\[-?\d", text), path
        if path.name != "gated_delta.py":
            n = text.count('@ lp["wq"]')
            assert n == (path.name == "transformer.py"), (path, n)
