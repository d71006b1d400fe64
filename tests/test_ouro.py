"""Ouro (a looped stack: the same blocks ``total_ut_steps`` times a token,
K/V of its own for every pass) at a small size on the CPU, seeded float32
weights: ``Transformer.apply`` and the ragged engine against
``benchmarks/reference/ouro.py``; the cache under one page id (prefix
adoption, preemption under ``PoolExhausted``, export / import, trim's
copy-on-write) against an undisturbed run; the pool's byte arithmetic; the
one-pass step's lowered text against the parent's; the ``ouro``
translation of ``checkpoint/hf.py``; the tree ``benchmarks/weights.py``
makes; and what a looped model refuses."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import ouro as ref
from deepspeed_tpu.checkpoint import hf
from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.inference.kv_cache import (PoolExhausted,
                                              assert_block_balance,
                                              kv_blocks_for_bytes,
                                              kv_page_bytes)
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

N_LAYERS, SEED, VOCAB = 3, 3_500_000_017, 256


def hc_of(passes=4, threshold=1.0):
    return {"model_type": "ouro", "vocab_size": VOCAB, "hidden_size": 64,
            "intermediate_size": 128, "num_hidden_layers": N_LAYERS,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
            "max_position_embeddings": 512, "tie_word_embeddings": False,
            "sliding_window": None, "use_sliding_window": False,
            "rope_scaling": None, "layer_types": ["full_attention"] * N_LAYERS,
            "total_ut_steps": passes, "early_exit_threshold": threshold}


def build(passes=4, threshold=1.0):
    hc = hc_of(passes, threshold)
    c = hf.ouro_config(hc)
    c.remat, c.use_flash = False, False
    model = Transformer(c)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return hc, model, weights.make(shapes, SEED, jnp.float32, N_LAYERS)


@pytest.fixture(scope="module")
def built():
    """Four passes at a threshold the seeded gate crosses at every pass."""
    return build(4, 0.5)


def _reference(hc, params, fed, cols_of):
    """Reference logits of sequence i at the positions ``cols_of[i]``."""
    width = max(map(len, fed))
    tokens = np.zeros((len(fed), width), np.int32)
    for i, f in enumerate(fed):
        tokens[i, :len(f)] = f
    rows = np.concatenate([[i] * len(c) for i, c in enumerate(cols_of)])
    return np.asarray(ref.logits_at(
        params, jnp.asarray(tokens), jnp.asarray(rows),
        jnp.asarray(np.concatenate(cols_of)), hc, N_LAYERS))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _prompts(*lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, (n,)).tolist() for n in lens]


# ----------------------------------------------------------------------
# (a) the dense forward
@pytest.mark.parametrize("threshold", [1.0, 0.5])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_dense_forward_agrees_with_the_reference(passes, threshold):
    hc, model, params = build(passes, threshold)
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 40), 1,
                                        VOCAB))
    got = jax.jit(model.apply)(params, jnp.asarray(tok))
    want = _reference(hc, params, tok.tolist(), [np.arange(40)] * 2)
    assert _rel(got.reshape(80, -1), want).max() < 1e-4
    # what the case exercises: with a gate that fires, tokens leave at
    # more than one pass; at the published threshold all at the last
    left = np.bincount(np.asarray(ref.exit_pass(
        ref.passes(params, jnp.asarray(tok), hc, N_LAYERS)[1],
        threshold)).ravel(), minlength=passes)
    if threshold < 1 and passes > 1:
        assert (left > 0).sum() >= 2, left
    else:
        assert left[-1] == 80, left


def test_the_loss_differentiates_through_the_passes(built):
    _, model, params = built
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 1, VOCAB)
    loss, grads = jax.value_and_grad(model.loss)(params, {"input_ids": tok})
    assert np.isfinite(loss) and 4.0 < float(loss) < 8.0
    norms = {k: float(jnp.linalg.norm(v)) for k, v in grads["layers"].items()}
    assert all(np.isfinite(n) and n > 0 for n in norms.values()), norms


# ----------------------------------------------------------------------
# (b) the ragged engine
def _engine(built, **kw):
    _, model, params = built
    cfg = dict(token_budget=32, max_seqs=4, kv_block_size=8, n_kv_blocks=48,
               max_context=128, dtype=jnp.float32)
    cfg.update(kw)
    return RaggedInferenceEngine(model, RaggedConfig(**cfg), params=params)


def _prefill(eng, uids, prompts):
    """put() until every prompt is in; returns (rows, puts made)."""
    rows, puts = eng.put(uids, prompts), 1
    while np.isnan(rows[:, 0]).any():
        todo = [i for i in range(len(uids)) if np.isnan(rows[i, 0])]
        rows[todo] = eng.put([uids[i] for i in todo], [[] for _ in todo])
        puts += 1
    return rows.copy(), puts


def _decode(eng, uids, fed, rows, steps):
    """``steps`` greedy tokens through the paged cache; returns the rows
    after each, [n, steps, vocab]."""
    got = []
    for _ in range(steps):
        nxt = np.argmax(rows, -1)
        for f, t in zip(fed, nxt):
            f.append(int(t))
        rows = eng.put(uids, [[int(t)] for t in nxt]).copy()
        got.append(rows)
    return np.stack(got, 1)


@pytest.mark.parametrize("threshold", [1.0, 0.5])
@pytest.mark.parametrize("lens,puts", [((20, 9), 1), ((40, 9, 17), 3)],
                         ids=["one_tick", "split_over_ticks"])
def test_ragged_engine_agrees_with_the_reference(lens, puts, threshold):
    """Prefill (in one tick, or with prompts split over ticks of a 32-lane
    budget), then 8 decode steps: every pass's cache layer is written by
    prefill and read by decode. Logits, not tokens."""
    b = build(4, threshold)
    eng, prompts = _engine(b), _prompts(*lens)
    uids = list(range(1, len(lens) + 1))
    rows, made = _prefill(eng, uids, prompts)
    assert made == puts
    fed = [list(p) for p in prompts]
    got = np.concatenate([rows[:, None], _decode(eng, uids, fed, rows, 8)], 1)
    want = _reference(b[0], b[2], fed,
                      [np.arange(len(p) - 1, len(p) + 8) for p in prompts])
    assert _rel(got.reshape(len(want), -1), want).max() < 1e-4
    eng.flush(uids)
    assert_block_balance(eng, expect_free=48)


def test_decode_steps_and_generate_share_the_looped_core(built):
    hc, _, params = built
    eng, prompts = _engine(built), _prompts(30, 21)
    rows, _ = _prefill(eng, [1, 2], prompts)
    first = {u: int(np.argmax(r)) for u, r in zip([1, 2], rows)}
    chains = eng.decode_steps(first, 6)
    fed = [p + [first[u]] + chains[u][:-1] for u, p in zip([1, 2], prompts)]
    want = _reference(hc, params, fed, [np.arange(len(p), len(p) + 6)
                                        for p in prompts]).reshape(2, 6, -1)
    assert [np.argmax(w, -1).tolist() for w in want] == [chains[1], chains[2]]
    eng.flush([1, 2])
    out = _engine(built).generate({7: prompts[0]}, max_new_tokens=7)
    assert out[7] == [first[1]] + chains[1]


# ----------------------------------------------------------------------
# (c) one page id, every pass's page: the books never learn of passes
def _undisturbed(built, prompt, steps):
    """(tokens fed, rows after the prompt and after each decode step)."""
    eng = _engine(built)
    rows, _ = _prefill(eng, [1], [prompt])
    fed = [list(prompt)]
    return fed[0], np.concatenate(
        [rows[:, None], _decode(eng, [1], fed, rows, steps)], 1)[0]


def _prefix_adopted(built):
    """A second prompt shares 24 tokens (3 whole pages) with a flushed
    one: it adopts the pages, every pass's rows with them."""
    eng = _engine(built, enable_prefix_cache=True)
    a, = _prompts(30)
    b = a[:24] + _prompts(11, seed=5)[0]
    _prefill(eng, [1], [a])
    eng.flush([1])
    rows, _ = _prefill(eng, [2], [b])
    assert eng.prefix_cache.hits == 1 and eng.seqs[2].blocks[:3] \
        == eng.prefix_cache.lookup(a[:24])[1]
    fed = [list(b)]
    got = np.concatenate([rows[:, None], _decode(eng, [2], fed, rows, 3)], 1)
    return eng, [2], got[0], _undisturbed(built, b, 3)[1]


def _preempted_under_pool_exhausted(built):
    """A pool of 8 pages and two sequences that want 10: a decode step
    raises ``PoolExhausted`` with nothing granted, the caller preempts one
    (its whole pages go to the prefix cache), the other decodes on, the
    first resumes from the tokens it was handed back."""
    eng = _engine(built, n_kv_blocks=8, enable_prefix_cache=True)
    a, b = _prompts(30, 31)
    rows, _ = _prefill(eng, [1, 2], [a, b])
    fed = [list(a), list(b)]
    _decode(eng, [1], fed[:1], rows[:1], 2)       # a: 32 tokens, 4 pages
    fed[1].append(int(np.argmax(rows[1])))
    eng.put([2], [[fed[1][-1]]])                  # b: 32 tokens, 4 pages
    want = _undisturbed(built, a, 5)
    nxt = want[0][32]                             # a's next token
    with pytest.raises(PoolExhausted):
        eng.put([1], [[nxt]])                     # a 5th page: none free
    seq = eng.seqs[1]
    del seq.tokens[seq.seen:]                     # the token was not taken
    assert eng.preempt(2) == fed[1]               # its pages: cache-only now
    fed_a = [fed[0]]
    rows_a = eng.put([1], [[nxt]]).copy()         # evicts what it needs
    fed_a[0].append(nxt)
    got = np.concatenate([rows_a[:, None],
                          _decode(eng, [1], fed_a, rows_a, 2)], 1)[0]
    assert fed_a[0] == want[0][:len(fed_a[0])]
    return eng, [1], got, want[1][3:6]


def _exported_and_imported(built):
    """Prefill and two decode steps on one engine, the pages of every pass
    exported, imported into another, which decodes on."""
    src, dst = _engine(built), _engine(built)
    a, = _prompts(27)
    rows, _ = _prefill(src, [1], [a])
    fed = [list(a)]
    rows = _decode(src, [1], fed, rows, 2)[:, -1]
    export = src.export_kv(1)
    c = built[1].config
    assert export.n_layers == 4 * N_LAYERS and export.k_pages.shape \
        == (4 * N_LAYERS, 4, c.n_kv_heads, 8, c.head_dim)
    assert export.nbytes == 4 * src.kv_bytes_per_token * 8
    dst.import_kv(9, export)
    got = _decode(dst, [9], fed, rows, 3)[0]
    src.flush([1])
    assert_block_balance(src, expect_free=48)
    return dst, [9], got, _undisturbed(built, a, 5)[1][3:6]


def _trimmed_onto_a_private_copy(built):
    """A sequence adopts a cached prefix and is trimmed into its last
    shared page: copy-on-write copies that page for every pass."""
    eng = _engine(built, enable_prefix_cache=True)
    a, = _prompts(30)
    _prefill(eng, [1], [a])
    eng.flush([1])
    rows, _ = _prefill(eng, [2], [a])           # adopts 3 pages
    shared = eng.seqs[2].blocks[2]
    eng.trim(2, 20)                             # into the third page
    assert eng.seqs[2].blocks[2] != shared
    fed = [a[:20]]
    tail = a[20:26]
    got = []
    for t in tail:                              # writes the private page
        fed[0].append(t)
        got.append(eng.put([2], [[t]]).copy())
    # the cache's own copy of the shared page is untouched: a third
    # sequence adopting it reads what the first wrote
    rows3, _ = _prefill(eng, [3], [a])
    want = _undisturbed(built, a, 1)[1][:1]
    assert _rel(rows3, want).max() < 1e-4
    eng.flush([3])
    ref_rows = _reference(built[0], built[2], fed, [np.arange(20, 26)])
    return eng, [2], np.concatenate(got), ref_rows


DISTURBED = {"prefix_adopted": _prefix_adopted,
             "preempted_under_pool_exhausted": _preempted_under_pool_exhausted,
             "exported_and_imported": _exported_and_imported,
             "trimmed_onto_a_private_copy": _trimmed_onto_a_private_copy}


@pytest.mark.parametrize("how", list(DISTURBED))
def test_a_disturbed_run_gives_the_logits_of_an_undisturbed_one(built, how):
    eng, uids, got, want = DISTURBED[how](built)
    assert _rel(got, want).max() < 1e-4
    assert_block_balance(eng)
    eng.flush(uids)
    if eng.prefix_cache is not None:
        eng.prefix_cache.drop_all(eng.allocator)
    assert_block_balance(eng, expect_free=eng.config.n_kv_blocks)


def test_behind_the_server_with_preemption_and_token_ids(built):
    """The normal path: ``ServingEngine`` (policy ``slo``, prefix cache on,
    ``return_token_ids``) over a pool of 8 pages that two requests of 6 do
    not fit at once: the higher priority evicts the lower, which resumes
    on the pages the prefix cache kept, and each ends with the tokens of
    an engine that ran it alone."""
    from deepspeed_tpu.serving import ServingEngine

    low_p, high_p = _prompts(30, 28, seed=3)
    alone = [_engine(built).generate({1: p}, max_new_tokens=10)[1]
             for p in (low_p, high_p)]
    eng = _engine(built, n_kv_blocks=8, enable_prefix_cache=True)
    srv = ServingEngine(eng, {"policy": "slo", "kv_pressure": 0.0,
                              "reserve_output_blocks": True}, start=False)
    assert eng._token_ids

    def tick_until(done):
        for _ in range(300):
            if done():
                return
            srv._tick()
        raise AssertionError("not reached in 300 ticks")

    low = srv.submit(low_p, max_new_tokens=10, priority=0)
    tick_until(lambda: len(low.tokens) >= 3)
    high = srv.submit(high_p, max_new_tokens=10, priority=5)
    srv._tick()                          # admission preempts ``low``
    assert low.preemptions == 1
    tick_until(lambda: low.is_terminal and high.is_terminal)
    srv.close()
    assert [list(low.tokens), list(high.tokens)] == alone
    assert eng.prefix_cache.hits >= 1    # the resume rode cached pages
    eng.prefix_cache.drop_all(eng.allocator)
    assert_block_balance(eng, expect_free=8)


# ----------------------------------------------------------------------
# (d) the pool's bytes
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_a_page_weighs_its_passes(passes):
    c = hf.ouro_config(hc_of(passes))
    one = hf.ouro_config(hc_of(1))
    cfg = RaggedConfig(max_seqs=4, kv_block_size=8, n_kv_blocks=10,
                       dtype=jnp.bfloat16)
    per_pass = 2 * N_LAYERS * 4 * 8 * 16 * 2        # K + V, bf16
    assert kv_page_bytes(one, cfg) == per_pass
    assert kv_page_bytes(c, cfg) == passes * per_pass
    assert kv_blocks_for_bytes(40 * per_pass, c, cfg) == 40 // passes
    assert kv_cache.cache_layers(c) == passes * N_LAYERS
    pool = kv_cache.new_pool(c, cfg)
    assert [a.shape for a in pool.k] == [(passes * 11, 4, 8, 16)] * N_LAYERS
    q = RaggedConfig(max_seqs=4, kv_block_size=8, n_kv_blocks=10,
                     kv_quant="int8")
    assert kv_page_bytes(c, q) == passes * N_LAYERS * 2 * 4 * 8 * (16 + 4)


def test_the_published_size():
    """Ouro-2.6B as the catalog gives it: 2.668 B parameters, 1.5 MiB of
    K/V a token, a 16-token page of 25.2 MB."""
    c = hf.ouro_config(_catalog_config())
    assert c.param_count() == 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632
                                    + 4 * 2048) + 2048 \
        + 2 * 49152 * 2048 + 2049 == 2_667_974_657
    page = kv_page_bytes(c, RaggedConfig(kv_block_size=16))
    assert page == 16 * 1_572_864 == 25_165_824
    # the layers run four times a token, the head once
    once = 6.0 * (2 * 49152 * 2048 + 2049)
    assert c.flops_per_token(128) == once + 4 * (
        6.0 * c.param_count() - once + 12.0 * 2048 * 48 * 128)


# ----------------------------------------------------------------------
# (e) with one pass, the step is the parent's program
PARENT_STEP = {"jax": "0.9.0", "sha256": "dd78ae1ad016a24edc4b0686ea456ec6"
               "5f084cb6fdd2c940727dfc8b286e8cfd"}


def test_one_pass_lowers_to_the_parent_step():
    """The SplitFuse step of a Mistral-shaped tiny model (grouped-query,
    windowed, untied) lowers to the text it had before passes existed
    (sha256 of ``lower(...).as_text()`` at commit 5de8e75, the parent of
    PR 35, under this jax): no loop, no offset, no other shape. A later PR
    that changes the step on purpose records its own text here: PR 51's,
    whose program takes the tick's five int32 arrays as one packed argument
    and opens with five slices and a reshape; against 5de8e75's text
    (``ef4bd197...``) nothing else differs but the numbering of values.
    PR 54's: two ``stablehlo.optimization_barrier``, one a layer over its
    q, k and v between the products and the head split
    (``Transformer._qkv``'s ``seam``); against PR 51's text
    (``b7f4e014...``) nothing else differs but that numbering."""
    if jax.__version__ != PARENT_STEP["jax"]:
        pytest.skip(f"recorded under jax {PARENT_STEP['jax']}")
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.ops.ragged_host import build_batch, fill_tables

    m = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
              d_ff=128, vocab_size=128, max_seq_len=256,
              attn_windows=(32, 32), tie_embeddings=False, use_flash=False,
              remat=False)
    eng = RaggedInferenceEngine(m, RaggedConfig(
        token_budget=64, max_seqs=4, kv_block_size=16, n_kv_blocks=32,
        max_context=128))
    tok, slot, pos, _ = build_batch([], [], [], 64)
    # at the precision the benchmark serves in, not conftest's "highest"
    with jax.default_matmul_precision("default"):
        text = eng._build_step().lower(
            eng.params, eng.kv_pool, jnp.asarray(tok), jnp.asarray(slot),
            jnp.asarray(pos),
            jnp.asarray(fill_tables([], [], 4, eng.max_pages)),
            jnp.zeros((4,), jnp.int32), 4).as_text()
    assert "stablehlo.while" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP["sha256"]


def test_the_looped_step_is_one_rolled_loop(built):
    """Four passes are one ``while`` around one body of the stack's blocks,
    not four bodies: the gather path's program (the kernel's, at the
    published size for a described chip: tests/test_tpu_compile.py)."""
    from deepspeed_tpu.ops.ragged_host import build_batch, fill_tables

    eng = _engine(built)
    tok, slot, pos, _ = build_batch([], [], [], 32)
    text = eng._build_step().lower(
        eng.params, eng.kv_pool, jnp.asarray(tok), jnp.asarray(slot),
        jnp.asarray(pos), jnp.asarray(fill_tables([], [], 4, eng.max_pages)),
        jnp.zeros((4,), jnp.int32), 4).as_text()
    assert text.count("stablehlo.while") == 1


# ----------------------------------------------------------------------
# (f) the translation of config.json and of the checkpoint's names
def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = next((json.loads(l) for l in open(path) if '"Ouro-2.6B"' in l),
               None) if os.path.exists(path) else None
    if row is not None:
        return row["config"]
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmarks", "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def test_hf_config_of_the_published_model(tmp_path):
    published = _catalog_config()
    with open(tmp_path / "config.json", "w") as f:
        json.dump(published, f)
    family, c = hf.hf_config(str(tmp_path))
    assert family == "ouro" and c.n_layers == 48 and c.d_model == 2048
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.d_ff) == (16, 16, 128, 5632)
    assert c.vocab_size == 49152 and not c.tie_embeddings
    assert c.total_ut_steps == 4 and c.early_exit_threshold == 1.0
    assert c.sandwich_norm and c.prenorm and not c.branch_norm
    assert c.position == "rope" and c.rope_theta == 1e6 and c.norm_eps == 1e-6
    assert c.attn_windows is None and c.layer_types is None
    assert hf.ouro_config(published, 6).n_layers == 6
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        hf.ouro_config(dict(published, rope_scaling={"type": "yarn"}))


def test_checkpoint_names_map_onto_the_tree(built):
    _, model, _ = built
    c = model.config
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    d, f, L = c.d_model, c.d_ff, "model.layers.{}."
    state = {"model.embed_tokens.weight": rng.normal(size=(VOCAB, d)),
             "model.norm.weight": rng.normal(size=(d,)),
             "lm_head.weight": rng.normal(size=(VOCAB, d)),
             "model.early_exit_gate.weight": rng.normal(size=(1, d)),
             "model.early_exit_gate.bias": rng.normal(size=(1,))}
    for i in range(N_LAYERS):
        for name in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm",
                     "post_attention_layernorm_2"):
            state[L.format(i) + name + ".weight"] = rng.normal(size=(d,))
        for x in "qkvo":
            state[L.format(i) + f"self_attn.{x}_proj.weight"] = \
                rng.normal(size=(d, d))
        for x, shape in (("gate", (f, d)), ("up", (f, d)), ("down", (d, f))):
            state[L.format(i) + f"mlp.{x}_proj.weight"] = \
                rng.normal(size=shape)
    kept = dict(state)
    mapped = hf.map_hf_params(state, "ouro", c)
    assert jax.tree_util.tree_structure(mapped) \
        == jax.tree_util.tree_structure(shapes)
    jax.tree_util.tree_map(lambda a, s: np.testing.assert_equal(
        a.shape, s.shape), mapped, shapes)
    lay = mapped["layers"]
    np.testing.assert_array_equal(
        lay["attn_post_norm_w"][1], kept[L.format(1)
                                         + "input_layernorm_2.weight"])
    np.testing.assert_array_equal(
        lay["mlp_norm_w"][2], kept[L.format(2)
                                   + "post_attention_layernorm.weight"])
    np.testing.assert_array_equal(
        lay["mlp_post_norm_w"][0], kept[L.format(0)
                                        + "post_attention_layernorm_2.weight"])
    np.testing.assert_array_equal(
        mapped["exit_gate_w"][:, 0], kept["model.early_exit_gate.weight"][0])
    np.testing.assert_array_equal(
        lay["wq"][1], kept[L.format(1) + "self_attn.q_proj.weight"].T)


# ----------------------------------------------------------------------
# (g) the tree the benchmark's weights are made for
def test_weights_make_builds_the_tree(built):
    _, model, params = built
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(shapes)
    lay = params["layers"]
    assert {"attn_norm_w", "attn_post_norm_w", "mlp_norm_w",
            "mlp_post_norm_w"} <= set(lay)
    for k in ("attn_post_norm_w", "mlp_post_norm_w"):   # gains near one
        assert lay[k].shape == (N_LAYERS, 64)
        assert 0.05 < float(jnp.std(lay[k])) < 0.2
        assert abs(float(jnp.mean(lay[k])) - 1) < 0.05
    assert params["exit_gate_w"].shape == (64, 1)
    assert 0.05 < float(jnp.std(params["exit_gate_w"])) < 0.25
    assert params["b_exit_gate"].shape == (1,) \
        and float(params["b_exit_gate"][0]) == 0.0
    # one pass: a plain stack with sandwich norms, no gate to make
    one = jax.eval_shape(build(1)[1].init, jax.random.PRNGKey(0))
    assert "exit_gate_w" not in one and "attn_post_norm_w" in one["layers"]


# ----------------------------------------------------------------------
# what a looped model refuses, and what it says of itself
def test_dense_kv_cache_and_pipeline_refuse_a_looped_stack(built):
    _, model, params = built
    cache = jnp.zeros((N_LAYERS, 1, 8, 4, 16))
    with pytest.raises(NotImplementedError, match="looped"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32),
                    kv_caches=(cache, cache), cache_pos=0)
    model._pipe_size, model._mesh = 2, object()
    try:
        with pytest.raises(NotImplementedError, match="looped"):
            model.pipeline_loss(params, {"input_ids": jnp.zeros((2, 4),
                                                                jnp.int32)},
                                None, 2)
    finally:
        model._pipe_size, model._mesh = 1, None


@pytest.mark.parametrize("bad", [
    dict(total_ut_steps=0), dict(total_ut_steps=2, prenorm=False),
    dict(total_ut_steps=2, layer_types=("full", "linear"),
         linear_n_k_heads=2, linear_k_dim=8, linear_v_dim=8),
    dict(sandwich_norm=True, branch_norm=True)],
    ids=["no_pass", "post_ln", "recurrent_layers", "two_wirings"])
def test_config_refuses_what_the_loop_cannot_run(bad):
    with pytest.raises(ValueError):
        TransformerConfig(n_layers=2, d_model=32, n_heads=2, **bad)


def test_span_attributes_gauge_and_counter(built, monkeypatch, tmp_path):
    """``ragged.put`` says ``passes`` and ``kv_layers``; the registry holds
    the bytes of K/V a token; at a threshold of 1 every token is counted
    at the last pass, from the host's static knowledge."""
    from deepspeed_tpu.inference import ragged as ragged_mod
    from deepspeed_tpu.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    seen = {}

    class Span:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **attrs):
            seen.update(attrs)

    real = ragged_mod.annotate
    monkeypatch.setattr(
        ragged_mod, "annotate",
        lambda name, **attrs: Span() if name == "ragged.put"
        else real(name, **attrs))
    tel = Telemetry(TelemetryConfig(enabled=True, output_dir=str(tmp_path),
                                    jsonl_path="", stall_detection=False))
    set_telemetry(tel)
    try:
        eng = _engine(build(4, 1.0))
        _prefill(eng, [1, 2], _prompts(40, 9))     # 40 > 32 lanes: 2 ticks
        reg = tel.registry
        assert reg.gauge("inference/kv_bytes_per_token").value \
            == eng.kv_bytes_per_token == 4 * N_LAYERS * 2 * 4 * 16 * 4
    finally:
        set_telemetry(None)
    assert seen["passes"] == 4 and seen["kv_layers"] == 4 * N_LAYERS
