"""Ragged/continuous-batching engine tests (FastGen v2 parity surface:
reference tests/unit/inference/v2/ragged/*)."""

import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.kv_cache import BlockedAllocator
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import Llama
import jax
import jax.numpy as jnp


def _llama():
    return Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 vocab_size=128, max_seq_len=256, use_flash=False, remat=False)


def _cfg(**kw):
    kw.setdefault("token_budget", 32)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("n_kv_blocks", 64)
    kw.setdefault("max_context", 128)
    kw.setdefault("dtype", jnp.float32)
    return RaggedConfig(**kw)


def test_blocked_allocator():
    alloc = BlockedAllocator(8)
    a = alloc.allocate(3)
    b = alloc.allocate(2)
    assert len(set(a) | set(b)) == 5 and alloc.free_blocks == 3
    alloc.free(a)
    assert alloc.free_blocks == 6
    with pytest.raises(RuntimeError):
        alloc.allocate(7)


def test_put_matches_dense_engine():
    """Paged ragged decode must agree with the dense KV-cache engine."""
    model = _llama()
    rng = jax.random.PRNGKey(5)
    params = model.init(rng)

    dense = InferenceEngine(model, InferenceConfig(dtype="float32", temperature=0.0),
                            params=params)
    prompt = np.random.default_rng(0).integers(0, 128, (1, 8)).astype(np.int32)
    expected = dense.generate(prompt, max_new_tokens=6)[0, 8:]

    ragged = RaggedInferenceEngine(model, _cfg(), params=params)
    out = ragged.generate({7: list(prompt[0])}, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out[7]), expected)


def test_mixed_batch_isolation():
    """Two interleaved sequences must generate exactly what they generate
    alone (no KV cross-talk through the shared pool)."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(6))
    p1 = list(np.random.default_rng(1).integers(0, 128, 8))
    p2 = list(np.random.default_rng(2).integers(0, 128, 11))

    solo1 = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {1: p1}, max_new_tokens=5)[1]
    solo2 = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {2: p2}, max_new_tokens=5)[2]

    both = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {1: p1, 2: p2}, max_new_tokens=5)
    assert both[1] == solo1
    assert both[2] == solo2


def test_chunked_prefill_across_steps():
    """A prompt longer than the token budget prefills across multiple put()
    calls (Dynamic SplitFuse) and still matches the dense engine."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(7))
    prompt = np.random.default_rng(3).integers(0, 128, (1, 50)).astype(np.int32)

    dense = InferenceEngine(model, InferenceConfig(dtype="float32", temperature=0.0),
                            params=params)
    expected = dense.generate(prompt, max_new_tokens=3)[0, 50:]

    ragged = RaggedInferenceEngine(model, _cfg(token_budget=16), params=params)
    logits = ragged.put([9], [list(prompt[0])])
    n_steps = 1
    while np.isnan(logits).any():       # prompt still prefilling
        logits = ragged.put([9], [[]])
        n_steps += 1
    assert n_steps == 4                  # ceil(50/16) chunks
    toks = [int(np.argmax(logits[0]))]
    for _ in range(2):
        logits = ragged.put([9], [[toks[-1]]])
        toks.append(int(np.argmax(logits[0])))
    np.testing.assert_array_equal(np.asarray(toks), expected)


def test_trim_rewinds_context_exactly():
    """trim(uid, n) after a decode_steps chunk must restore the sequence to
    the same state as one that never generated past n: the continuation
    tokens must match a fresh engine fed the trimmed prefix (the post-EOS
    pollution fix for callers mixing decode_steps with further serving)."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(8))
    prompt = list(np.random.default_rng(4).integers(0, 128, 9))

    eng = RaggedInferenceEngine(model, _cfg(), params=params)
    logits = eng.put([3], [prompt])
    first = int(np.argmax(logits[0]))
    chain = eng.decode_steps({3: first}, 6)[3]   # admits first + chain[:-1]
    # pretend chain[1] was EOS: rewind to prompt + first + chain[:2]
    keep = len(prompt) + 3
    blocks_before = len(eng.seqs[3].blocks)
    eng.trim(3, keep)
    assert eng.seqs[3].seen == keep and len(eng.seqs[3].tokens) == keep
    assert len(eng.seqs[3].blocks) <= blocks_before

    # continue the trimmed sequence one token at a time
    cont = []
    logits = eng.put([3], [[chain[2]]])
    for _ in range(3):
        t = int(np.argmax(logits[0]))
        cont.append(t)
        logits = eng.put([3], [[t]])

    # oracle: a fresh engine that only ever saw the trimmed stream
    ref = RaggedInferenceEngine(model, _cfg(), params=params)
    logits = ref.put([5], [prompt + [first] + chain[:3]])
    expected = []
    for _ in range(3):
        t = int(np.argmax(logits[0]))
        expected.append(t)
        logits = ref.put([5], [[t]])
    assert cont == expected


def test_flush_releases_resources():
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg())
    free0 = eng.allocator.free_blocks
    eng.put([1], [[5, 6, 7, 8]])
    assert eng.allocator.free_blocks < free0
    eng.flush([1])
    assert eng.allocator.free_blocks == free0
    assert eng.cache.free_slots == eng.config.max_seqs


def test_max_context_rejected():
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(max_context=16))
    with pytest.raises(ValueError):
        eng.put([1], [list(range(17))])
    with pytest.raises(ValueError):
        RaggedInferenceEngine(model, _cfg(max_context=512))


def test_pool_exhaustion_is_atomic():
    """Failed put() must not advance any sequence's seen counter."""
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(n_kv_blocks=2, max_seqs=4))
    eng.put([1], [[1, 2, 3, 4, 5, 6, 7, 8]])      # 1 block
    with pytest.raises(RuntimeError):
        # needs 2 more blocks but only 1 free
        eng.put([2], [list(range(16))])
    assert eng.seqs[2].seen == 0                    # untouched
    assert eng.seqs[1].seen == 8


def test_query_reflects_capacity():
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(max_context=32, token_budget=16))
    tokens, free = eng.query(1)
    assert tokens == 16 and free == eng.config.n_kv_blocks
    eng.put([1], [list(range(30))])  # 16 + 14 across two steps
    eng.put([1], [[]])
    tokens, _ = eng.query(1)
    assert tokens == 2                # only 2 context slots left
    # known uid mid-stream: can_schedule charges only incremental blocks
    assert eng.can_schedule([1], [2])


def test_can_schedule_and_slot_exhaustion():
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(max_seqs=2))
    assert eng.can_schedule([1, 2], [8, 8])
    assert not eng.can_schedule([1, 2, 3], [8, 8, 8])
    eng.put([1], [[1, 2]])
    eng.put([2], [[3, 4]])
    with pytest.raises(RuntimeError):
        eng.put([3], [[5, 6]])


def _assert_ragged_matches_dense(model, params, prompts, max_new_tokens):
    """Shared ragged-vs-dense greedy parity scaffold: serve ``prompts``
    (uid -> tokens) through the ragged engine, compare token-exact against
    the dense-KV engine row by row."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.parallel.mesh import reset_topology

    reset_topology()
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=64, max_seqs=4, kv_block_size=8,
                            n_kv_blocks=64, max_context=64,
                            dtype=jnp.float32), params=params)
    out = eng.generate({k: list(v) for k, v in prompts.items()},
                       max_new_tokens=max_new_tokens)
    reset_topology()
    dense = dst.init_inference(model=(model, params),
                               config={"dtype": "fp32", "temperature": 0.0})
    for uid, prompt in prompts.items():
        ref = dense.generate(np.asarray([prompt], np.int32),
                             max_new_tokens=max_new_tokens)
        np.testing.assert_array_equal(np.asarray(out[uid]),
                                      ref[0, len(prompt):], err_msg=f"uid {uid}")


def test_ragged_serves_moe_model():
    """FastGen + MoE (the reference's Mixtral-class serving): ragged
    continuous batching over a GPTMoE model matches the dense-KV engine's
    greedy decode."""
    from deepspeed_tpu.models import GPTMoE

    # n_experts > top_k: routing is genuinely selective, so this also
    # proves the no-drop grouped-GEMM dispatch (capacity semantics would
    # make logits depend on co-scheduled traffic)
    model = GPTMoE("tiny", n_experts=4, top_k=1, n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=4, vocab_size=64, max_seq_len=64,
                   use_flash=False, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    _assert_ragged_matches_dense(
        model, params, {7: list(range(1, 9)), 9: list(range(20, 30))}, 6)


def _tiny_moe(activation):
    from deepspeed_tpu.models.moe import MoETransformer, MoETransformerConfig

    glu = activation == "silu_glu"
    return MoETransformer(MoETransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, n_experts=4, top_k=2, activation=activation,
        norm="rms" if glu else "layer", position="rope" if glu else "learned",
        use_bias=not glu, tie_embeddings=not glu, use_flash=False,
        remat=False))


@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
def test_put_reads_expert_stacks_in_place_and_matches_apply(activation):
    """A Mixtral-shaped and a GPT-MoE-shaped (gelu, expert biases) model
    through ``put``, a prompt and then three decode steps: the step hands
    ``ragged_dot`` the whole expert stacks and the layer's place
    (``no_drop_moe``), and every logit row is ``model.apply``'s."""
    from deepspeed_tpu.parallel.mesh import reset_topology

    reset_topology()
    model = _tiny_moe(activation)
    params = model.init(jax.random.PRNGKey(2))
    if activation == "gelu":
        for i, b in enumerate(("b_up", "b_down")):
            params["layers"][b] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), params["layers"][b].shape)
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=32, max_seqs=2, kv_block_size=8,
                            n_kv_blocks=16, max_context=64,
                            dtype=jnp.float32), params=params)
    assert eng.expert_bytes_in_place == sum(
        params["layers"][k].nbytes for k in model.stacked_operands
        if k in params["layers"]) > 0
    toks = [int(t) for t in
            np.random.default_rng(3).integers(1, 64, (14,))]
    fed = toks[:10]
    got = [eng.put([1], [fed])[0]]
    for t in toks[10:13]:
        fed = fed + [t]
        got.append(eng.put([1], [[t]])[0])
    want = np.asarray(model.apply(params, jnp.asarray([fed], jnp.int32)))[0]
    np.testing.assert_allclose(np.asarray(got), want[9:13], rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("family", ["dense", "moe", "moe_expert_parallel"])
def test_expert_bytes_in_place_gauge(family, tmp_path):
    """``inference/expert_bytes_in_place``: the expert stacks' bytes where
    the step indexes them by layer, 0 for a dense model and under expert
    parallelism (there the step slices, as for every other leaf)."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    class Cfg:
        enabled = True
        output_dir = str(tmp_path)

    mesh_mod.reset_topology()
    model = _llama() if family == "dense" else _tiny_moe("silu_glu")
    topo = mesh_mod.Topology.build_virtual({"expert": 2}) \
        if family == "moe_expert_parallel" else None
    t = Telemetry(config=Cfg())
    set_telemetry(t)
    try:
        eng = RaggedInferenceEngine(
            model, RaggedConfig(token_budget=16, max_seqs=2, kv_block_size=8,
                                n_kv_blocks=16, max_context=32,
                                dtype=jnp.float32), topology=topo)
        want = 3 * 3 * 4 * 32 * 64 * 4 if family == "moe" else 0
        assert eng.expert_bytes_in_place == want
        assert t.registry.gauge(
            "inference/expert_bytes_in_place").value == want
        assert eng._experts_in_place == (family != "moe_expert_parallel")
    finally:
        t.close()
        set_telemetry(None)
        mesh_mod.reset_topology()


@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
def test_expert_products_follow_the_rule(activation, monkeypatch, tmp_path):
    """A Mixtral-shaped and a GPT-MoE-shaped model served on the kernel
    path (``DST_RAGGED_FORCE_PALLAS=interpret``): the experts' products
    are the Pallas grouped matmul's (``parallel/moe.expert_product``: 64
    rows over 4 experts, under the ridge; the rule reads the path and the
    rows an expert and nothing of the matrices), the tokens are the
    ``gather`` path's one for
    one, and ``ragged.put``'s ``expert_kernel`` and the two tick counters
    say which product each engine's programs hold."""
    from deepspeed_tpu.config import TelemetryConfig
    from deepspeed_tpu.parallel.mesh import reset_topology
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    reset_topology()
    model = _tiny_moe(activation)
    params = model.init(jax.random.PRNGKey(2))
    cfg = RaggedConfig(token_budget=32, max_seqs=4, kv_block_size=16,
                       n_kv_blocks=32, max_context=64, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = {1: rng.integers(1, 64, (9,)).tolist(),
               2: rng.integers(1, 64, (21,)).tolist()}
    got = {}
    for path in ("gather", "pallas_interpret"):
        if path == "pallas_interpret":
            monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
        tel = Telemetry(TelemetryConfig(
            enabled=True, output_dir=str(tmp_path / path), jsonl_path="",
            stall_detection=False))
        set_telemetry(tel)
        try:
            eng = RaggedInferenceEngine(model, cfg, params=params)
            assert eng.attention_path == path
            kernel = path != "gather"
            assert eng._expert_product(32) == \
                ("kernel" if kernel else "ragged_dot")
            seen = []
            attrs = eng._sched_attrs
            eng._sched_attrs = lambda *a: seen.append(attrs(*a)) or seen[-1]
            names = ("ragged_steps", "expert_kernel_ticks",
                     "expert_ragged_dot_ticks")
            count = lambda: np.array([tel.registry.counter(
                f"inference/{n}").value for n in names])
            before = count()        # the registry outlives a Telemetry
            got[path] = eng.generate(dict(prompts), max_new_tokens=6)
            steps, by_kernel, by_ragged_dot = count() - before
            assert steps == len(seen) > 0
            assert {a["expert_kernel"] for a in seen} == {int(kernel)}
            assert (by_kernel, by_ragged_dot) == \
                ((steps, 0) if kernel else (0, steps))
        finally:
            tel.close()
            set_telemetry(None)
    assert got["pallas_interpret"] == got["gather"]


@pytest.mark.parametrize("path,head_dim,kv_heads,programs", [
    ("pallas_interpret", 128, 1, 2),   # the grid over query tiles
    ("pallas_interpret", 64, 2, 2),    # ... two heads of 64 a 128-lane row
    ("pallas_interpret", 64, 1, 10),   # the lane grid: lanes x page bucket
    ("gather", 128, 1, 10)],
    ids=["tiled", "two_heads_a_row", "lane_grid", "gather"])
def test_step_programs_a_lane_bucket_where_no_page_bucket_is_read(
        path, head_dim, kv_heads, programs, monkeypatch, tmp_path):
    """Where the paged kernel walks query tiles the live-page bucket
    bounds nothing, and the engine holds one step program a lane bucket
    whatever bucket it is warmed or called at (``_program_pages``): after
    ``warm_step`` at every (lanes, pages) pair ``_step_fn`` holds as many
    programs as there are lane buckets, a ``put`` compiles nothing, and
    its rows are bit for bit those of an engine warmed at the tick's own
    shape alone. Two KV heads of 64 share a row of the pool and take that
    grid and the row writer (``write_tiles`` > 0). An engine on the lane
    grid (ONE KV head of 64 fills half a row: the pool keeps a head a
    row), whose grid is made of the bucket, and one on the ``gather`` path
    keep a program a pair. The gauge ``inference/step_programs`` says which; the span's
    ``pages`` stays the live bucket."""
    from deepspeed_tpu.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry, set_telemetry

    if path == "pallas_interpret":
        monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    model = Llama("tiny", n_layers=1, d_model=2 * head_dim, n_heads=2,
                  n_kv_heads=kv_heads, vocab_size=128, max_seq_len=256,
                  use_flash=False, remat=False)
    params = model.init(jax.random.PRNGKey(3))
    cfg = _cfg(token_budget=96, kv_block_size=16, n_kv_blocks=32,
               max_context=256)
    tel = Telemetry(TelemetryConfig(enabled=True, output_dir=str(tmp_path),
                                    jsonl_path="", stall_detection=False))
    set_telemetry(tel)
    try:
        eng = RaggedInferenceEngine(model, cfg, params=params)
        assert eng.attention_path == path
        assert eng._buckets == [64, 96] and eng.max_pages == 16
        assert eng._pages_key == (programs == 10)
        pairs = [(b, p) for b in eng._buckets for p in (1, 2, 4, 8, 16)]
        for lanes, pages in pairs:
            eng.warm_step(lanes, pages)
        gauge = tel.registry.gauge("inference/step_programs")
        assert eng._step_fn._cache_size() == programs == gauge.value
        seen = []
        attrs = eng._sched_attrs
        eng._sched_attrs = lambda *a: seen.append(attrs(*a)) or seen[-1]
        prompts = [list(range(1, 70)), [5, 6, 7]]     # 72 lanes, 5 pages
        rows = [eng.put([1, 2], prompts), eng.put([1, 2], [[9], [9]])]
        assert [(a["lanes"], a["pages"]) for a in seen] == [(96, 8), (64, 8)]
        assert eng._step_fn._cache_size() == programs == gauge.value
        # the row writer wherever the tiled grid: 5 + 1 tiles, then 2
        assert eng._writes_pages == (programs == 2)
        assert [a["write_tiles"] for a in seen] == \
            ([6, 2] if programs == 2 else [0, 0])
        assert eng.kv_pool.k[0].shape[1:] == (1, 16, kv_heads * head_dim)

        alone = RaggedInferenceEngine(model, cfg, params=params)
        alone.warm_step(96, 8)
        alone.warm_step(64, 8)
        assert alone._step_fn._cache_size() == 2
        want = [alone.put([1, 2], prompts), alone.put([1, 2], [[9], [9]])]
        for got, w in zip(rows, want):
            assert np.isfinite(got).all()
            np.testing.assert_array_equal(got, w)
        assert alone._step_fn._cache_size() == 2
    finally:
        tel.close()
        set_telemetry(None)


def test_pages_travel_a_head_a_row_whatever_the_pool_shares():
    """Two KV heads of 64 share a row of the pool; an export carries them
    a head a row (``KVExport``'s documented shape), so the importer's
    next step reads bit for bit what the exporter's does, and a pool that
    keeps a head a row (the same model over a model axis of 2: one head a
    device fills half a row) takes the same pages."""
    from deepspeed_tpu.inference import kv_cache

    model = Llama("tiny", n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                  vocab_size=128, max_seq_len=128, use_flash=False,
                  remat=False)
    params = model.init(jax.random.PRNGKey(5))
    cfg = _cfg(kv_block_size=16, n_kv_blocks=16, token_budget=64)
    a, b = (RaggedInferenceEngine(model, cfg, params=params) for _ in "ab")
    assert a.kv_pool.k[0].shape == (17, 1, 16, 128)
    rows = a.put([7], [list(range(1, 40))])
    export = a.export_kv(7)
    assert export.k_pages.shape == (2, 3, 2, 16, 64)
    b.import_kv(7, export)
    for f in ("k", "v"):
        for x, y in zip(getattr(a.kv_pool, f), getattr(b.kv_pool, f)):
            np.testing.assert_array_equal(
                np.asarray(x[np.asarray(a.seqs[7].blocks)]),
                np.asarray(y[np.asarray(b.seqs[7].blocks)]))
    nxt = [[int(np.argmax(rows[0]))]]
    np.testing.assert_array_equal(a.put([7], nxt), b.put([7], nxt))
    # ... and into leaves a head a row, as pool_leaves gives a model axis
    # of 2: the page a head a row is the wire's page
    apart = kv_cache.KVPool(*(
        tuple(jnp.zeros(kind.shape, kind.dtype) for _ in range(kind.n))
        for kind in kv_cache.pool_leaves(model.config, cfg, 2)))
    assert apart.k[0].shape == (17, 2, 16, 64)
    moves = kv_cache.PageMoves(model.config)
    apart = moves.write(apart, [4, 9, 2],
                        (export.k_pages, export.v_pages, None, None), 8)
    np.testing.assert_array_equal(np.asarray(apart.k[1][jnp.asarray([4, 9, 2])]),
                                  export.k_pages[1])
    np.testing.assert_array_equal(moves.gather(apart, [4, 9, 2])[1],
                                  export.v_pages)


def test_dense_model_has_no_expert_product(tmp_path):
    """No routed experts, no attribute and no tick counted."""
    eng = RaggedInferenceEngine(_llama(), _cfg())
    assert eng._expert_product(32) is None
    eng.put([1], [[3, 4, 5]])
    assert "expert_kernel" not in eng._sched_attrs([], 32, 1)


def test_ragged_serves_windowed_moe():
    """Mixtral-class serving: routed experts + a BINDING sliding window
    in the ragged engine, token-exact vs the dense-KV engine."""
    from deepspeed_tpu.models import GPTMoE

    model = GPTMoE("tiny", n_experts=4, top_k=1, n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=4, vocab_size=64, max_seq_len=64,
                   use_flash=False, remat=False, attn_windows=(8, 8))
    params = model.init(jax.random.PRNGKey(0))
    # prompt 14 > window 8: the band binds during decode
    _assert_ragged_matches_dense(model, params, {3: list(range(1, 15))}, 8)


def test_ragged_serves_relu_activation():
    """OPT-style relu MLP must not silently become gelu in the ragged step."""
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            max_seq_len=64, norm="layer", activation="relu",
                            position="learned", use_bias=True,
                            use_flash=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(1))
    _assert_ragged_matches_dense(model, params, {1: list(range(1, 9))}, 6)


# the forms _qkv's first half takes before the head split (the seam the
# served step puts its barrier at): a bias on each product, a norm
# over the whole projection, a norm a head
SEAM_FAMILIES = {"qkv_bias": dict(qkv_bias=True),
                 "qk_norm": dict(qk_norm=True),
                 "qk_norm_heads": dict(qk_norm=True, qk_norm_heads=True),
                 "qkv_bias_qk_norm": dict(qkv_bias=True, qk_norm=True)}


def _seam_model(family):
    """A rotary model of ``SEAM_FAMILIES`` with its biases and norm gains
    random, so that one applied out of order, or dropped, shows."""
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    model = Transformer(TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=128, norm="rms", activation="silu_glu", position="rope",
        use_bias=False, tie_embeddings=False, use_flash=False, remat=False,
        **SEAM_FAMILIES[family]))
    params = model.init(jax.random.PRNGKey(3))
    names = [n for n in ("bq", "bk", "bv", "q_norm_w", "k_norm_w")
             if n in params["layers"]]
    for key, name in zip(jax.random.split(jax.random.PRNGKey(11), len(names)),
                         names):
        leaf = params["layers"][name]
        params["layers"][name] = (1.0 if name.endswith("norm_w") else 0.0) \
            + 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)
    return model, params


def _assert_step_matches_unjoined_qkv(model, params, prompt):
    """One served step over ``prompt``: the logits of its last token are
    the model's own (``apply``, whose ``_qkv`` runs unjoined), and the K /
    V rows the step wrote into the first layer's pages are ``_qkv``'s k
    and v of the embedded prompt, bias, QK-norm and rotary in the order
    its docstring states, on both sides of the seam."""
    from deepspeed_tpu.ops.rotary import rope_frequencies

    c = model.config
    eng = RaggedInferenceEngine(model, _cfg(), params=params)
    logits = eng.put([5], [list(prompt)])
    tokens = jnp.asarray([prompt], jnp.int32)
    np.testing.assert_allclose(
        logits[0], np.asarray(model.apply(params, tokens)[0, -1]),
        rtol=2e-5, atol=2e-5)
    positions = jnp.arange(len(prompt))[None]
    _, lp = model.layer_params(params["layers"], 0)
    _, kk, vv = model._qkv(
        model._embed(params, tokens, positions=positions), lp,
        rope_frequencies(c.rotary_dim, c.max_seq_len, c.rope_theta,
                         c.rope_yarn), positions)
    bs = eng.config.kv_block_size
    pages = np.asarray(eng.seqs[5].blocks)
    for leaf, want in ((eng.kv_pool.k[0], kk), (eng.kv_pool.v[0], vv)):
        assert leaf.shape[1:] == (c.n_kv_heads, bs, c.head_dim)
        rows = np.asarray(leaf)[pages].transpose(0, 2, 1, 3).reshape(
            -1, c.n_kv_heads, c.head_dim)[:len(prompt)]
        np.testing.assert_allclose(rows, np.asarray(want[0]),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("family", ["gpt2", "opt", *SEAM_FAMILIES])
def test_ragged_serves_gpt2_and_opt_layouts(family):
    """Non-llama families through continuous batching (the reference's
    FastGen ships OPT support, inference/v2/model_implementations/opt/):
    learned positions via model._embed, the layernorm path, and biased
    projections — token-exact vs the dense engine. ``SEAM_FAMILIES``:
    rotary models whose q / k / v take a bias or a QK-norm between the
    product and the head split, where the served step joins ``_qkv``'s
    halves with a barrier and the dense engine with nothing: token-exact
    too, and the step's logits and written rows against the unjoined
    ``_qkv``."""
    from deepspeed_tpu.models import GPT2, OPT

    prompts = {2: list(range(1, 9)), 4: list(range(30, 44))}
    if family in SEAM_FAMILIES:
        model, params = _seam_model(family)
        _assert_step_matches_unjoined_qkv(model, params, prompts[4])
    else:
        factory, size = (GPT2, "tiny") if family == "gpt2" else (OPT, "125m")
        model = factory(size, n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        vocab_size=128, max_seq_len=128, use_flash=False,
                        remat=False)
        params = model.init(jax.random.PRNGKey(0))
    _assert_ragged_matches_dense(model, params, prompts, 6)


def test_ragged_serves_internlm_layout():
    """InternLM layout: use_bias=False but qkv AND o_proj biases present
    (checkpoint/hf.py internlm config). The ragged core must apply the
    o_proj bias — advisor r4 high finding: it was gated on use_bias and
    silently dropped every layer's attention output bias."""
    from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, n_kv_heads=4, max_seq_len=256,
                            norm="rms", activation="silu_glu",
                            position="rope", use_bias=False, qkv_bias=True,
                            attn_o_bias=True, tie_embeddings=False,
                            use_flash=False, remat=False)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(2))
    # biases init to zeros — randomize them so dropping one is visible
    kb = jax.random.split(jax.random.PRNGKey(9), 4)
    for i, name in enumerate(("bq", "bk", "bv", "bo")):
        params["layers"][name] = 0.5 * jax.random.normal(
            kb[i], params["layers"][name].shape, jnp.float32)
    _assert_ragged_matches_dense(
        model, params, {3: list(range(1, 9)), 5: list(range(40, 50))}, 6)


def test_sampled_decode_chunk_invariant_and_seeded():
    """temperature>0 sampling: same engine seed -> identical streams
    regardless of decode chunking; different seed -> different tokens;
    all tokens in-vocab."""
    rng = np.random.default_rng(21)
    prompts = {i: rng.integers(1, 128, (9 + 3 * i,)).tolist() for i in range(2)}
    model = _llama()
    params = model.init(jax.random.PRNGKey(0))  # FIXED weights across runs:
    # the engine rng below then seeds ONLY the sampler streams

    def run(seed, chunk):
        eng = RaggedInferenceEngine(
            model, _cfg(temperature=0.8, top_k=20), params=params,
            rng=jax.random.PRNGKey(seed))
        return eng.generate({k: list(v) for k, v in prompts.items()},
                            max_new_tokens=12, decode_chunk=chunk)

    a, b, c = run(5, 1), run(5, 7), run(6, 7)
    for u in prompts:
        assert a[u] == b[u], (u, a[u], b[u])       # chunk-invariant
        assert all(0 <= t < 128 for t in a[u])
    assert any(a[u] != c[u] for u in prompts)       # seed actually matters

    greedy = RaggedInferenceEngine(model, _cfg(), params=params,
                                   rng=jax.random.PRNGKey(5)).generate(
        {k: list(v) for k, v in prompts.items()}, max_new_tokens=12)
    assert any(a[u] != greedy[u] for u in prompts)  # not secretly argmax


def test_chunked_decode_matches_single_step():
    """generate() with a multi-token on-device decode chunk must produce
    exactly the tokens of the one-token-at-a-time path (same model, same
    prompts), including across page-boundary crossings mid-chunk."""
    rng = np.random.default_rng(11)
    prompts = {i: rng.integers(1, 128, (11 + 5 * i,)).tolist() for i in range(3)}
    outs = []
    for chunk in (1, 7):
        eng = RaggedInferenceEngine(_llama(), _cfg(),
                                    rng=jax.random.PRNGKey(3))
        outs.append(eng.generate({k: list(v) for k, v in prompts.items()},
                                 max_new_tokens=20, decode_chunk=chunk))
    for u in prompts:
        assert outs[0][u] == outs[1][u], (u, outs[0][u], outs[1][u])
        assert len(outs[0][u]) == 20


def test_chunked_decode_eos_and_k_guard():
    """EOS inside a decode chunk stops that sequence; decode_steps rejects
    k < 1 and context overflow before touching any allocator state."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(1, 128, (9,)).tolist()
    eng = RaggedInferenceEngine(_llama(), _cfg(), rng=jax.random.PRNGKey(3))
    ref = eng.generate({0: list(prompt)}, max_new_tokens=12, decode_chunk=1)
    eos = ref[0][3]
    eng2 = RaggedInferenceEngine(_llama(), _cfg(), rng=jax.random.PRNGKey(3))
    out = eng2.generate({0: list(prompt)}, max_new_tokens=12,
                        eos_token_id=eos, decode_chunk=5)
    assert out[0] == ref[0][:4], (out[0], ref[0])

    eng3 = RaggedInferenceEngine(_llama(), _cfg(), rng=jax.random.PRNGKey(3))
    eng3.put([7, 8], [prompt, prompt[:5]])
    free_before = eng3.allocator.free_blocks
    blocks_before = {u: list(eng3.seqs[u].blocks) for u in (7, 8)}
    with pytest.raises(ValueError, match="k >= 1"):
        eng3.decode_steps({7: 5}, 0)
    # multi-uid: uid 8 (5 seen) fits and is validated first; uid 7 (9 seen)
    # overflows — the whole call must reject before uid 8 is granted blocks
    ctx = eng3.config.max_context
    with pytest.raises(ValueError, match="max_context"):
        eng3.decode_steps({8: 5, 7: 5}, ctx - len(prompt) + 1)
    assert eng3.allocator.free_blocks == free_before
    assert {u: list(eng3.seqs[u].blocks) for u in (7, 8)} == blocks_before


def test_ragged_tp_serving_matches_single_device():
    """TP serving (FastGen v2's tensor-parallel configuration): params +
    KV pool sharded over the 'model' axis, GSPMD partitions the ragged
    step — greedy output must be token-exact vs the unsharded engine."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                  vocab_size=256, max_seq_len=128, use_flash=False,
                  remat=False)
    cfg = RaggedConfig(token_budget=64, max_seqs=4, kv_block_size=16,
                       n_kv_blocks=64, max_context=128)
    rng = np.random.default_rng(11)
    prompts = {1: rng.integers(1, 256, (9,)).tolist(),
               2: rng.integers(1, 256, (17,)).tolist()}

    eng = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(3))
    want = eng.generate(dict(prompts), max_new_tokens=8)

    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"model": 2})
    eng_tp = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(3),
                                   topology=topo)
    got = eng_tp.generate(dict(prompts), max_new_tokens=8)
    assert got == want, (got, want)


# head_dim 16 rides the kernel's (lane, chunk) grid, 128 its query tiles
# (ops/pallas/paged_attention.py chooses on the pool's lane width)
HEAD_SHAPES = pytest.mark.parametrize(
    "d_model,n_heads", [(64, 4), (256, 2)], ids=["hd16", "hd128"])


@HEAD_SHAPES
def test_ragged_tp_serving_on_pallas_kernel_path(monkeypatch, d_model,
                                                 n_heads):
    """TP serving on the PAGED KERNEL path (not the gather fallback): the
    kernel runs inside a shard_map over the 'model' axis — heads + KV pool
    sharded, tables/positions and the work list replicated. Token-exact vs
    the unsharded gather engine, in the CPU interpret lane (the r4
    verdict's directive: `use_pallas` must no longer require tp_size == 1)."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    model = Llama("tiny", n_layers=2, d_model=d_model, n_heads=n_heads,
                  n_kv_heads=n_heads, vocab_size=256, max_seq_len=128,
                  use_flash=False, remat=False)
    cfg = RaggedConfig(token_budget=64, max_seqs=4, kv_block_size=16,
                       n_kv_blocks=64, max_context=128, dtype=jnp.float32)
    rng = np.random.default_rng(12)
    prompts = {1: rng.integers(1, 256, (9,)).tolist(),
               2: rng.integers(1, 256, (17,)).tolist()}

    mesh_mod.reset_topology()
    eng = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(3))
    want = eng.generate(dict(prompts), max_new_tokens=8)   # gather path

    monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    # single-device kernel path first: the interpret lever itself
    eng_k = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(3))
    got_k = eng_k.generate(dict(prompts), max_new_tokens=8)
    assert got_k == want, (got_k, want)

    # now the sharded kernel: TP2 over the model axis
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"model": 2})
    eng_tp = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(3),
                                   topology=topo)
    got = eng_tp.generate(dict(prompts), max_new_tokens=8)
    assert got == want, (got, want)


def test_ragged_tp_rejects_indivisible_heads():
    from deepspeed_tpu.parallel import mesh as mesh_mod

    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=1,
                  vocab_size=256, max_seq_len=128, use_flash=False,
                  remat=False)
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"model": 2})
    with pytest.raises(ValueError, match="n_kv_heads"):
        RaggedInferenceEngine(model, RaggedConfig(max_context=128),
                              topology=topo)


@pytest.mark.parametrize("kernel_path", [False, True])
def test_ragged_expert_parallel_serving(kernel_path, monkeypatch):
    """MoE serving over a TP x EP mesh (the reference's Mixtral serving
    composition): expert banks shard over 'expert', heads/pool over
    'model' — on both the gather path and the Pallas kernel path (the
    kernel's shard_map manualizes only 'model'; expert routing stays
    GSPMD's). Greedy output token-exact vs the unsharded engine."""
    from deepspeed_tpu.models import GPTMoE
    from deepspeed_tpu.parallel import mesh as mesh_mod

    model = GPTMoE("tiny", n_experts=4, n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=4, vocab_size=256, max_seq_len=128,
                   use_flash=False, remat=False)
    cfg = RaggedConfig(token_budget=64, max_seqs=4, kv_block_size=16,
                       n_kv_blocks=64, max_context=128)
    rng = np.random.default_rng(13)
    prompts = {5: rng.integers(1, 256, (11,)).tolist(),
               6: rng.integers(1, 256, (20,)).tolist()}

    mesh_mod.reset_topology()
    eng = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(4))
    want = eng.generate(dict(prompts), max_new_tokens=6)

    if kernel_path:
        monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"expert": 2, "model": 2})
    eng_ep = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(4),
                                   topology=topo)
    got = eng_ep.generate(dict(prompts), max_new_tokens=6)
    assert got == want, (got, want)
    # the layer axis cannot merge with a sharded expert axis: the sharded
    # step slices its expert leaves, the unsharded one reads them in place
    assert eng._experts_in_place and not eng_ep._experts_in_place


@HEAD_SHAPES
@pytest.mark.parametrize("kernel_path", [False, True])
def test_ragged_tp_windowed_serving(kernel_path, monkeypatch, d_model,
                                    n_heads):
    """Binding sliding windows under TP serving, on both attention paths:
    the banded gather AND the banded Pallas kernel inside the TP
    shard_map (interpret lane) — token-exact vs unsharded."""
    from deepspeed_tpu.parallel import mesh as mesh_mod

    model = Llama("tiny", n_layers=2, d_model=d_model, n_heads=n_heads,
                  n_kv_heads=n_heads, vocab_size=256, max_seq_len=128,
                  use_flash=False, remat=False, attn_windows=(32, 32))
    cfg = RaggedConfig(token_budget=64, max_seqs=4, kv_block_size=16,
                       n_kv_blocks=64, max_context=128)
    rng = np.random.default_rng(17)
    prompts = {1: rng.integers(1, 256, (40,)).tolist(),
               2: rng.integers(1, 256, (50,)).tolist()}

    mesh_mod.reset_topology()
    eng = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(6))
    want = eng.generate(dict(prompts), max_new_tokens=6)

    if kernel_path:
        monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"model": 2})
    eng_tp = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(6),
                                   topology=topo)
    got = eng_tp.generate(dict(prompts), max_new_tokens=6)
    assert got == want, (got, want)


def test_decode_steps_eos_freeze_keeps_context_clean():
    """On-device EOS freeze: a lane that samples EOS mid-chunk stops
    feeding tokens (KV routes to the sink page, position halts), so a
    later put() on the same uid continues from an UNPOLLUTED context —
    logits must match a fresh engine that never saw the post-EOS steps."""
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=256, max_seq_len=128, use_flash=False,
                  remat=False)
    params = model.init(jax.random.PRNGKey(0))
    cfg = dict(token_budget=64, max_seqs=4, kv_block_size=16,
               n_kv_blocks=64, max_context=128)
    rng = np.random.default_rng(23)
    prompt = rng.integers(1, 256, (12,)).tolist()

    eng = RaggedInferenceEngine(model, RaggedConfig(**cfg), params=params)
    row = eng.put([1], [prompt])
    first = int(np.argmax(row[0]))
    # find the eos id that the chain will hit mid-chunk: run a probe chunk
    probe = eng.decode_steps({1: first}, 6)[1]
    eos = probe[2]                       # pretend token at step 2 is EOS
    eng.flush([1])

    # engine A: same decode WITH the freeze
    eng_a = RaggedInferenceEngine(model, RaggedConfig(**cfg), params=params)
    first_a = int(np.argmax(eng_a.put([1], [prompt])[0]))
    assert first_a == first
    chain = eng_a.decode_steps({1: first}, 6, eos_token_id=eos)[1]
    j = chain.index(eos)
    assert chain[j + 1:] == [eos] * (6 - j - 1)   # frozen fillers
    fed = [first] + chain[:j]
    assert eng_a.seqs[1].seen == len(prompt) + len(fed)
    cont_a = eng_a.put([1], [[97]])

    # engine B: fresh, fed exactly prompt + fed tokens, then the same put
    eng_b = RaggedInferenceEngine(model, RaggedConfig(**cfg), params=params)
    eng_b.put([1], [prompt + fed])
    cont_b = eng_b.put([1], [[97]])
    np.testing.assert_allclose(cont_a[0], cont_b[0], rtol=1e-4, atol=1e-4)


def test_stream_matches_generate():
    """stream() yields the same tokens generate() returns, incrementally,
    and flushes its uid at stream end (incl. early break)."""
    model = Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  vocab_size=256, max_seq_len=128, use_flash=False,
                  remat=False)
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(token_budget=64, max_seqs=4, kv_block_size=16,
              n_kv_blocks=64, max_context=128)
    prompt = np.random.default_rng(31).integers(1, 256, (10,)).tolist()

    eng = RaggedInferenceEngine(model, RaggedConfig(**kw), params=params)
    want = eng.generate({7: prompt}, max_new_tokens=12)[7]

    eng2 = RaggedInferenceEngine(model, RaggedConfig(**kw), params=params)
    got = list(eng2.stream(7, prompt, max_new_tokens=12))
    assert got == want
    assert 7 not in eng2.seqs                     # flushed at stream end

    # early consumer break still releases the uid's slot + blocks
    eng3 = RaggedInferenceEngine(model, RaggedConfig(**kw), params=params)
    it = eng3.stream(8, prompt, max_new_tokens=12)
    next(it)
    it.close()
    assert 8 not in eng3.seqs


# ---------------------------------------------------------------------
# automatic prefix caching (beyond-reference: FastGen recomputes every
# prompt; here completed sequences publish KV pages for full-block
# prefix reuse)
def _pc_cfg(**kw):
    kw.setdefault("enable_prefix_cache", True)
    return _cfg(**kw)


def test_prefix_cache_reuse_token_exact():
    """A prompt sharing a cached full-block prefix must adopt its KV pages
    (no recompute) and still produce token-exact output vs a cache-less
    engine."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(21)
    P = rng.integers(1, 128, (20,)).tolist()          # 2 full blocks @ bs 8

    oracle = RaggedInferenceEngine(model, _cfg(), params=params)
    want_p = oracle.generate({1: list(P)}, max_new_tokens=6)[1]

    eng = RaggedInferenceEngine(model, _pc_cfg(), params=params)
    out1 = eng.generate({1: list(P)}, max_new_tokens=6)[1]
    assert out1 == want_p
    assert eng.prefix_cache.hits == 0 and len(eng.prefix_cache) > 0

    # same prompt again: must hit the cache and stay exact
    out2 = eng.generate({2: list(P)}, max_new_tokens=6)[2]
    assert out2 == want_p
    assert eng.prefix_cache.hits >= 1

    # different tail sharing the first block only
    Q = P[:8] + rng.integers(1, 128, (7,)).tolist()
    want_q = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {3: list(Q)}, max_new_tokens=6)[3]
    out3 = eng.generate({3: list(Q)}, max_new_tokens=6)[3]
    assert out3 == want_q


def test_prefix_cache_shares_pages_and_refcounts():
    """The adopted pages are the SAME block ids (shared, refcounted), and
    pool accounting balances: cache-held pages return to the free list on
    drop_all."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(6))
    eng = RaggedInferenceEngine(model, _pc_cfg(), params=params)
    P = list(range(1, 21))                            # 20 tokens, bs 8
    eng.generate({1: P}, max_new_tokens=4)
    cached = next(iter(eng.prefix_cache._entries.values()))
    free_before = eng.allocator.free_blocks

    eng.put([2], [list(P)])
    seq = eng.seqs[2]
    assert seq.blocks[: len(cached)] == cached        # identity, not copies
    assert all(eng.allocator.refcount(b) >= 2 for b in cached)
    eng.flush([2])
    assert eng.allocator.free_blocks == free_before
    eng.prefix_cache.drop_all(eng.allocator)
    assert eng.allocator.free_blocks == eng.allocator.n_blocks


def test_prefix_cache_eviction_under_pool_pressure():
    """Cache-held pages are reclaimable: a prompt that needs more blocks
    than the free list holds evicts LRU prefixes instead of failing."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(7))
    # tiny pool: 10 blocks of 8 -> an 80-token budget total
    eng = RaggedInferenceEngine(
        model, _pc_cfg(n_kv_blocks=10, max_context=64), params=params)
    rng = np.random.default_rng(31)
    A = rng.integers(1, 128, (30,)).tolist()
    eng.generate({1: list(A)}, max_new_tokens=4)      # publishes ~4 blocks
    held = len(eng.prefix_cache)
    assert held > 0
    B = rng.integers(1, 128, (40,)).tolist()
    # admission must count cache-only-held pages as reclaimable: a
    # cache-saturated pool would otherwise starve can_schedule forever
    assert eng.can_schedule([2], [len(B) + 4])
    want = RaggedInferenceEngine(
        model, _cfg(n_kv_blocks=10, max_context=64),
        params=params).generate({2: list(B)}, max_new_tokens=4)[2]
    out = eng.generate({2: list(B)}, max_new_tokens=4)[2]
    assert out == want                                # evicted, not crashed


def test_prefix_cache_trim_copy_on_write():
    """Trimming into a SHARED block must not corrupt the cached copy:
    the sequence gets a private page; a later prompt reusing the cache
    still reproduces the original continuation."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(8))
    P = list(np.random.default_rng(41).integers(1, 128, (16,)))  # 2 blocks

    eng = RaggedInferenceEngine(model, _pc_cfg(), params=params)
    want = eng.generate({1: [int(t) for t in P]}, max_new_tokens=6)[1]

    # adopt the cached prefix — sharing is capped at len-1, so with a
    # 16-token prompt only block 0 (positions 0-7) is shared
    eng.put([2], [[int(t) for t in P]])
    shared_block = eng.seqs[2].blocks[0]
    assert eng.allocator.refcount(shared_block) >= 2
    # trim INTO the shared block (pos 4): must copy-on-write
    eng.trim(2, 4)
    assert eng.seqs[2].blocks[0] != shared_block      # private CoW page
    assert eng.allocator.refcount(shared_block) >= 1  # cache still holds it
    # scribble new tokens through the trimmed sequence (writes rows 4..)
    logits = eng.put([2], [[3, 5, 7, 9]])
    for _ in range(3):
        t = int(np.argmax(logits[0]))
        logits = eng.put([2], [[t]])
    eng.flush([2])

    # the cached prefix must be unpolluted: same prompt, same answer
    out = eng.generate({3: [int(t) for t in P]}, max_new_tokens=6)[3]
    assert out == want


def _check_tables(eng):
    """Every table the engine hands its step from now on is held to what
    ``fill_tables`` builds from the live block lists; returns the count."""
    from deepspeed_tpu.ops.ragged_host import fill_tables
    patched, calls = eng._host_tables, []

    def checked():
        got = patched()
        live = list(eng.seqs.values())
        np.testing.assert_array_equal(got, fill_tables(
            [s.blocks for s in live], [s.slot for s in live],
            eng.config.max_seqs, eng.max_pages))
        assert got is not eng._table    # the next tick patches the table
        calls.append(1)
        return got

    eng._host_tables = checked
    return calls


@pytest.mark.parametrize("scenario", ["trim_cow_flush_reuse", "speculative"])
def test_block_table_kept_between_ticks_is_fill_tables(scenario):
    """The table patched from the tick before equals one built anew, over
    what rewrites a block list or a slot: growth, a trim (with a
    copy-on-write of the boundary page), a flush, a slot and a uid taken
    again by a shorter sequence, speculation's trims."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(8))
    P = [int(t) for t in np.random.default_rng(41).integers(1, 128, (40,))]
    if scenario == "speculative":
        eng = RaggedInferenceEngine(model, _cfg(), params=params)
        calls = _check_tables(eng)
        eng.generate_speculative({1: [5, 6, 7, 8] * 6, 2: P[:17]},
                                 max_new_tokens=12)
        assert len(calls) > 3
        return
    eng = RaggedInferenceEngine(model, _pc_cfg(), params=params)
    calls = _check_tables(eng)
    eng.generate({1: P}, max_new_tokens=6)
    eng.put([2, 3], [P, P[:9]])                   # 2 adopts 1's pages
    assert eng.allocator.refcount(eng.seqs[2].blocks[0]) >= 2
    eng.trim(2, 12)                               # into a shared page
    logits = eng.put([2, 3], [[3, 5, 7, 9], [4]])
    for _ in range(10):                           # 2 grows past its old end
        logits = eng.put([2, 3], [[int(np.argmax(r))] for r in logits])
    eng.trim(3, 2)
    eng.put([3], [[8]])
    slot = eng.seqs[2].slot
    eng.flush([2])
    eng.put([3], [[9]])                           # 2's row is zero again
    eng.put([2, 3], [P[20:23], [1]])              # the uid again, shorter
    assert eng.seqs[2].slot == slot
    eng.flush([2, 3])
    assert len(calls) > 15


# ---------------------------------------------------------------------
# prompt-lookup speculative decoding (beyond-reference: FastGen decodes
# one token per step; here n-gram drafts verify as a chain in one step)
def test_speculative_matches_generate_token_exact():
    """Greedy acceptance makes generate_speculative token-IDENTICAL to
    generate() — on a repetitive prompt (drafts accepted) AND a random
    one (drafts mostly rejected)."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(9))
    rep = [5, 6, 7, 8] * 6                        # n-gram heaven
    rnd = list(np.random.default_rng(51).integers(1, 128, (17,)))

    for prompt in (rep, rnd):
        want = RaggedInferenceEngine(model, _cfg(), params=params).generate(
            {1: [int(t) for t in prompt]}, max_new_tokens=12)[1]
        eng = RaggedInferenceEngine(model, _cfg(), params=params)
        got = eng.generate_speculative({1: [int(t) for t in prompt]},
                                       max_new_tokens=12)[1]
        assert got == want, (got, want)
        assert eng.spec_stats["rounds"] >= 1


def test_speculative_acceptance_machinery(monkeypatch):
    """With an ORACLE draft (the true continuation), every proposal must
    be accepted and the device-round count collapses to
    ceil(tokens / (lookahead+1)) — pins the verify/accept/trim path
    independently of whether a random model happens to be repetitive."""
    import deepspeed_tpu.inference.ragged as ragged_mod

    model = _llama()
    params = model.init(jax.random.PRNGKey(9))
    P = list(np.random.default_rng(53).integers(1, 128, (13,)))
    want = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {1: list(P)}, max_new_tokens=12)[1]
    full = P + want

    def oracle(self, uid, next_token, ngram, k):
        ctx = self.seqs[uid].tokens + [next_token]
        assert list(ctx) == full[:len(ctx)]        # stream stays validated
        return full[len(ctx): len(ctx) + k]

    # the draft seam is the memoized draft_tokens (NgramIndex) now —
    # override it with the oracle at the same boundary
    monkeypatch.setattr(ragged_mod.RaggedInferenceEngine, "draft_tokens",
                        oracle)
    eng = RaggedInferenceEngine(model, _cfg(), params=params)
    got = eng.generate_speculative({1: list(P)}, max_new_tokens=12,
                                   lookahead=4)[1]
    assert got == want, (got, want)
    assert eng.spec_stats["accepted"] == eng.spec_stats["proposed"] > 0
    assert eng.spec_stats["rounds"] == 3           # ceil(11 / 5) rounds


def test_speculative_eos_and_multi_sequence():
    """EOS inside an accepted chain stops that sequence exactly where
    generate() stops it; mixed batches verify independently."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(10))
    p1 = [9, 2, 9, 2] * 5
    p2 = list(np.random.default_rng(52).integers(1, 128, (11,)))
    ref_eng = RaggedInferenceEngine(model, _cfg(), params=params)
    ref = ref_eng.generate({1: list(p1), 2: list(p2)}, max_new_tokens=10)
    # pick an eos that actually occurs mid-stream for seq 1 (else fall
    # back to exercising the no-eos path — still a valid parity check)
    eos = ref[1][3] if len(ref[1]) > 4 else None
    want = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {1: list(p1), 2: list(p2)}, max_new_tokens=10, eos_token_id=eos)

    eng = RaggedInferenceEngine(model, _cfg(), params=params)
    got = eng.generate_speculative({1: list(p1), 2: list(p2)},
                                   max_new_tokens=10, eos_token_id=eos)
    assert got == want, (got, want)


def test_speculative_composes_with_prefix_cache():
    """Speculative decoding + prefix caching together: trim-rewinds into
    private tail blocks never touch cached pages; output stays exact."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(11))
    P = [3, 4, 5] * 8                              # 24 tokens, repetitive
    want = RaggedInferenceEngine(model, _cfg(), params=params).generate(
        {1: list(P)}, max_new_tokens=10)[1]
    eng = RaggedInferenceEngine(model, _pc_cfg(), params=params)
    a = eng.generate_speculative({1: list(P)}, max_new_tokens=10)[1]
    b = eng.generate_speculative({2: list(P)}, max_new_tokens=10)[2]
    assert a == want and b == want
    assert eng.prefix_cache.hits >= 1              # cache hit on round 2


def test_speculative_rejects_sampling():
    model = _llama()
    eng = RaggedInferenceEngine(model, _cfg(temperature=0.8),
                                rng=jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="greedy-only"):
        eng.generate_speculative({1: [1, 2, 3]})


def test_prompt_lookup_drafting():
    from deepspeed_tpu.inference.drafter import _prompt_lookup

    ctx = [1, 2, 3, 9, 9, 1, 2, 3]
    assert _prompt_lookup(ctx, 3, 2) == [9, 9]     # follows [1,2,3]
    assert _prompt_lookup(ctx, 3, 5) == [9, 9, 1, 2, 3]
    assert _prompt_lookup([7, 8, 9], 3, 2) == []   # no earlier occurrence
    # prefers the hit with a full-k continuation (j=0 gives two tokens)
    assert _prompt_lookup([5, 5, 5, 5], 2, 2) == [5, 5]
    assert _prompt_lookup([1, 2], 3, 2) == []      # shorter than ngram


def test_speculative_budget_clamp():
    """Many live sequences x large lookahead under a small token budget:
    chains must fair-share the budget (no StopIteration off the bucket
    list) and stay token-exact."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(12))
    rng = np.random.default_rng(61)
    prompts = {i: rng.integers(1, 128, (9,)).tolist() for i in range(4)}
    cfg = dict(token_budget=16, max_seqs=4)
    want = RaggedInferenceEngine(model, _cfg(**cfg), params=params).generate(
        {u: list(p) for u, p in prompts.items()}, max_new_tokens=6)
    eng = RaggedInferenceEngine(model, _cfg(**cfg), params=params)
    got = eng.generate_speculative({u: list(p) for u, p in prompts.items()},
                                   max_new_tokens=6, lookahead=32)
    assert got == want, (got, want)


def test_stream_composes_with_prefix_cache():
    """stream() flushes on close, publishing into the prefix cache; a
    second stream of the same prompt adopts the pages and yields the
    identical token sequence."""
    model = _llama()
    params = model.init(jax.random.PRNGKey(13))
    P = list(np.random.default_rng(71).integers(1, 128, (20,)))

    ref_eng = RaggedInferenceEngine(model, _cfg(), params=params)
    want = list(ref_eng.stream(1, list(P), max_new_tokens=8))

    eng = RaggedInferenceEngine(model, _pc_cfg(), params=params)
    a = list(eng.stream(1, list(P), max_new_tokens=8))
    b = list(eng.stream(2, list(P), max_new_tokens=8))
    assert a == want and b == want
    assert eng.prefix_cache.hits >= 1


def _head_window_write(leaf, page, row, new):
    """The row write as it was before ``write_kv_rows``: the KV-head axis a
    *window* of the scatter (XLA:TPU answers with a layout round trip of
    the whole leaf). Lives on only here, as the oracle."""
    return leaf.at[page, :, row].set(new.astype(leaf.dtype))


@pytest.mark.parametrize("engine", ["gather", "pallas_interpret"])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("entry", ["put", "put_spec", "decode_steps"])
def test_row_write_lands_where_the_head_window_scatter_did(entry, kv_quant,
                                                           engine,
                                                           monkeypatch):
    """One compiled call of each entry point over hand-made lanes — a
    prefill chunk crossing pages, decode lanes, inactive lanes and a lane
    at or past ``max_context`` (the tail of a multi-step decode) — leaves
    every page but the sink bit-equal to what the old formulation wrote,
    payload and scale leaves, on a pool that held other values before.
    ``pallas_interpret``: the engine as the TPU runs it, head_dim 128, the
    rows written by ``write_kv_pages`` (a quantized pool's by the scatter
    still), against the gather engine under the old formulation; one
    layer, whose rows do not depend on how attention was computed."""
    if engine == "gather":
        model = _llama()
    else:
        model = Llama("tiny", n_layers=1, d_model=256, n_heads=2,
                      n_kv_heads=1, vocab_size=128, max_seq_len=256,
                      use_flash=False, remat=False)
    cfg = _cfg(max_context=64, n_kv_blocks=32, kv_quant=kv_quant)
    S, n, mp = cfg.max_seqs, cfg.n_kv_blocks, 64 // cfg.kv_block_size
    tables = jnp.arange(S * mp, dtype=jnp.int32).reshape(S, mp)
    i32 = lambda xs: jnp.asarray(xs, jnp.int32)
    if entry == "decode_steps":          # one lane a slot, four steps
        slots, pos = i32([0, 1, -1, 3]), i32([20, 62, 0, 40])  # 62 -> 65
    else:                                # T = 32 lanes
        chunk = list(range(5, 21))       # slot 0: 16 tokens over 3 pages
        slots = i32([0] * 16 + [1, 2, 3] + [-1] * 13)
        pos = i32(chunk + [30, 64, 63] + [0] * 13)   # slot 2 is past
    toks = (jnp.arange(slots.shape[0], dtype=jnp.int32) * 7 + 3) % 128

    def run(write):
        with monkeypatch.context() as m:
            if write is not None:
                m.setattr("deepspeed_tpu.ops.pallas.paged_attention."
                          "write_kv_rows", write)
            elif engine == "pallas_interpret":
                m.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
            eng = RaggedInferenceEngine(model, cfg, rng=jax.random.PRNGKey(5))
            assert eng.attention_path == ("gather" if write else engine)
            assert eng._writes_pages == (write is None and kv_quant == "none"
                                         and engine == "pallas_interpret")
            rng = np.random.default_rng(9)
            fill = lambda x: jnp.asarray(
                rng.integers(-100, 100, x.shape).astype(x.dtype)
                if jnp.issubdtype(x.dtype, jnp.integer)
                else rng.uniform(0.01, 1.0, x.shape).astype(x.dtype))
            pools = jax.tree_util.tree_map(fill, eng.kv_pool)
            if entry == "put":
                out = eng._build_step()(eng.params, pools, toks, slots, pos,
                                        tables, i32([15, 16, 18, 18]), mp)
            elif entry == "put_spec":
                out = eng._build_verify()(
                    eng.params, pools, toks, slots, pos, tables,
                    i32([[12, 13, 14, 15], [16] * 4, [18] * 4, [18] * 4]), mp)
            else:
                out = eng._build_decode()(
                    eng.params, pools, toks, pos, slots, tables,
                    i32([0, 1, 2, 3]), jax.random.PRNGKey(0), mp, -1)
        return jax.tree_util.tree_map(np.asarray, out)

    (got, got_pools), (want, want_pools) = run(None), run(_head_window_write)
    leaves = jax.tree_util.tree_leaves(got_pools)
    assert len(leaves) == (4 if kv_quant == "int8" else 2) * model.config.n_layers
    for a, b in zip(leaves, jax.tree_util.tree_leaves(want_pools)):
        assert a.shape[0] == n + 1 and a.dtype == b.dtype
        np.testing.assert_array_equal(a[:n], b[:n])
    if engine == "gather":
        np.testing.assert_array_equal(got, want)
    elif entry == "decode_steps":   # slot 2 is not live: its ids are junk
        np.testing.assert_array_equal(got[[0, 1, 3]], want[[0, 1, 3]])
    else:       # another attention formulation: the same logits, not bits
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("held", ["rows", "one_row", "nothing"])
def test_put_reuses_its_rows_buffer_only_once_released(held):
    """put() hands back a view of a buffer it keeps (a new [n, vocab]
    array every tick is megabytes of first-touched pages): the next call
    writes into the same buffer only when nothing refers to the last
    call's rows, or to a row of them, any more."""
    eng = RaggedInferenceEngine(_llama(), _cfg())
    rows = eng.put([1, 2], [[1, 2, 3], [4, 5]])
    was, before = id(rows.base), rows.copy()   # an id holds no reference
    keep = {"rows": rows, "one_row": rows[1], "nothing": None}[held]
    del rows
    again = eng.put([1, 2], [[7], [8]])
    assert (id(again.base) == was) == (held == "nothing")
    if keep is not None:     # what the caller still holds is untouched
        np.testing.assert_array_equal(keep, before if held == "rows"
                                      else before[1])
    assert not np.isnan(again).any() and again.shape == (2, 128)
