"""A.X-K1 (``model_type: axk1``) at a small size on the CPU: latent attention
(MLA) dense in ``model.apply`` and absorbed over latent pages in the served
step, the sigmoid router's group-limited choice, the shared expert, the
leading dense layer and the expert share, each against the plain reference
``benchmarks/reference/axk1.py`` or a hand-written loop."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, weights
from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.parallel import moe as pmoe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_LAYERS, ROUTED, VOCAB = 3, 48, 128


def small_cfg(held=(0, 6), **over):
    """The cell's configuration file with every size cut: 48 routed experts
    in 8 groups of 6, 4 a token inside 4 groups; ``held`` of them here."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "a.x-k1.json")))
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
               kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=64,
               v_head_dim=32, vocab_size=VOCAB, num_experts_per_tok=4,
               n_routed_experts=held[1] - held[0], experts_held=list(held),
               num_hidden_layers=N_LAYERS, max_position_embeddings=512)
    cfg["published"] = dict(cfg["published"], n_routed_experts=ROUTED)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    cfg.update(over)
    return cfg


def build(cfg, seed=5, dtype=jnp.float32):
    model = harness.find("architectures", "axk1").build(cfg, N_LAYERS)
    model.config.use_flash = model.config.remat = False
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, weights.make(shapes, seed, dtype, N_LAYERS)


def engine_of(model, params, **over):
    kw = dict(token_budget=32, max_seqs=4, kv_block_size=16, n_kv_blocks=48,
              max_context=128, dtype=jnp.float32, enable_prefix_cache=True)
    kw.update(over)
    return RaggedInferenceEngine(model, RaggedConfig(**kw), params=params)


def reference_logits(cfg, params, tokens):
    ref = harness.find("reference", "axk1")
    b, s = tokens.shape
    rows, cols = np.repeat(np.arange(b), s), np.tile(np.arange(s), b)
    return np.asarray(ref.logits_at(params, jnp.asarray(tokens), rows, cols,
                                    cfg, N_LAYERS)).reshape(b, s, -1)


@pytest.fixture(scope="module")
def small():
    cfg = small_cfg()
    model, params = build(cfg)
    tokens = np.random.default_rng(0).integers(1, VOCAB, (2, 48))
    return cfg, model, params, tokens, reference_logits(cfg, params, tokens)


def test_config_is_the_published_one_cut():
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "a.x-k1.json")))
    c = harness.find("architectures", "axk1").build(cfg, 6).config
    assert (c.d_model, c.n_heads, c.head_dim, c.v_head_dim) == (7168, 64, 192, 128)
    assert (c.q_lora_rank, c.kv_lora_rank, c.latent_row) == (1536, 512, 640)
    assert (c.n_experts, c.n_held, c.top_k, c.n_groups, c.topk_groups) \
        == (192, 12, 8, 8, 4)
    assert (c.scoring, c.routed_scale) == ("sigmoid", 2.5)
    assert (c.first_dense_layers, c.dense_d_ff, c.d_ff) == (1, 18432, 2048)
    assert c.attn_scale == pytest.approx(0.130861, rel=1e-5)
    # the issue's arithmetic: 4.166 B parameters, 8.33 GB in bfloat16
    assert c.param_count() == 4_166_294_528
    # one row a token a layer, and no K / V a head
    rcfg = RaggedConfig(kv_block_size=16, n_kv_blocks=8, dtype=jnp.bfloat16)
    kinds = kv_cache.pool_leaves(c, rcfg)
    assert kinds.k.n == 0 and kinds.latent.n == 6
    assert kinds.latent.shape == (9, 1, 16, 640)
    assert kv_cache.kv_page_bytes(c, rcfg) == 16 * 640 * 2 * 6
    assert kv_cache.kv_blocks_for_bytes(10 * 16 * 640 * 2 * 6, c, rcfg) == 10


def test_yarn_frequencies_are_the_references():
    from deepspeed_tpu.ops.rotary import rope_frequencies

    ref = harness.find("reference", "axk1")
    want = ref.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)
    got = np.asarray(rope_frequencies(64, 3, 10000.0, (32.0, 4096, 32.0, 1.0)))
    np.testing.assert_allclose(got[1], want, rtol=1e-6)
    plain = np.asarray(rope_frequencies(64, 3, 10000.0))[1]
    assert got[1][0] == plain[0] and got[1][-1] == pytest.approx(plain[-1] / 32)
    assert (np.diff(got[1] / plain) <= 1e-9).all()     # a ramp, downwards


def test_model_apply_is_the_reference(small):
    cfg, model, params, tokens, want = small
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("path", ["gather", "pallas_interpret"])
def test_prefill_in_two_chunks_then_decode_through_latent_pages(
        small, path, monkeypatch):
    """The absorbed form over the latent leaf against the reference's
    expanded attention: a prompt of 40 tokens under a budget of 32 (two
    chunks), then 8 decode steps; float32, to 1e-5 of the logits' scale."""
    cfg, model, params, tokens, want = small
    if path == "pallas_interpret":
        monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    eng = engine_of(model, params)
    assert eng.attention_path == path and not eng._writes_pages
    assert eng.kv_pool.k == () and len(eng.kv_pool.latent) == N_LAYERS
    uids = [11, 12]
    rows = eng.put(uids, [tokens[0, :40].tolist(), tokens[1, :17].tolist()])
    assert np.isnan(rows[0, 0]) and not np.isnan(rows[1, 0])
    np.testing.assert_allclose(rows[1], want[1, 16], rtol=1e-4, atol=1e-5)
    rows = eng.put([11], [[]])
    np.testing.assert_allclose(rows[0], want[0, 39], rtol=1e-4, atol=1e-5)
    for t in range(40, 48):
        rows = eng.put([11], [[int(tokens[0, t])]])
        np.testing.assert_allclose(rows[0], want[0, t], rtol=1e-4, atol=1e-5)
    eng.flush(uids)
    kv_cache.assert_block_balance(eng)


def test_adopted_document_decodes_to_the_same_logits(small):
    """A sequence that adopts another's cached document through PrefixCache
    (latent pages, shared and refcounted) against one that prefilled it."""
    cfg, model, params, tokens, want = small
    eng = engine_of(model, params)
    doc, q1, q2 = tokens[0, :32].tolist(), [5, 6, 7], tokens[0, 32:40].tolist()
    first = eng.put([1], [doc + q1])
    while np.isnan(first[0, 0]):
        first = eng.put([1], [[]])
    eng.flush([1])                      # publishes the document's two pages
    assert len(eng.prefix_cache) >= 2
    before = eng.prefix_cache.hits
    rows = eng.put([2], [doc + q2])     # adopts 32 tokens, prefills 8
    assert eng.prefix_cache.hits == before + 1 and eng.seqs[2].seen == 40
    np.testing.assert_allclose(rows[0], want[0, 39], rtol=1e-4, atol=1e-5)
    for t in range(40, 44):
        rows = eng.put([2], [[int(tokens[0, t])]])
        np.testing.assert_allclose(rows[0], want[0, t], rtol=1e-4, atol=1e-5)
    eng.flush([2])
    eng.prefix_cache.drop_all(eng.allocator)
    kv_cache.assert_block_balance(eng)


# ----------------------------------------------------------------------
# the router
def loop_route(logits, n_groups, topk_groups, top_k, scale):
    """The issue's rule, a token and an expert at a time."""
    out = []
    for row in np.asarray(logits, np.float64):
        s = 1.0 / (1.0 + np.exp(-row))
        size = len(s) // n_groups
        group = [max(s[g * size:(g + 1) * size]) for g in range(n_groups)]
        best = sorted(range(n_groups), key=lambda g: (-group[g], g))[:topk_groups]
        inside = [e for e in range(len(s)) if e // size in best]
        chosen = sorted(inside, key=lambda e: (-s[e], e))[:top_k]
        total = sum(s[e] for e in chosen) + 1e-20
        out.append({e: scale * s[e] / total for e in chosen})
    return out


def test_router_is_the_hand_written_loop():
    cfg = pmoe.GateConfig(n_experts=ROUTED, top_k=4, scoring="sigmoid",
                          n_groups=8, topk_groups=4, routed_scale=2.5)
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(64, ROUTED)).astype(np.float32)
    logits[0, 7] = logits[0, 2] = 3.0            # a tie inside group 0
    logits[1, :] = 0.0                           # every score the same
    logits[2, 40] = 9.0                          # one expert carries a group
    _, w, idx = pmoe.route(jnp.asarray(logits), cfg)
    want = loop_route(logits, 8, 4, 4, 2.5)
    for t in range(64):
        got = dict(zip(np.asarray(idx[t]).tolist(), np.asarray(w[t]).tolist()))
        assert sorted(got) == sorted(want[t]), t
        for e, v in want[t].items():
            assert got[e] == pytest.approx(v, rel=1e-5)
    # ties go to the lower index, in the groups and in the experts
    assert sorted(np.asarray(idx[1]).tolist()) == [0, 1, 2, 3]
    row0 = np.asarray(idx[0]).tolist()
    assert row0.index(2) + 1 == row0.index(7)
    assert 40 in np.asarray(idx[2]).tolist()
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


def test_default_routing_is_bit_equal_to_the_softmax_top_k():
    """Mixtral's and SDAR's gates: the defaults are operation for operation
    what the dropless branch computed before ``route``."""
    rng = np.random.default_rng(4)
    for n_experts, k in ((8, 2), (128, 8)):
        logits = jnp.asarray(rng.normal(size=(96, n_experts)), jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
        got = pmoe.route(logits, pmoe.GateConfig(n_experts=n_experts, top_k=k))
        for a, b in zip(got, (probs, topw, topi)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_default_moe_layer_is_bit_equal_with_and_without_a_share():
    """A layer that holds every expert computes what it did: the whole
    range as ``experts_held`` adds the masks and changes no bit."""
    rng = jax.random.PRNGKey(2)
    x = jax.random.normal(rng, (2, 24, 32), jnp.float32)
    base = pmoe.MoELayer(32, 48, pmoe.GateConfig(n_experts=8, top_k=2))
    held = pmoe.MoELayer(32, 48, pmoe.GateConfig(n_experts=8, top_k=2,
                                                  experts_held=(0, 8)))
    params = base.init(rng)
    a, _ = base.apply(params, x, training=False)
    b, _ = held.apply(params, x, training=False)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_capacity_gate_refuses_what_it_does_not_implement():
    logits = jnp.zeros((8, 8), jnp.float32)
    for kw in (dict(scoring="sigmoid"), dict(n_groups=2, topk_groups=1),
               dict(top_k=4), dict(routed_scale=2.5),
               dict(experts_held=(0, 4))):
        cfg = pmoe.GateConfig(n_experts=8, **{"top_k": 2, **kw})
        with pytest.raises(NotImplementedError, match="capacity gate"):
            pmoe.top_k_gating(logits, cfg, 4)
    with pytest.raises(ValueError):
        pmoe.GateConfig(n_experts=8, n_groups=3)
    with pytest.raises(ValueError):
        pmoe.GateConfig(n_experts=8, experts_held=(4, 12))


# ----------------------------------------------------------------------
# the expert share
def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The guide's test: each of 8 shares of 6 experts computes its own
    experts' part, weighted over all 4 chosen; the parts, with the shared
    expert counted once, add up to the reference's uncut layer."""
    ref = harness.find("reference", "axk1")
    whole_cfg = small_cfg(held=(0, ROUTED))
    model, params = build(whole_cfg)
    lw = {k: params["layers"][k][0] for k in ref.MOE}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 64), jnp.float32)
    static = dict(ref._static_cfg(whole_cfg))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.expert_mlp(x, lw, static, None))
        shared = np.asarray(ref.base.swiglu(x, lw["ws_gate"], lw["ws_up"],
                                            lw["ws_down"], None))
    total = np.zeros_like(want)
    n_shares, per = 8, ROUTED // 8
    for i in range(n_shares):
        first = i * per
        layer = pmoe.MoELayer(
            64, 32, pmoe.GateConfig(
                n_experts=ROUTED, top_k=4, scoring="sigmoid", n_groups=8,
                topk_groups=4, routed_scale=2.5,
                experts_held=(first, first + per)), n_shared_experts=1)
        own = {k: (v[first:first + per] if k in pmoe.RAGGED_OPERANDS else v)
               for k, v in lw.items()}
        out, _ = layer.apply(own, x, training=False)
        total += np.asarray(out) - shared      # what every share has alike
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    assert np.abs(want - shared).max() > 0.1   # the routed part is no zero


def test_dead_lanes_and_absent_experts_reach_no_group():
    """Group sizes: pairs of experts this holder lacks, and pairs of lanes
    that are not live, are in none."""
    rng = np.random.default_rng(6)
    S, k, E = 12, 4, 6
    idx = jnp.asarray(rng.integers(0, ROUTED, (S, k)), jnp.int32)
    idx = idx.at[0].set(jnp.asarray([6, 7, 8, 30]))    # three of them held
    idx = idx.at[1].set(jnp.asarray([6, 7, 8, 9]))     # a dead lane's
    live = jnp.ones((S,), bool).at[1].set(False)
    w = jnp.full((S, k), 0.25, jnp.float32)
    x = jnp.asarray(rng.normal(size=(S, 16)), jnp.float32)
    params = {n: jnp.asarray(rng.normal(size=(E,) + shape), jnp.float32)
              for n, shape in (("w_gate", (16, 8)), ("w_up", (16, 8)),
                               ("w_down", (8, 16)))}
    tally = []
    out = pmoe.no_drop_moe(x, w, idx, params, "silu_glu", held_from=6,
                           live=live, tally=tally)
    (touched, kept), = tally
    held = (np.asarray(idx) >= 6) & (np.asarray(idx) < 12) \
        & np.asarray(live)[:, None]
    assert int(kept) == held.sum() and held[0].sum() == 3 and not held[1].any()
    assert int(touched) == len(set(np.asarray(idx)[held].tolist()))
    assert not np.asarray(out[1]).any()                # the dead lane: zeros
    # each live lane: its held experts' weighted outputs, and nothing else
    for t in (0, 5):
        want = np.zeros(16)
        for e, g in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if 6 <= e < 12:
                h = np.asarray(x[t]) @ np.asarray(params["w_gate"][e - 6])
                u = np.asarray(x[t]) @ np.asarray(params["w_up"][e - 6])
                want += g * ((h / (1 + np.exp(-h)) * u)
                             @ np.asarray(params["w_down"][e - 6]))
        np.testing.assert_allclose(np.asarray(out[t]), want, rtol=1e-4,
                                   atol=1e-5)


def test_served_step_counts_experts_touched(small):
    """``ragged.put``'s count comes from the device with the ids: padding
    lanes reach no expert, so the count follows the live lanes alone."""
    cfg, model, params, tokens, want = small
    eng = engine_of(model, params)
    eng.return_token_ids()
    seen = []
    record = eng._record_step_telemetry
    eng._record_step_telemetry = lambda sched, n, attrs: (
        seen.append(dict(attrs)), record(sched, n, attrs))
    ids = eng.put([3], [tokens[0, :9].tolist()])
    assert ids.shape == (1,) and ids[0] == int(np.argmax(want[0, 8]))
    a = seen[-1]
    assert a["experts_held"] == 6 * 2 and a["latent_layers"] == N_LAYERS
    assert a["ctx_rows"] == 9 and a["lanes"] == 32
    # 9 live lanes x 4 pairs x 2 expert layers at the most, 1/8 of them here
    assert 0 <= a["pairs_kept"] <= 72 and a["experts_touched"] <= 12
    assert (a["experts_touched"] == 0) == (a["pairs_kept"] == 0)
    assert a["matched"] == 0 and a["prompt"] == 9
    eng.flush([3])


# ----------------------------------------------------------------------
# refusals, and the checkpoint mapper
def test_refusals_by_message(small):
    cfg, model, params, tokens, want = small
    with pytest.raises(NotImplementedError, match="latent"):
        engine_of(model, params, kv_quant="int8")
    eng = engine_of(model, params)
    with pytest.raises(NotImplementedError, match="experts_held"):
        eng.put_spec([1], [[3, 4]], [[]])
    with pytest.raises(NotImplementedError, match="latent"):
        kv_cache.refuse_latent(model.config, "tensor-parallel serving")
    with pytest.raises(NotImplementedError, match="dense KV cache"):
        model.apply(params, jnp.asarray(tokens[:, :4]),
                    kv_caches=(jnp.zeros(()), jnp.zeros(())), cache_pos=0)


def test_hf_mapper_reads_deepseek_v3_names():
    """``axk1`` in ``_MAPPERS``: a state dict under DeepSeek-V3's names,
    written from a native tree (kv_b_proj joined, the rotated columns
    interleaved), maps back to that tree."""
    from deepspeed_tpu.checkpoint import hf

    cfg = small_cfg()
    model, params = build(cfg)
    c = model.config
    assert "axk1" in hf._MAPPERS
    lay = {k: np.asarray(v) for k, v in params["layers"].items()
           if k != "dense"}
    h, dn, dr, dv, r = 4, 32, 64, 32, 128
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    inter = np.argsort(halves)              # native order -> DeepSeek's
    state = {"model.embed_tokens.weight": np.asarray(params["tok_embed"]),
             "model.norm.weight": np.asarray(params["final_norm_w"]),
             "lm_head.weight": np.asarray(params["lm_head"]).T}
    for i in range(N_LAYERS):
        p = f"model.layers.{i}."
        uq = lay["w_uq"][i].reshape(-1, h, dn + dr)
        uq = np.concatenate([uq[..., :dn], uq[..., dn:][..., inter]], -1)
        dkv = np.concatenate([lay["w_dkv"][i][:, :r],
                              lay["w_dkv"][i][:, r:][:, inter]], -1)
        ukv = np.concatenate([lay["w_uk"][i].reshape(r, h, dn),
                              lay["w_uv"][i].reshape(r, h, dv)], -1)
        state.update({
            p + "input_layernorm.weight": lay["attn_norm_w"][i],
            p + "post_attention_layernorm.weight": lay["mlp_norm_w"][i],
            p + "self_attn.q_a_proj.weight": lay["w_dq"][i].T,
            p + "self_attn.q_a_layernorm.weight": lay["q_lora_norm_w"][i],
            p + "self_attn.q_b_proj.weight": uq.reshape(-1, h * (dn + dr)).T,
            p + "self_attn.kv_a_proj_with_mqa.weight": dkv.T,
            p + "self_attn.kv_a_layernorm.weight": lay["kv_lora_norm_w"][i],
            p + "self_attn.kv_b_proj.weight": ukv.reshape(r, -1).T,
            p + "self_attn.o_proj.weight": lay["wo"][i].T})
        if i < c.first_dense_layers:
            for leaf, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                               ("w_down", "down_proj")):
                state[p + f"mlp.{name}.weight"] = np.asarray(
                    params["layers"]["dense"][leaf][i]).T
            continue
        m = i - c.first_dense_layers
        state[p + "mlp.gate.weight"] = lay["wg"][m].T
        for leaf, name in (("gate", "gate_proj"), ("up", "up_proj"),
                           ("down", "down_proj")):
            state[p + f"mlp.shared_experts.{name}.weight"] = \
                lay["ws_" + leaf][m].T
            for e in range(c.n_held):
                state[p + f"mlp.experts.{e}.{name}.weight"] = \
                    lay["w_" + leaf][m][e].T
    got = hf.map_hf_params(dict(state), "axk1", c)
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    a, b = flat(got), flat(params)
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    state["model.layers.1.mlp.gate.e_score_correction_bias"] = np.zeros(ROUTED)
    with pytest.raises(NotImplementedError, match="noaux_tc"):
        hf.map_hf_params(dict(state), "axk1", c)
    # what the router and the rotary tables do not implement, by message
    yarn = small_cfg()["rope_scaling"]
    for over, said in ((dict(topk_method="noaux_tc"), "noaux_tc"),
                       (dict(norm_topk_prob=False), "norm_topk_prob"),
                       (dict(rope_scaling=dict(yarn, mscale=0.7)), "mscale")):
        with pytest.raises(NotImplementedError, match=said):
            hf.axk1_config(dict(small_cfg(), n_routed_experts=ROUTED, **over))
