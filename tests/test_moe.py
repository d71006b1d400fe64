"""MoE tests (parity with reference tests/unit/moe/test_moe.py:
gating correctness, capacity semantics, EP training e2e)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.models import GPTMoE
from deepspeed_tpu.parallel.moe import GateConfig, MoELayer, capacity, top_k_gating
from deepspeed_tpu.runtime.dataloader import shard_batch


def test_capacity_formula():
    cfg = GateConfig(n_experts=8, top_k=2, capacity_factor=1.0, min_capacity=4)
    assert capacity(64, cfg, training=True) == 16  # 64*1.0*2/8
    assert capacity(4, cfg, training=True) == 4    # min floor


def test_top1_gating_each_token_routed_once():
    cfg = GateConfig(n_experts=4, top_k=1, capacity_factor=4.0)
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(16, 4)), jnp.float32)
    combine, dispatch, aux = top_k_gating(logits, cfg, cap=16)
    per_token = np.asarray(dispatch.sum(axis=(1, 2)))
    assert (per_token <= 1).all() and per_token.sum() == 16  # ample capacity: all kept
    assert float(aux) > 0


def test_top2_gating_two_experts_per_token():
    cfg = GateConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(16, 4)), jnp.float32)
    combine, dispatch, _ = top_k_gating(logits, cfg, cap=32)
    per_token = np.asarray(dispatch.sum(axis=(1, 2)))
    assert (per_token == 2).all()
    # combine weights ~ normalized
    w = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(w, 1.0, rtol=1e-5)


def test_capacity_drops_tokens():
    cfg = GateConfig(n_experts=2, top_k=1, capacity_factor=0.25, min_capacity=1)
    logits = jnp.zeros((16, 2))  # all tokens tie -> same expert after argmax
    cap = capacity(16, cfg, training=True)  # 2
    _, dispatch, _ = top_k_gating(logits, cfg, cap=cap)
    assert int(dispatch.sum()) <= cap * 2


def test_moe_layer_forward_shape():
    layer = MoELayer(d_model=32, d_ff=64, gate=GateConfig(n_experts=4, top_k=2))
    params = layer.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 32)), jnp.float32)
    out, aux = layer.apply(params, x)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))


def test_moe_model_trains_ep_mesh():
    """GPT-MoE trains on a data=2 x expert=4 mesh (EP + DP composition,
    reference BASELINE config[4] shape)."""
    model = GPTMoE("tiny", n_experts=4, n_layers=2, capacity_factor=2.0,
                   use_flash=False, remat=False)
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": 2, "expert": 4},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = dst.initialize(model=model, config=cfg, rng=jax.random.PRNGKey(0))
    w_up = engine.params["layers"]["w_up"]
    assert "expert" in str(w_up.sharding.spec)
    toks = np.random.default_rng(0).integers(0, 1024, (8, 64)).astype(np.int32)
    batch = shard_batch({"input_ids": toks}, engine.topo)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_aux_loss_nonzero():
    model = GPTMoE("tiny", n_experts=4, n_layers=2, use_flash=False, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 1024, (2, 32)).astype(np.int32)
    _logits, aux = model.apply(params, toks, return_aux=True)
    assert float(aux) > 0


def test_moe_no_drop_keeps_all_tokens():
    cfg = GateConfig(n_experts=2, top_k=1, capacity_factor=0.25, min_capacity=1,
                     drop_tokens=False)
    logits = jnp.zeros((16, 2))  # worst case: all tokens to one expert
    cap = capacity(16, cfg, training=True)
    assert cap == 16
    _, dispatch, _ = top_k_gating(logits, cfg, cap=cap)
    assert int(dispatch.sum()) == 16  # nothing dropped


def test_moe_flops_counts_active_params_only():
    from deepspeed_tpu.models import gpt_moe_config

    cfg = gpt_moe_config("tiny", n_experts=8, top_k=2)
    assert cfg.active_param_count() < cfg.param_count()
    assert cfg.flops_per_token(64) < 6.0 * cfg.param_count() + 12 * cfg.n_layers * cfg.d_model * 64


def test_moe_aux_loss_under_jit_is_usable():
    """Regression: aux must come back explicitly, never via traced self-state."""
    model = GPTMoE("tiny", n_experts=4, n_layers=2, use_flash=False, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 1024, (2, 32)).astype(np.int32)
    f = jax.jit(lambda p, t: model.apply(p, t, return_aux=True))
    _, aux1 = f(params, toks)
    _, aux2 = f(params, toks)  # second (cached) call must still work
    assert float(aux1) == float(aux2) and float(aux1) > 0


def test_moe_param_count():
    model = GPTMoE("tiny", n_experts=4, n_layers=2, use_flash=False, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    actual = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    assert actual == model.config.param_count()


# ----------------------------------------------------------------------
# the experts' products on the stored stack (no_drop_moe's ``layer``)
def _stacked_experts(activation, L=3, E=4, d=16, f=32, S=24, k=2):
    """A stack of expert banks, rows for one layer and a routing in which
    expert 2 gets no row."""
    moe = MoELayer(d, f, GateConfig(n_experts=E, top_k=k),
                   activation=activation, use_bias=activation == "gelu")
    stack = moe.init(jax.random.PRNGKey(3), n_layers=L)
    for i, b in enumerate(("b_up", "b_down")):     # init's biases are zeros
        if b in stack:
            stack[b] = 0.1 * jax.random.normal(jax.random.PRNGKey(40 + i),
                                               stack[b].shape)
    kx, kp, ki = jax.random.split(jax.random.PRNGKey(5), 3)
    idx = jax.random.randint(ki, (S, k), 0, E - 1)
    idx = jnp.where(idx >= 2, idx + 1, idx)        # never expert 2
    return (stack, jax.random.normal(kx, (S, d)),
            jax.nn.softmax(jax.random.normal(kp, (S, k)), -1), idx)


@pytest.mark.parametrize("li", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
def test_no_drop_moe_on_the_stack_is_the_sliced_call(activation, li):
    """The expert matrices unsliced and the layer's place beside them give
    bit for bit what the layer's own slices give (the other layers' groups
    are empty), biases and an expert with no row included."""
    from deepspeed_tpu.parallel.moe import RAGGED_OPERANDS, no_drop_moe

    stack, x, probs, idx = _stacked_experts(activation)
    assert 2 not in np.asarray(idx)
    sliced = {n: a[li] for n, a in stack.items()}
    whole = {n: a if n in RAGGED_OPERANDS else a[li]
             for n, a in stack.items()}
    want = jax.jit(lambda p: no_drop_moe(x, probs, idx, p, activation))(sliced)
    got = jax.jit(lambda p: no_drop_moe(x, probs, idx, p, activation,
                                        layer=li))(whole)
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tiny_hybrid():
    from deepspeed_tpu.checkpoint import hf
    from deepspeed_tpu.models.transformer import Transformer

    hc = {"model_type": "olmo_hybrid", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 64, "num_hidden_layers": 4,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "max_position_embeddings": 64, "attention_bias": False,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "layer_types": ["linear_attention"] * 3 + ["full_attention"],
          "linear_num_key_heads": 2, "linear_num_value_heads": 2,
          "linear_key_head_dim": 8, "linear_value_head_dim": 16,
          "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
          "rope_parameters": {"rope_theta": None}}
    return Transformer(hf.olmo_hybrid_config(hc, 4))


@pytest.mark.parametrize("family", ["dense", "layer_types", "moe",
                                    "moe_in_place"])
def test_layer_params_slices_every_leaf_but_the_named_operands(family):
    """A dense tree and a ``layer_types`` tree come back as they always
    did, whatever ``in_place`` says; only a model that names
    ``stacked_operands`` keeps those leaves whole, with the layer beside
    them, and only when asked."""
    from deepspeed_tpu.models import Llama

    in_place = family != "moe"
    if family == "dense":
        model = Llama("tiny", n_layers=3, d_model=32, n_heads=4,
                      vocab_size=64, max_seq_len=32)
    elif family == "layer_types":
        model = _tiny_hybrid()
    else:
        model = GPTMoE("tiny", n_experts=4, n_layers=3, d_model=32,
                       n_heads=4, vocab_size=64, max_seq_len=32)
    layers = model.init(jax.random.PRNGKey(0))["layers"]
    c = model.config
    for li in range(c.n_layers):
        kind, lp = model.layer_params(layers, li, in_place)
        whole = model.stacked_operands if family == "moe_in_place" else ()
        want = {k: v[li] for k, v in layers.items()
                if k not in ("full", "linear") and k not in whole}
        if c.layer_types is not None:
            assert kind == c.layer_types[li]
            at = c.layers_of(kind).index(li)
            want.update({k: v[at] for k, v in layers[kind].items()})
        else:
            assert kind == "full"
        if whole:
            assert lp.pop("layer") == li
            assert whole == ("w_gate", "w_up", "w_down")
            for k in ("w_up", "w_down"):           # gelu experts: no w_gate
                assert lp.pop(k) is layers[k]
        assert sorted(lp) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(lp[k]),
                                          np.asarray(want[k]))
