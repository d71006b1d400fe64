"""Where the optimizer state lies, and what the update computes there
(docs/communication.md "Where a moment lies").

``ZeroShardingRules.opt_state_shardings`` gives every part of the optimizer
state that is shaped like the parameters the spec its gradient has, so the
elementwise update reads ``g``, ``mu``, ``nu`` and the master in one layout.
The first half holds the rule leaf by leaf, from shapes alone (no engine, no
array); the second half holds the update itself to a plain float32
reference fed the engine's own gradients, and to the same engine at stage
0 — the benchmark's ``correct`` stops at the gradient norm (PERF.md
section 7 q. 1), so a layout change of the update is checked here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as dst
from deepspeed_tpu.config import ZeroConfig
from deepspeed_tpu.models import Llama
from deepspeed_tpu.models.moe import GPTMoE
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.zero import ZeroShardingRules
from deepspeed_tpu.runtime.dataloader import shard_batch
from deepspeed_tpu.runtime.optimizers import (Transform, as_transform,
                                              build_optimizer)

VOCAB = 128
#: at stage 3 the masters under it stay whole on every chip (the norms'
#: vectors here); the moments are cut all the same
THRESHOLD = 1000


def mistral_layout():
    """Mistral's layout at a tiny size: grouped-query attention, SwiGLU,
    an untied head, layers stacked along a leading axis."""
    return Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 d_ff=256, vocab_size=VOCAB, max_seq_len=64, use_flash=False,
                 remat=False)


def expert_layout():
    return GPTMoE("tiny", n_experts=4, d_model=64, n_layers=2, n_heads=4,
                  d_ff=128, vocab_size=VOCAB, max_seq_len=64,
                  activation="silu_glu", use_flash=False, remat=False)


LAYOUTS = {"mistral": mistral_layout, "expert": expert_layout}


@pytest.fixture(autouse=True)
def _fresh_topology():
    yield
    mesh_mod.reset_topology()


def _rules(layout, model_axis, stage, threshold=THRESHOLD):
    """(rules, parameter shapes, the model's specs) on four virtual devices:
    ``data`` x ``model`` = 4."""
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual(
        {"data": 4 // model_axis, "model": model_axis})
    model = LAYOUTS[layout]().bind_topology(topo)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = ZeroShardingRules(topo, ZeroConfig(
        stage=stage, stage3_param_persistence_threshold=threshold))
    return rules, shapes, model.partition_specs(shapes, topo)


def _specs(shardings):
    return [s.spec for s in jax.tree_util.tree_leaves(shardings)]


def _shape_only(rules, state_shapes):
    """The rule as it stood: every leaf by its shape alone."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(rules.topo.mesh,
                                rules.state_spec(tuple(s.shape), None)),
        state_shapes)


def _axes(spec):
    return {a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}


# ----------------------------------------------------------------------
# the rule, leaf by leaf
@pytest.mark.parametrize("stage", [1, 2, 3])
@pytest.mark.parametrize("model_axis", [1, 2])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_moment_lies_as_its_gradient_does(layout, model_axis, stage):
    rules, shapes, tp = _rules(layout, model_axis, stage)
    state = jax.eval_shape(build_optimizer("adamw", {}).init, shapes)
    placed = rules.opt_state_shardings(state, shapes, tp)
    assert jax.tree_util.tree_structure(placed) \
        == jax.tree_util.tree_structure(state)
    assert placed.count.spec == P()
    # the gradient's shard as the update reads it: at stage 1 the gradients
    # arrive whole (psum) and the update reads a chip's slice of them, which
    # is the layout ``grad_shardings`` constrains them onto from stage 2 up
    reduced = ZeroShardingRules(rules.topo, ZeroConfig(
        stage=max(stage, 2), stage3_param_persistence_threshold=THRESHOLD))
    grads = _specs(reduced.grad_shardings(shapes, tp))
    if stage >= 2:
        assert grads == _specs(rules.grad_shardings(shapes, tp))
    assert _specs(placed.mu) == grads and _specs(placed.nu) == grads
    masters = _specs(rules.param_shardings(shapes, tp))
    leaves = jax.tree_util.tree_leaves(shapes)
    cut = 0
    for leaf, moment, master, base in zip(
            leaves, grads, masters,
            jax.tree_util.tree_leaves(
                tp, is_leaf=lambda x: isinstance(x, P))):
        # a moment carries the model's axes where the model put them
        assert [e for e in base] == [
            m if b is not None else None for m, b in zip(moment, base)]
        if stage == 3 and leaf.size >= THRESHOLD:
            assert moment == master, (leaf.shape, moment, master)
            cut += 1
        elif stage == 3:
            assert "data" not in _axes(master) and "data" in _axes(moment)
        else:
            assert "data" not in _axes(master)
    if stage == 3:
        assert 0 < cut < len(leaves)       # both sides of the threshold
    # and the old rule did disagree, on the leaves a model spec occupies a
    # dimension of: that is what this file's PR repaired
    assert _specs(_shape_only(rules, state).mu) != grads


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_without_specs_or_on_a_foreign_state_the_rule_is_the_old_one(stage):
    """``tp_specs=None`` gives the shape-only rule letter for letter, and so
    does every leaf of a state that is not shaped like the parameters: a flat
    buffer, a tree of the parameters' structure with one leaf factored, a
    scalar. A subtree that IS shaped like them inside such a state takes
    the gradient's specs."""
    rules, shapes, tp = _rules("mistral", 1, stage)
    adam = jax.eval_shape(build_optimizer("adamw", {}).init, shapes)
    for got in (rules.opt_state_shardings(adam),
                rules.opt_state_shardings(adam, shapes),
                rules.opt_state_shardings(adam, shapes, None),
                rules.opt_state_shardings(adam, None, tp)):
        assert _specs(got) == _specs(_shape_only(rules, adam))
    factored = jax.tree_util.tree_map(lambda s: s, shapes)
    factored["layers"] = dict(factored["layers"])
    factored["layers"]["w_down"] = jax.ShapeDtypeStruct((2, 256), jnp.float32)
    foreign = {"scale": jax.ShapeDtypeStruct((), jnp.float32),
               "flat": jax.ShapeDtypeStruct((4096,), jnp.float32),
               "factored": factored, "momentum": shapes}
    placed = rules.opt_state_shardings(foreign, shapes, tp)
    old = _shape_only(rules, foreign)
    for key in ("scale", "flat", "factored"):
        assert _specs(placed[key]) == _specs(old[key]), key
    assert placed["scale"].spec == P()
    want = jax.tree_util.tree_map(
        lambda s, t: rules.state_spec(tuple(s.shape), t), shapes, tp)
    assert _specs(placed["momentum"]) == jax.tree_util.tree_leaves(
        want, is_leaf=lambda x: isinstance(x, P))
    if stage >= 1:
        assert _specs(placed["momentum"]) != _specs(old["momentum"])


@pytest.mark.parametrize("name", ["adamw", "sgd", "lamb", "lion", "adagrad",
                                  "optax_adamw", "optax_chain"])
def test_every_optimizer_s_moments_are_found_by_structure(name):
    """The five ``init``s of ``runtime/optimizers.py`` and an ``optax`` state
    handed in through ``as_transform``: whatever the class, each subtree
    shaped like the parameters gets the gradient's specs, each scalar
    replicates, and nothing else is in any of them."""
    rules, shapes, tp = _rules("mistral", 1, 3)
    if name == "optax_adamw":
        opt = as_transform(optax.adamw(1e-3))
    elif name == "optax_chain":
        opt = as_transform(optax.chain(optax.clip_by_global_norm(1.0),
                                       optax.sgd(1e-3, momentum=0.9)))
    else:
        opt = build_optimizer(name, {"momentum": 0.9} if name == "sgd" else {})
    state = jax.eval_shape(opt.init, shapes)
    placed = rules.opt_state_shardings(state, shapes, tp)
    grads = _specs(rules.grad_shardings(shapes, tp))
    n = len(grads)
    got = _specs(placed)
    scalars = [s for s, leaf in zip(got, jax.tree_util.tree_leaves(state))
               if leaf.ndim == 0]
    trees = [s for s, leaf in zip(got, jax.tree_util.tree_leaves(state))
             if leaf.ndim]
    assert all(s == P() for s in scalars)
    assert trees and len(trees) % n == 0
    for i in range(0, len(trees), n):
        assert trees[i:i + n] == grads


# ----------------------------------------------------------------------
# the update, held to a float32 reference
def recording(inner: Transform) -> Transform:
    """``inner`` with the (clipped, float32) gradients of its last update
    kept in the state: the engine's own gradients, read back bit for bit.
    The copy is shaped like the parameters, so it lies as a moment does."""

    def init(params):
        return (inner.init(params), jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def update(grads, state, params):
        updates, new = inner.update(grads, state[0], params)
        return updates, (new, jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads))

    return Transform(init, update)


LR, B1, B2, EPS, WD, MOMENTUM = 1e-3, 0.9, 0.999, 1e-8, 0.01, 0.9


def reference_update(name, params, state, grads, t):
    """Plain ``jax.numpy`` float32 on whole arrays of one device: AdamW as
    ``runtime/optimizers.adam`` states it (decoupled decay, bias correction)
    or SGD with momentum."""
    f32 = jnp.float32
    if name == "sgd":
        mom = jax.tree_util.tree_map(lambda m, g: f32(MOMENTUM) * m + g,
                                     state, grads)
        return jax.tree_util.tree_map(lambda p, m: p + (-f32(LR) * m),
                                      params, mom), mom
    mu, nu = state
    mu = jax.tree_util.tree_map(lambda m, g: f32(B1) * m + f32(1 - B1) * g,
                                mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: f32(B2) * v + f32(1 - B2) * jnp.square(g), nu, grads)
    c1 = 1 - f32(B1) ** f32(t)
    c2 = 1 - f32(B2) ** f32(t)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p + (-f32(LR) * ((m / c1) / (jnp.sqrt(v / c2) + EPS)
                                         + f32(WD) * p)), params, mu, nu)
    return new, (mu, nu)


def _trainer(name, stage):
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"data": 4, "model": 1})
    model = mistral_layout()
    params = {"adamw": {"lr": LR, "betas": (B1, B2), "eps": EPS,
                        "weight_decay": WD},
              "sgd": {"lr": LR, "momentum": MOMENTUM}}[name]
    engine, _, _, _ = dst.initialize(
        model=model, params=model.init(jax.random.PRNGKey(5)), topology=topo,
        optimizer=recording(build_optimizer(name, params)),
        config={"train_batch_size": 8, "steps_per_print": 1_000_000,
                "gradient_clipping": 1.0, "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": stage,
                    "stage3_param_persistence_threshold": 0}})
    rng = np.random.default_rng(1)
    batches = [shard_batch({"input_ids": jnp.asarray(
        rng.integers(1, VOCAB, (8, 32)), jnp.int32)}, engine.topo)
        for _ in range(3)]
    return engine, batches


def _host(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(np.asarray(x)), tree)


@pytest.mark.parametrize("name", ["adamw", "sgd"])
def test_three_steps_of_the_zero3_update_are_the_float32_reference_s(name):
    """ZeRO-3 over ``data=4`` with a ``model`` axis of 1, three
    ``train_batch`` steps. After each, the engine's parameters against the
    reference's update of the parameters it had before the step, fed the
    gradients the engine's update was handed: within four float32 ulps of
    the parameter and four of the learning rate, the update's scale (the
    compiler may contract a multiply and an add, and where ``b1 * m`` and
    ``(1 - b1) * g`` cancel, a last bit of either is many of a small
    parameter's: 35 were seen on one of 1.5e-5). Dropping the decay moves
    a parameter by 400 times that. Then the same trainer at stage 0: its
    loss after three steps within the bfloat16 forward's noise of the
    ZeRO-3 one's."""
    engine, batches = _trainer(name, 3)
    try:
        # every moment where its gradient lies, every master too (threshold 0)
        moments = engine.opt_state_shardings[0][1:]
        for tree in (*[m for m in moments if m is not None],
                     engine.opt_state_shardings[1], engine.param_shardings):
            assert _specs(tree) == _specs(engine.grad_shardings)
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), _host(engine.params))
        state = zeros if name == "sgd" else (zeros, zeros)
        losses = []
        for t, batch in enumerate(batches, start=1):
            before = _host(engine.params)
            losses.append(float(engine.train_batch(batch)["loss"]))
            grads = _host(engine.opt_state[1])
            want, state = reference_update(name, before, state, grads, t)
            for (path, w), g, b in zip(
                    jax.tree_util.tree_leaves_with_path(want),
                    jax.tree_util.tree_leaves(_host(engine.params)),
                    jax.tree_util.tree_leaves(before)):
                w, g, b = np.asarray(w), np.asarray(g), np.asarray(b)
                assert np.any(g != b), jax.tree_util.keystr(path)
                room = 4 * np.spacing(np.maximum(np.abs(w), np.abs(b))) \
                    + 4 * np.spacing(np.float32(LR))
                assert np.all(np.abs(g - w) <= room), (
                    t, jax.tree_util.keystr(path),
                    float(np.max(np.abs(g - w) / room)))
            # the engine's moments are the reference's too
            inner = engine.opt_state[0]
            for got, ref in zip(inner[1:] if name == "adamw" else inner[1:2],
                                state if name == "adamw" else (state,)):
                for a, r in zip(jax.tree_util.tree_leaves(_host(got)),
                                jax.tree_util.tree_leaves(ref)):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                               rtol=1e-6, atol=1e-12)
        assert losses[-1] < losses[0]
    finally:
        engine.close()
    plain, batches = _trainer(name, 0)
    try:
        plain_losses = [float(plain.train_batch(b)["loss"]) for b in batches]
    finally:
        plain.close()
    np.testing.assert_allclose(losses, plain_losses, rtol=2e-3)
