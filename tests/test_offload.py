"""Optimizer-state offload (ZeRO-Offload / Infinity parity:
reference tests/unit/runtime/zero offload lanes)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as dst
from deepspeed_tpu.models import Llama
from deepspeed_tpu.runtime.dataloader import shard_batch
from deepspeed_tpu.parallel import mesh as mesh_mod
# the CPU backend only exposes unpinned_host; accelerators pinned_host
from deepspeed_tpu.runtime.engine import host_memory_kind


def _model():
    return Llama("tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                 vocab_size=64, max_seq_len=16, use_flash=False, remat=False)


def _config(offload, **kw):
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 1, "offload_optimizer": offload},
        "steps_per_print": 1000,
    }
    cfg.update(kw)
    return cfg


def _batch(seed=0):
    t = np.random.default_rng(seed).integers(0, 64, (8, 16)).astype(np.int32)
    return {"input_ids": jnp.asarray(t)}


def _run(engine, steps=6):
    losses = []
    for _ in range(steps):
        losses.append(float(engine.train_batch(
            shard_batch(_batch(), engine.topo))["loss"]))
    return losses


def test_cpu_offload_trains_and_matches_placement():
    engine, _, _, _ = dst.initialize(
        model=_model(), config=_config({"device": "cpu"}),
        rng=jax.random.PRNGKey(0))
    assert engine._offload_device == "cpu"
    # array state parked in host memory between steps (scalars stay on device)
    kinds = {leaf.sharding.memory_kind
             for leaf in jax.tree_util.tree_leaves(engine.opt_state)
             if leaf.ndim >= 1}
    assert kinds == {host_memory_kind()}
    losses = _run(engine)
    assert losses[-1] < losses[0]


def test_cpu_offload_same_trajectory_as_device():
    mesh_mod.reset_topology()
    e1, _, _, _ = dst.initialize(model=_model(), config=_config({"device": "none"}),
                                 rng=jax.random.PRNGKey(1))
    l1 = _run(e1, steps=4)
    mesh_mod.reset_topology()
    e2, _, _, _ = dst.initialize(model=_model(), config=_config({"device": "cpu"}),
                                 rng=jax.random.PRNGKey(1))
    l2 = _run(e2, steps=4)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_cpu_offload_round_trips_a_step_in_the_gradients_layout():
    """The host shardings are the device ones leaf by leaf, so under ZeRO-3
    a parked moment lies as its gradient does too: up to the device for a
    step and back, the layout holds and the trajectory is the
    device-resident engine's."""
    zero = lambda offload: {"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0,
        "offload_optimizer": offload}}
    specs = lambda tree: [s.spec for s in jax.tree_util.tree_leaves(tree)]
    mesh_mod.reset_topology()
    plain, _, _, _ = dst.initialize(
        model=_model(), config=_config({}, **zero({"device": "none"})),
        rng=jax.random.PRNGKey(3))
    want = _run(plain, steps=3)
    mesh_mod.reset_topology()
    engine, _, _, _ = dst.initialize(
        model=_model(), config=_config({}, **zero({"device": "cpu"})),
        rng=jax.random.PRNGKey(3))
    assert engine._offload_device == "cpu"
    grads = specs(engine.grad_shardings)
    assert specs(engine.opt_state_shardings.mu) == grads \
        == specs(engine._opt_host_shardings.nu) == specs(plain.grad_shardings)
    # the rule that saw shapes alone cut other dimensions of some leaves
    assert grads != [engine.zero_rules.state_spec(tuple(x.shape), None)
                     for x in jax.tree_util.tree_leaves(engine.params)]

    def parked():
        moments = jax.tree_util.tree_leaves(
            (engine.opt_state.mu, engine.opt_state.nu))
        assert {x.sharding.memory_kind for x in moments} \
            == {host_memory_kind()}
        return [x.sharding.spec for x in moments]

    assert parked() == grads + grads
    got = _run(engine, steps=3)
    assert parked() == grads + grads
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_nvme_offload_trains(tmp_path):
    engine, _, _, _ = dst.initialize(
        model=_model(),
        config=_config({"device": "nvme", "nvme_path": str(tmp_path / "swap")}),
        rng=jax.random.PRNGKey(2))
    assert engine._offload_device == "nvme"
    losses = _run(engine, steps=4)
    assert losses[-1] < losses[0]
    # state lives on disk between steps
    assert engine.opt_state is None
    assert engine._nvme_swapper.swapper.bytes_on_disk() > 0
    # checkpoint save/load works with swapped state
    ckpt = tmp_path / "ckpt"
    engine.save_checkpoint(str(ckpt), tag="t")
    engine.load_checkpoint(str(ckpt), tag="t")
    losses2 = _run(engine, steps=2)
    assert np.isfinite(losses2).all()
