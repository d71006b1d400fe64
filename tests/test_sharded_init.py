"""Sharded construction (zero.Init parity) + ZeRO-3 param offload.

Reference surface: runtime/zero/partition_parameters.py:734 (zero.Init —
params materialize directly as partitions), runtime/zero/stage3.py:558 +
partitioned_param_swapper.py (param offload to CPU/NVMe between steps).
"""

import jax
import numpy as np

import deepspeed_tpu as dst
from deepspeed_tpu.models import Llama
from deepspeed_tpu.runtime.dataloader import shard_batch
# the CPU backend only exposes unpinned_host; accelerators pinned_host
from deepspeed_tpu.runtime.engine import host_memory_kind


def _model():
    return Llama("tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 vocab_size=128, max_seq_len=32, use_flash=False, remat=False)


def _config(**zero_extra):
    return {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0,
                              **zero_extra},
        "steps_per_print": 1000,
    }


def _batch(seed=0):
    t = np.random.default_rng(seed).integers(0, 128, (8, 32)).astype(np.int32)
    return {"input_ids": t}


def test_init_constructs_params_sharded():
    """No device ever holds a full big leaf: initialize() jits model.init
    with ZeRO out_shardings, so >host-RAM models can construct."""
    engine, _, _, _ = dst.initialize(model=_model(), config=_config(),
                                     rng=jax.random.PRNGKey(0))
    checked = 0
    for leaf in jax.tree_util.tree_leaves(engine.params):
        if leaf.size < 8 or leaf.size % 8 != 0:
            continue
        shard = leaf.addressable_shards[0].data.size
        if shard < leaf.size:
            assert shard == leaf.size // 8, (leaf.shape, shard)
            checked += 1
    assert checked >= 4, "no leaves actually sharded — init not sharded?"


def test_param_offload_cpu_parks_between_steps():
    engine, _, _, _ = dst.initialize(
        model=_model(),
        config=_config(offload_param={"device": "cpu"}),
        rng=jax.random.PRNGKey(0))
    assert engine._param_offload_device == "cpu"
    kinds = {leaf.sharding.memory_kind
             for leaf in jax.tree_util.tree_leaves(engine.params)
             if leaf.ndim >= 1}
    assert kinds == {host_memory_kind()}, kinds
    losses = [float(engine.train_batch(
        shard_batch(_batch(), engine.topo))["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0], losses
    # parked again after the step
    kinds = {leaf.sharding.memory_kind
             for leaf in jax.tree_util.tree_leaves(engine.params)
             if leaf.ndim >= 1}
    assert kinds == {host_memory_kind()}, kinds


def test_param_offload_cpu_same_trajectory_as_device():
    e_off, _, _, _ = dst.initialize(
        model=_model(), config=_config(offload_param={"device": "cpu"}),
        rng=jax.random.PRNGKey(0))
    from deepspeed_tpu.parallel.mesh import reset_topology
    reset_topology()
    e_dev, _, _, _ = dst.initialize(model=_model(), config=_config(),
                                    rng=jax.random.PRNGKey(0))
    for i in range(4):
        b = _batch(i)
        l_off = float(e_off.train_batch(shard_batch(b, e_off.topo))["loss"])
        l_dev = float(e_dev.train_batch(shard_batch(b, e_dev.topo))["loss"])
        np.testing.assert_allclose(l_off, l_dev, rtol=1e-5)


def test_param_offload_nvme_roundtrip(tmp_path):
    engine, _, _, _ = dst.initialize(
        model=_model(),
        config=_config(offload_param={"device": "nvme",
                                      "nvme_path": str(tmp_path)}),
        rng=jax.random.PRNGKey(0))
    assert engine._param_offload_device == "nvme"
    assert engine.params is None  # on disk between steps
    losses = [float(engine.train_batch(
        shard_batch(_batch(), engine.topo))["loss"]) for _ in range(4)]
    assert losses[-1] < losses[0], losses
    assert engine.params is None
    # checkpointing still sees the full state
    path = engine.save_checkpoint(str(tmp_path / "ckpt"))
    assert path
