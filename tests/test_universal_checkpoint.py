"""Universal checkpoint: resume across a CHANGED mesh and ZeRO stage, plus
the offline CLI tools (reference ds_to_universal.py + zero_to_fp32.py)."""

import numpy as np
import jax
import jax.numpy as jnp

import deepspeed_tpu as dst
from deepspeed_tpu.checkpoint.universal import (load_universal, to_universal,
                                                zero_to_fp32)
from deepspeed_tpu.models import Llama
from deepspeed_tpu.parallel.mesh import reset_topology
from deepspeed_tpu.runtime.dataloader import shard_batch


def _model():
    return Llama("tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                 vocab_size=64, max_seq_len=16, use_flash=False, remat=False)


def _engine(mesh, stage):
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
           "mesh": mesh,
           "zero_optimization": {"stage": stage,
                                 "stage3_param_persistence_threshold": 0},
           "steps_per_print": 1000}
    engine, _, _, _ = dst.initialize(model=_model(), config=cfg,
                                     rng=jax.random.PRNGKey(0))
    return engine


def _batch(seed=0):
    return {"input_ids": np.random.default_rng(seed).integers(
        0, 64, (8, 16)).astype(np.int32)}


def test_resume_across_mesh_and_stage(tmp_path):
    """Train ZeRO-3 on dp4xtp2, reload on dp8 ZeRO-1: training state
    (params, optimizer moments, step) must carry over exactly."""
    e1 = _engine({"data": 4, "model": 2}, stage=3)
    for i in range(4):
        e1.train_batch(shard_batch(_batch(i), e1.topo))
    ref_loss = float(e1.eval_batch(shard_batch(_batch(9), e1.topo)))
    e1.save_checkpoint(str(tmp_path), tag="x")

    reset_topology()
    e2 = _engine({"data": 8}, stage=1)
    e2.load_checkpoint(str(tmp_path), tag="x")
    assert e2.global_steps == 4
    got_loss = float(e2.eval_batch(shard_batch(_batch(9), e2.topo)))
    np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-5)
    # optimizer state carried over: next steps keep improving smoothly
    l5 = float(e2.train_batch(shard_batch(_batch(4), e2.topo))["loss"])
    assert np.isfinite(l5)


def test_state_saved_in_the_shape_only_layout_resumes_under_the_new_rule(
        tmp_path):
    """A checkpoint written while the moments lay as ``opt_state_shardings``
    placed them before it took the model's specs (each leaf cut by its shape
    alone: the layout built by hand here) loads onto the layout the rule
    gives now, and the steps after it are the uninterrupted run's to the
    bit."""
    from jax.sharding import NamedSharding

    e1 = _engine({"data": 8}, stage=3)
    for i in range(2):
        e1.train_batch(shard_batch(_batch(i), e1.topo))
    new = e1.opt_state_shardings
    old = jax.tree_util.tree_map(
        lambda s, x: NamedSharding(s.mesh, e1.zero_rules.state_spec(
            tuple(x.shape), None)), new, e1.opt_state)
    specs = lambda tree: [s.spec for s in jax.tree_util.tree_leaves(tree)]
    moved = sum(a != b for a, b in zip(specs(old), specs(new)))
    assert moved >= 8, (specs(old), specs(new))   # four leaves, two moments
    assert specs(new.mu) == specs(e1.grad_shardings) == specs(new.nu)
    e1.opt_state = jax.device_put(e1.opt_state, old)
    assert [x.sharding.spec for x in jax.tree_util.tree_leaves(e1.opt_state)] \
        == specs(old)
    e1.save_checkpoint(str(tmp_path), tag="old")
    e1.opt_state = jax.device_put(e1.opt_state, new)
    want = [float(e1.train_batch(shard_batch(_batch(i), e1.topo))["loss"])
            for i in (2, 3)]

    reset_topology()
    e2 = _engine({"data": 8}, stage=3)
    e2.load_checkpoint(str(tmp_path), tag="old")
    assert e2.global_steps == 2
    for leaf, sh in zip(jax.tree_util.tree_leaves(e2.opt_state),
                        jax.tree_util.tree_leaves(e2.opt_state_shardings)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    got = [float(e2.train_batch(shard_batch(_batch(i), e2.topo))["loss"])
           for i in (2, 3)]
    assert got == want


def test_universal_cli_roundtrip(tmp_path):
    # the offline CLI surface on top of the conversion + cross-mesh load
    # machinery that test_resume_across_mesh_and_stage pins
    e = _engine({"data": 8}, stage=3)
    e.train_batch(shard_batch(_batch(0), e.topo))
    e.save_checkpoint(str(tmp_path / "ck"), tag="t")

    out_dir = to_universal(str(tmp_path / "ck"), str(tmp_path / "uni"), tag="t")
    flat = load_universal(out_dir)
    assert len(flat) >= 6
    # keys are framework-free and arrays are full (unsharded) logical shapes
    tok = [k for k in flat if "tok_embed" in k]
    assert tok and flat[tok[0]].shape == (64, 32)

    npz_path = zero_to_fp32(str(tmp_path / "ck"), str(tmp_path / "fp32.npz"),
                            tag="t")
    loaded = np.load(npz_path)
    assert all(loaded[k].dtype == np.float32 for k in loaded.files)
    # fp32 consolidation matches the engine's live params
    live = e.get_fp32_state_dict()
    leaves, _ = jax.tree_util.tree_flatten_with_path(live)
    total_live = sum(np.asarray(v).size for _, v in leaves)
    total_cli = sum(loaded[k].size for k in loaded.files)
    assert total_cli == total_live


def test_universal_cli_main(tmp_path):
    from deepspeed_tpu.checkpoint.universal import main

    e = _engine({"data": 8}, stage=2)
    e.save_checkpoint(str(tmp_path / "ck"))  # default tag + latest pointer
    rc = main(["zero-to-fp32", str(tmp_path / "ck"), str(tmp_path / "out.npz")])
    assert rc == 0
    assert (tmp_path / "out.npz").exists()
