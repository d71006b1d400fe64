"""Aux subsystems: flops profiler, activation checkpointing, eigenvalue,
elasticity, PLD, tiling, curriculum/data sampler, random-LTD, launcher,
env report, hybrid engine.

Mirrors the reference's per-subsystem unit files (tests/unit/profiling,
tests/unit/elasticity, tests/unit/runtime/test_pld.py,
tests/unit/runtime/zero/test_zero_tiled.py,
tests/unit/runtime/test_data_efficiency.py, tests/unit/launcher)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst


# ----------------------------------------------------------------------
# flops profiler
def test_flops_profiler_measure():
    from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler, count_params

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 128))
    prof = FlopsProfiler(peak_flops=1e12)
    res = prof.measure(lambda w, x: x @ w, w, x, params={"w": w}, iters=2, warmup=1)
    # 2 * 64 * 128 * 128 = 2.1e6 flops; cost analysis or 0 fallback
    assert res.params == 128 * 128
    if res.flops:
        assert res.flops == pytest.approx(2 * 64 * 128 * 128, rel=0.5)
    assert res.duration_s > 0
    assert count_params({"a": w, "b": x}) == 128 * 128 + 64 * 128


def test_get_model_profile():
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.profiling.flops_profiler import get_model_profile

    model = Llama("tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                  vocab_size=64, max_seq_len=16, use_flash=False, remat=False)
    tokens = np.zeros((2, 16), np.int32)
    res = get_model_profile(model, {"input_ids": tokens})
    assert res.params > 0 and res.duration_s > 0


def test_peak_is_that_of_the_engines_mesh_not_of_every_device(monkeypatch):
    """A process may hold a sub-mesh (chip_smoke.py --chips 4 trains on one
    of four devices): the MFU denominator is per-chip peak x the devices
    of the mesh the engine runs on. Off-TPU there is no peak at all, and a
    TPU kind without published peaks is an error, never a default."""
    from types import SimpleNamespace

    from deepspeed_tpu.parallel.mesh import Topology
    from deepspeed_tpu.profiling import flops_profiler as fp
    from simple_model import init_mlp_params, mlp_loss

    topo = Topology.build_virtual({"data": 2})   # 2 of the 8 virtual devices
    engine, _, _, _ = dst.initialize(
        loss_fn=mlp_loss, params=init_mlp_params(jax.random.PRNGKey(0)),
        config={"train_batch_size": 4,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
        topology=topo)
    assert fp.device_peaks() is None            # the CPU has no peak
    assert engine._get_peak_flops() == 0.0      # ... and MFU is not reported
    monkeypatch.setattr(fp, "device_peaks", lambda device=None: {"bf16_flops": 1e12})
    engine._peak_flops = None
    assert engine._get_peak_flops() == 2e12
    assert fp.FlopsProfiler(mesh=topo.mesh).peak_flops == 2e12
    assert fp.FlopsProfiler().peak_flops == 2e12    # the current topology's
    monkeypatch.undo()
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert fp.device_peaks(v5e)["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        fp.device_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v9"))


# ----------------------------------------------------------------------
# activation checkpointing
def test_activation_checkpointing_policies():
    from deepspeed_tpu.runtime import activation_checkpointing as ac

    def f(x):
        return jnp.sum(jnp.tanh(x @ x.T) ** 2)

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
    base = jax.grad(f)(x)
    for policy in ("full", "selective", "nothing"):
        g = jax.grad(ac.checkpoint_wrapper(f, policy=policy))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(base), rtol=1e-5)
    # megatron-style immediate application
    y = ac.checkpoint(lambda a: a * 2, jnp.ones(3))
    np.testing.assert_allclose(np.asarray(y), 2.0)
    with pytest.raises(ValueError):
        ac.checkpoint_wrapper(f, policy="bogus")


# ----------------------------------------------------------------------
# eigenvalue
def test_eigenvalue_power_iteration():
    from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

    # quadratic loss 0.5 x^T A x has Hessian A: top eigenvalue known
    evs = np.array([5.0, 2.0, 1.0, 0.5])
    q, _ = np.linalg.qr(np.random.default_rng(0).normal(size=(4, 4)))
    A = jnp.asarray(q @ np.diag(evs) @ q.T, jnp.float32)

    def loss(p):
        x = p["x"]
        return 0.5 * x @ A @ x

    est = Eigenvalue(max_iter=200, tol=1e-5).compute_eigenvalue(
        loss, {"x": jnp.ones(4)})
    assert est == pytest.approx(5.0, rel=1e-2)


# ----------------------------------------------------------------------
# elasticity
def test_compute_elastic_config():
    from deepspeed_tpu.elasticity import ElasticityError, compute_elastic_config

    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 100,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.2}}
    batch, gpus = compute_elastic_config(cfg)
    assert batch <= 100 and len(gpus) > 0
    # every valid gpu count divides the batch with some micro size
    for n in gpus:
        assert any(batch % (mb * n) == 0 for mb in (2, 4))
    b2, g2, micro = compute_elastic_config(cfg, world_size=gpus[0])
    assert b2 == batch and micro in (2, 4)
    with pytest.raises(ElasticityError):
        compute_elastic_config({"elasticity": {"enabled": False}})


def test_elastic_config_immutable():
    from deepspeed_tpu.elasticity import ensure_immutable_elastic_config
    from deepspeed_tpu.elasticity.elasticity import _frozen

    _frozen.clear()
    e = {"enabled": True, "max_train_batch_size": 64}
    ensure_immutable_elastic_config(e)
    ensure_immutable_elastic_config(e)  # same fingerprint fine
    from deepspeed_tpu.elasticity import ElasticityError

    with pytest.raises(ElasticityError):
        ensure_immutable_elastic_config({"enabled": True, "max_train_batch_size": 32})
    _frozen.clear()


# ----------------------------------------------------------------------
# progressive layer drop
def test_pld_schedule():
    from deepspeed_tpu.runtime.progressive_layer_drop import (
        ProgressiveLayerDrop, layer_keep_probs, sample_layer_mask)

    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    t0 = pld.update_state(0)
    t_inf = pld.update_state(10_000)
    assert t0 == pytest.approx(1.0) and t_inf == pytest.approx(0.5, abs=1e-3)
    assert pld.get_state()["pld_theta"] == t_inf
    probs = layer_keep_probs(0.5, 8)
    assert probs[0] == 1.0 and probs[-1] > 0.5
    mask = sample_layer_mask(jax.random.PRNGKey(0), 0.5, 8)
    assert mask.shape == (8,)
    assert ((np.asarray(mask) == 0) | (np.asarray(mask) >= 1.0)).all()


# ----------------------------------------------------------------------
# tiled linear
def test_tiled_linear_matches_dense():
    from deepspeed_tpu.runtime.tiling import TiledLinear

    layer = TiledLinear(32, 48, in_splits=4, out_splits=3)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
    out = layer.apply(params, x)
    dense = x @ layer.full_weight(params) + params["b"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-5,
                               atol=1e-5)
    parts = layer.apply(params, x, combine_out_splits=False)
    assert len(parts) == 3 and parts[0].shape == (5, 16)


# ----------------------------------------------------------------------
# curriculum + sampler + random-ltd
def test_curriculum_scheduler_types():
    from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler

    lin = CurriculumScheduler({"curriculum_type": "fixed_linear",
                               "min_difficulty": 8, "max_difficulty": 64,
                               "schedule_config": {"total_curriculum_step": 100,
                                                   "difficulty_step": 8}})
    assert lin.update_difficulty(0) == 8
    assert lin.update_difficulty(50) == 32
    assert lin.update_difficulty(1000) == 64
    root = CurriculumScheduler({"curriculum_type": "fixed_root",
                                "min_difficulty": 0, "max_difficulty": 100,
                                "schedule_config": {"total_curriculum_step": 100,
                                                    "root_degree": 2,
                                                    "difficulty_step": 1}})
    assert root.update_difficulty(25) == 50  # sqrt(0.25) = 0.5
    disc = CurriculumScheduler({"curriculum_type": "fixed_discrete",
                                "min_difficulty": 1, "max_difficulty": 3,
                                "schedule_config": {"difficulty": [1, 2, 3],
                                                    "max_step": [10, 20]}})
    assert disc.update_difficulty(5) == 1
    assert disc.update_difficulty(15) == 2
    assert disc.update_difficulty(25) == 3


def test_data_sampler_curriculum_and_dp_shard():
    from deepspeed_tpu.runtime.data_pipeline import (CurriculumScheduler,
                                                     DeepSpeedDataSampler)

    n = 64
    difficulties = np.arange(n) % 8  # 0..7
    cur = CurriculumScheduler({"curriculum_type": "fixed_linear",
                               "min_difficulty": 2, "max_difficulty": 8,
                               "schedule_config": {"total_curriculum_step": 10,
                                                   "difficulty_step": 1}})
    cur_cfg = {"curriculum_type": "fixed_linear",
               "min_difficulty": 2, "max_difficulty": 8,
               "schedule_config": {"total_curriculum_step": 10,
                                   "difficulty_step": 1}}
    ranks = []
    for rank in range(2):
        s = DeepSpeedDataSampler(n, difficulties, CurriculumScheduler(cur_cfg),
                                 batch_size=8,
                                 data_parallel_rank=rank, data_parallel_size=2,
                                 seed=3)
        batches = list(s)
        assert all(len(b) == 4 for b in batches)
        # early batches only contain easy samples
        assert (difficulties[batches[0]] <= 2).all()
        ranks.append(batches)
    # dp shards are disjoint per step
    for b0, b1 in zip(*ranks):
        assert not set(b0) & set(b1)


def test_data_sampler_no_duplicates_or_skips_as_curriculum_grows():
    """Regression: samples unlocking mid-epoch must neither re-yield already
    consumed samples nor permanently skip new ones (advisor round-1 finding:
    a flat cursor into a recomputed eligible array shifts under growth)."""
    from deepspeed_tpu.runtime.data_pipeline import (CurriculumScheduler,
                                                     DeepSpeedDataSampler)

    n = 96
    difficulties = np.arange(n) % 8
    cur_cfg = {"curriculum_type": "fixed_linear",
               "min_difficulty": 1, "max_difficulty": 8,
               "schedule_config": {"total_curriculum_step": 6,
                                   "difficulty_step": 1}}
    s = DeepSpeedDataSampler(n, difficulties, CurriculumScheduler(cur_cfg),
                             batch_size=4, data_parallel_rank=0,
                             data_parallel_size=1, seed=7, drop_last=False)
    seen = np.concatenate(list(s))
    assert len(seen) == len(set(seen.tolist())), "duplicate samples yielded"
    assert set(seen.tolist()) == set(range(n)), "samples permanently skipped"


def test_random_ltd():
    from deepspeed_tpu.runtime.data_pipeline import (
        RandomLTDScheduler, random_ltd_gather, random_ltd_scatter)
    from deepspeed_tpu.runtime.data_pipeline.random_ltd import (
        apply_random_ltd, random_ltd_indices)

    sched = RandomLTDScheduler(total_layers=4, mini_seq=16, full_seq=64,
                               total_steps=100, step_size=16)
    assert sched.update_seq(0) == 16
    assert sched.update_seq(100) == 64
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 8))
    idx = random_ltd_indices(jax.random.PRNGKey(1), 32, 16, 2)
    assert idx.shape == (2, 16)
    assert (np.diff(np.asarray(idx), axis=1) > 0).all()  # sorted unique
    sub = random_ltd_gather(x, idx)
    back = random_ltd_scatter(x, sub * 2, idx)
    # kept tokens doubled, dropped tokens untouched
    kept_mask = np.zeros((2, 32), bool)
    for b in range(2):
        kept_mask[b, np.asarray(idx)[b]] = True
    np.testing.assert_allclose(np.asarray(back)[kept_mask],
                               np.asarray(x)[kept_mask] * 2, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(back)[~kept_mask],
                               np.asarray(x)[~kept_mask], rtol=1e-6)
    out = apply_random_ltd(lambda t: t + 1, x, jax.random.PRNGKey(2), keep=16)
    assert out.shape == x.shape


# ----------------------------------------------------------------------
# launcher + env report
def test_launcher_hostfile_and_filters(tmp_path):
    from deepspeed_tpu.launcher.runner import (decode_world_info,
                                               encode_world_info,
                                               fetch_hostfile,
                                               filter_resources)

    hf = tmp_path / "hostfile"
    hf.write_text("worker-0 slots=4\nworker-1 slots=4\n# comment\nworker-2 slots=8\n")
    res = fetch_hostfile(str(hf))
    assert res == {"worker-0": 4, "worker-1": 4, "worker-2": 8}
    inc = filter_resources(res, include="worker-0:0;1,worker-2", exclude="")
    assert inc == {"worker-0": [0, 1], "worker-2": list(range(8))}
    exc = filter_resources(res, include="", exclude="worker-1")
    assert set(exc) == {"worker-0", "worker-2"}
    blob = encode_world_info(inc)
    assert decode_world_info(blob) == {"worker-0": [0, 1],
                                       "worker-2": list(range(8))}
    with pytest.raises(ValueError):
        filter_resources(res, include="worker-0", exclude="worker-1")


def test_launcher_env(tmp_path):
    from deepspeed_tpu.launcher.runner import build_env, parse_args

    args = parse_args(["--master_addr", "10.0.0.1", "--master_port", "1234",
                       "--node_rank", "1", "train.py", "--foo"])
    env = build_env(args, {"a": [0], "b": [0]})
    assert env["COORDINATOR_ADDRESS"] == "10.0.0.1:1234"
    assert env["NUM_PROCESSES"] == "2"
    assert env["PROCESS_ID"] == "1"
    assert args.user_args == ["--foo"]


def test_env_report(capsys):
    from deepspeed_tpu.env_report import main

    assert main() == 0
    out = capsys.readouterr().out
    assert "op compatibility" in out and "jax version" in out


# ----------------------------------------------------------------------
# hybrid engine
def test_hybrid_engine_train_and_generate():
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.runtime.hybrid_engine import HybridEngine
    from deepspeed_tpu.inference.engine import InferenceConfig
    from deepspeed_tpu.runtime.dataloader import shard_batch

    model = Llama("tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                  vocab_size=64, max_seq_len=64, use_flash=False, remat=False)
    engine, _, _, _ = dst.initialize(model=model, config={
        "train_batch_size": 8, "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
        "mesh": {"data": 4, "model": 2}, "steps_per_print": 1000,
    }, rng=jax.random.PRNGKey(0))
    hybrid = HybridEngine(engine, InferenceConfig(dtype="float32", temperature=0.0))
    prompt = np.random.default_rng(0).integers(0, 64, (2, 4)).astype(np.int32)
    gen0 = hybrid.generate(prompt, max_new_tokens=4)
    batch = {"input_ids": np.random.default_rng(1).integers(0, 64, (8, 16)).astype(np.int32)}
    for _ in range(5):
        hybrid.train_batch(shard_batch(batch, engine.topo))
    gen1 = hybrid.generate(prompt, max_new_tokens=4)
    # weights moved -> generation changes (live-weight sharing works)
    assert gen0.shape == gen1.shape == (2, 8)
    assert not np.array_equal(gen0, gen1)


def test_see_memory_usage_runs():
    """memory_breakdown analog (reference runtime/utils.py
    see_memory_usage): returns host RSS always; device stats when the
    backend exposes an allocator."""
    from deepspeed_tpu.utils.memory import see_memory_usage

    stats = see_memory_usage("unit-test", force=True)
    assert stats.get("host_rss_gb", 0) > 0


def test_profiler_trace_and_annotations(tmp_path):
    """trace() captures an XLA profile; annotate wraps work in a named
    range (reference range_push parity)."""
    import os

    from deepspeed_tpu.profiling.trace import annotate, trace

    calls = []

    def f(x):
        calls.append(x)
        return x + 1

    logdir = str(tmp_path / "prof")
    with trace(logdir):
        with annotate("outer", n=1) as span:
            assert f(1) == 2
            span.set_metadata(done=1)
    assert calls == [1]
    # a trace directory with at least one event file must exist
    found = []
    for root, _, files in os.walk(logdir):
        found.extend(files)
    assert found, "no profiler output written"
