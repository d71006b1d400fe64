"""Fault tolerance: atomic checkpoint commits, preemption-safe auto-resume,
divergence guards, and the seeded fault-injection harness.

Every recovery path is proven deterministically via resilience/chaos.py:
a crash before commit leaves the previous checkpoint loadable; a crash
after commit resumes at the exact step with an identical loss trajectory;
a corrupted shard is detected by the manifest and skipped; SIGTERM at
step K produces an emergency checkpoint and a clean drain — and with
every guard off, the step path performs zero extra host syncs.
"""

import json
import os
import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.resilience import (
    CollectiveFault,
    FaultInjector,
    InjectedFault,
    PreemptionGuard,
    RetryBudget,
    RetryError,
    RetryPolicy,
    corrupt_tag,
    install_fault_injector,
    retry_call,
)
from deepspeed_tpu.runtime.checkpoint import (
    COMMITTED_FILE,
    MANIFEST_FILE,
    CheckpointEngine,
    find_valid_tag,
    verify_tag,
)
from deepspeed_tpu.telemetry.registry import get_registry


@pytest.fixture(autouse=True)
def _clear_injector():
    yield
    install_fault_injector(None)


# ----------------------------------------------------------------------
# tiny deterministic training setup

def _loss_fn(params, batch, rng):
    x, y = batch["x"], batch["y"]
    p = x @ params["w"] + params["b"]
    return jnp.mean((p - y) ** 2) * batch["scale"][0]


def _params():
    return {"w": jnp.ones((8, 4), jnp.float32) * 0.1,
            "b": jnp.zeros((4,), jnp.float32)}


def _batch(i, scale=1.0):
    rng = np.random.default_rng(1000 + i)
    return {"x": rng.normal(size=(16, 8)).astype(np.float32),
            "y": rng.normal(size=(16, 4)).astype(np.float32),
            "scale": np.full((16,), scale, np.float32)}


def _engine(extra=None):
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "steps_per_print": 1000}
    cfg.update(extra or {})
    engine, _, _, _ = dst.initialize(loss_fn=_loss_fn, params=_params(),
                                     config=cfg)
    return engine


# ----------------------------------------------------------------------
# commit protocol

def test_commit_protocol_layout_and_latest(tmp_path):
    d = str(tmp_path)
    ck = CheckpointEngine()
    path = ck.save(d, "t1", {"a": np.arange(8, dtype=np.float32)},
                   client_state={"global_steps": 1})
    assert os.path.isfile(os.path.join(path, COMMITTED_FILE))
    assert os.path.isfile(os.path.join(path, MANIFEST_FILE))
    with open(os.path.join(path, MANIFEST_FILE)) as f:
        manifest = json.load(f)
    assert "meta.json" in manifest["files"]
    assert any(rel.startswith("state") for rel in manifest["files"])
    ok, reason = verify_tag(path)
    assert ok, reason
    with open(os.path.join(d, "latest")) as f:
        assert f.read().strip() == "t1"
    # no temp debris after a clean save
    assert not [n for n in os.listdir(d) if n.startswith(".tmp-")]


def test_crash_before_commit_preserves_previous(tmp_path):
    d = str(tmp_path)
    e = _engine({"checkpoint": {"save_dir": d}})
    install_fault_injector(FaultInjector(crash_before_commit_at_save=2))
    e.train_batch(_batch(0))
    e.save_checkpoint(d)  # save #1: commits fine at step 1
    e.train_batch(_batch(1))
    with pytest.raises(InjectedFault):
        e.save_checkpoint(d)  # save #2: dies before the atomic rename
    install_fault_injector(None)
    # the torn save never reached its final path; only temp debris remains
    assert not os.path.isdir(os.path.join(d, "global_step2"))
    assert find_valid_tag(d) == "global_step1"
    # auto-load falls back to the surviving tag and rewinds the engine
    assert e.load_checkpoint(d, auto=True) is not None
    assert e.global_steps == 1


def test_crash_after_commit_resumes_bit_exact(tmp_path):
    """The acceptance trajectory: kill the worker right after the commit
    rename (latest pointer never updated), auto-resume, and the remaining
    steps' losses must be IDENTICAL to an uninterrupted run."""
    d = str(tmp_path)
    ref = _engine()
    ref_losses = [float(ref.train_batch(_batch(i))["loss"]) for i in range(6)]

    e = _engine({"checkpoint": {"save_dir": d}})
    for i in range(3):
        e.train_batch(_batch(i))
    install_fault_injector(FaultInjector(crash_after_commit_at_save=1))
    with pytest.raises(InjectedFault):
        e.save_checkpoint(d)
    install_fault_injector(None)
    # commit happened before the crash: the tag is durable and valid even
    # though the 'latest' pointer was never written
    assert not os.path.isfile(os.path.join(d, "latest"))
    assert find_valid_tag(d) == "global_step3"

    e2 = _engine({"checkpoint": {"save_dir": d, "auto_resume": True}})
    assert e2.global_steps == 3
    resumed = [float(e2.train_batch(_batch(i))["loss"]) for i in range(3, 6)]
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=0, atol=0)


def test_corrupt_shard_detected_and_skipped(tmp_path):
    d = str(tmp_path)
    ck = CheckpointEngine()
    state = {"a": np.arange(16, dtype=np.float32)}
    ck.save(d, "s1", state)
    path2 = ck.save(d, "s2", state)
    corrupt_tag(path2)
    ok, reason = verify_tag(path2)
    assert not ok and "checksum mismatch" in reason
    # auto pick falls back past the corrupted newest tag
    assert find_valid_tag(d) == "s1"
    assert ck.load(d)["tag"] == "s1"
    # an explicitly requested corrupt tag is refused, not substituted
    assert ck.load(d, tag="s2") is None


def test_injector_corrupt_shard_hook(tmp_path):
    d = str(tmp_path)
    ck = CheckpointEngine()
    install_fault_injector(FaultInjector(corrupt_shard_at_save=1, seed=7))
    path = ck.save(d, "c1", {"a": np.arange(16, dtype=np.float32)})
    install_fault_injector(None)
    ok, _reason = verify_tag(path)
    assert not ok
    assert get_registry().counter("resilience/chaos/corrupt_shard").value >= 1


def test_keep_last_n_gc_never_deletes_only_valid(tmp_path):
    d = str(tmp_path)
    ck = CheckpointEngine(keep_last_n=2)
    state = {"a": np.arange(8, dtype=np.float32)}
    for i in range(4):
        ck.save(d, f"t{i}", state)
    tags = sorted(n for n in os.listdir(d) if n.startswith("t"))
    assert tags == ["t2", "t3"]
    # newest tag bit-corrupted: it must NOT count toward the keep quota
    # (GC checksums its keep candidates), so a keep_last_n=1 pass retains
    # the older tag — the only valid checkpoint is never deleted
    corrupt_tag(os.path.join(d, "t3"))
    ck1 = CheckpointEngine(keep_last_n=1)
    ck1._gc(d)
    remaining = sorted(n for n in os.listdir(d) if n.startswith("t"))
    assert remaining == ["t2", "t3"]
    assert find_valid_tag(d) == "t2"


# ----------------------------------------------------------------------
# preemption drain + emergency checkpoint

def test_sigterm_at_step_k_emergency_checkpoint_and_resume(tmp_path):
    d = str(tmp_path)
    e = _engine({"checkpoint": {"save_dir": d},
                 "resilience": {"chaos": {"enabled": True,
                                          "sigterm_at_step": 2}}})
    with PreemptionGuard() as guard:
        e.attach_preemption_guard(guard)
        steps = 0
        for i in range(8):
            e.train_batch(_batch(i))
            steps += 1
            if e.should_stop:
                break
    # SIGTERM raised entering the step with global_steps==2; that step
    # completes (drain at the boundary, never mid-step), then the
    # emergency checkpoint lands at step 3
    assert e.stop_reason == "preempted"
    assert steps == 3
    assert get_registry().counter("resilience/preemptions").value >= 1
    assert get_registry().counter("resilience/emergency_saves").value >= 1
    # the emergency tag is a committed, auto-resumable checkpoint (the
    # fresh-process auto_resume path itself is covered by
    # test_crash_after_commit_resumes_bit_exact)
    assert find_valid_tag(d) == "global_step3"
    ok, reason = verify_tag(os.path.join(d, "global_step3"))
    assert ok, reason


# ----------------------------------------------------------------------
# divergence guards

def test_nan_guard_skip_is_traced_and_keeps_params(tmp_path):
    e = _engine({"resilience": {"divergence": {"nan_action": "skip"}}})
    # the skip compiles into the step: no host-side guard, no extra syncs
    assert e._divergence is None and not e._ft_active
    e.train_batch(_batch(0))
    before = jax.device_get(e.params)
    m = e.train_batch(_batch(1, scale=np.nan))
    assert bool(m["skipped"])
    after = jax.device_get(e.params)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(float(e.train_batch(_batch(2))["loss"]))


def test_spike_guard_rolls_back_to_last_checkpoint(tmp_path):
    d = str(tmp_path)
    e = _engine({"checkpoint": {"save_dir": d, "save_interval": 1,
                                "keep_last_n": 2},
                 "resilience": {"divergence": {"spike_action": "rollback",
                                               "spike_factor": 5.0,
                                               "warmup_steps": 2,
                                               "window": 8}}})
    for i in range(4):
        e.train_batch(_batch(i))
    assert e.global_steps == 4
    e.train_batch(_batch(4, scale=500.0))  # loss explodes -> rollback
    assert e.global_steps == 4  # restored from the step-4 checkpoint
    assert get_registry().counter("resilience/divergence/spike").value >= 1
    assert get_registry().counter("resilience/rollbacks").value >= 1
    # training continues from the restored state
    assert np.isfinite(float(e.train_batch(_batch(5))["loss"]))


def test_rollback_loop_escalates_to_halt(tmp_path):
    """Bit-exact resume replays a deterministic fault identically, so a
    rollback that never progresses past the diverging step must escalate
    to halt after max_rollbacks instead of looping forever."""
    from deepspeed_tpu.resilience import DivergenceError

    d = str(tmp_path)
    e = _engine({"checkpoint": {"save_dir": d, "save_interval": 1,
                                "keep_last_n": 2},
                 "resilience": {"divergence": {"nan_action": "rollback",
                                               "max_rollbacks": 2}}})
    for i in range(3):
        e.train_batch(_batch(i))
    e.train_batch(_batch(3, scale=np.nan))  # rollback 1
    assert e.global_steps == 3
    e.train_batch(_batch(3, scale=np.nan))  # rollback 2
    assert e.global_steps == 3
    with pytest.raises(DivergenceError, match="rollback"):
        e.train_batch(_batch(3, scale=np.nan))  # escalates
    assert get_registry().counter("resilience/rollbacks").value >= 2


def test_nan_guard_halt_raises(tmp_path):
    from deepspeed_tpu.resilience import DivergenceError

    e = _engine({"resilience": {"divergence": {"nan_action": "halt"}}})
    e.train_batch(_batch(0))
    with pytest.raises(DivergenceError):
        e.train_batch(_batch(1, scale=np.nan))
    assert e.stop_reason == "divergence:nan"


def test_zero_extra_host_syncs_when_guards_disabled(monkeypatch):
    e = _engine()
    assert e._divergence is None
    assert not e._ft_active
    assert e.preemption_guard is None

    def boom(*a, **k):
        raise AssertionError("_after_step must not run with guards off")

    monkeypatch.setattr(e, "_after_step", boom)
    m = e.train_batch(_batch(0))
    assert np.isfinite(float(m["loss"]))


# ----------------------------------------------------------------------
# retry: jitter + shared budget

def test_retry_jitter_bounds_backoff():
    delays = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise OSError("flake")
        return "ok"

    out = retry_call(flaky,
                     policy=RetryPolicy(max_attempts=5, backoff_s=1.0,
                                        backoff_multiplier=2.0, jitter=0.5),
                     op="jit_test", sleep=delays.append,
                     rng=random.Random(0))
    assert out == "ok" and len(delays) == 3
    for base, d in zip([1.0, 2.0, 4.0], delays):
        assert base <= d <= base * 1.5
    assert get_registry().counter("resilience/attempts/jit_test").value == 4


def test_retry_budget_exhausts_across_calls():
    budget = RetryBudget(max_retries=3)

    def always_fails():
        raise OSError("down")

    policy = RetryPolicy(max_attempts=10, backoff_s=0.0)
    with pytest.raises(RetryError):
        retry_call(always_fails, policy=policy, op="b1",
                   sleep=lambda _d: None, budget=budget)
    # 3 retries consumed by the first call; the second gets none
    assert budget.remaining == 0
    with pytest.raises(RetryError):
        retry_call(always_fails, policy=policy, op="b2",
                   sleep=lambda _d: None, budget=budget)
    assert get_registry().counter("resilience/failures/b2").value == 1


# ----------------------------------------------------------------------
# collective chaos through the comm facade

def _spmd_all_reduce(topo, fn):
    """One facade all_reduce inside shard_map (version-tolerant wrapper)."""
    from jax.sharding import PartitionSpec as P


    smapped = jax.shard_map(fn, mesh=topo.mesh, axis_names={"data"},
                               in_specs=P("data"), out_specs=P(),
                               check_vma=False)
    return jax.jit(smapped)(jnp.ones((8,), jnp.float32))


def test_collective_fail_injected_via_facade_hook(topo8):
    from deepspeed_tpu.comm import comm

    install_fault_injector(FaultInjector(collective_fail_op="all_reduce",
                                         collective_fail_at_call=2))
    out = _spmd_all_reduce(topo8, lambda x: comm.all_reduce(x, "data"))
    np.testing.assert_allclose(np.asarray(out), 8.0)  # call 1 passes
    with pytest.raises(CollectiveFault):  # call 2 fails at trace time
        _spmd_all_reduce(topo8, lambda x: comm.all_reduce(x, "data") * 2)
    assert get_registry().counter(
        "resilience/chaos/collective_fail/all_reduce").value == 1


def test_collective_delay_injected(topo8):
    from deepspeed_tpu.comm import comm

    install_fault_injector(FaultInjector(collective_delay_s=0.001,
                                         collective_delay_every=1))
    _spmd_all_reduce(topo8, lambda x: comm.all_reduce(x, "data"))
    assert get_registry().counter(
        "resilience/chaos/collective_delay/all_reduce").value >= 1


# ----------------------------------------------------------------------
# dataloader position rides in the checkpoint

def test_dataloader_position_resumes_exact_order(topo8):
    from deepspeed_tpu.runtime.dataloader import DataLoader

    data = {"x": np.arange(64, dtype=np.float32).reshape(64, 1)}
    ref = DataLoader(data, 8, topo8, shuffle=True, seed=5)
    ref_batches = [np.asarray(b["x"]).ravel().tolist() for b in ref]

    a = DataLoader(data, 8, topo8, shuffle=True, seed=5)
    it = iter(a)
    for _ in range(3):
        next(it)
    sd = a.state_dict()
    assert sd["batch_index"] == 3

    b = DataLoader(data, 8, topo8, shuffle=True, seed=5)
    b.load_state_dict(sd)
    resumed = [np.asarray(x["x"]).ravel().tolist() for x in b]
    assert resumed == ref_batches[3:]


def test_dataloader_epoch_boundary_state_normalizes(topo8):
    """A checkpoint taken right after an epoch's LAST batch must resume
    into the next epoch, not replay the finished one."""
    from deepspeed_tpu.runtime.dataloader import DataLoader, RepeatingLoader

    data = {"x": np.arange(32, dtype=np.float32).reshape(32, 1)}
    a = DataLoader(data, 8, topo8, shuffle=True, seed=5)  # 4 batches/epoch
    for _ in iter(a):
        pass  # consume exactly one full epoch
    sd = a.state_dict()
    assert sd == {"epoch": 1, "batch_index": 0, "seed": 5}

    ref = DataLoader(data, 8, topo8, shuffle=True, seed=5)
    rit = iter(RepeatingLoader(ref))
    ref_next = [np.asarray(next(rit)["x"]).ravel().tolist()
                for _ in range(8)][4:]  # epoch-1 batches of a straight run

    b = DataLoader(data, 8, topo8, shuffle=True, seed=5)
    b.load_state_dict(sd)
    got = [np.asarray(x["x"]).ravel().tolist() for x in b]
    assert got == ref_next


def test_dataloader_live_iterator_rewinds_after_rollback(topo8):
    """Divergence rollback restores the loader position through
    load_state_dict while the training loop keeps its live iterator: the
    very next yield must come from the restored position."""
    from deepspeed_tpu.runtime.dataloader import DataLoader

    data = {"x": np.arange(64, dtype=np.float32).reshape(64, 1)}
    a = DataLoader(data, 8, topo8, shuffle=True, seed=5)
    ref = [np.asarray(b["x"]).ravel().tolist() for b in a]
    a.set_epoch(0)
    it = iter(a)
    for _ in range(5):
        next(it)
    a.load_state_dict({"epoch": 0, "batch_index": 2, "seed": 5})
    got = np.asarray(next(it)["x"]).ravel().tolist()
    assert got == ref[2]
    assert a.state_dict()["batch_index"] == 3


# ----------------------------------------------------------------------
# elastic agent: backoff, restart reasons, heartbeat status

def test_agent_backoff_reasons_and_heartbeat(tmp_path):
    import sys

    from deepspeed_tpu.launcher.agent import ElasticAgent

    marker = tmp_path / "attempts"
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        "sys.exit(0 if n >= 2 else 7)\n")
    hb = str(tmp_path / "heartbeat.json")
    delays = []
    seen_states = []

    def fake_sleep(d):
        delays.append(d)
        with open(hb) as f:
            seen_states.append(json.load(f))

    agent = ElasticAgent([sys.executable, str(script)], max_restarts=3,
                         backoff_s=0.01, backoff_multiplier=2.0,
                         jitter=0.5, heartbeat_path=hb, sleep=fake_sleep,
                         rng=random.Random(0))
    report = agent.run()
    assert report.succeeded and report.restarts == 2
    assert report.reasons == ["exit:7", "exit:7"]
    # exponential, jitter-bounded backoff between the two restarts
    assert len(delays) == 2
    assert 0.01 <= delays[0] <= 0.015
    assert 0.02 <= delays[1] <= 0.03
    # during the relaunch window the heartbeat says "restarting" + reason,
    # so a watchdog can tell a restart from a hang
    assert [s["state"] for s in seen_states] == ["restarting", "restarting"]
    assert seen_states[0]["reason"] == "exit:7"
    with open(hb) as f:
        assert json.load(f)["state"] == "done"
    assert get_registry().counter(
        "resilience/restart_reasons/exit:7").value >= 2


def test_classify_exit_taxonomy():
    import signal as _signal

    from deepspeed_tpu.launcher.agent import (PLANNED_ROLLOUT_EXIT,
                                              classify_exit)

    assert classify_exit(7) == "exit:7"
    assert classify_exit(-int(_signal.SIGKILL)) == "signal:SIGKILL"
    assert classify_exit(PLANNED_ROLLOUT_EXIT) == "planned:rollout"
    # the planned taxonomy is opt-out: with no planned codes, 86 is just
    # another failure
    assert classify_exit(PLANNED_ROLLOUT_EXIT,
                         planned_codes=()) == "exit:86"


def test_agent_planned_rollout_restart_is_free(tmp_path):
    """A worker exiting PLANNED_ROLLOUT_EXIT (rollout reload) relaunches
    immediately: no restart budget consumed, no backoff slept — with
    max_restarts=0 two planned reloads still reach the clean exit."""
    import sys

    from deepspeed_tpu.launcher.agent import (PLANNED_ROLLOUT_EXIT,
                                              ElasticAgent)

    log = tmp_path / "launches"
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"p = {str(log)!r}\n"
        "n = int(os.environ['DST_ELASTIC_RESTART'])\n"
        "open(p, 'a').write(str(n) + '\\n')\n"
        f"sys.exit(0 if n >= 2 else {PLANNED_ROLLOUT_EXIT})\n")

    def no_sleep(d):
        raise AssertionError(f"planned reload slept {d}s")

    agent = ElasticAgent([sys.executable, str(script)], max_restarts=0,
                         sleep=no_sleep)
    report = agent.run()
    assert report.succeeded and report.restarts == 0
    assert report.planned_restarts == 2
    assert report.reasons == ["planned:rollout", "planned:rollout"]
    # the reload counter still increments so the trainee resumes from
    # its latest checkpoint on every planned launch
    assert log.read_text().split() == ["0", "1", "2"]
    assert get_registry().counter(
        "resilience/restart_reasons/planned:rollout").value >= 2


def test_agent_planned_cap_falls_through_to_failure(tmp_path):
    """Past max_planned_restarts a 'planned' exit is treated as the
    crash loop it is: budget consumed, backoff slept."""
    import sys

    from deepspeed_tpu.launcher.agent import (PLANNED_ROLLOUT_EXIT,
                                              ElasticAgent)

    script = tmp_path / "worker.py"
    script.write_text(f"import sys; sys.exit({PLANNED_ROLLOUT_EXIT})\n")
    delays = []
    agent = ElasticAgent([sys.executable, str(script)], max_restarts=1,
                         backoff_s=0.01, max_planned_restarts=2,
                         sleep=delays.append, rng=random.Random(0))
    report = agent.run()
    assert not report.succeeded
    assert report.returncode == PLANNED_ROLLOUT_EXIT
    assert report.planned_restarts == 2
    assert report.restarts == 1
    assert len(delays) == 1     # only the budgeted restart backs off


def test_agent_heartbeat_marks_planned_window(tmp_path):
    """The restarting heartbeat during a planned reload carries
    planned=true and a zero delay, so an external watchdog reads the
    flip window as routine instead of paging."""
    from deepspeed_tpu.launcher.agent import ElasticAgent

    hb = str(tmp_path / "hb.json")
    agent = ElasticAgent(["true"], heartbeat_path=hb)
    agent._write_status("restarting", 0, reason="planned:rollout",
                        next_delay_s=0.0)
    with open(hb) as f:
        rec = json.load(f)
    assert rec["planned"] is True
    assert rec["next_delay_s"] == 0.0
    agent._write_status("restarting", 1, reason="exit:7",
                        next_delay_s=0.5)
    with open(hb) as f:
        assert "planned" not in json.load(f)
