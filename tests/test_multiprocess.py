"""Two-process distributed integration test (reference
tests/unit/common.py:107 DistributedTest pattern: N local ranks on one
host). Covers the only otherwise-untested path in comm/comm.py — the
``jax.distributed.initialize`` rendezvous branch — plus a cross-process DP
training step."""

import os
import socket
import subprocess
import sys

import numpy as np

_WORKER = r"""
import os
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")

from deepspeed_tpu import comm

comm.init_distributed()
assert comm.is_initialized()
assert comm.get_world_size() == 2, comm.get_world_size()
rank = comm.get_rank()
assert len(jax.devices()) == 4, jax.devices()  # 2 local x 2 processes

import deepspeed_tpu as dst
from deepspeed_tpu.runtime.dataloader import shard_batch

def loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"]
    return ((pred - batch["y"]) ** 2).mean()

params = {"w": np.zeros((8, 4), np.float32)}
cfg = {"train_batch_size": 8,
       "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
       "mesh": {"data": 4}, "steps_per_print": 1000}
engine, _, _, _ = dst.initialize(loss_fn=loss_fn, params=params, config=cfg)

rng = np.random.default_rng(0)  # identical data on both ranks
batch = {"x": rng.normal(size=(8, 8)).astype(np.float32),
         "y": rng.normal(size=(8, 4)).astype(np.float32)}
losses = [float(engine.train_batch(shard_batch(batch, engine.topo))["loss"])
          for _ in range(3)]
assert losses[-1] < losses[0], losses
print(f"RANK{rank}_LOSSES={losses}", flush=True)
print(f"RANK{rank}_OK", flush=True)
"""


def test_two_process_dp_training(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NUM_PROCESSES"] = "2"
        env["PROCESS_ID"] = str(pid)
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))

    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"RANK{rank}_OK" in out
    # DP semantics: both ranks observe the SAME global loss trajectory
    l0 = outs[0][1].split("RANK0_LOSSES=")[1].splitlines()[0]
    l1 = outs[1][1].split("RANK1_LOSSES=")[1].splitlines()[0]
    np.testing.assert_allclose(eval(l0), eval(l1), rtol=1e-6)
