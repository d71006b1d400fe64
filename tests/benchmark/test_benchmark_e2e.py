"""The command end to end: it refuses to run without a TPU; cut to a size the
CPU holds (and steered past that refusal in the test) every cell runs through
its runner and prints the contract's last line; and a new configuration,
traffic mix, cell, per-layer metric, runner and reader are new files plus
entries in ``BENCHMARK.json``, with no edit to a file that is there."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
E2E = {"serve": {"itl_p50_ms", "setup_s"},
       "serve_tail": {"itl_p50_ms", "itl_p95_ms", "setup_s"},
       "train": {"train_tok_s", "setup_s"}}


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral-7b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_run_refuses_an_unknown_cell():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "no-such.cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no workload" in p.stderr


@pytest.mark.parametrize("cell,kind,devices", [
    ("mistral-7b.chat", "serve", 1), ("mixtral-8x7b.chat", "serve_tail", 1),
    ("mistral-7b.train", "train", 1), ("mistral-7b.zero3-x4", "train", 4)])
def test_cell_runs_end_to_end_at_a_tiny_size(tiny_root, run_cell, cell, kind,
                                             devices):
    rc, last, out = run_cell(tiny_root, "--workload", cell, "--seed",
                             "3000000019", "--seconds", "2", "--trace", "0",
                             devices=devices)
    assert rc == 0, out[-3000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last["metrics"]) == E2E[kind]
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"   # named for what it is
    assert last["device"]["count"] == devices
    # every number compared is printed beside its limit, and the reference
    # agrees; a run whose Pallas kernel did not run is not correct
    checks = [l for l in out.splitlines() if l.startswith("check ")]
    precision = [l for l in checks if "_err" in l]
    assert precision and all(l.endswith(" ok") for l in precision), checks
    assert any("kernel_missing" in l and "OUTSIDE" in l for l in checks)
    assert last["correct"] is False
    assert "compiled_in_window: 0 " in out


def _add(path, data):
    assert not os.path.exists(path)          # new files only
    with open(path, "w") as f:
        f.write(data if isinstance(data, str) else json.dumps(data))


def test_new_cell_metric_runner_and_reader_are_new_files(tiny_root, run_cell):
    b = os.path.join(tiny_root, "benchmarks")
    before = {}
    for d, _, files in os.walk(b):
        if "__pycache__" not in d:
            for f in files:
                p = os.path.join(d, f)
                before[p] = open(p, "rb").read()
    cfg = json.load(open(os.path.join(b, "configs", "mistral-7b.json")))
    _add(os.path.join(b, "configs", "dummy-1b.json"),
         dict(cfg, source="https://example.org/dummy-1b/config.json"))
    _add(os.path.join(b, "traffic", "dummy-mix.json"),
         {"generator": "dummy_gen", "n": 5})
    _add(os.path.join(b, "workloads", "dummy-1b.dummy-mix.json"),
         {"config": "dummy-1b", "traffic": "dummy-mix", "runner": "dummy_runner",
          "chips": 1})
    _add(os.path.join(b, "metrics", "dummy_count.json"),
         {"layer": "dummy layer", "unit": "items", "moves": "dummy_rate",
          "reader": "dummy_reader", "args": {"scale": 2}})
    _add(os.path.join(b, "generators", "dummy_gen.py"),
         "def items(mix, seed):\n    return list(range(seed % 7, seed % 7 + mix['n']))\n")
    _add(os.path.join(b, "readers", "dummy_reader.py"),
         "def read(record, args):\n    return float(len(record['items']) * args['scale'])\n")
    _add(os.path.join(b, "runners", "dummy_runner.py"), '''
from benchmarks import harness
import jax.numpy as jnp

class _T:                      # stands in for a trace: one op on one chip
    device_ops = {0: [("fusion", 1.0, 2.0)]}
    host = [("bench_open", 0.5, 0.5), ("bench_close", 3.0, 3.0)]

def run(cell, seed, seconds, trace, env):
    items = harness.find("generators", cell.traffic["generator"]).items(
        cell.traffic, seed)
    total = float(jnp.sum(jnp.asarray(items)))
    rec = {"items": items, "host_spans": (), "gap_name": "idle",
           "loaded_trace": _T()} if trace else None
    return {"correct": total == sum(items), "attempted": len(items),
            "failed": 0, "setup_s": 1.0,
            "end_to_end": {"dummy_rate": len(items) / seconds}, "record": rec}
''')
    bench_path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    bench["configs"].append({"name": "dummy-1b", "source":
                             "https://example.org/dummy-1b/config.json",
                             "file": "benchmarks/configs/dummy-1b.json",
                             "reduced": ["num_hidden_layers"], "why": "dummy"})
    bench["workloads"].append({"name": "dummy-1b.dummy-mix", "config":
                               "dummy-1b", "traffic": "dummy-mix", "chips": 1,
                               "why": "dummy"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "items/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dummy-1b.dummy-mix"]})
    bench["per_layer"].append({"name": "dummy_count", "unit": "items",
                               "better": "higher", "source": "program_counter",
                               "layer": "dummy layer", "moves": "dummy_rate",
                               "workloads": ["dummy-1b.dummy-mix"]})
    json.dump(bench, open(bench_path, "w"))

    rc, last, out = run_cell(tiny_root, "--workload", "dummy-1b.dummy-mix",
                             "--seed", "3000000019", "--seconds", "2",
                             "--trace", "0")
    assert rc == 0, out[-3000:]
    assert last["correct"] and last["attempted"] == 5
    assert last["metrics"] == {"dummy_rate": {"value": 2.5, "unit": "items/s"},
                               "setup_s": {"value": 1.0, "unit": "s"}}
    rc, last, out = run_cell(tiny_root, "--workload", "dummy-1b.dummy-mix",
                             "--seed", "3000000019", "--seconds", "2",
                             "--trace", "1")
    assert rc == 0, out[-3000:]
    assert last["metrics"] == {"dummy_count": {"value": 10.0, "unit": "items"}}
    assert last["device"]["busy_s"] == 1.0 and last["device"]["window_s"] == 2.5
    assert last["breakdown"]["device_ops"] == [["fusion", 1.0]]
    assert last["breakdown"]["idle_gaps"] == [["idle", 1.5]]
    for p, data in before.items():           # nothing that was there changed
        assert open(p, "rb").read() == data, p
    # and the cells that were there still load
    rc, last, out = run_cell(tiny_root, "--workload", "mistral-7b.train",
                             "--seed", "5", "--seconds", "1", "--trace", "0")
    assert rc == 0 and "train_tok_s" in last["metrics"], out[-2000:]
