"""Shared by the benchmark's tests: a temporary copy of the benchmark cut to
a size the CPU holds, and a way to run its command there.

The copy holds ``BENCHMARK.json`` and ``benchmarks/`` only; its
configurations keep the architecture and lose the widths. ``run.py`` refuses
anything but a TPU, so the child steers ``require_device`` in the test (not
through an option of the program) and says so in the device it reports.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256, "max_position_embeddings": 512}
TINY_ENGINE = {"token_budget": 64, "max_seqs": 4, "kv_block_size": 16,
               "max_context": 128, "max_kv_blocks": 64, "kv_reserve_bytes": 0}
TINY_CHAT = {"prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                               "min": 8, "max": 48},
             "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                               "min": 2, "max": 12},
             "lead_seconds": 1, "grace_seconds": 30}
TINY_CHECK = {"sequences": 2, "max_prompt_tokens": 40, "decode_steps": 3,
              "reference_tokens": 48}

CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from benchmarks import harness, run
import jax
harness.require_device = lambda chips: (jax.devices()[0],
                                        harness.peaks_of("TPU v5 lite"))
sys.exit(run.main({argv!r}))
"""


def _edit(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make_tiny_root(dst: str) -> str:
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    b = os.path.join(dst, "benchmarks")
    for name in os.listdir(os.path.join(b, "configs")):
        def cut(c):
            c.update(TINY)
            c["sliding_window"] = 32 if c.get("sliding_window") else None
            c.get("engine", {}).update(TINY_ENGINE)
        _edit(os.path.join(b, "configs", name), cut)
    _edit(os.path.join(b, "traffic", "chat.json"), lambda t: t.update(TINY_CHAT))
    for name in ("packed-2k", "packed-2k-x4"):
        _edit(os.path.join(b, "traffic", name + ".json"),
              lambda t: t.update(seq_len=64))
    for name in os.listdir(os.path.join(b, "workloads")):
        def cut(w):
            w.pop("num_hidden_layers", None)
            if "rate_per_s" in w:
                w["rate_per_s"] = 4.0
                w["check"].update(TINY_CHECK)
            else:       # bfloat16 on the CPU at width 64 is coarser
                w["check"]["limits"] = {"loss_rel_err": 2e-3,
                                        "grad_norm_rel_err": 1e-2}
        _edit(os.path.join(b, "workloads", name), cut)
    return dst


def run_cell(root: str, *argv: str, devices: int = 1, timeout: int = 600):
    """Runs the copy's command in a child on the CPU; returns (exit code,
    the last line parsed or None, all output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # the copy's own .jax_cache
    p = subprocess.run([sys.executable, "-c",
                        CHILD.format(root=root, argv=list(argv))],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=timeout)
    last = None
    lines = p.stdout.strip().splitlines()
    if p.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return p.returncode, last, p.stdout + p.stderr


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module", name="run_cell")
def _run_cell():
    return run_cell
