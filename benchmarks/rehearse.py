#!/usr/bin/env python3
"""Rehearsal without the chip: compiles the cells' programs at their real
sizes for a described (not attached) TPU v5e 2x2 and prints what each needs
of a chip's memory. Run by hand before a chip call, on the CPU host:

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py [cell ...]

For each serving cell: the engine's SplitFuse step at the widest and the
decode lane bucket, and one layer of the plain reference. For each training
cell: the plain reference's loss-and-gradient program as it is placed (under
ZeRO-3 the weights arrive sharded over four chips). The trainer's own step is
compiled for the described chip by ``tests/test_tpu_compile.py``. Nothing
runs, so this says nothing about results or speed.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import harness  # noqa: E402


def _place(tree, sharding):
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


def _report(what: str, compiled, t0: float) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"  {what}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, on a device "
          f"{total / 1e9:.2f} GB; compiled in {time.time() - t0:.0f} s",
          flush=True)


def serve_cell(cell, desc) -> None:
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    one = SingleDeviceSharding(desc.devices[0])
    cfg, ecfg = cell.config, cell.config["engine"]
    model = harness.find("architectures", cfg["architecture"]).build(
        cfg, cell.n_layers)
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=ecfg["token_budget"],
                            max_seqs=ecfg["max_seqs"],
                            kv_block_size=ecfg["kv_block_size"],
                            n_kv_blocks=1024,
                            max_context=ecfg["max_context"]), params={})
    params = jax.eval_shape(partial(model.init, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    for lanes in (ecfg["token_budget"], 64):
        t0 = time.time()
        c = eng._build_step().lower(
            _place(params, one), _place(eng.kv_pool, one), i32(lanes),
            i32(lanes), i32(lanes), i32(ecfg["max_seqs"], eng.max_pages),
            i32(ecfg["max_seqs"]), 128).compile()
        _report(f"engine step, {lanes} lanes x 128 pages, 1024-page pool "
                f"({c.as_text().count('tpu_custom_call')} kernel calls)",
                c, t0)
    ref = harness.find("reference", cfg["architecture"])
    chk = cell.spec["check"]
    tokens = i32(chk["sequences"], chk["reference_tokens"])
    pos = i32(chk["sequences"] * (chk["decode_steps"] + 1))
    t0 = time.time()
    fn = lambda w, t, r, c: ref.logits_at(w, t, r, c, cfg, cell.n_layers)
    c = jax.jit(fn).lower(_place(params, one), tokens, pos, pos).compile()
    _report("reference, whole forward in one program (the run goes layer "
            "by layer and holds less)", c, t0)


def train_cell(cell, desc) -> None:
    from deepspeed_tpu.config import Config, MeshConfig
    from deepspeed_tpu.parallel.mesh import Topology
    from deepspeed_tpu.parallel.zero import ZeroShardingRules
    from benchmarks.runners import train_steps

    devices = desc.devices[:cell.chips]
    topo = Topology.build(MeshConfig(data=len(devices)), devices=devices)
    cfg = cell.config
    model = harness.find("architectures", cfg["architecture"]).build(
        cfg, cell.n_layers).bind_topology(topo)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = ZeroShardingRules(
        topo, Config.from_any(train_steps.train_config(cell)).zero)
    p_sh = rules.param_shardings(shapes, model.partition_specs(shapes, topo))
    tokens = jax.ShapeDtypeStruct(
        (cell.traffic["global_batch"], cell.traffic["seq_len"]), jnp.int32,
        sharding=topo.batch_sharding(2))
    ref = harness.find("reference", cfg["architecture"])
    w_sh, t_sh = train_steps.reference_placement(cell, topo, shapes)
    if cell.chips > 1:
        p_sh = w_sh
        tokens = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                      sharding=t_sh)
    t0 = time.time()
    fn = lambda w, t: ref.loss_and_grad_norm(
        w, t, cfg, cell.n_layers, None,
        grad_shardings=w_sh if cell.chips > 1 else None)
    c = jax.jit(fn).lower(_place(shapes, p_sh), tokens).compile()
    _report(f"reference loss and gradient norm, float32, "
            f"{cell.n_layers} layers on {len(devices)} chip(s)", c, t0)


def main(argv) -> int:
    from jax.experimental import topologies

    import deepspeed_tpu.ops.attention as attention

    jax.config.update("jax_enable_compilation_cache", False)
    attention._on_tpu = lambda: True   # dispatch sees the CPU: steer it here
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for name in argv or [w["name"] for w in bench["workloads"]]:
        cell = harness.Cell(name)
        print(f"{name} ({cell.spec['runner']}, {cell.n_layers} layers):",
              flush=True)
        {"serve_open_loop": serve_cell,
         "train_steps": train_cell}[cell.spec["runner"]](cell, desc)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
