"""Runner ``train_steps``: the trainer over one chip or a four-chip ``data``
mesh, one ``train_batch`` a step on a new packed batch from the seed, every
step fenced with ``block_until_ready``.

Set-up (counted in ``setup_s``): float32 weights on the device(s) from the
seed, placed as the engine's ZeRO stage places them; the plain reference's
loss and gradient norm on batch 0, before the engine exists; the engine
(``deepspeed_tpu.initialize`` with those weights), its AOT-compiled step,
and step 0, whose loss and gradient norm are what ``correct`` compares.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness, weights
from benchmarks.harness import say


def train_config(cell) -> Dict[str, Any]:
    t = cell.config["train"]
    return {
        "train_batch_size": cell.traffic["global_batch"],
        "optimizer": t["optimizer"],
        "zero_optimization": {"stage": cell.spec["zero_stage"],
                              "stage3_param_persistence_threshold": 0},
        "bf16": {"enabled": True},
        "gradient_clipping": t["gradient_clipping"],
        "steps_per_print": 1_000_000,
    }


def build(cell, seed: int):
    """(topology, model, float32 weights placed by the ZeRO rules)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.config import Config
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.zero import ZeroShardingRules

    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual({"data": cell.chips})
    model = harness.find("architectures", cell.config["architecture"]).build(
        cell.config, cell.n_layers)
    model.bind_topology(topo)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = ZeroShardingRules(topo, Config.from_any(train_config(cell)).zero)
    shardings = rules.param_shardings(shapes,
                                      model.partition_specs(shapes, topo))
    params = weights.make(shapes, seed, jnp.float32, cell.n_layers, shardings)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    say(f"model: {cell.config['architecture']} {cell.n_layers} layers, {n} "
        f"parameters, float32 masters, ZeRO stage {cell.spec['zero_stage']} "
        f"over data={cell.chips}; batch {cell.traffic['global_batch']} x "
        f"{cell.traffic['seq_len']}")
    return topo, model, params


def reference_placement(cell, topo, params):
    """(weight shardings, token sharding) for the reference on this cell's
    chips: one chip holds it whole; over several, every matrix is split
    along its wide side and the batch is whole on each (the reference's own
    layout: the float32 gradient of a model that ZeRO-3 shards fits no one
    chip, and this way no chip holds a whole matrix)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    ref = harness.find("reference", cell.config["architecture"])
    axis = "data" if cell.chips > 1 else None
    specs = ref.split_specs(params, axis)
    place = lambda s: NamedSharding(topo.mesh, s)
    return (jax.tree_util.tree_map(
        place, specs, is_leaf=lambda x: isinstance(x, PartitionSpec)),
        place(PartitionSpec()))


def reference_numbers(cell, topo, params, tokens, quant=None):
    """The reference's (loss, gradient norm) on one batch."""
    import jax

    ref = harness.find("reference", cell.config["architecture"])
    w_sh, t_sh = reference_placement(cell, topo, params)
    if cell.chips > 1:       # a second copy in the reference's own layout
        params = jax.device_put(params, w_sh)
        tokens = jax.device_put(tokens, t_sh)
    loss, gnorm = ref.loss_and_grad_norm(
        params, tokens, cell.config, cell.n_layers, quant,
        grad_shardings=w_sh if cell.chips > 1 else None)
    return float(loss), float(gnorm)


def run(cell, seed: int, seconds: float, trace: bool, env) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu as dst
    from deepspeed_tpu.ops.attention import DISPATCH
    from deepspeed_tpu.runtime.dataloader import shard_batch

    gen = harness.find("generators", cell.traffic["generator"])
    vocab = cell.config["vocab_size"]
    DISPATCH.clear()
    topo, model, params = build(cell, seed)

    def next_batch(step: int):
        return shard_batch(
            {"input_ids": gen.batch(cell.traffic, seed, step, vocab)}, topo)

    batch0 = next_batch(0)
    want_loss, want_gnorm = reference_numbers(cell, topo, params,
                                              batch0["input_ids"])
    gc.collect()
    engine, _, _, _ = dst.initialize(model=model, params=params,
                                     config=train_config(cell), topology=topo,
                                     rng=jax.random.PRNGKey(seed % (2**31 - 1)))
    del params
    if not engine.warmup(batch0):
        raise harness.BenchError("AOT warm-up of the train step failed")
    hlo = engine._train_step_aot.as_text()
    m = engine.train_batch(batch0)
    got_loss, got_gnorm = float(m["loss"]), float(m["grad_norm"])
    say(f"step 0: loss {got_loss:.6f} (reference {want_loss:.6f}), gradient "
        f"norm {got_gnorm:.6f} (reference {want_gnorm:.6f}); "
        f"{env.compiles.summary()}")
    numbers = {"loss_rel_err": abs(got_loss - want_loss) / abs(want_loss),
               "grad_norm_rel_err": abs(got_gnorm - want_gnorm)
               / abs(want_gnorm)}
    ok = all([harness.check_line(k, numbers[k], lim)
              for k, lim in cell.spec["check"]["limits"].items()])
    flash = DISPATCH.get("flash_pallas", 0) > 0 \
        and not DISPATCH.get("flash_jnp") and "tpu_custom_call" in hlo
    say(f"attention dispatch {dict(DISPATCH)}, tpu_custom_call x"
        f"{hlo.count('tpu_custom_call')}; collectives in the step: "
        + ", ".join(f"{op} x{hlo.count(op + '(')}" for op in
                    ("all-gather", "reduce-scatter", "all-reduce")))
    ok &= harness.check_line("flash_kernel_missing", float(not flash), 0)
    if cell.spec["zero_stage"] == 3 and cell.chips > 1:
        sharded = "all-gather" in hlo and "reduce-scatter" in hlo
        ok &= harness.check_line("zero3_collectives_missing",
                                 float(not sharded), 0)
    # one more step outside the window: the second call of the AOT program
    batch = next_batch(1)
    jax.block_until_ready(engine.train_batch(batch)["loss"])

    tracer = env.tracer(0) if trace else None
    n_trace = int(cell.spec["trace_steps"])
    mark = env.compiles.mark()
    setup_s = harness.process_age_s()
    t0 = time.perf_counter()
    if tracer:
        tracer.start(timed=False)
    steps: List[Dict[str, float]] = []
    losses: List[float] = []
    step = 2
    while time.perf_counter() - t0 < seconds:
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("next_batch"):
            batch = next_batch(step)
        b = time.perf_counter()
        with jax.profiler.TraceAnnotation("train_batch"):
            m = engine.train_batch(batch)
            jax.block_until_ready(m["loss"])
        c = time.perf_counter()
        steps.append({"next_batch": b - a, "train_batch": c - b, "end": c - t0})
        losses.append(m["loss"])
        step += 1
        if tracer and len(steps) == n_trace:
            tracer.stop()
    elapsed = steps[-1]["end"]
    losses = [float(l) for l in losses]
    tokens_a_step = cell.traffic["global_batch"] * cell.traffic["seq_len"]
    compiled = env.compiles.since(mark)
    for name, s in compiled:
        say(f"FAULT: program {name!r} compiled inside the window ({s:.2f} s)")
    ok &= harness.check_line("compiled_in_window", len(compiled), 0)
    ok &= harness.check_line("nonfinite_losses",
                             float(sum(not np.isfinite(l) for l in losses)), 0)
    say(f"window: {len(steps)} fenced steps in {elapsed:.3f} s; step median "
        f"{harness.median([s['train_batch'] for s in steps]) * 1e3:.3f} ms; "
        f"loss after {min(8, len(losses))} steps "
        f"{losses[min(8, len(losses)) - 1]:.6f}, last {losses[-1]:.6f}")
    record = None
    if trace:
        record = {"tracer": tracer, "steps": steps[:n_trace],
                  "all_steps": steps, "tokens_a_step": tokens_a_step,
                  "host_spans": ("train_batch", "next_batch"),
                  "gap_name": "between_steps", "n_layers": cell.n_layers}
    engine.close()
    return {"correct": bool(ok), "attempted": len(steps), "failed": 0,
            "setup_s": setup_s,
            "end_to_end": {"train_tok_s": len(steps) * tokens_a_step
                           / elapsed},
            "record": record}
