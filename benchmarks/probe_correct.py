#!/usr/bin/env python3
"""By hand, on the chip: what ``correct`` compares, for many seeds in one
process, the program beside the controls (the reference computed in int8 and
in fp8, the precisions below the configuration's bfloat16). Limits are set from these two
readings and from nothing else (PERF.md section 2).

    python benchmarks/probe_correct.py <cell> <seed> [<seed> ...]
    PROBE_CONTROLS=fp8 python benchmarks/probe_correct.py ...   # one control

Prints one JSON line a seed. A serving cell keeps one engine and gives it
each seed's weights; a training cell builds its engine anew for each seed.
"""

from __future__ import annotations

import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness, weights  # noqa: E402

CONTROLS = tuple(os.environ.get("PROBE_CONTROLS", "int8,fp8").split(","))


def serve(cell, seeds):
    import jax
    import numpy as np

    from benchmarks.runners import serve_open_loop as r

    model, params, engine = r.build_engine(cell, seeds[0])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    chk = cell.spec["check"]
    for seed in seeds:
        engine.params = None
        del params
        gc.collect()
        params = weights.make(shapes, seed, engine.config.dtype, cell.n_layers)
        engine.params = params
        prompts = r.check_prompts(cell, seed)
        lens = [len(p) for p in prompts]
        fed, got = r.engine_logits(engine, prompts, chk["decode_steps"])
        want = r.reference_logits(cell, params, fed, lens, chk["decode_steps"])
        e = r.position_errors(got, want)
        row = {"cell": cell.name, "seed": seed, "prompts": lens,
               "program": {"median": float(np.median(e)),
                           "max": float(e.max())}}
        for q in CONTROLS:
            low = r.reference_logits(cell, params, fed, lens,
                                     chk["decode_steps"], q)
            c = r.position_errors(low, want)
            row["control_" + q] = {"median": float(np.median(c)),
                                   "max": float(c.max())}
        print(json.dumps(row), flush=True)


def train(cell, seeds):
    import jax

    import deepspeed_tpu as dst
    from deepspeed_tpu.runtime.dataloader import shard_batch

    from benchmarks.runners import train_steps as r

    gen = harness.find("generators", cell.traffic["generator"])
    for seed in seeds:
        topo, model, params = r.build(cell, seed)
        batch = shard_batch({"input_ids": gen.batch(
            cell.traffic, seed, 0, cell.config["vocab_size"])}, topo)
        want = r.reference_numbers(cell, topo, params, batch["input_ids"])
        lows = {q: r.reference_numbers(cell, topo, params,
                                       batch["input_ids"], q)
                for q in CONTROLS}
        engine, _, _, _ = dst.initialize(
            model=model, params=params, config=r.train_config(cell),
            topology=topo, rng=jax.random.PRNGKey(0))
        del params
        m = engine.train_batch(batch)
        got = (float(m["loss"]), float(m["grad_norm"]))
        engine.close()
        del engine, m, batch
        gc.collect()
        rel = lambda a, b: abs(a - b) / abs(b)
        row = {"cell": cell.name, "seed": seed, "reference": want,
               "program": {"loss_rel_err": rel(got[0], want[0]),
                           "grad_norm_rel_err": rel(got[1], want[1])}}
        for q, low in lows.items():
            row["control_" + q] = {"loss_rel_err": rel(low[0], want[0]),
                                   "grad_norm_rel_err": rel(low[1], want[1])}
        print(json.dumps(row), flush=True)


def main(argv):
    cell = harness.Cell(argv[0])
    harness.require_device(cell.chips)
    harness.place_cache()
    {"serve_open_loop": serve, "train_steps": train}[cell.spec["runner"]](
        cell, [int(s) for s in argv[1:]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
