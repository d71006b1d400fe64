"""Training throughput of one Llama-layout model on the device JAX gives
this process: tokens/s and MFU through the full TrainEngine (bf16, remat,
Pallas flash attention), one ``train_batch`` call per step.

One process, one JSON line on stdout. The row names the device it ran on
(``platform``, ``device_kind``, ``device_count``); times are fenced with
``block_until_ready``; there is no accelerator probe, no retry ladder and
no CPU fallback — without a TPU, or on a TPU whose ``device_kind`` has no
published peak, it exits non-zero and prints no row.

This is what is left of the old headline benchmark, kept runnable until
ROADMAP.md S0 replaces it with real cells (the 350M model here is neither
a published width nor a ZeRO-sharded job). ``chip_smoke.py`` is the
bring-up proof; this is not a benchmark design. The old fused
``train_steps`` leg is gone: at this batch and remat policy its program
does not fit the chip (16.58 GB of 15.75 GB at compile, PR 22's chip run),
which the old script caught and hid.
"""

from __future__ import annotations

import json
import os
import sys
import time


def main() -> int:
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: no TPU found — JAX reports platform "
                 f"{dev.platform!r} ({dev.device_kind}); a number from this "
                 f"device would not be a benchmark number")

    import deepspeed_tpu as dst
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.profiling.flops_profiler import device_peaks
    from deepspeed_tpu.runtime.compile_cache import place_compile_cache
    from deepspeed_tpu.runtime.dataloader import shard_batch

    peaks = device_peaks(dev)  # raises for a TPU kind without peaks
    place_compile_cache(default_dir=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))

    # ~350M-param Llama layout sized for one v5e chip with Adam fp32 state
    batch_size, seq_len, steps, warmup, remat = 8, 2048, 10, 2, "selective"
    model = Llama("tiny", d_model=1024, n_layers=24, n_heads=16,
                  n_kv_heads=16, d_ff=2816, vocab_size=32000,
                  max_seq_len=seq_len, remat=True, remat_policy=remat,
                  use_flash=True)
    config = {
        "train_batch_size": batch_size,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 0},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
    }
    engine, _, _, _ = dst.initialize(model=model, config=config,
                                     rng=jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(
        0, model.config.vocab_size, (batch_size, seq_len)).astype(np.int32)
    batch = shard_batch({"input_ids": tokens}, engine.topo)

    # steps are data-dependent through the engine state, so fencing the
    # last loss fences them all
    for _ in range(warmup):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0

    tokens_per_step = batch_size * (seq_len - 1)
    flops_per_token = model.config.flops_per_token(seq_len)
    peak = peaks["bf16_flops"] * engine.topo.world_size
    tok_per_sec = tokens_per_step * steps / dt

    print(json.dumps({
        "metric": "llama_350m_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "extra": {
            "mfu": round(tok_per_sec * flops_per_token / peak, 4),
            "peak_source": peaks["source"],
            "params": model.config.param_count(),
            "flash_attention": True,
            "batch_size": batch_size,
            "remat": remat,
            "step_ms": round(dt / steps * 1e3, 1),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
