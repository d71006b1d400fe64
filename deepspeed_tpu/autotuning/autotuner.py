"""Autotuner: mesh-shape × micro-batch × remat search via compile-time
analysis.

Reference surface: ``deepspeed/autotuning/autotuner.py:404`` (``tune``) —
the reference launches real training experiments per candidate (ZeRO stage
sweep, micro-batch sweep, per-config trials through the launcher). On TPU
the same search is nearly free: every candidate is AOT-compiled
(``jax.jit(...).lower(...).compile()`` on ShapeDtypeStructs — no params are
ever materialized) and scored from XLA's own ``memory_analysis()`` /
``cost_analysis()``:

* feasibility — peak device bytes (args + temps + outputs) must fit the
  per-chip HBM budget;
* cost — a roofline estimate ``max(flops/peak_flops, bytes/hbm_bw)`` over
  the compiled step.

The candidate step is a faithful proxy of ``TrainEngine``'s fused
train_step (grads in compute dtype + ZeRO sharding constraints + AdamW
update on fp32 master params); its compiled memory/flops profile is what
the real engine step will see.
"""

from __future__ import annotations

import itertools
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config, MeshConfig
from ..parallel.mesh import Topology
from ..parallel.zero import ZeroShardingRules
from ..utils.logging import log_dist


# chip generation -> (bf16 peak FLOP/s, HBM bytes, HBM GB/s)
CHIP_SPECS = {
    "v5e": (197e12, 16e9, 819e9),
    "v5p": (459e12, 95e9, 2765e9),
    "v4": (275e12, 32e9, 1228e9),
    "v6e": (918e12, 32e9, 1640e9),
    "cpu": (1e12, 8e9, 100e9),  # test stand-in
}


@dataclass
class TuningConstraints:
    """Search-space bounds (reference autotuning/config.py analog)."""

    n_devices: Optional[int] = None
    chip: str = "v5e"
    hbm_bytes: Optional[float] = None          # override chip HBM
    global_batch: int = 32
    seq_len: int = 2048
    micro_batches: List[int] = field(default_factory=lambda: [1, 2, 4, 8])
    zero_stages: List[int] = field(default_factory=lambda: [3])
    tp_sizes: Optional[List[int]] = None       # default: divisors of n_devices
    # Ulysses sequence-parallel degrees to try (long-context configs where
    # activations, not params, bound memory); 1 = off
    sp_sizes: List[int] = field(default_factory=lambda: [1])
    remat_options: List[bool] = field(default_factory=lambda: [True, False])


@dataclass
class CandidateResult:
    mesh: Dict[str, int]
    micro_batch: int
    zero_stage: int
    remat: bool
    feasible: bool
    peak_bytes: float
    flops: float
    est_step_s: float
    error: Optional[str] = None

    def config_overrides(self) -> Dict[str, Any]:
        return {
            "mesh": self.mesh,
            "train_micro_batch_size_per_gpu": self.micro_batch,
            "zero_optimization": {"stage": self.zero_stage},
        }


class Autotuner:
    """``tune()`` parity (reference autotuner.py:404) — returns the best
    config plus a ranked report of every candidate."""

    def __init__(self, model_factory: Callable[..., Any],
                 constraints: TuningConstraints,
                 base_config: Optional[Dict[str, Any]] = None):
        self.model_factory = model_factory
        self.c = constraints
        self.base_config = dict(base_config or {})
        n = self.c.n_devices or len(jax.devices())
        self.n_devices = n
        peak, hbm, bw = CHIP_SPECS.get(self.c.chip, CHIP_SPECS["v5e"])
        self.peak_flops, self.hbm_bw = peak, bw
        self.hbm_bytes = self.c.hbm_bytes if self.c.hbm_bytes else hbm

    # -- candidate enumeration -----------------------------------------
    def candidates(self) -> List[Dict[str, Any]]:
        n = self.n_devices
        tps = self.c.tp_sizes or [t for t in (1, 2, 4, 8) if n % t == 0 and t <= n]
        out = []
        for tp, sp, mb, stage, remat in itertools.product(
                tps, self.c.sp_sizes, self.c.micro_batches,
                self.c.zero_stages, self.c.remat_options):
            if n % (tp * sp):
                continue
            dp = n // (tp * sp)
            if self.c.global_batch % (dp * mb):
                continue
            mesh = {"data": dp, "model": tp}
            if sp > 1:
                mesh["seq"] = sp
            out.append({"mesh": mesh, "micro_batch": mb,
                        "zero_stage": stage, "remat": remat})
        return out

    # -- per-candidate compile + analysis ------------------------------
    def evaluate(self, cand: Dict[str, Any]) -> CandidateResult:
        try:
            return self._evaluate(cand)
        except Exception as e:  # infeasible shapes, partitioner errors, ...
            return CandidateResult(
                mesh=cand["mesh"], micro_batch=cand["micro_batch"],
                zero_stage=cand["zero_stage"], remat=cand["remat"],
                feasible=False, peak_bytes=float("inf"), flops=0.0,
                est_step_s=float("inf"), error=f"{type(e).__name__}: {e}")

    def _evaluate(self, cand: Dict[str, Any]) -> CandidateResult:
        model = self.model_factory(remat=cand["remat"])
        topo = Topology.build(MeshConfig(**cand["mesh"]),
                              devices=jax.devices()[:self.n_devices])
        cfg = Config.from_any({**self.base_config,
                               "train_batch_size": self.c.global_batch,
                               **{k: v for k, v in
                                  {"zero_optimization":
                                   {"stage": cand["zero_stage"]}}.items()}})
        rules = ZeroShardingRules(topo, cfg.zero)

        rng = jax.random.PRNGKey(0)
        param_struct = jax.eval_shape(model.init, rng)
        tp_specs = (model.partition_specs(param_struct, topo)
                    if hasattr(model, "partition_specs") else None)
        if hasattr(model, "bind_topology"):
            model.bind_topology(topo)
        p32 = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), param_struct)
        param_sh = rules.param_shardings(p32, tp_specs)
        grad_sh = rules.grad_shardings(p32, tp_specs)

        dp = topo.data_parallel_size
        mb = cand["micro_batch"]
        batch_struct = {"input_ids": jax.ShapeDtypeStruct(
            (dp * mb, self.c.seq_len), jnp.int32)}
        batch_sh = {"input_ids": topo.batch_sharding(2)}

        # proxy of TrainEngine's fused step: bf16 grads + ZeRO constraints +
        # AdamW(fp32 master) update — same compiled memory/flops profile
        def step(params, mu, nu, batch, rng):
            def loss_fn(p):
                pc = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.bfloat16)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
                return model.loss(pc, batch, rng)

            grads = jax.grad(loss_fn)(params)
            grads = jax.lax.with_sharding_constraint(grads, grad_sh)
            t = jax.tree_util.tree_map
            mu = t(lambda m, g: 0.9 * m + 0.1 * g, mu, grads)
            nu = t(lambda v, g: 0.99 * v + 0.01 * g * g, nu, grads)
            params = t(lambda p, m, v: p - 1e-4 * m / (jnp.sqrt(v) + 1e-8),
                       params, mu, nu)
            return (jax.lax.with_sharding_constraint(params, param_sh),
                    mu, nu)

        opt_sh = rules.opt_state_shardings(p32, p32, tp_specs)
        lowered = jax.jit(
            step,
            in_shardings=(param_sh, opt_sh, opt_sh, batch_sh, None),
            out_shardings=(param_sh, opt_sh, opt_sh),
        ).lower(p32, p32, p32, batch_struct,
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        compiled = lowered.compile()

        mem = compiled.memory_analysis()
        peak = 0.0
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            peak += float(getattr(mem, attr, 0.0) or 0.0)
        # analyses report whole-program bytes; per-device = /n for sharded
        peak_per_dev = peak / max(1, self.n_devices)

        cost = compiled.cost_analysis() or {}
        if isinstance(cost, list):  # older jax returns [dict]
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))
        gas = self.c.global_batch // (dp * mb)
        per_dev_flops = flops / max(1, self.n_devices)
        est = gas * max(per_dev_flops / self.peak_flops,
                        (bytes_accessed / max(1, self.n_devices)) / self.hbm_bw)
        return CandidateResult(
            mesh=cand["mesh"], micro_batch=mb, zero_stage=cand["zero_stage"],
            remat=cand["remat"], feasible=peak_per_dev <= self.hbm_bytes,
            peak_bytes=peak_per_dev, flops=flops, est_step_s=est)

    # -- search --------------------------------------------------------
    def tune(self) -> Dict[str, Any]:
        results = [self.evaluate(c) for c in self.candidates()]
        feasible = [r for r in results if r.feasible]
        ranked = sorted(feasible, key=lambda r: r.est_step_s)
        report = {
            "n_devices": self.n_devices,
            "chip": self.c.chip,
            "candidates": [r.__dict__ for r in
                           sorted(results, key=lambda r: r.est_step_s)],
            "best": ranked[0].__dict__ if ranked else None,
        }
        if ranked:
            log_dist(f"autotune: best {ranked[0].mesh} mb={ranked[0].micro_batch} "
                     f"remat={ranked[0].remat} est={ranked[0].est_step_s * 1e3:.2f} ms "
                     f"({len(feasible)}/{len(results)} feasible)")
        return report

    def write_report(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.tune(), f, indent=2)


def autotune(model_factory: Callable[..., Any],
             constraints: Optional[TuningConstraints] = None,
             base_config: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One-call tuner: returns the winning config overrides dict (merge into
    your training config) plus the full report under ``"report"``."""
    tuner = Autotuner(model_factory, constraints or TuningConstraints(),
                      base_config)
    report = tuner.tune()
    if report["best"] is None:
        raise RuntimeError("autotune: no feasible candidate "
                           f"(tried {len(report['candidates'])})")
    best = report["best"]
    return {"mesh": best["mesh"],
            "train_micro_batch_size_per_gpu": best["micro_batch"],
            "zero_optimization": {"stage": best["zero_stage"]},
            "remat": best["remat"],
            "report": report}
