"""FLOPs profiler.

Parity with the reference's ``deepspeed/profiling/flops_profiler/profiler.py``
(FlopsProfiler :28 — ``start_profile`` :72, ``stop_profile``,
``get_total_flops/params/duration``, ``print_model_profile`` :282,
``get_model_profile`` module entry). The reference monkey-patches
``torch.nn.functional`` to count MACs module-by-module; under XLA the
compiler already knows the FLOPs of the optimized program, so this profiler
reads ``Compiled.cost_analysis()`` — the numbers reflect what actually runs
(post-fusion), not a Python-side estimate — and falls back to the model's
analytic ``flops_per_token`` when cost analysis is unavailable.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..utils.logging import log_dist, logger


def count_params(params: Any) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)
                   if hasattr(x, "shape")))


def flops_of(fn: Callable, *args, **kwargs) -> Optional[float]:
    """FLOPs of one call of ``fn`` as XLA will execute it (post-fusion),
    via compiled cost analysis. None when the backend doesn't report it."""
    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # older jax returns [dict]
            cost = cost[0] if cost else {}
        f = cost.get("flops")
        return float(f) if f and f > 0 else None
    except Exception as e:  # pragma: no cover - backend-specific
        logger.debug(f"cost_analysis unavailable: {e}")
        return None


@dataclass
class ProfileResult:
    flops: float                 # per step
    macs: float
    params: int
    duration_s: float = 0.0
    tflops_per_s: float = 0.0
    mfu: float = 0.0

    def __repr__(self):
        return (f"ProfileResult(flops={self.flops:.3e}, params={self.params:,}, "
                f"tflops/s={self.tflops_per_s:.1f}, mfu={self.mfu:.1%})")


class FlopsProfiler:
    """Step profiler around a jitted train/eval function.

    Usage parity with the reference (start_profile/stop_profile/
    get_total_*): attach to an engine (``engine.flops_profiler``) or use
    standalone around any function.
    """

    def __init__(self, peak_flops: Optional[float] = None, mesh: Any = None):
        # the peak is that of the mesh the profiled function runs on
        # (default: the current topology's), not of every local device
        if not peak_flops:
            if mesh is None:
                from ..parallel.mesh import get_topology

                mesh = get_topology().mesh
            peak_flops = mesh_peak_flops(mesh.devices.size)
        self.peak_flops = peak_flops
        self._flops: Optional[float] = None
        self._params: int = 0
        self._t0: Optional[float] = None
        self._steps = 0
        self._elapsed = 0.0

    # -- reference API surface -----------------------------------------
    def start_profile(self) -> None:
        self._t0 = time.perf_counter()

    def stop_profile(self) -> None:
        if self._t0 is not None:
            self._elapsed += time.perf_counter() - self._t0
            self._steps += 1
            self._t0 = None

    def reset_profile(self) -> None:
        self._steps = 0
        self._elapsed = 0.0

    def get_total_flops(self, as_string: bool = False):
        total = (self._flops or 0.0) * max(self._steps, 1)
        return _num_to_string(total, "FLOPs") if as_string else total

    def get_total_params(self, as_string: bool = False):
        return _num_to_string(self._params, "params") if as_string else self._params

    def get_total_duration(self, as_string: bool = False):
        return f"{self._elapsed:.3f} s" if as_string else self._elapsed

    # -- measurement ----------------------------------------------------
    def measure(self, fn: Callable, *args, analytic_flops: Optional[float] = None,
                params: Any = None, iters: int = 5, warmup: int = 2,
                **kwargs) -> ProfileResult:
        """Compile-count + wall-time ``fn``; returns per-step numbers."""
        if params is not None:
            self._params = count_params(params)
        flops = flops_of(fn, *args, **kwargs) or analytic_flops or 0.0
        self._flops = flops
        jitted = jax.jit(fn)  # dslint: disable=recompile-hazard -- the profiler measures compile + first-run cost deliberately
        out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        for _ in range(max(warmup - 1, 0)):
            out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        tflops = flops / dt / 1e12 if dt > 0 else 0.0
        return ProfileResult(
            flops=flops, macs=flops / 2, params=self._params, duration_s=dt,
            tflops_per_s=tflops,
            mfu=(flops / dt / self.peak_flops) if dt > 0 and self.peak_flops else 0.0)

    def print_model_profile(self, result: ProfileResult, detailed: bool = True) -> str:
        lines = [
            "-------------------------- DeepSpeed-TPU Flops Profiler --------------------------",
            f"params:                 {_num_to_string(result.params, '')}",
            f"fwd+bwd FLOPs per step: {_num_to_string(result.flops, 'FLOPs')}",
            f"MACs per step:          {_num_to_string(result.macs, 'MACs')}",
            f"step latency:           {result.duration_s * 1e3:.2f} ms",
            f"achieved:               {result.tflops_per_s:.2f} TFLOPS ({result.mfu:.1%} MFU)",
            "----------------------------------------------------------------------------------",
        ]
        text = "\n".join(lines)
        log_dist(text)
        return text


def get_model_profile(model, batch, rng=None, params=None,
                      peak_flops: Optional[float] = None) -> ProfileResult:
    """Module-level entry (reference get_model_profile): profile one
    training loss step of a deepspeed_tpu model."""
    import jax.numpy as jnp

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = params if params is not None else model.init(rng)
    prof = FlopsProfiler(peak_flops=peak_flops)
    tokens = batch["input_ids"] if isinstance(batch, dict) else batch
    analytic = None
    if hasattr(model, "config") and hasattr(model.config, "flops_per_token"):
        b, s = tokens.shape
        # forward-only: 1/3 of the fwd+bwd estimate (6N -> 2N)
        analytic = model.config.flops_per_token(s) / 3.0 * b * s
    return prof.measure(lambda p, t: model.loss(p, {"input_ids": t}, rng),
                        params, jnp.asarray(tokens),
                        analytic_flops=analytic, params=params)


# Published per-chip peaks keyed by jax's ``device_kind`` — the one table
# chip_smoke.py, this profiler and the engine's MFU read. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip); jax names that chip "TPU v5 lite".
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def device_peaks(device: Any = None) -> Optional[Dict[str, Any]]:
    """Published peaks of ``device`` (default: the first local device).
    None off-TPU — the CPU has no peak and MFU is not reported; a TPU
    ``device_kind`` missing from :data:`DEVICE_PEAKS` is an error, never a
    default."""
    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return None
    if device.device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for TPU device_kind {device.device_kind!r}: "
            f"add it to profiling/flops_profiler.DEVICE_PEAKS with its source "
            f"(have {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device.device_kind]


def mesh_peak_flops(n_devices: int) -> float:
    """bf16 peak of ``n_devices`` local chips; 0.0 off-TPU."""
    peaks = device_peaks()
    return peaks["bf16_flops"] * n_devices if peaks else 0.0


def _num_to_string(num: float, unit: str) -> str:
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(num) >= scale:
            return f"{num / scale:.2f} {suffix}{unit}"
    return f"{num:.2f} {unit}"
