"""Wall-clock and throughput timers.

Capability parity with the reference's ``deepspeed/utils/timer.py``
(SynchronizedWallClockTimer + ThroughputTimer driven by EngineTimers,
engine.py:140). On TPU, synchronization means ``jax.block_until_ready`` on a
representative array instead of CUDA events.
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

from .logging import log_dist


def _fence(obj: Any) -> None:
    """Host-side completion fence: block until the device has produced
    ``obj`` (JAX returns from a dispatch before the device finishes)."""
    import jax

    jax.block_until_ready(obj)


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self._start: Optional[float] = None
        self.elapsed_total = 0.0
        self.count = 0

    def start(self) -> None:
        self._start = time.perf_counter()

    def stop(self, sync_obj: Any = None) -> float:
        if sync_obj is not None:
            _fence(sync_obj)
        assert self._start is not None, f"timer {self.name} stopped before start"
        dt = time.perf_counter() - self._start
        self.elapsed_total += dt  # dslint: disable=races -- legacy reference-compat shim: each named timer is started/stopped by one engine thread; the monitor role reaches mean_ms only through a diagnostic log path that tolerates a stale float
        self.count += 1  # dslint: disable=races -- same single-timing-thread contract as elapsed_total above
        self._start = None
        return dt

    def mean_ms(self) -> float:
        return (self.elapsed_total / self.count * 1e3) if self.count else 0.0

    def reset(self) -> None:
        self.elapsed_total = 0.0
        self.count = 0
        self._start = None


class SynchronizedWallClockTimer:
    """Named-timer registry (reference utils/timer.py same-named class)."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)  # dslint: disable=races -- legacy reference-compat shim: timers are registered by the engine thread during setup; log() readers tolerate a momentarily missing name
        return self.timers[name]

    def log(self, names: Optional[List[str]] = None, reset: bool = True) -> str:
        names = names or list(self.timers)
        parts = [f"{n}: {self.timers[n].mean_ms():.2f}ms" for n in names if n in self.timers]
        msg = " | ".join(parts)
        if msg:
            log_dist(f"time (ms) | {msg}")
        if reset:
            for n in names:
                if n in self.timers:
                    self.timers[n].reset()
        return msg


class ThroughputTimer:
    """Samples/sec + TFLOPs tracking (reference utils/timer.py ThroughputTimer)."""

    def __init__(self, batch_size: int, steps_per_output: int = 50, monitor_memory: bool = False):
        self.batch_size = batch_size
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.total_samples = 0
        self.total_time = 0.0
        self._start = None
        self.step_count = 0
        self._window_time = 0.0
        self._window_steps = 0
        self.last_step_s: Optional[float] = None
        # latest device-memory sample (report boundaries only, so the
        # steady-state step never pays the allocator-stats call)
        self.last_memory: Dict[str, float] = {}

    def start(self) -> None:
        self._start = time.perf_counter()

    def will_report_next(self) -> bool:
        """True if the NEXT stop() will emit the throughput line — the
        engine uses this to decide whether to pass a sync object, so the
        report-boundary predicate lives in exactly one place."""
        return (self.step_count + 1) % self.steps_per_output == 0

    def stop(self, sync_obj: Any = None, report_speed: bool = True) -> Optional[float]:
        if self._start is None:
            return None
        if sync_obj is not None:
            _fence(sync_obj)
        dt = time.perf_counter() - self._start
        self._start = None
        self.step_count += 1
        self.total_samples += self.batch_size
        self.total_time += dt
        self._window_time += dt
        self._window_steps += 1
        self.last_step_s = dt
        if report_speed and self.step_count % self.steps_per_output == 0:
            # window-averaged ms/step: under async dispatch the engine only
            # syncs at the report boundary, so the boundary step's own dt
            # covers the whole drained window — dt alone would read ~window x
            # the true step time (and ~0 on unsynced steps)
            ms = self._window_time / self._window_steps * 1e3
            mem = ""
            if self.monitor_memory:
                # report boundary == already host-synced (the engine passed
                # a sync object), so sampling allocator stats here adds no
                # extra device round trip to the steady-state step
                from .memory import device_memory_stats

                self.last_memory = device_memory_stats()
                if self.last_memory:
                    mem = ", " + ", ".join(
                        f"{k}={v}" for k, v in self.last_memory.items())
            log_dist(
                f"step {self.step_count}: {self.avg_samples_per_sec():.2f} samples/s, "
                f"{ms:.1f} ms/step (avg over {self._window_steps}){mem}"
            )
            self._window_time = 0.0
            self._window_steps = 0
        return dt

    def avg_samples_per_sec(self) -> float:
        return self.total_samples / self.total_time if self.total_time else 0.0
