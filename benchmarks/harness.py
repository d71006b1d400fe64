"""What every runner, reader and test of the benchmark shares: finding a
cell's files by the names in ``BENCHMARK.json``, finding code by name, the
device and its published peaks, the compile counter, percentiles, and the
result line.

Whatever belongs to one configuration, traffic mix, cell or per-layer
metric is a file of its own under ``benchmarks/``; adding one is new files
plus an entry in ``BENCHMARK.json`` and no edit here.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import sys
from typing import Any, Dict, List, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The benchmark cannot run this cell as asked: no result is printed."""


def say(*parts: Any) -> None:
    """An earlier line of the run (anything but the last is free text)."""
    print(*parts, flush=True)


def read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's clock:
    set-up counts from the process's first instruction, not from the first
    line of ``run.py``."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# ----------------------------------------------------------------------
# files by name
class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = read_json(os.path.join(root, "BENCHMARK.json"))
        entry = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entry:
            raise BenchError(
                f"no workload {name!r} in BENCHMARK.json (have "
                f"{[w['name'] for w in self.bench['workloads']]})")
        self.name = name
        self.entry = entry[0]
        self.chips = int(self.entry["chips"])
        cfg_entry = [c for c in self.bench["configs"]
                     if c["name"] == self.entry["config"]][0]
        self.config = read_json(os.path.join(root, cfg_entry["file"]))
        self.spec = read_json(self.path("workloads", name + ".json"))
        for key, want in (("config", self.entry["config"]),
                          ("traffic", self.entry["traffic"]),
                          ("chips", self.chips)):
            if self.spec[key] != want:
                raise BenchError(f"workloads/{name}.json says {key}="
                                 f"{self.spec[key]!r}, BENCHMARK.json {want!r}")
        self.traffic = read_json(
            self.path("traffic", self.entry["traffic"] + ".json"))
        # a cell may cut depth further than its configuration's file
        self.n_layers = int(self.spec.get("num_hidden_layers",
                                          self.config["num_hidden_layers"]))

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "benchmarks", *parts)

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell
        reports: those that list it, and those that list no cell."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def metric_spec(self, metric: str) -> Dict[str, Any]:
        return read_json(self.path("metrics", metric + ".json"))


def find(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py``: runners, generators,
    readers, references, architectures and operation counts are found by
    the name a data file gives, so a new one is a new file."""
    if not name.replace("_", "").isalnum():
        raise BenchError(f"bad {kind} name {name!r}")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmarks.{kind}.{name}")


# ----------------------------------------------------------------------
# the device
def require_device(chips: int) -> Tuple[Any, Dict[str, float]]:
    """The first device and its published peaks. Anything but ``chips``
    TPU devices of a kind in ``peaks.json`` is an error: no fallback."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX reports platform {dev.platform!r} "
                         f"({dev.device_kind}); the benchmark measures on "
                         f"the accelerator or not at all")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX reports "
                         f"{len(devices)}")
    return dev, peaks_of(dev.device_kind)


def peaks_of(device_kind: str) -> Dict[str, float]:
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise BenchError(f"no published peaks for device_kind "
                         f"{device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime counts them."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def place_cache() -> str:
    """JAX's persistent compilation cache: where the environment says, else
    at the fixed ``<checkout>/.jax_cache`` (the path is part of the key)."""
    import jax

    from deepspeed_tpu.runtime.compile_cache import place_compile_cache

    cache_dir = place_compile_cache(
        default_dir=os.path.join(ROOT, ".jax_cache"))
    n_bytes = sum(e.stat().st_size for e in os.scandir(cache_dir)
                  if e.is_file()) if os.path.isdir(cache_dir) else 0
    came_with = jax.config.jax_compilation_cache_max_size
    # a serving cell's step programs alone outgrow the 192 MiB cap the chip
    # machines come with: under it every run evicts what the next one needs
    # and compiles a third of them again (PERF.md, PR 24). The benchmark's
    # own processes keep every entry; the directory is still the given one.
    jax.config.update("jax_compilation_cache_max_size", -1)
    say(f"compile cache: {cache_dir} holds {n_bytes} bytes; the cap it came "
        f"with, {came_with} bytes, is lifted for this process")
    return cache_dir


class CompileCounter:
    """Programs the backend compiled, from JAX's own monitoring events (as
    ``chip_smoke.CompileLedger`` counts them): a persistent-cache hit counts
    too, since reading an executable back inside the window stalls it."""

    def __init__(self):
        import jax

        self.programs: List[Tuple[str, float]] = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.programs.append((str(kw.get("fun_name", "?")), seconds))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> int:
        return len(self.programs)

    def since(self, mark: int) -> List[Tuple[str, float]]:
        return self.programs[mark:]

    def summary(self) -> str:
        total = sum(s for _, s in self.programs)
        return (f"{len(self.programs)} programs, {total:.1f} s in the "
                f"backend, persistent cache {self.hits} hits "
                f"{self.misses} misses")


# ----------------------------------------------------------------------
# arithmetic
def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics; NaN for no values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def check_line(name: str, value: float, limit: float) -> bool:
    """Prints one compared number beside its limit; True when inside."""
    ok = bool(value <= limit)  # NaN is outside
    say(f"check {name}: {value:.6g} (limit {limit:.6g}) "
        f"{'ok' if ok else 'OUTSIDE'}")
    return ok
