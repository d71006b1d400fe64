#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process: finds the cell's files by the names in ``BENCHMARK.json``,
fails without the TPU chips the cell asks for or on a ``device_kind`` that
``benchmarks/peaks.json`` lacks, places the compile cache, hands the cell to
its runner (``benchmarks/runners/<kind>.py``), and prints as the last line
of its output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and, traced, ``breakdown``). With ``--trace 0``
the metrics are the cell's end-to-end metrics, taken with the profiler off;
with ``--trace 1`` its per-layer metrics, each from a reader of its own
(``benchmarks/readers/<name>.py``, named by ``benchmarks/metrics/<metric>.json``).

Nothing here is for use by hand: ``benchmarks/sweep.py`` offers a serving
cell other rates, many windows to a process.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, trace_reduce  # noqa: E402
from benchmarks.harness import say  # noqa: E402


class Tracer:
    """One profiler trace of the first ``seconds`` of the window, in a
    directory of its own under the run's TMPDIR. Two marker annotations,
    ``bench_open`` and ``bench_close``, bound the traced window on the
    trace's own clock."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._timer: Optional[threading.Timer] = None
        self._lock = threading.Lock()
        self._stopped = False

    def start(self, timed: bool = True) -> None:
        import jax

        options = None
        if hasattr(jax.profiler, "ProfileOptions"):
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # the spans are ours, by name
        kw = {"profiler_options": options} if options is not None else {}
        jax.profiler.start_trace(self.dir, **kw)
        self.opened_at = time.perf_counter()   # the marker, on the host clock
        with jax.profiler.TraceAnnotation("bench_open"):
            pass
        if timed:
            self._timer = threading.Timer(self.seconds, self.stop)
            self._timer.daemon = True
            self._timer.start()

    def stop(self) -> None:
        import jax

        with self._lock:
            if self._stopped:
                return
            self._stopped = True
            with jax.profiler.TraceAnnotation("bench_close"):
                pass
            jax.profiler.stop_trace()

    def finish(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
        self.stop()

    def remove(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class Env:
    """What run.py hands a runner."""

    def __init__(self, dev, peaks):
        self.dev, self.peaks = dev, peaks
        self.compiles = harness.CompileCounter()
        self.tracers = []

    def tracer(self, seconds: float) -> Tracer:
        t = Tracer(seconds)
        self.tracers.append(t)
        return t


def per_layer(cell, record: Dict[str, Any]) -> Dict[str, Any]:
    """Every per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = cell.metric_spec(m["name"])
        value = harness.find("readers", spec["reader"]).read(
            record, dict(spec.get("args", {})))
        if value is None or not math.isfinite(value):
            say(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_record(cell, record: Dict[str, Any], env: Env) -> Dict[str, Any]:
    """Adds the loaded trace, its window and the device's busy time."""
    names = set(record["host_spans"]) | {"bench_open", "bench_close"}
    tracer = record.get("tracer")
    trace = record.get("loaded_trace") or trace_reduce.load(
        trace_reduce.find_xplane(tracer.dir), names)
    lo = trace_reduce.window_of(trace, "bench_open")[0]
    hi = trace_reduce.window_of(trace, "bench_close")[0]
    record.update(trace=trace, window=(lo, hi), peaks=env.peaks, cell=cell,
                  chips=cell.chips)
    if tracer is not None:
        # host-clock times (time.perf_counter) + to_trace = the trace's clock
        record["to_trace"] = lo - tracer.opened_at
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = harness.Cell(args.workload)
        dev, peaks = harness.require_device(cell.chips)
    except harness.BenchError as e:
        sys.exit(f"benchmarks/run.py: {e}")
    import jax

    say(f"cell {cell.name}: {cell.entry['why']}")
    say(f"device: {dev.device_kind} x{cell.chips}, jax {jax.__version__}, "
        f"seed {args.seed}, window {args.seconds} s, trace {args.trace}")
    harness.place_cache()
    env = Env(dev, peaks)
    runner = harness.find("runners", cell.spec["runner"])
    try:
        res = runner.run(cell, args.seed, args.seconds, bool(args.trace), env)
    finally:
        for t in env.tracers:
            t.finish()
    say(f"compiles: {env.compiles.summary()}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": harness.memory_peak_bytes()}
    line: Dict[str, Any] = {"correct": res["correct"],
                            "attempted": res["attempted"],
                            "failed": res["failed"]}
    if args.trace:
        record = traced_record(cell, res["record"], env)
        lo, hi = record["window"]
        trace = record["trace"]
        device["busy_s"] = trace_reduce.busy_seconds(trace, lo, hi)
        device["window_s"] = hi - lo
        line["metrics"] = per_layer(cell, record)
        line["breakdown"] = {
            "device_ops": trace_reduce.top(
                trace_reduce.op_seconds(trace, lo, hi)),
            "idle_gaps": trace_reduce.top(trace_reduce.idle_gaps(
                trace, lo, hi, record["host_spans"], record["gap_name"]))}
        if not device["busy_s"] > 0:
            sys.exit("benchmarks/run.py: the trace shows no operation on "
                     "the device")
        for t in env.tracers:
            t.remove()
    else:
        values = dict(res["end_to_end"], setup_s=res["setup_s"])
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.metrics("end_to_end")}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
