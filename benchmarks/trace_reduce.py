"""From a profiler trace to numbers: the one reduction every PR shares.

``load`` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``, nothing but
JAX) into plain lists: for each device its operation events, and the
host's named spans (``jax.profiler.TraceAnnotation``), all in seconds on
the trace's one clock. The functions below work on those lists only, so a
hand-built trace tests them as well as a recorded one.

    python benchmarks/trace_reduce.py <file.xplane.pb>   # look at one by hand

What a TPU v5e trace looks like (read by hand, PR 24): one plane
``/device:TPU:<n>`` a chip. Its line ``XLA Ops`` holds one event per executed
HLO operation, named by the instruction's whole text (``%fusion.228 = (f32[2,
2048]{1,0:T(2,128)}, ...) fusion(...)``, kilobytes for a custom call): kept
here without the ``%`` and the layouts, cut to 200 characters (a custom call's
target is put after them: ``@tpu_custom_call`` is a Pallas kernel), and ``short``
gives the name before `` = ``. A ``while`` (the scan over layers) is an event
that spans its body's events. A Pallas kernel is a ``custom-call`` named after
the JAX scope it was traced in (``step.29``, ``checkpoint.23``), not after
its function, so the readers know kernels by the shape of their result. ``Async
XLA Ops`` holds one event per asynchronous operation from its start to its
done (prefetch copies; collectives). ``XLA Modules`` (one event a program)
and ``Steps`` would count the same time twice and are not read. Host threads
are lines of the plane ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start s, end s

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
LAYOUT = re.compile(r"\{[^{}]*\}")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
CLEANED = re.compile(r" @(\S+)$")
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")


@dataclass
class Trace:
    """``device_ops[chip]``: that chip's operation events by start time;
    ``host``: the host's annotation events by start time."""

    device_ops: Dict[int, List[Event]] = field(default_factory=dict)
    async_ops: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def clean(text: str) -> str:
    """An operation's name as kept: no ``%``, no layouts, 200 characters,
    and for a custom call `` @<its target>`` after them (``tpu_custom_call``
    is a Pallas kernel)."""
    target = TARGET.search(text) or CLEANED.search(text)   # idempotent
    name = LAYOUT.sub("", text[:1200].replace("%", ""))
    if target:
        name = name.split(" @")[0]
    return f"{name[:200]} @{target.group(1)}" if target else name[:200]


def short(name: str) -> str:
    return name.split(" = ")[0]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"{len(files)} .xplane.pb files under {trace_dir}")
    return files[0]


def load(path: str, host_names: Optional[Iterable[str]] = None) -> Trace:
    """``host_names``: keep only host events of these names (a traced
    server's host plane holds every TraceMe of the runtime too)."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path), host_names)


def from_profile(profile, host_names: Optional[Iterable[str]] = None
                 ) -> Trace:
    keep = set(host_names) if host_names is not None else None
    out = Trace()
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                into = {OPS_LINE: out.device_ops,
                        ASYNC_LINE: out.async_ops}.get(line.name)
                if into is not None:
                    names: Dict[str, str] = {}
                    into[int(m.group(1))] = sorted(
                        ((names.setdefault(e.name, clean(e.name)),
                          e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events), key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if keep is None or e.name in keep:
                        out.host.append((e.name, e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns) * 1e-9))
    out.host.sort(key=lambda e: e[1])
    return out


# ----------------------------------------------------------------------
# interval arithmetic
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The points of union ``a`` that no interval of union ``b`` covers
    (both disjoint and sorted, as ``union`` returns them)."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the disjoint sorted ``intervals`` cover."""
    return total(clip(intervals, lo, hi))


# ----------------------------------------------------------------------
# what the readers ask
def window_of(trace: Trace, name: str) -> Interval:
    """The one host span of that name (the window's two markers)."""
    spans = [(a, b) for n, a, b in trace.host if n == name]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} host spans named {name!r}")
    return spans[0]


def busy(trace: Trace, chip: int, lo: float, hi: float) -> List[Interval]:
    """Union of the chip's operation intervals inside [lo, hi]."""
    return union(clip(((a, b) for _, a, b in trace.device_ops[chip]), lo, hi))


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds an operation ran, averaged over the chips in the trace."""
    chips = sorted(trace.device_ops)
    if not chips:
        return 0.0
    return sum(total(busy(trace, c, lo, hi)) for c in chips) / len(chips)


def op_seconds(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Summed duration of each operation (by its short name) inside
    [lo, hi], averaged over chips; a ``while`` that only spans its body's
    operations is left out."""
    out: Dict[str, float] = {}
    chips = sorted(trace.device_ops)
    for c in chips:
        for n, a, b in trace.device_ops[c]:
            d = min(b, hi) - max(a, lo)
            n = short(n)
            if d > 0 and not CONTAINER.match(n):
                out[n] = out.get(n, 0.0) + d / len(chips)
    return out


def matching(trace: Trace, chip: int, pattern: re.Pattern, lo: float,
             hi: float) -> List[Event]:
    return [(n, a, b) for n, a, b in trace.device_ops[chip]
            if pattern.search(n) and a >= lo and b <= hi]


def spans(trace: Trace, name: str, lo: float, hi: float) -> List[Interval]:
    """Host spans of that name lying wholly inside [lo, hi]."""
    return [(a, b) for n, a, b in trace.host
            if n == name and a >= lo and b <= hi]


def collectives(trace: Trace, chip: int, lo: float, hi: float
                ) -> Tuple[List[Interval], List[Interval]]:
    """(collective intervals, the part of them with no compute beside it)
    on one chip. A synchronous collective is its own event. An asynchronous
    one is in flight from the start of ``<op>-start`` to the end of
    ``<op>-done``: one event of the asynchronous line where the trace has
    it, else the two events paired here; the compute scheduled between them
    hides it. Compute is every operation that is neither a collective nor a
    ``while`` around others."""
    flights: List[Interval] = [
        (a, b) for n, a, b in trace.async_ops.get(chip, ())
        if COLLECTIVE.match(n)]
    paired = not flights
    open_starts: Dict[str, List[float]] = {}
    for n, a, b in trace.device_ops[chip]:
        n = short(n)
        if not COLLECTIVE.match(n):
            continue
        base = n.split(".")[0]
        suffix = n[len(base):]
        if not paired and base.endswith(("-start", "-done")):
            continue
        if base.endswith("-start"):
            open_starts.setdefault(base[:-6] + suffix, []).append(a)
        elif base.endswith("-done"):
            began = open_starts.get(base[:-5] + suffix)
            flights.append((began.pop(0) if began else a, b))
        else:
            flights.append((a, b))
    flights = union(clip(flights, lo, hi))
    compute = union(clip(((a, b) for n, a, b in trace.device_ops[chip]
                          if not COLLECTIVE.match(short(n))
                          and not CONTAINER.match(short(n))), lo, hi))
    return flights, subtract(flights, compute)


def idle_gaps(trace: Trace, lo: float, hi: float,
              span_names: Sequence[str], fallback: str) -> Dict[str, float]:
    """Idle seconds of chip 0 inside [lo, hi] by the host span in force at
    each gap's middle: the innermost of ``span_names`` that covers it, or
    ``fallback`` between them."""
    chip = sorted(trace.device_ops)[0]
    gaps = subtract([(lo, hi)], busy(trace, chip, lo, hi))
    host = [(n, a, b) for n, a, b in trace.host if n in span_names]
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        inside = [(e - s, n) for n, s, e in host if s <= mid <= e]
        name = min(inside)[1] if inside else fallback
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


# ----------------------------------------------------------------------
def describe(path: str, n: int = 25) -> None:
    """Planes, lines, and the names that take most time: for reading a
    trace by hand before writing a reader against it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            by_name: Dict[str, List[float]] = {}
            for e in events:
                by_name.setdefault(e.name, []).append(e.duration_ns * 1e-9)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{t0 * 1e-9:.6f}..{t1 * 1e-9:.6f} s")
            for name, ds in sorted(by_name.items(),
                                   key=lambda kv: -sum(kv[1]))[:n]:
                print(f"    {sum(ds):10.6f} s  x{len(ds):<6d} {name[:110]}")


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
