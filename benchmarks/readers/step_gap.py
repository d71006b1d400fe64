"""The gap between two device steps of a serving tick on chip 0, cut where
the work changes hands, in ms: the median over the gaps of the traced
window whose second tick is decode-only. ``args['segment']`` names the part:
  gap     - ``S' - E``: the last operation of tick N's step ends (``E``),
            the first of tick N + 1's starts (``S'``). The device's clock
  between - tick N's ``ragged.fetch`` ends -> tick N + 1's ``ragged.h2d``
            starts: rows, emit, retire, admission, packing. The host's
  h2d     - ``ragged.h2d``'s length: the tick's arrays sent. The host's
  call    - ``ragged.call``'s length: the jitted step called until it
            returns (enqueued, not run). The host's
  handoff - ``gap - (call'.end - fetch.end)``: the way back (``E`` -> the
            ids on the host) plus the launch (the call's return -> ``S'``,
            negative where the chip starts before the call returns). A
            difference of two device times less one of two host times: an
            offset between the two clocks cancels
``gap = between + h2d + call + handoff`` gap by gap, up to what
``ragged.dispatch`` holds between its two children (printed).

A tick is a ``ragged.put`` inside the window with its ``ragged.h2d``,
``ragged.call`` and ``ragged.fetch``; a gap joins two consecutive ticks of a
thread with no ``serve.wait`` between them (an idle server is not a gap).
Each ``put`` waits for its own result, so the steps never overlap and the
k-th step is the k-th call's. Where one ends and the next begins is read
off the device's line alone: between ``fetch.end`` and ``call'.start`` the
host has nothing enqueued, so the chip's widest idle stretch that meets
that interval is the gap, ``E`` and ``S'`` its two ends. (Cutting the
operations at ``call'.start`` itself would hand a step's first operations
to the tick before as soon as the profiler places the device's line a
call's length early against the host's: it does, by 1 to 2 ms, in a
machine's first traced run, PERF.md section 6, PR 40.) Host-to-device copies
are no events of the ``XLA Ops`` line in a TPU v5e trace (PR 40's traces:
where the two clocks are lined up to 0.1 ms the chip is busy for 3 to 260 us
of a window's 160 to 250 hand-overs together), so no operation has to be
left out by the program it belongs to.

What does lean on how the profiler lines the two clocks up is printed on an
earlier line and is no metric: the way back and the launch apart, and the
interval of offsets ``d`` (the device's line placed ``d`` late against the
host's) that the trace allows, from the two things that cannot happen: a
step starting before its call began (``d <= S - call.start``) and the ids on
the host before the step ended (``d >= E - fetch.end``), over every tick.
The trace as it stands is ``d = 0``: an interval that is empty or does not
hold 0 says the lining-up is off. Nothing to read where the trace holds no
``ragged.call`` (a program without it).

    python -m benchmarks.readers.step_gap <file.xplane.pb>   # any session
"""

from __future__ import annotations

import bisect
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmarks import harness, program_trace as pt, trace_reduce as tr

SEGMENTS = ("gap", "between", "h2d", "call", "handoff")
PARTS = ("ragged.h2d", "ragged.call", "ragged.fetch")


class Tick(NamedTuple):
    put: pt.Span
    h2d: pt.Span
    call: pt.Span
    fetch: pt.Span


class Gap(NamedTuple):
    """One pair of consecutive ticks; seconds. ``residue``: what
    ``ragged.dispatch`` holds between its two children."""

    first: Tick
    second: Tick
    e: float                     # step N's last operation ends
    s: float                     # step N + 1's first operation starts
    busy: float                  # the chip busy inside the hand-over
    waited: bool                 # a ``serve.wait`` between the two ticks

    @property
    def gap(self) -> float:
        return self.s - self.e

    @property
    def between(self) -> float:
        return self.second.h2d.start - self.first.fetch.end

    @property
    def h2d(self) -> float:
        return self.second.h2d.end - self.second.h2d.start

    @property
    def call(self) -> float:
        return self.second.call.end - self.second.call.start

    @property
    def handoff(self) -> float:
        return self.gap - (self.second.call.end - self.first.fetch.end)

    @property
    def residue(self) -> float:
        return self.second.call.start - self.second.h2d.end

    @property
    def way_back(self) -> float:
        return self.first.fetch.end - self.e

    @property
    def launch(self) -> float:
        return self.s - self.second.call.end

    @property
    def decode(self) -> bool:
        return self.second.put.attrs.get("prefill", 0) == 0


def ticks_of(spans: Sequence[pt.Span], lo: float, hi: float) -> List[Tick]:
    """The whole ticks inside [lo, hi], in the list's order (thread after
    thread, each by start)."""
    puts = pt.inside(spans, "ragged.put", lo, hi)
    found: Dict[int, Dict[str, pt.Span]] = {i: {} for i in puts}
    for s in spans:
        if s.name in PARTS:
            up = s.parent
            while up is not None and up not in found:
                up = spans[up].parent
            if up is not None:
                found[up][s.name] = s
    return [Tick(spans[i], *(found[i][n] for n in PARTS)) for i in puts
            if len(found[i]) == len(PARTS)]


def gaps_of(ticks: Sequence[Tick], waits: Sequence[float],
            busy: Sequence[tr.Interval]) -> List[Gap]:
    """Every pair of consecutive ticks of a thread with the device's gap
    between their steps. ``waits``: the starts of the ``serve.wait`` spans,
    sorted; ``busy``: the chip's operation intervals, as
    ``trace_reduce.union`` gives them. The chip is idle all through a
    hand-over, so a pair whose hand-over meets no idle stretch at least as
    long as itself (a lining-up off by more than a gap) is left out, not
    misread."""
    idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    ends = [b for _, b in idle]
    out = []
    for first, second in zip(ticks, ticks[1:]):
        if second.put.start < first.put.end:       # another thread's
            continue
        a, b = first.fetch.end, second.call.start
        k = bisect.bisect_right(ends, a)       # the first that ends after a
        e, s, busy_inside = a, a, b - a
        while k < len(idle) and idle[k][0] < b:
            busy_inside -= min(b, idle[k][1]) - max(a, idle[k][0])
            if idle[k][1] - idle[k][0] > s - e:
                e, s = idle[k]
            k += 1
        if s > e and s - e >= b - a:
            w = bisect.bisect_left(waits, first.put.end)
            out.append(Gap(first, second, e, s, busy_inside,
                           w < len(waits) and waits[w] < second.put.start))
    return out


def offsets(gaps: Sequence[Gap]) -> Tuple[float, float]:
    """(lowest, highest) offset of the device's line against the host's
    that no tick of ``gaps`` forbids, in seconds."""
    return (max(g.e - g.first.fetch.end for g in gaps),
            min(g.s - g.second.call.start for g in gaps))


def _medians(gaps: Sequence[Gap], names=SEGMENTS) -> Dict[str, float]:
    return {n: harness.median([getattr(g, n) for g in gaps]) * 1e3
            for n in names}


def _said(values: Dict[str, float]) -> str:
    return ", ".join(f"{n} {v:.4f} ms" for n, v in values.items())


def segments(spans: Sequence[pt.Span], ops: Sequence[pt.Op], lo: float,
             hi: float) -> Optional[Dict[str, float]]:
    """{segment: median ms over the gaps before a decode-only tick}, and
    the earlier lines; None where there is nothing to read."""
    if not ops or not any(s.name == "ragged.call" for s in spans):
        return None
    ticks = ticks_of(spans, lo, hi)
    busy = tr.union((o.start, o.end) for o in ops)
    pairs = gaps_of(ticks, sorted(s.start for s in spans
                                  if s.name == "serve.wait"), busy)
    kept = [g for g in pairs if not g.waited]
    decode = [g for g in kept if g.decode]
    counts = (f"step gaps over the traced window: {len(ticks)} ticks, "
              f"{len(pairs)} pairs of them with the chip's gap found, "
              f"{len(pairs) - len(kept)} with a serve.wait between "
              f"(dropped), {len(kept)} gaps, {len(decode)} of them before a "
              f"decode-only tick")
    if not kept:
        harness.say(counts)
        return None
    harness.say(
        f"{counts}; medians over all {len(kept)}: {_said(_medians(kept))}; "
        f"ragged.dispatch between its two children: "
        f"{_said(_medians(kept, ('residue',)))}, largest "
        f"{max(g.residue for g in kept) * 1e3:.4f} ms")
    lo_d, hi_d = offsets(pairs)
    early = sum(g.launch < 0 for g in kept)
    inside = sum(g.busy for g in kept)
    harness.say(
        f"by the trace's lining-up of the two clocks (no metric): "
        f"{_said(_medians(kept, ('way_back', 'launch')))} (way_back: the "
        f"step's end -> ragged.fetch's end; launch: ragged.call's return "
        f"-> the step's start, {early} of {len(kept)} before it); chip "
        f"busy inside the hand-overs (fetch's end -> call's start): "
        f"{inside:.6f} s; offsets of the device's line against the host's "
        f"that {len(pairs)} pairs of ticks allow: [{lo_d * 1e3:.4f}, "
        f"{hi_d * 1e3:.4f}] ms, "
        + ("EMPTY: no offset fits every tick" if lo_d > hi_d else
           "holds 0" if lo_d <= 0.0 <= hi_d else
           "does NOT hold 0: as lined up, something that cannot happen"))
    if not decode:
        return None
    out = _medians(decode)
    parts = sum(out[n] for n in SEGMENTS[1:])
    harness.say(
        f"step gaps before a decode-only tick ({len(decode)}): "
        f"{_said(out)}; the four parts' medians add up to {parts:.4f} ms, "
        f"{out['gap'] - parts:+.4f} from the gap's")
    return out


def read(record, args):
    if "step_gap" not in record:
        trace = pt.of(record)
        record["step_gap"] = segments(
            trace.spans, trace.ops.get(min(trace.ops, default=0), []),
            *record["window"])
    found = record["step_gap"]
    return None if found is None else found[args["segment"]]


if __name__ == "__main__":
    loaded = pt.load(sys.argv[1])
    if segments(loaded.spans, loaded.ops.get(min(loaded.ops, default=0), []),
                float("-inf"), float("inf")) is None:
        harness.say("step gaps: nothing to read (no ragged.call span, no "
                    "device line, or no gap before a decode-only tick)")
