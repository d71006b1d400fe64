"""Share of the traced window, in %, in which chip 0 ran no operation while
the program was in one kind of host span (``benchmarks/program_trace.py``):
every moment of every idle gap goes to the innermost program span in force
then, and ``args['bucket']`` names the group of spans to add up:
  prepare - ``ragged.admit``, ``ragged.pack``, ``ragged.dispatch``, and
            ``ragged.put`` outside its phases: before the step is enqueued
  fetch   - ``ragged.fetch``, ``ragged.rows``: the logits come back
  server  - any ``serve.*`` span but ``serve.wait``, outside ``ragged.put``
  waiting - ``serve.wait``, or no program span at all
A gap is split where a span starts or ends inside it, not given whole to
the span over its middle (as ``trace_reduce.idle_gaps`` does with the
runner's one span): the gap between two steps runs from the fetch's tail
through the rows, the server's phases and the next tick's packing, and its
middle says nothing of the rest. The four add up to
``device_idle_pct.serve`` of a one-chip cell. Nothing to read where the
trace holds no ``ragged.put`` span (a program without them). Says every
span's idle seconds on an earlier line.
"""

from benchmarks import harness, program_trace as pt, trace_reduce as tr

NO_SPAN = "(no span)"


def bucket_of(name: str) -> str:
    if name in ("ragged.fetch", "ragged.rows"):
        return "fetch"
    if name.startswith("ragged."):
        return "prepare"
    if name.startswith("serve.") and name != "serve.wait":
        return "server"
    return "waiting"


def innermost(spans, lo, hi):
    """[lo, hi] cut into (start, end, name of the innermost span in force,
    or ``NO_SPAN``), in order: of the spans over a moment, the shortest."""
    cuts = sorted({lo, hi} | {t for s in spans for t in (s.start, s.end)
                              if lo < t < hi})
    order = sorted(spans, key=lambda s: s.start)
    live, k, out = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k].start <= a:
            live.append(order[k])
            k += 1
        live = [s for s in live if s.end >= b]
        name = min(((s.end - s.start, s.name) for s in live),
                   default=(0.0, NO_SPAN))[1]
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_seconds(record):
    """{span name: idle seconds of chip 0 inside the window}, once."""
    if "idle_by_span" not in record:
        spans = pt.of(record).spans
        lo, hi = record["window"]
        trace = record["trace"]
        out = {}
        if pt.inside(spans, "ragged.put", lo, hi) and trace.device_ops:
            chip = sorted(trace.device_ops)[0]
            gaps = tr.subtract([(lo, hi)], tr.busy(trace, chip, lo, hi))
            g = 0
            for a, b, name in innermost(spans, lo, hi):
                while g < len(gaps) and gaps[g][1] <= a:
                    g += 1
                k = g
                while k < len(gaps) and gaps[k][0] < b:
                    idle = min(b, gaps[k][1]) - max(a, gaps[k][0])
                    out[name] = out.get(name, 0.0) + idle
                    k += 1
            harness.say("device idle by the innermost program span: "
                        + ", ".join(f"{n} {v:.6f} s" for n, v in sorted(
                            out.items(), key=lambda kv: -kv[1]))
                        + f"; window {hi - lo:.6f} s")
        record["idle_by_span"] = out
    return record["idle_by_span"]


def read(record, args):
    idle = idle_seconds(record)
    lo, hi = record["window"]
    if not idle or hi <= lo:
        return None
    return 100.0 * sum(v for n, v in idle.items()
                       if bucket_of(n) == args["bucket"]) / (hi - lo)
