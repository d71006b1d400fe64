"""A statistic of the host spans of one name inside the traced window, in
ms. ``args``: ``span`` (the name) and ``stat``:
  duration - median length of the span
  device   - median device-busy time inside the span (mean over chips)
  host     - median of the span's length less the device-busy time inside it
"""

from benchmarks import harness, trace_reduce as tr


def read(record, args):
    trace = record["trace"]
    lo, hi = record["window"]
    spans = tr.spans(trace, args["span"], lo, hi)
    if not spans:
        return None
    if args["stat"] == "duration":
        return harness.median([b - a for a, b in spans]) * 1e3
    chips = sorted(trace.device_ops)
    busy = {c: tr.busy(trace, c, lo, hi) for c in chips}
    inside = [sum(tr.covered(busy[c], a, b) for c in chips) / len(chips)
              for a, b in spans]
    if args["stat"] == "device":
        return harness.median(inside) * 1e3
    if args["stat"] == "host":
        return harness.median([b - a - d for (a, b), d in
                               zip(spans, inside)]) * 1e3
    raise ValueError(f"unknown stat {args['stat']!r}")
