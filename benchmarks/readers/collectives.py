"""Collective time of the traced steps, mean over chips. ``args['what']``:
  ms_per_step - time a collective was in flight, per step, in ms
  exposed_pct - the share of that time with no compute running beside it
Returns nothing where the trace holds no collective (one chip)."""

from benchmarks import trace_reduce as tr


def read(record, args):
    trace = record["trace"]
    lo, hi = record["window"]
    n_steps = len(tr.spans(trace, "train_batch", lo, hi))
    flight = exposed = 0.0
    for chip in sorted(trace.device_ops):
        f, e = tr.collectives(trace, chip, lo, hi)
        flight += tr.total(f) / len(trace.device_ops)
        exposed += tr.total(e) / len(trace.device_ops)
    if not flight or not n_steps:
        return None
    if args["what"] == "ms_per_step":
        return flight / n_steps * 1e3
    if args["what"] == "exposed_pct":
        return 100.0 * exposed / flight
    raise ValueError(f"unknown what {args['what']!r}")
