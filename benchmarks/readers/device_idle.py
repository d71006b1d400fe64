"""Share of the traced window, in %, in which no operation ran on the
device (mean over chips): 1 - union of the operation intervals over the
window."""

from benchmarks import trace_reduce as tr


def read(record, args):
    lo, hi = record["window"]
    if hi <= lo or not record["trace"].device_ops:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(record["trace"], lo, hi) / (hi - lo))
