"""How late the load generator sent, 95th percentile in ms: actual send
against the time the request was due."""

from benchmarks import harness


def read(record, args):
    late = record.get("late_s")
    return harness.percentile(late, 95) * 1e3 if late else None
