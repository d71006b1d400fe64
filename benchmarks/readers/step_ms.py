"""Median fenced step time in ms by the host's clock (``train_batch`` and
the wait for its loss), over the traced steps."""

from benchmarks import harness


def read(record, args):
    steps = record.get("steps")
    return harness.median([s["train_batch"] for s in steps]) * 1e3 \
        if steps else None
