"""Median host time, in ms, from the end of one engine call to the start of
the next while a sequence is live in the engine: what the server spends
between ticks on dispatch, callbacks, retiring, admission and the feed."""

from benchmarks import harness


def read(record, args):
    calls = record.get("calls") or []
    lo = record["t0"]                  # the window's start, host clock
    gaps = [b["t0"] - a["t1"] for a, b in zip(calls, calls[1:])
            if a["live_after"] > 0 and a["t0"] >= lo]
    return harness.median(gaps) * 1e3 if gaps else None
