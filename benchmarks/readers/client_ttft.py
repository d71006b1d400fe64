"""Time to first token at the client, 95th percentile in ms, over the
requests due inside the (traced) window: first ``on_token`` minus the time
the request was due; a request with no token enters at the time the client
gave up. Kept among the per-layer metrics where a window holds too few
requests for the tail to carry a bound."""

from benchmarks import harness


def read(record, args):
    reqs = record.get("requests") or []
    gave_up = record.get("gave_up_s")
    ttft = [(s.times[0] if s.times else gave_up) - s.due for s in reqs
            if s.times or gave_up is not None]
    return harness.percentile(ttft, 95) * 1e3 if ttft else None
