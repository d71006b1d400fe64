"""Wait in the server's queue, 95th percentile in ms: ``Request.t_first_admit
- t_submit`` on the server's own clock, over the requests due in the window
that were admitted."""

from benchmarks import harness


def read(record, args):
    waits = [s.req.t_first_admit - s.req.t_submit
             for s in record.get("requests", [])
             if s.req is not None and s.req.t_first_admit is not None
             and s.req.t_submit is not None]
    return harness.percentile(waits, 95) * 1e3 if waits else None
