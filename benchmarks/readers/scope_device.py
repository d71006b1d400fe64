"""Device time, in ms, under one of the program's ``jax.named_scope``s
(``benchmarks/program_trace.py`` says where a trace keeps them), a host
span: the median over the spans of the summed durations of the operations
that started while the span was in force, mean over chips. ``args``:
  span  - ``ragged.put``: the operations that start inside the span (the
          call returns after its step has run), and only decode-only ticks
          (``prefill`` = 0); ``train.step``: from the span's start to the
          next one's (the call returns before its step has run; the
          runner fences each step), the last to the window's end
  scope - serving: the scope (``attn``, ``ffn``, ``head``)
  pass  - training: ``fwd`` (no ``transpose(`` in the operation's
          ``op_name``, outside ``optimizer``), ``bwd`` (``transpose(``:
          what ``jax.checkpoint`` recomputes counts here too) or
          ``optimizer`` (the scope, which has no backward)
Nothing to read where no operation carries a scope (a program without
them). Says every scope's median on an earlier line, the inner scopes, what
carried no scope and the busy time of the same spans too.
"""

from benchmarks import harness, program_trace as pt, trace_reduce as tr


def per_span(record, span: str):
    """[{(scope or scope/inner, backward): seconds, 'busy': seconds}] for
    each span of that name, once a record and name."""
    key = "scope_device." + span
    if key in record:
        return record[key]
    program = pt.of(record)
    lo, hi = record["window"]
    idx = sorted(pt.inside(program.spans, span, lo, hi),
                 key=lambda i: program.spans[i].start)
    if span == "ragged.put":
        idx = [i for i in idx if program.spans[i].attrs.get("prefill") == 0]
        bounds = [(program.spans[i].start, program.spans[i].end) for i in idx]
    else:
        starts = [program.spans[i].start for i in idx]
        bounds = list(zip(starts, starts[1:] + [hi]))
    chips = sorted(program.ops)
    rows = {c: pt.scoped(program.ops[c]) for c in chips}
    out = []
    if any(k != pt.NO_SCOPE for c in chips for _, k, _ in rows[c]):
        for a, b in bounds:
            cell = {}
            for c in chips:
                for k, v in pt.device_seconds(rows[c], a, b).items():
                    cell[k] = cell.get(k, 0.0) + v / len(chips)
            cell["busy"] = sum(tr.total(tr.union(
                (o.start, o.end) for o, _, _ in rows[c]
                if a <= o.start < b)) for c in chips) / len(chips)
            out.append(cell)
    if out:
        keys = sorted({k for cell in out for k in cell if k != "busy"})
        harness.say(
            f"device time by scope over {len(out)} {span} spans, medians: "
            + ", ".join(f"{k}{' backward' if back else ''} "
                        f"{harness.median([c.get((k, back), 0.0) for c in out]) * 1e3:.3f} ms"
                        for k, back in keys)
            + f"; busy {harness.median([c['busy'] for c in out]) * 1e3:.3f}"
              " ms")
    record[key] = out
    return out


def read(record, args):
    cells = per_span(record, args["span"])
    if not cells:
        return None

    def wanted(key, back):
        top = key.split("/")[0]
        if "scope" in args:
            return top == args["scope"]
        if args["pass"] == "optimizer":
            return top == "optimizer"
        return top != "optimizer" and back == (args["pass"] == "bwd")

    return harness.median([sum(v for k, v in cell.items()
                               if k != "busy" and wanted(*k))
                           for cell in cells]) * 1e3
