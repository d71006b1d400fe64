"""A statistic of the program's own host spans of one name inside the traced
window (``benchmarks/program_trace.py``), chosen by an integer attribute.
``args``:
  span  - the span's name (``ragged.put``, ``serve.tick``)
  attr, is - optional: keep spans whose attribute ``attr`` is ``zero`` or
          ``positive`` (``prefill``: lanes given to sequences still inside
          their prompt; zero is a decode-only tick)
  less  - optional: from each span's length take the spans of this name
          nested inside it (``serve.tick`` less ``ragged.put`` is the
          server's own time in a tick); spans holding none are left out
  stat  - ``median_ms`` of the lengths kept, or ``share_pct``: how many of
          the spans of that name were kept
Says on an earlier line how many ``ragged.put`` spans the window held and how
long they took by (lanes, pages), and the medians of the phases inside them.
"""

from benchmarks import harness, program_trace as pt

PHASES = ("ragged.admit", "ragged.pack", "ragged.dispatch", "ragged.fetch",
          "ragged.rows")


def table(spans, lo, hi) -> None:
    """The whole table, once a record."""
    puts = [spans[i] for i in pt.inside(spans, "ragged.put", lo, hi)]
    by = {}
    for s in puts:
        kind = "prefill" if s.attrs.get("prefill", 0) > 0 else "decode"
        by.setdefault((s.attrs.get("lanes"), s.attrs.get("pages"), kind),
                      []).append(s.end - s.start)
    rows = ", ".join(
        f"{lanes} lanes x {pages} pages {kind}: {len(d)} of median "
        f"{harness.median(d) * 1e3:.3f} ms"
        for (lanes, pages, kind), d in sorted(
            by.items(), key=lambda kv: [str(k) for k in kv[0]]))
    phases = ", ".join(
        f"{name} {harness.median([spans[i].end - spans[i].start for i in idx]) * 1e3:.3f} ms"
        for name in PHASES
        for idx in [pt.inside(spans, name, lo, hi)] if idx)
    harness.say(f"ragged.put over the traced window: {len(puts)} spans; "
                f"{rows or 'none'}; phase medians: {phases or 'none'}")


def read(record, args):
    spans = pt.of(record).spans
    lo, hi = record["window"]
    if not record.get("span_table_said"):
        record["span_table_said"] = True
        table(spans, lo, hi)
    named = pt.inside(spans, args["span"], lo, hi)
    kept = named
    if "attr" in args:
        want = {"zero": lambda v: v == 0, "positive": lambda v: v > 0}[
            args["is"]]
        kept = [i for i in named if args["attr"] in spans[i].attrs
                and want(spans[i].attrs[args["attr"]])]
    if args["stat"] == "share_pct":
        return 100.0 * len(kept) / len(named) if named else None
    lengths = {i: spans[i].end - spans[i].start for i in kept}
    if "less" in args:
        nested = {}
        for j in pt.inside(spans, args["less"], lo, hi):
            up = spans[j].parent
            while up is not None and up not in lengths:
                up = spans[up].parent
            if up is not None:
                nested[up] = nested.get(up, 0.0) + (spans[j].end
                                                    - spans[j].start)
        lengths = {i: lengths[i] - d for i, d in nested.items()}
    if args["stat"] != "median_ms":
        raise ValueError(f"unknown stat {args['stat']!r}")
    return harness.median(list(lengths.values())) * 1e3 if lengths else None
