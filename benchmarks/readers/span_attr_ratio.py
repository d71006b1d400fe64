"""The ratio, in %, of two integer attributes of the program's own host
spans of one name summed over the traced window
(``benchmarks/program_trace.py``). ``args``:
  span - the span's name (``ragged.put``)
  num, den - the attributes: 100 * sum(num) / sum(den) over the spans that
         carry both
  attr, is - optional, as ``span_attr_stat``: keep spans whose attribute
         ``attr`` is ``zero`` or ``positive`` (``prefill`` zero: a
         decode-only tick)
Nothing to read where no span carries both attributes (a program without
them) or the denominators sum to zero. Says the sums on an earlier line.
"""

from benchmarks import harness, program_trace as pt


def read(record, args):
    spans = pt.of(record).spans
    lo, hi = record["window"]
    kept = [spans[i] for i in pt.inside(spans, args["span"], lo, hi)
            if args["num"] in spans[i].attrs and args["den"] in spans[i].attrs]
    if "attr" in args:
        want = {"zero": lambda v: v == 0, "positive": lambda v: v > 0}[
            args["is"]]
        kept = [s for s in kept if args["attr"] in s.attrs
                and want(s.attrs[args["attr"]])]
    num = sum(s.attrs[args["num"]] for s in kept)
    den = sum(s.attrs[args["den"]] for s in kept)
    if not den:
        return None
    harness.say(f"{args['span']} over the traced window: {len(kept)} spans "
                f"with {args['num']} {num} of {args['den']} {den}")
    return 100.0 * num / den
