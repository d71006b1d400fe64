"""Passes of the step a token costs a model that generates by diffusion over
blocks: over the ``ragged.put`` spans inside the traced window, the sum of
``block_seqs`` (sequences whose block under way the pass denoises) over the
sum of ``decided`` (tokens the pass before decided, known when a pass is
built). Two passes a block of four and a commit folded into the next
block's first pass read 0.5; a commit in a pass of its own 0.75; more where
a sequence waits for a tick's budget. ``args``:
  span - ``ragged.put``
Nothing to read where the spans carry no ``block_seqs`` (a model of one
token a step, or a program from before the attribute) or nothing was
decided in the window.
"""

from benchmarks import harness, program_trace as pt


def passes_per_token(attrs) -> float | None:
    """``attrs``: the spans' attribute dicts, in any order."""
    mine = [a for a in attrs if "block_seqs" in a and "decided" in a]
    decided = sum(a["decided"] for a in mine)
    if not mine or decided <= 0:
        return None
    return sum(a["block_seqs"] for a in mine) / decided


def read(record, args):
    spans = pt.of(record).spans
    lo, hi = record["window"]
    attrs = [spans[i].attrs for i in pt.inside(spans, args["span"], lo, hi)]
    value = passes_per_token(attrs)
    if value is not None:
        mine = [a for a in attrs if "block_seqs" in a]
        harness.say(
            f"block passes over {len(mine)} {args['span']} spans: "
            f"{sum(a['block_seqs'] for a in mine)} sequence passes, "
            f"{sum(a['decided'] for a in mine)} tokens decided, "
            f"{sum(a.get('commits', 0) for a in mine)} blocks committed")
    return value
