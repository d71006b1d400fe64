"""Device time, in ms, under a ``jax.named_scope`` path of the program that
``benchmarks/program_trace.py``'s fixed list of scopes does not hold
(``linear_attn`` and, inside it, ``conv``, ``delta_step``, ``delta_chunk``:
PR 28): the median over the decode-only ``ragged.put`` spans (``prefill`` =
0) of the summed durations of the operations that started while the span
was in force and whose ``op_name`` holds the scopes of ``args['scope']`` as
adjacent path components. ``args``:
  span  - ``ragged.put``
  scope - the path, outermost first: ``["linear_attn"]``,
          ``["linear_attn", "delta_step"]``
Nothing to read where no operation carries that path (a program without
the scope, as the parent of the PR that brought it). Says the inner scopes'
medians on an earlier line.
"""

from typing import Dict, List, Sequence, Tuple

from benchmarks import harness, program_trace as pt, trace_reduce as tr


def path_of(op_name: str) -> List[str]:
    """The path components of an ``op_name``, each by its last word (a
    transform may wrap a scope: ``transpose(jvp(linear_attn))``)."""
    out = []
    for part in op_name.split("/"):
        words = pt.WORD.findall(part)
        out.append(words[-1] if words else "")
    return out


def under(op_name: str, scope: Sequence[str]) -> bool:
    path, n = path_of(op_name), len(scope)
    return any(path[i:i + n] == list(scope)
               for i in range(len(path) - n + 1))


def per_span(record, span: str, scope: Sequence[str]
             ) -> List[Tuple[pt.Span, float, Dict[str, float]]]:
    """[(span, seconds under ``scope``, {next inner scope: seconds})] for
    every decode-only span of that name in the window, mean over chips;
    empty where no operation in the window carries the scope."""
    program = pt.of(record)
    lo, hi = record["window"]
    spans = sorted((program.spans[i] for i in
                    pt.inside(program.spans, span, lo, hi)
                    if program.spans[i].attrs.get("prefill") == 0),
                   key=lambda s: s.start)
    chips = sorted(program.ops)
    rows = {c: [(o, path_of(o.op_name)) for o in program.ops[c]
                if not tr.CONTAINER.match(o.name)
                and under(o.op_name, scope)] for c in chips}
    if not any(rows.values()):
        return []
    out = []
    for s in spans:
        inner: Dict[str, float] = {}
        for c in chips:
            for o, path in rows[c]:
                if s.start <= o.start < s.end:
                    at = path.index(scope[-1]) + 1
                    key = path[at] if at < len(path) else ""
                    inner[key] = inner.get(key, 0.0) \
                        + (o.end - o.start) / len(chips)
        out.append((s, sum(inner.values()), inner))
    return out


def read(record, args):
    cells = per_span(record, args["span"], args["scope"])
    if not cells:
        return None
    keys = sorted({k for _, _, inner in cells for k in inner})
    harness.say(
        f"device time under {'/'.join(args['scope'])} over {len(cells)} "
        f"decode-only {args['span']} spans, medians by the next scope: "
        + ", ".join(f"{k or '(itself)'} "
                    f"{harness.median([i.get(k, 0.0) for _, _, i in cells]) * 1e3:.3f} ms"
                    for k in keys))
    return harness.median([s for _, s, _ in cells]) * 1e3
