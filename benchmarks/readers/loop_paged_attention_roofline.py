"""The paged-attention kernel's share of its roofline, in %, for a program
whose kernel calls a tick are not its weight layers: a looped stack runs
the kernel once a layer a PASS. ``readers/paged_attention_roofline.py``'s
number, the same operations and bytes a call
(``benchmarks/ops_bytes/paged_attention.py`` on the live contexts the
traced engine recorded) and the same kernel time, with the calls a tick
taken from the program's own ``ragged.put`` spans (attribute ``kv_layers``:
the layers that hold pages, times the passes) and not from the cell's
``n_layers``. ``args['kernel']``: as the other reader's. Nothing to read
where no span in the window carries ``kv_layers`` (the parent of the PR
that brought it), or where they disagree."""

from benchmarks import program_trace as pt
from benchmarks.readers import paged_attention_roofline


def read(record, args):
    program = pt.of(record)
    lo, hi = record["window"]
    calls = {program.spans[i].attrs.get("kv_layers")
             for i in pt.inside(program.spans, "ragged.put", lo, hi)}
    if len(calls) != 1:
        return None
    (n,) = calls
    return paged_attention_roofline.read(dict(record, n_layers=n), args) \
        if n else None
