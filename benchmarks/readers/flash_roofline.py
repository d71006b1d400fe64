"""A flash-attention kernel's share of its roofline in the traced steps, in
%. ``args``: ``kernels`` - regular expressions for the operation names of
the pass's kernels in the trace; ``passes`` - ``forward`` or ``backward``.
Operations come from the cell's shapes
(``benchmarks/ops_bytes/flash_attention.py``), once for every time the
forward kernel ran (activation checkpointing runs it again in the backward
pass, and the second run is real work of the kernel), over the published
peak, over the kernels' device time. Says which bound on an earlier line."""

import re

from benchmarks import harness, trace_reduce as tr
from benchmarks.ops_bytes import flash_attention


def read(record, args):
    trace = record["trace"]
    lo, hi = record["window"]
    cell = record["cell"]
    cfg = cell.config
    chip = sorted(trace.device_ops)[0]
    events = [e for p in args["kernels"]
              for e in tr.matching(trace, chip, re.compile(p), lo, hi)]
    first = tr.matching(trace, chip, re.compile(args["kernels"][0]), lo, hi)
    if not events:
        return None
    seconds = tr.total((a, b) for _, a, b in events)
    rows = cell.traffic["global_batch"] // record["chips"]
    shape = (rows, cell.traffic["seq_len"], cfg["num_attention_heads"],
             cfg["head_dim"])
    window = cfg.get("sliding_window") or 0
    fwd_bytes, bwd_bytes = flash_attention.io_bytes(
        rows, cell.traffic["seq_len"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    if args["passes"] == "forward":
        flops, moved = flash_attention.forward_flops(*shape, window), fwd_bytes
    else:
        flops, moved = flash_attention.backward_flops(*shape, window), bwd_bytes
    calls = len(first)                 # one call of the pass a first kernel
    peaks = record["peaks"]
    t_flops = calls * flops / peaks["bf16_flops_per_s"]
    t_bytes = calls * moved / peaks["hbm_bytes_per_s"]
    harness.say(f"flash {args['passes']}: {calls} calls, {seconds:.6f} s on "
                f"the device; least {t_flops:.6f} s by operations, "
                f"{t_bytes:.6f} s by bytes: bound by "
                f"{'bytes' if t_bytes >= t_flops else 'operations'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
