"""The paged-attention kernel's share of its roofline, in %: the least time
the chip could take for the traced calls (the larger of operations over peak
FLOP/s and bytes over peak bytes/s, from
``benchmarks/ops_bytes/paged_attention.py`` on the live contexts the traced
engine recorded for each call, times the layers) over the kernel's device
time in the same calls. ``args['kernel']``: a regular expression for the
kernel's operation name in the trace. Says which bound on an earlier line."""

import re

from benchmarks import harness, trace_reduce as tr
from benchmarks.ops_bytes import paged_attention


def read(record, args):
    trace = record["trace"]
    lo, hi = record["window"]
    cell = record["cell"]
    cfg = cell.config
    off = record["to_trace"]           # the wrapper's host clock -> trace's
    calls = [c for c in record["calls"]
             if c["t0"] + off >= lo and c["t1"] + off <= hi]
    if not calls:
        return None
    chip = sorted(trace.device_ops)[0]
    kernel = re.compile(args["kernel"])
    seconds = tr.total((a, b) for _, a, b in
                       tr.matching(trace, chip, kernel, calls[0]["t0"] + off,
                                   calls[-1]["t1"] + off))
    flops = moved = 0.0
    for c in calls:
        f, m = paged_attention.ops_and_bytes(
            c["seqs"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg.get("sliding_window") or 0)
        flops += f * record["n_layers"]
        moved += m * record["n_layers"]
    if not seconds or not moved:
        return None
    peaks = record["peaks"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    harness.say(f"paged attention over {len(calls)} calls: {seconds:.6f} s on "
                f"the device; least {t_flops:.6f} s by operations, "
                f"{t_bytes:.6f} s by bytes: bound by "
                f"{'bytes' if t_bytes >= t_flops else 'operations'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
