"""Model FLOP/s utilization in %: the operations the traced steps' tokens
require (``benchmarks/ops_bytes/train_step.py``: parameters that multiply
and causal attention, nothing recomputed) over the time those steps took
end to end (batch making included), over chips x the published peak."""

from benchmarks.ops_bytes import train_step


def read(record, args):
    steps = record.get("steps")
    if not steps:
        return None
    cell = record["cell"]
    seconds = sum(s["next_batch"] + s["train_batch"] for s in steps)
    tokens = len(steps) * record["tokens_a_step"]
    flops = tokens * train_step.flops_per_token(
        cell.config, record["n_layers"], cell.traffic["seq_len"])
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / seconds / peak
