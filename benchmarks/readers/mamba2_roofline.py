"""The Mamba-2 one-token step's share of its roofline, in %: the least time
the chip could take for the traced decode-only ticks (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from
``benchmarks/ops_bytes/mamba2.py`` on the sequences each tick decoded, the
span's ``decode`` attribute, times the recurrent layers, its
``state_layers`` attribute) over the device time under the scope
``args['scope']`` in the same ticks (``readers/named_scope_device.py``). It
reads by scope and by the span's counts, so it measures the same work
whatever implements the step: XLA's form over the whole state leaf pays for
every slot and reads well under 100. Nothing to read on a program whose
spans lack ``state_layers`` or whose operations lack the scope. Says which
bound on an earlier line."""

from benchmarks import harness
from benchmarks.ops_bytes import mamba2
from benchmarks.readers import named_scope_device


def read(record, args):
    cells = named_scope_device.per_span(record, args["span"], args["scope"])
    cfg = record["cell"].config
    seconds = sum(s for _, s, _ in cells)
    if not seconds or any("state_layers" not in span.attrs
                          for span, _, _ in cells):
        return None
    flops = moved = 0.0
    for span, _, _ in cells:
        f, m = mamba2.ops_and_bytes(
            span.attrs.get("decode", 0), cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_n_groups"], cfg["mamba_d_state"])
        flops += f * span.attrs["state_layers"]
        moved += m * span.attrs["state_layers"]
    peaks = record["peaks"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    harness.say(f"ssd step over {len(cells)} decode-only ticks: "
                f"{seconds:.6f} s on the device; least {t_flops:.6f} s by "
                f"operations, {t_bytes:.6f} s by bytes: bound by "
                f"{'bytes' if t_bytes >= t_flops else 'operations'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
