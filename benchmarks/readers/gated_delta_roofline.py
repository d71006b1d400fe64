"""The gated delta rule's one-token step's share of its roofline, in %: the
least time the chip could take for the traced decode-only ticks (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, from
``benchmarks/ops_bytes/gated_delta.py`` on the sequences each tick decoded,
the span's ``decode`` attribute, times the linear layers) over the device
time under the scope ``args['scope']`` in the same ticks
(``readers/named_scope_device.py``). Says which bound on an earlier line."""

from benchmarks import harness
from benchmarks.ops_bytes import gated_delta
from benchmarks.readers import named_scope_device


def read(record, args):
    cells = named_scope_device.per_span(record, args["span"], args["scope"])
    cfg = record["cell"].config
    layers = cfg.get("layer_types", [])[:record["n_layers"]]
    n_linear = sum(k == "linear_attention" for k in layers)
    seconds = sum(s for _, s, _ in cells)
    if not cells or not seconds or not n_linear:
        return None
    flops = moved = 0.0
    for span, _, _ in cells:
        f, m = gated_delta.ops_and_bytes(
            span.attrs.get("decode", 0), cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
        flops += f * n_linear
        moved += m * n_linear
    peaks = record["peaks"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    harness.say(f"delta step over {len(cells)} decode-only ticks: "
                f"{seconds:.6f} s on the device; least {t_flops:.6f} s by "
                f"operations, {t_bytes:.6f} s by bytes: bound by "
                f"{'bytes' if t_bytes >= t_flops else 'operations'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
