"""Latent attention's share of its roofline, in %: the least time the chip
could take for the traced decode-only ticks (the larger of operations over
peak FLOP/s and bytes over peak bytes/s, from
``benchmarks/ops_bytes/latent_attention.py`` on each tick's ``ctx_rows``,
the rows of the latent leaf its sequences' contexts hold, and ``decode``,
its tokens, times the span's ``latent_layers``) over the device time under
the scope ``args['scope']`` in the same ticks
(``readers/named_scope_device.py``). By scope and by the span's counts, so
it reads the same work whatever implements the kernel. Nothing to read
where no span carries ``ctx_rows`` or no operation the scope (a program
without latent attention). Says which bound on an earlier line."""

from benchmarks import harness
from benchmarks.ops_bytes import latent_attention
from benchmarks.readers import named_scope_device


def read(record, args):
    cells = [c for c in named_scope_device.per_span(
        record, args["span"], args["scope"]) if "ctx_rows" in c[0].attrs]
    seconds = sum(s for _, s, _ in cells)
    if not cells or not seconds:
        return None
    cfg = record["cell"].config
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    flops = moved = 0.0
    for span, _, _ in cells:
        f, m = latent_attention.ops_and_bytes(
            span.attrs["ctx_rows"], span.attrs.get("decode", 0),
            cfg["num_attention_heads"], row, cfg["kv_lora_rank"])
        layers = span.attrs.get("latent_layers", record["n_layers"])
        flops += f * layers
        moved += m * layers
    peaks = record["peaks"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    harness.say(f"latent attention over {len(cells)} decode-only ticks: "
                f"{seconds:.6f} s on the device; least {t_flops:.6f} s by "
                f"operations, {t_bytes:.6f} s by bytes: bound by "
                f"{'bytes' if t_bytes >= t_flops else 'operations'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
