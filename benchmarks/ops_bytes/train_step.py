"""Operations one trained token requires, forward and backward, for the
model utilization: 6 x the parameters that multiply (every layer's
projections and feed-forward, and the output head; the input embedding is a
lookup and multiplies nothing) plus causal attention at min(seq, window).
Recomputed operations (activation checkpointing) are not required
operations and are not counted."""

from benchmarks.ops_bytes import flash_attention


def multiplying_params(cfg, n_layers: int) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    experts = cfg.get("num_experts_per_tok", 1)
    router = d * cfg["num_local_experts"] if "num_local_experts" in cfg else 0
    mlp = experts * 3 * d * f + router
    return n_layers * (attn + mlp) + d * cfg["vocab_size"]


def flops_per_token(cfg, n_layers: int, seq: int) -> float:
    window = cfg.get("sliding_window") or 0
    attn = 3.5 * flash_attention.forward_flops(
        1, seq, cfg["num_attention_heads"], cfg["head_dim"], window) / seq
    return 6.0 * multiplying_params(cfg, n_layers) + n_layers * attn
