"""The program's model for a ``mistral`` configuration file: the
translation ``deepspeed_tpu/checkpoint/hf.py`` makes of its config.json."""

from deepspeed_tpu.models import Llama


def build(cfg, n_layers: int):
    window = cfg.get("sliding_window") or 0
    return Llama(
        "7b", vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=n_layers, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        attn_windows=(window,) * n_layers if window else None,
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], use_flash=True)
