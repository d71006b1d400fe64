"""The program's model for an ``olmo_hybrid`` configuration file: the
translation ``deepspeed_tpu/checkpoint/hf.py`` makes of its config.json,
cut to the first ``n_layers`` of ``layer_types``."""

from deepspeed_tpu.checkpoint.hf import olmo_hybrid_config
from deepspeed_tpu.models.transformer import Transformer


def build(cfg, n_layers: int):
    return Transformer(olmo_hybrid_config(cfg, n_layers))
