"""The program's model for a ``granite_hybrid`` configuration file: the
translation ``deepspeed_tpu/checkpoint/hf.py`` makes of its config.json
(``model_type: granitemoehybrid``), cut to the first ``n_layers`` of
``layer_types``."""

from deepspeed_tpu.checkpoint.hf import granite_hybrid_config
from deepspeed_tpu.models.transformer import Transformer


def build(cfg, n_layers: int):
    return Transformer(granite_hybrid_config(cfg, n_layers))
