"""The program's model for an ``ouro`` configuration file: the translation
``deepspeed_tpu/checkpoint/hf.py`` makes of its config.json, cut to the
first ``n_layers`` (every pass runs those)."""

from deepspeed_tpu.checkpoint.hf import ouro_config
from deepspeed_tpu.models.transformer import Transformer


def build(cfg, n_layers: int):
    return Transformer(ouro_config(cfg, n_layers))
