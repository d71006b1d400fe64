"""The program's model for an ``axk1`` configuration file: the translation
``deepspeed_tpu/checkpoint/hf.py`` makes of its config.json (``model_type:
axk1``), cut to the first ``n_layers``. The file's ``n_routed_experts`` is
what this chip holds (``experts_held``, a range of router ids) of the
``published`` count, which stays the router's width."""

from deepspeed_tpu.checkpoint.hf import axk1_config
from deepspeed_tpu.models.moe import MoETransformer


def build(cfg, n_layers: int):
    published = {**cfg, "n_routed_experts":
                 cfg["published"]["n_routed_experts"]}
    return MoETransformer(axk1_config(published, n_layers,
                                      tuple(cfg["experts_held"])))
