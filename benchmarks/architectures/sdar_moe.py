"""The program's model for an ``sdar_moe`` configuration file: the
translation ``deepspeed_tpu/checkpoint/hf.py`` makes of its config.json
(``model_type: sdar_moe``), cut to the first ``n_layers``. The generation
settings the published config.json leaves out (block length, mask id,
denoising steps) are the file's ``assumed`` ones."""

from deepspeed_tpu.checkpoint.hf import SDAR_DEFAULTS, sdar_moe_config
from deepspeed_tpu.models.moe import MoETransformer


def build(cfg, n_layers: int):
    assumed = cfg.get("assumed", {})
    gen = {k: assumed[k] for k in SDAR_DEFAULTS if k in assumed}
    return MoETransformer(sdar_moe_config({**cfg, **gen}, n_layers))
