"""MoE transformer model family.

Parity target: DeepSpeed-MoE models (reference ``deepspeed/moe/layer.py``
MoE facade + GPT-MoE configurations from BASELINE.json configs[4]). Every
layer's FFN is an expert bank routed by top-k gating
(:mod:`deepspeed_tpu.parallel.moe`); expert weights are stacked
``[n_layers, E, ...]`` and sharded over the ``expert`` (and ``model``) mesh
axes, composing with ZeRO <=2 over ``data`` — the same composition rule as
the reference (stage_1_and_2.py:566 _configure_moe_settings: MoE requires
ZeRO <= 2).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.moe import RAGGED_OPERANDS, GateConfig, MoELayer
from .transformer import Transformer, TransformerConfig


@dataclass
class MoETransformerConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    min_capacity: int = 4
    aux_loss_weight: float = 0.01
    noisy_gate_policy: Optional[str] = None

    def gate_config(self) -> GateConfig:
        return GateConfig(
            n_experts=self.n_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor, min_capacity=self.min_capacity,
            aux_loss_weight=self.aux_loss_weight,
            noisy_gate_policy=self.noisy_gate_policy)

    def param_count(self) -> int:
        d, f, n = self.d_model, self.d_ff, self.n_layers
        n_mats = 3 if self.activation == "silu_glu" else 2
        moe = self.n_experts * n_mats * d * f + d * self.n_experts
        return self._shared_param_count() + n * moe

    def active_param_count(self) -> int:
        """Parameters a single token actually exercises (top_k experts)."""
        d, f, n = self.d_model, self.d_ff, self.n_layers
        n_mats = 3 if self.activation == "silu_glu" else 2
        active_moe = self.top_k * n_mats * d * f + d * self.n_experts
        return self._shared_param_count() + n * active_moe

    def flops_per_token(self, seq_len: int) -> float:
        """MoE FLOPs count only the experts a token routes through (and
        the shared window-aware attention term)."""
        return 6.0 * self.active_param_count() \
            + 12.0 * self.d_model * self._attn_flop_len(seq_len)


class MoETransformer(Transformer):
    """Transformer with MoE FFN in every block."""

    stacked_operands = RAGGED_OPERANDS

    def __init__(self, config: MoETransformerConfig):
        super().__init__(config)
        self.moe = MoELayer(config.d_model, config.d_ff, config.gate_config(),
                            activation=config.activation,
                            use_bias=config.use_bias)

    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        k_dense, k_moe = jax.random.split(rng)
        params = super().init(k_dense, dtype)
        # replace dense FFN weights with the expert bank
        for key in ("w_up", "w_down", "w_gate", "b_up", "b_down"):
            params["layers"].pop(key, None)
        params["layers"].update(
            self.moe.init(k_moe, dtype, n_layers=self.config.n_layers))
        return params

    def _mlp(self, h, lp, rng=None, training=False):
        moe_params = {k: lp[k] for k in ("wg", "w_up", "w_down", "w_gate",
                                         "b_up", "b_down") if k in lp}
        # ``layer`` and ``experts_path``: the serving step's, where it hands
        # the expert stacks over whole (layer_params, ``in_place``)
        out, aux = self.moe.apply(moe_params, h, rng=rng, training=training,
                                  layer=lp.get("layer"),
                                  path=lp.get("experts_path", "gather"))
        return out, aux * self.config.aux_loss_weight

    def partition_specs(self, params, topo=None) -> Dict[str, Any]:
        specs = super(MoETransformer, self).partition_specs(
            {k: v for k, v in params.items()}, topo)
        layer_specs = dict(specs["layers"])
        for key in ("w_up", "w_down", "w_gate", "b_up", "b_down"):
            layer_specs.pop(key, None)
        pipe_size = topo.pipe_parallel_size if topo is not None else self._pipe_size
        layer_specs.update(self.moe.partition_specs(
            n_layers=self.config.n_layers,
            pipe="pipe" if pipe_size > 1 else None))
        specs["layers"] = layer_specs
        return specs


def gpt_moe_config(size: str = "350m", n_experts: int = 8, **overrides) -> MoETransformerConfig:
    """GPT-MoE presets (reference DeepSpeed-MoE GPT family)."""
    presets = {
        "tiny": dict(d_model=128, n_layers=2, n_heads=4, max_seq_len=256, vocab_size=1024),
        "350m": dict(d_model=1024, n_layers=24, n_heads=16, max_seq_len=2048, vocab_size=50257),
        "1.3b": dict(d_model=2048, n_layers=24, n_heads=32, max_seq_len=2048, vocab_size=50257),
    }
    if size not in presets:
        raise ValueError(f"unknown gpt-moe size '{size}'; have {sorted(presets)}")
    kw = dict(presets[size])
    kw.update(norm="layer", activation="gelu", position="learned", use_bias=False,
              tie_embeddings=True, n_experts=n_experts, norm_eps=1e-5)
    kw.update(overrides)
    return MoETransformerConfig(**kw)


def GPTMoE(size: str = "350m", n_experts: int = 8, **overrides) -> MoETransformer:
    return MoETransformer(gpt_moe_config(size, n_experts, **overrides))
