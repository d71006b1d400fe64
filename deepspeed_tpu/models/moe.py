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
from typing import Any, Dict, Optional, Tuple

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
    # DeepSeek-V3's family (parallel/moe.GateConfig has the rules): how the
    # router scores, the group-limited choice, what becomes of the chosen
    # weights, experts every token takes, leading layers whose feed-forward
    # is dense (of width dense_d_ff), and the experts this holder holds of
    # the n_experts the router scores (a range of router ids; None = all)
    scoring: str = "softmax"
    n_groups: int = 1
    topk_groups: int = 1
    routed_scale: float = 1.0
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    dense_d_ff: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        super().__post_init__()
        if not 0 <= self.first_dense_layers < self.n_layers:
            raise ValueError(
                f"first_dense_layers {self.first_dense_layers} of "
                f"{self.n_layers} layers leaves no expert layer")
        if self.first_dense_layers and (
                self.layer_types is not None or self.total_ut_steps > 1):
            raise NotImplementedError(
                "first_dense_layers with a hybrid or looped stack")
        self.dense_d_ff = self.dense_d_ff or self.d_ff
        self.gate_config()     # its own checks, at construction

    def gate_config(self) -> GateConfig:
        return GateConfig(
            n_experts=self.n_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor, min_capacity=self.min_capacity,
            aux_loss_weight=self.aux_loss_weight,
            noisy_gate_policy=self.noisy_gate_policy, scoring=self.scoring,
            n_groups=self.n_groups, topk_groups=self.topk_groups,
            routed_scale=self.routed_scale,
            experts_held=self.experts_held)

    @property
    def n_held(self) -> int:
        """Experts a layer's stacks hold: the weights' leading axis."""
        return self.gate_config().held[1]

    def _ffn_param_count(self, experts: int) -> int:
        d, f = self.d_model, self.d_ff
        n_mats = 3 if self.activation == "silu_glu" else 2
        n_moe = self.n_layers - self.first_dense_layers
        moe = (experts + self.n_shared_experts) * n_mats * d * f \
            + d * self.n_experts
        return n_moe * moe \
            + self.first_dense_layers * n_mats * d * self.dense_d_ff

    def param_count(self) -> int:
        """Parameters held: an expert share counts its own experts."""
        return self._shared_param_count() + self._ffn_param_count(self.n_held)

    def active_param_count(self) -> int:
        """Parameters a single token actually exercises (top_k experts)."""
        return self._shared_param_count() + self._ffn_param_count(self.top_k)

    def flops_per_token(self, seq_len: int) -> float:
        """MoE FLOPs count only the experts a token routes through (and
        the shared window-aware attention term)."""
        return 6.0 * self.active_param_count() \
            + 12.0 * self.d_model * self._attn_flop_len(seq_len)


class MoETransformer(Transformer):
    """Transformer with MoE FFN in every block."""

    stacked_operands = RAGGED_OPERANDS
    #: the expert layers' leaves, stacked over the expert layers alone where
    #: the first layers are dense (those layers' own sit under "dense")
    MOE_LEAVES = ("wg", "w_up", "w_down", "w_gate", "b_up", "b_down",
                  "ws_gate", "ws_up", "ws_down")

    def __init__(self, config: MoETransformerConfig):
        super().__init__(config)
        self.moe = MoELayer(config.d_model, config.d_ff, config.gate_config(),
                            activation=config.activation,
                            use_bias=config.use_bias,
                            n_shared_experts=config.n_shared_experts)

    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        c = self.config
        k_dense, k_moe = jax.random.split(rng)
        params = super().init(k_dense, dtype)
        layers = params["layers"]
        nd = c.first_dense_layers
        if nd:
            # the leading layers keep a dense feed-forward, of its own width
            # and under a stack of its own: with two shapes of feed-forward
            # no leaf of either spans every layer
            kd = jax.random.split(jax.random.fold_in(k_dense, 1), 3)
            make = lambda k, shape, fan: (jax.random.normal(
                k, (nd,) + shape, jnp.float32) * fan ** -0.5).astype(dtype)
            d, f = c.d_model, c.dense_d_ff
            layers["dense"] = {"w_up": make(kd[0], (d, f), d),
                               "w_down": make(kd[1], (f, d), f * 2 * c.n_layers)}
            if c.activation == "silu_glu":
                layers["dense"]["w_gate"] = make(kd[2], (d, f), d)
        # replace dense FFN weights with the expert bank
        for key in ("w_up", "w_down", "w_gate", "b_up", "b_down"):
            layers.pop(key, None)
        layers.update(self.moe.init(k_moe, dtype, n_layers=c.n_layers - nd))
        return params

    def layer_params(self, layers, li: int, in_place: bool = False,
                     period=None):
        """As the base's; with ``first_dense_layers`` a dense layer takes
        its feed-forward from the "dense" stack and an expert layer its
        leaves at its place among the expert layers."""
        nd = self.config.first_dense_layers
        if not nd:
            return super().layer_params(layers, li, in_place, period)
        assert period is None, "no rolled stack of experts"
        ffn = {k: v for k, v in layers.items() if k in self.MOE_LEAVES}
        kind, lp = super().layer_params(
            {k: v for k, v in layers.items()
             if k not in ffn and k != "dense"}, li)
        if li < nd:
            lp.update({k: v[li] for k, v in layers["dense"].items()})
            return kind, lp
        whole = self.stacked_operands if in_place else ()
        lp.update({k: v if k in whole else v[li - nd]
                   for k, v in ffn.items()})
        if whole:
            lp["layer"] = li - nd
        return kind, lp

    def _mlp(self, h, lp, rng=None, training=False):
        if "wg" not in lp:         # one of the leading dense layers
            return super()._mlp(h, lp, rng, training)
        moe_params = {k: lp[k] for k in self.MOE_LEAVES if k in lp}
        # ``layer`` and ``experts_path``: the serving step's, where it hands
        # the expert stacks over whole (layer_params, ``in_place``); ``live``
        # and ``tally`` likewise, for an expert share
        flat = lambda a: None if a is None else a.reshape(-1)
        out, aux = self.moe.apply(moe_params, h, rng=rng, training=training,
                                  layer=lp.get("layer"),
                                  path=lp.get("experts_path", "gather"),
                                  live=flat(lp.get("live")),
                                  tally=lp.get("tally"))
        return out, aux * self.config.aux_loss_weight

    def partition_specs(self, params, topo=None) -> Dict[str, Any]:
        specs = super(MoETransformer, self).partition_specs(
            {k: v for k, v in params.items()}, topo)
        layer_specs = dict(specs["layers"])
        for key in ("w_up", "w_down", "w_gate", "b_up", "b_down"):
            layer_specs.pop(key, None)
        pipe_size = topo.pipe_parallel_size if topo is not None else self._pipe_size
        pipe = "pipe" if pipe_size > 1 else None
        layer_specs.update(self.moe.partition_specs(
            n_layers=self.config.n_layers, pipe=pipe))
        if self.config.first_dense_layers:
            if pipe_size > 1:
                raise NotImplementedError(
                    "first_dense_layers under pipeline parallelism: the "
                    "two feed-forward stacks do not split by stage")
            layer_specs["dense"] = {
                k: P(None, "model", None) if k == "w_down"
                else P(None, None, "model") for k in params["layers"]["dense"]}
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
