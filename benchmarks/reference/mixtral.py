"""Plain reference for the Mixtral architecture (Jiang et al. 2024,
"Mixtral of Experts"): Mistral's block with the feed-forward replaced by a
sparse mixture of SwiGLU experts. The router is a linear layer over the
normed hidden state; its softmax is taken over the chosen top-k logits
(equally: softmax over all experts, renormalised over the chosen), and the
block's output is the weighted sum of the chosen experts' outputs.

Every expert is computed for every token and the unchosen ones weighted
zero, one expert at a time so that one expert's float32 copy is live: plain,
and eight times the work, which the sampled sequences can bear.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import mistral as base

F32 = jnp.float32


def expert_mlp(x, lw, cfg, quant):
    n_experts, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    router = x.astype(F32) @ lw["wg"].astype(F32)            # [b, s, E]
    top_logits, top_idx = jax.lax.top_k(router, k)
    top_w = jax.nn.softmax(top_logits, -1)                   # over the chosen
    weight = jnp.sum(jax.nn.one_hot(top_idx, n_experts, dtype=F32)
                     * top_w[..., None], axis=-2)            # [b, s, E]

    def one(acc, e):
        out = base.swiglu(x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e],
                          quant)
        return acc + out * weight[..., e, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32), jnp.arange(n_experts))
    return acc


def hidden(weights, tokens, cfg, n_layers, quant=None):
    return base.hidden(weights, tokens, cfg, n_layers, quant, expert_mlp)


def logits_at(weights, tokens, rows, cols, cfg, n_layers, quant=None):
    return base.logits_at(weights, tokens, rows, cols, cfg, n_layers, quant,
                          expert_mlp)


split_specs = base.split_specs


def loss_and_grad_norm(weights, tokens, cfg, n_layers, quant=None,
                       grad_shardings=None):
    return base.loss_and_grad_norm(weights, tokens, cfg, n_layers, quant,
                                   expert_mlp, grad_shardings)
