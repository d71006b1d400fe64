"""Plain reference for the Olmo-Hybrid architecture: OLMo-2/3 blocks whose
token mixer is, by ``layer_types``, either multi-head softmax attention or
a gated delta-rule layer ("Gated Delta Networks", Yang, Kautz, Hatamizadeh
2024), over a SwiGLU feed-forward and an untied head.

For one linear layer's head, token ``t``, input ``x_t``::

    q~, k~, v~ = x W_q, x W_k, x W_v
    q, k, v = silu(conv4(q~)), silu(conv4(k~)), silu(conv4(v~))   causal, depthwise
    q = l2norm(q) * dk^-0.5;  k = l2norm(k)
    beta = 2 sigmoid(x W_b);  alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))
    S <- alpha S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q
    y = concat_heads(rmsnorm_dv(o) * silu(x W_z)) W_o

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence is a ``lax.scan``
over tokens, one token at a time from a zero state; no chunking, no cache,
no kernels, and no code of ``deepspeed_tpu``. It reads the weight tree the
benchmark made from the seed (``benchmarks/weights.py``) and shares the
linear layer with its rounding control (``quant``), the norm and the
feed-forward with ``reference/mistral.py``.

Assumed, where the published ``config.json`` is silent (the configuration
file lists the same under ``assumed``):

* block wiring of the OLMo-2/3 family: ``h = x + norm(mix(x))``,
  ``out = h + norm(mlp(h))``, no norm before a branch, a final norm;
* QK-norm of that family: RMSNorm over the whole projected q and k of the
  full layers, before the heads are split;
* ``rope_parameters.rope_theta: null`` read as no rotary embedding;
* the recurrent state in float32; ``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``;
  the 2 on ``beta`` from ``linear_allow_neg_eigval``;
* the convolution's weight as one leaf ``conv_w`` [4, channels] over q|k|v
  side by side, tap ``i`` multiplying the input ``3 - i`` tokens back.

What ``benchmarks/weights.py`` gives the new leaves (by its rules on a
leaf's name and shape): the projections ``wq wk wv w_a w_beta w_z`` normal
with ``fan_in^-0.5``, ``wo`` that times ``(2 n_layers)^-0.5``, ``conv_w``
normal with 0.5 (its second-to-last axis is the 4 taps), ``o_norm_w`` and
the ``*_norm_w`` gains near one, and ``A_log`` and ``dt_bias`` (stacked
[linear layers, heads]) normal with ``(linear layers)^-0.5``: decays
``alpha`` of about 0.3 to 0.7 a token, a shorter memory than a trained
model's and the same arithmetic.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import mistral as base

F32 = jnp.float32


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def conv_silu(x, w):
    """x [b, s, ch], w [K, ch]: y_t = sum_i w[i] x_(t - (K - 1 - i))."""
    K, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[i].astype(F32) * padded[:, i:i + s]
                           for i in range(K)))


def linear_attention(x, lw, cfg, quant):
    b, s, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    cw = lw["conv_w"]
    q = conv_silu(base.linear(x, lw["wq"], quant), cw[:, :hk * dk])
    k = conv_silu(base.linear(x, lw["wk"], quant),
                  cw[:, hk * dk:2 * hk * dk])
    v = conv_silu(base.linear(x, lw["wv"], quant), cw[:, 2 * hk * dk:])
    q = l2norm(q.reshape(b, s, hk, dk)) * dk ** -0.5
    k = l2norm(k.reshape(b, s, hk, dk))
    v = v.reshape(b, s, hv, dv)
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(base.linear(x, lw["w_beta"], quant))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(lw["A_log"].astype(F32)) * jax.nn.softplus(
        base.linear(x, lw["w_a"], quant) + lw["dt_bias"].astype(F32)))

    def token(S, xs):
        q, k, v, alpha, beta = xs                  # [b, H, *], [b, H]
        S = alpha[..., None, None] * S
        u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    seq = lambda a: jnp.swapaxes(a, 0, 1)          # token axis first
    _, o = jax.lax.scan(token, jnp.zeros((b, hv, dk, dv), F32),
                        tuple(map(seq, (q, k, v, alpha, beta))))
    o = base.rms_norm(seq(o), lw["o_norm_w"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(base.linear(x, lw["w_z"], quant))
    return base.linear(o.reshape(b, s, hv * dv) * gate, lw["wo"], quant)


def full_attention(x, lw, cfg, quant):
    b, s, d = x.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    eps = cfg["rms_norm_eps"]
    q = base.rms_norm(base.linear(x, lw["wq"], quant), lw["q_norm_w"], eps)
    k = base.rms_norm(base.linear(x, lw["wk"], quant), lw["k_norm_w"], eps)
    v = base.linear(x, lw["wv"], quant)
    q = q.reshape(b, s, hq, hd)                    # no rotary embedding
    k = jnp.repeat(k.reshape(b, s, hkv, hd), hq // hkv, axis=2)
    v = jnp.repeat(v.reshape(b, s, hkv, hd), hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return base.linear(out.reshape(b, s, hq * hd), lw["wo"], quant)


MIXERS = {"linear_attention": ("linear", linear_attention),
          "full_attention": ("full", full_attention)}


def block(x, layers, li, at, kind: str, cfg, quant):
    """Layer ``li``: the common stack at ``li``, its kind's stack at ``at``
    (the layer's index among its kind); both indices taken inside the
    program, so one program serves every layer of a kind."""
    stack, mixer = MIXERS[kind]
    lw = {k: v[li] for k, v in layers.items() if not isinstance(v, dict)}
    lw.update({k: v[at] for k, v in layers[stack].items()})
    eps = cfg["rms_norm_eps"]
    h = x + base.rms_norm(mixer(x, lw, cfg, quant), lw["attn_norm_w"], eps)
    return h + base.rms_norm(base.dense_mlp(h, lw, cfg, quant),
                             lw["mlp_norm_w"], eps)


def _static_cfg(cfg: Dict[str, Any]) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_allow_neg_eigval")
    return tuple((k, cfg[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _jitted_block(static_cfg: Tuple, kind: str, quant):
    cfg = dict(static_cfg)

    def run(x, layers, li, at):
        with jax.default_matmul_precision("highest"):
            return block(x, layers, li, at, kind, cfg, quant)

    return jax.jit(run)


def hidden(weights, tokens, cfg, n_layers: int, quant=None):
    """tokens [b, s] -> final hidden states [b, s, d] in float32."""
    x = weights["tok_embed"][tokens].astype(F32)
    seen = {"linear_attention": 0, "full_attention": 0}
    for li, kind in enumerate(cfg["layer_types"][:n_layers]):
        x = _jitted_block(_static_cfg(cfg), kind, quant)(
            x, weights["layers"], li, seen[kind])
        seen[kind] += 1
    return x


def logits_at(weights, tokens, rows, cols, cfg, n_layers: int, quant=None):
    """Logits [n, vocab] of the full forward over ``tokens`` [b, s] at the
    positions (rows[i], cols[i])."""
    x = hidden(weights, tokens, cfg, n_layers, quant)[rows, cols]
    return base._jitted_head(cfg["rms_norm_eps"], quant)(
        x, weights["final_norm_w"], weights["lm_head"])
