"""Plain reference for the Granite-4.0-H architecture (``transformers``'
``GraniteMoeHybridForCausalLM``, dense members: no routed experts):
pre-norm blocks whose token mixer is, by ``layer_types``, grouped-query
softmax attention without positions or a Mamba-2 state-space layer
("Transformers are SSMs", Dao and Gu 2024), over a SwiGLU feed-forward, with
Granite's four scalars and a head tied to the embedding.

The block, input ``x``::

    h = x + residual_multiplier * mix(rms(x))
    out = h + residual_multiplier * mlp(rms(h));  mlp(u) = (silu(u W_gate) * u W_up) W_down

For one Mamba layer, token ``t``, input ``u_t``::

    [z, xBC, dt~] = u W_in
    xBC = silu(conv4(xBC) + b_conv)                 causal, depthwise
    [x, B, C] = xBC                                 x [H, P]; B, C [G, N]
    dt = softplus(dt~ + dt_bias);  a = exp(-exp(A_log) dt)
    S <- a S + (dt x) B^T;  y = S C + D x           S [P, N] a head
    o = (rmsnorm_w(y * silu(z)) over all H P channels) W_out

The embedding is multiplied by ``embedding_multiplier``, the attention
scores by ``attention_multiplier`` (not ``head_dim^-0.5``), and the logits
over the tied embedding divided by ``logits_scaling``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence is a ``lax.scan``
over tokens, one token at a time from a zero state; no chunking, no cache,
no kernels, and no code of ``deepspeed_tpu``. A layer is one program, so
one layer's float32 weights and activations are live at a time. It reads
the weight tree the benchmark made from the seed (``benchmarks/weights.py``)
and shares the linear layer with its rounding control (``quant``), the norm
and the feed-forward with ``reference/mistral.py``.

Nothing is assumed about the equations: ``tests/test_granite_hybrid.py``
holds the program to the published modelling code itself. The state is
kept in float32 (as that code keeps it).

What ``benchmarks/weights.py`` gives the new leaves (by its rules on a
leaf's name and shape): ``w_in`` and ``w_out`` normal with ``fan_in^-0.5``
(``w_out`` is not one of the names it scales down by depth, so the 36
state-space branches carry about a tenth of the final hidden state beside
the embedding's 12: a fault in the recurrence moves the logits by percents,
not by the rounding's tenths of a percent), ``conv_w`` normal with 0.5 (its
second-to-last axis is the 4 taps), ``ssm_norm_w`` and the ``*_norm_w``
gains near one, and ``conv_b``, ``A_log``, ``dt_bias`` and ``D`` (stacked
[mamba layers, ...]) normal with ``(mamba layers)^-0.5``: a convolution
bias that is not zero, steps ``dt`` of about 0.3 to 1.3 and decays ``a`` of
about 0.3 to 0.7 a token, a shorter memory than a trained model's and the
same arithmetic.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import mistral as base

F32 = jnp.float32


def conv_silu(x, w, b):
    """x [b, s, ch], w [K, ch], b [ch]: silu(b + sum_i w[i] x_(t - (K - 1 - i)))."""
    K, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(b.astype(F32) + sum(
        w[i].astype(F32) * padded[:, i:i + s] for i in range(K)))


def mamba(u, lw, cfg, quant):
    b, s, _ = u.shape
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di = H * P
    zxd = base.linear(u, lw["w_in"], quant)
    z, dt = zxd[..., :di], zxd[..., di + di + 2 * G * N:]
    xBC = conv_silu(zxd[..., di:di + di + 2 * G * N], lw["conv_w"],
                    lw["conv_b"])
    x = xBC[..., :di].reshape(b, s, H, P)
    B = jnp.repeat(xBC[..., di:di + G * N].reshape(b, s, G, N), H // G, 2)
    C = jnp.repeat(xBC[..., di + G * N:].reshape(b, s, G, N), H // G, 2)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))        # [b, s, H]
    a = jnp.exp(-jnp.exp(lw["A_log"].astype(F32)) * dt)
    D = lw["D"].astype(F32)

    def token(S, xs):
        x, B, C, dt, a = xs                        # [b, H, *], [b, H]
        S = a[..., None, None] * S \
            + (dt[..., None] * x)[..., None] * B[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C) + D[:, None] * x

    seq = lambda t: jnp.swapaxes(t, 0, 1)          # token axis first
    _, y = jax.lax.scan(token, jnp.zeros((b, H, P, N), F32),
                        tuple(map(seq, (x, B, C, dt, a))))
    y = seq(y).reshape(b, s, di) * jax.nn.silu(z)
    return base.linear(base.rms_norm(y, lw["ssm_norm_w"], cfg["rms_norm_eps"]),
                       lw["w_out"], quant)


def attention(x, lw, cfg, quant):
    b, s, d = x.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    q = base.linear(x, lw["wq"], quant).reshape(b, s, hq, hd)   # no positions
    k = base.linear(x, lw["wk"], quant).reshape(b, s, hkv, hd)
    v = base.linear(x, lw["wv"], quant).reshape(b, s, hkv, hd)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
        * F32(cfg["attention_multiplier"])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return base.linear(out.reshape(b, s, hq * hd), lw["wo"], quant)


MIXERS = {"mamba": ("mamba", mamba), "attention": ("full", attention)}


def block(x, layers, li, at, kind: str, cfg, quant):
    """Layer ``li``: the common stack at ``li``, its kind's stack at ``at``
    (the layer's index among its kind); both indices taken inside the
    program, so one program serves every layer of a kind."""
    stack, mixer = MIXERS[kind]
    lw = {k: v[li] for k, v in layers.items() if not isinstance(v, dict)}
    lw.update({k: v[at] for k, v in layers[stack].items()})
    eps, rm = cfg["rms_norm_eps"], F32(cfg["residual_multiplier"])
    h = x + rm * mixer(base.rms_norm(x, lw["attn_norm_w"], eps), lw, cfg,
                       quant)
    return h + rm * base.dense_mlp(base.rms_norm(h, lw["mlp_norm_w"], eps),
                                   lw, cfg, quant)


def _static_cfg(cfg: Dict[str, Any]) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "attention_multiplier", "residual_multiplier", "mamba_n_heads",
            "mamba_d_head", "mamba_n_groups", "mamba_d_state")
    return tuple((k, cfg[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _jitted_block(static_cfg: Tuple, kind: str, quant):
    cfg = dict(static_cfg)

    def run(x, layers, li, at):
        with jax.default_matmul_precision("highest"):
            return block(x, layers, li, at, kind, cfg, quant)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, scaling: float, quant):
    def run(x, gain, embed):
        with jax.default_matmul_precision("highest"):
            return base.linear(base.rms_norm(x, gain, eps), embed.T,
                               quant) / F32(scaling)

    return jax.jit(run)


def hidden(weights, tokens, cfg, n_layers: int, quant=None):
    """tokens [b, s] -> final hidden states [b, s, d] in float32."""
    x = weights["tok_embed"][tokens].astype(F32) \
        * F32(cfg["embedding_multiplier"])
    seen = {"mamba": 0, "attention": 0}
    for li, kind in enumerate(cfg["layer_types"][:n_layers]):
        x = _jitted_block(_static_cfg(cfg), kind, quant)(
            x, weights["layers"], li, seen[kind])
        seen[kind] += 1
    return x


def logits_at(weights, tokens, rows, cols, cfg, n_layers: int, quant=None):
    """Logits [n, vocab] of the full forward over ``tokens`` [b, s] at the
    positions (rows[i], cols[i]); the head is the embedding's transpose."""
    x = hidden(weights, tokens, cfg, n_layers, quant)[rows, cols]
    return _jitted_head(cfg["rms_norm_eps"], float(cfg["logits_scaling"]),
                        quant)(x, weights["final_norm_w"],
                               weights["tok_embed"])
