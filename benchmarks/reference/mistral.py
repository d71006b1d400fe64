"""Plain reference for the Mistral architecture (Jiang et al. 2023; the
published ``modeling_mistral`` description): pre-norm decoder blocks of
RMSNorm, grouped-query attention with rotary embeddings (rotate-half
pairing) under a causal sliding window, and a SwiGLU feed-forward; an
untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and no code of ``deepspeed_tpu``. It reads the weight
tree the benchmark made from the seed (``benchmarks/weights.py``), layer by
layer, so that one layer's float32 copy is live at a time.

``quant`` is the control of ``correct``: the same forward with every linear
layer's two operands rounded to int8 or to fp8 (e4m3), weights scaled per
output channel and activations per token: the precisions below bfloat16
that a later PR might be tempted to serve or train in.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round(x, axis: int, quant: str):
    """``x`` rounded to ``quant`` and back, scaled along ``axis`` (one scale
    a token for activations and their gradients, one an output channel for
    weights)."""
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        q = jnp.round(x / scale)
    else:
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _low_matmul(x, w, quant: str):
    return _round(x, -1, quant) @ _round(w, 0, quant)


def _low_fwd(x, w, quant):
    return _low_matmul(x, w, quant), (x, w)


def _low_bwd(quant, saved, dy):
    """The backward products in the low type as well, as training in that
    type computes them: dx = dy w^T and dw = x^T dy on rounded operands."""
    x, w = saved
    dyq = _round(dy, -1, quant)
    dx = dyq @ _round(w, 0, quant).T
    x2, dy2 = x.reshape(-1, x.shape[-1]), dyq.reshape(-1, dy.shape[-1])
    return dx, _round(x2, -1, quant).T @ dy2


_low_matmul.defvjp(_low_fwd, _low_bwd)


def linear(x, w, quant: Optional[str]):
    """x [..., k] @ w [k, n] in float32; with ``quant`` ("int8" or "fp8",
    e4m3) the product, and in the backward pass its two, take operands
    rounded to that type."""
    x, w = x.astype(F32), w.astype(F32)
    if quant is not None:
        return _low_matmul(x, w, quant)
    return x @ w


def rms_norm(x, gain, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(F32)


def rotary(x, positions, theta: float):
    """x [b, s, h, d]; rotate-half pairing: (x1, x2) -> (x1 cos - x2 sin,
    x2 cos + x1 sin) with x1, x2 the two halves of the head."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[..., None] * inv            # [b, s, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lw, cfg, quant):
    b, s, _ = x.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q = linear(x, lw["wq"], quant).reshape(b, s, hq, hd)
    k = linear(x, lw["wk"], quant).reshape(b, s, hkv, hd)
    v = linear(x, lw["wv"], quant).reshape(b, s, hkv, hd)
    q, k = rotary(q, pos, cfg["rope_theta"]), rotary(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if cfg.get("sliding_window"):
        seen &= j > i - cfg["sliding_window"]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return linear(out.reshape(b, s, hq * hd), lw["wo"], quant)


def swiglu(x, w_gate, w_up, w_down, quant):
    return linear(jax.nn.silu(linear(x, w_gate, quant))
                  * linear(x, w_up, quant), w_down, quant)


def dense_mlp(x, lw, cfg, quant):
    return swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def block(x, layers, li, cfg, quant, mlp: Callable):
    """Layer ``li`` of the stacked tree ``layers`` (index taken inside the
    program, so no second copy of the stack is made)."""
    lw = jax.tree_util.tree_map(lambda a: a[li], layers)
    x = x + attention(rms_norm(x, lw["attn_norm_w"], cfg["rms_norm_eps"]),
                      lw, cfg, quant)
    return x + mlp(rms_norm(x, lw["mlp_norm_w"], cfg["rms_norm_eps"]),
                   lw, cfg, quant)


def _static_cfg(cfg: Dict[str, Any]) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps", "sliding_window",
            "num_local_experts", "num_experts_per_tok")
    return tuple((k, cfg.get(k)) for k in keys)


@functools.lru_cache(maxsize=None)
def _jitted_block(static_cfg: tuple, quant, mlp):
    cfg = dict(static_cfg)

    def run(x, layers, li):
        with jax.default_matmul_precision("highest"):
            return block(x, layers, li, cfg, quant, mlp)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, quant):
    def run(x, gain, head):
        with jax.default_matmul_precision("highest"):
            return linear(rms_norm(x, gain, eps), head, quant)

    return jax.jit(run)


def hidden(weights, tokens, cfg, n_layers: int, quant=None,
           mlp: Callable = dense_mlp):
    """tokens [b, s] -> final hidden states [b, s, d] in float32."""
    x = weights["tok_embed"][tokens].astype(F32)
    run = _jitted_block(_static_cfg(cfg), quant, mlp)
    for li in range(n_layers):
        x = run(x, weights["layers"], li)
    return x


def logits_at(weights, tokens, rows, cols, cfg, n_layers: int, quant=None,
              mlp: Callable = dense_mlp):
    """Logits [n, vocab] of the full forward over ``tokens`` [b, s] at the
    positions (rows[i], cols[i])."""
    x = hidden(weights, tokens, cfg, n_layers, quant, mlp)[rows, cols]
    return _jitted_head(cfg["rms_norm_eps"], quant)(
        x, weights["final_norm_w"], weights["lm_head"])


def loss_fn(weights, tokens, cfg, n_layers: int, quant=None,
            mlp: Callable = dense_mlp):
    """Mean next-token cross entropy over tokens [b, s] (s - 1 targets a
    row). Each block is under ``jax.checkpoint``: the same mathematics,
    less memory held for the gradient."""
    x = weights["tok_embed"][tokens].astype(F32)
    step = jax.checkpoint(
        lambda x, li: block(x, weights["layers"], li, cfg, quant, mlp))
    for li in range(n_layers):
        x = step(x, li)
    logits = linear(rms_norm(x, weights["final_norm_w"],
                             cfg["rms_norm_eps"]), weights["lm_head"], quant)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
    return -jnp.mean(picked)


def split_specs(weights, axis: str):
    """Where the reference runs over several chips (a model whose float32
    gradient no one chip holds), how its weights are laid over the mesh axis
    ``axis``: every matrix split along its wide side (heads, feed-forward
    width, experts' width, vocabulary), so that no chip ever holds a whole
    matrix or a whole gradient. The program stays the plain one above; the
    compiler partitions it to follow its inputs."""
    from jax.sharding import PartitionSpec as P

    def spec(path, a):
        name = str(getattr(path[-1], "key", path[-1]))
        if a.ndim < 2 or name == "wg":
            return P()
        if name in ("wo", "w_down", "tok_embed"):     # wide side comes first
            return P(*([None] * (a.ndim - 2) + [axis, None]))
        return P(*([None] * (a.ndim - 1) + [axis]))

    return jax.tree_util.tree_map_with_path(spec, weights)


def loss_and_grad_norm(weights, tokens, cfg, n_layers: int, quant=None,
                       mlp: Callable = dense_mlp, grad_shardings=None):
    """(loss, global L2 norm of its gradient) in float32, one program.
    ``grad_shardings`` (the weights' own, where they are split over chips)
    keeps each gradient laid out as its weight is."""
    cfg = dict(_static_cfg(cfg))

    def run(weights, tokens):
        with jax.default_matmul_precision("highest"):
            w32 = jax.tree_util.tree_map(lambda a: a.astype(F32), weights)
            loss, grads = jax.value_and_grad(loss_fn)(
                w32, tokens, cfg, n_layers, quant, mlp)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            sq = sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))
            return loss, jnp.sqrt(sq)

    return jax.jit(run)(weights, tokens)
