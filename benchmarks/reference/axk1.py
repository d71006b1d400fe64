"""Plain reference for SKT's A.X-K1 (``model_type: axk1``; the config is
DeepSeek-V3's key for key, Liu et al. 2024): pre-norm decoder blocks of
RMSNorm, multi-head latent attention, a dense SwiGLU feed-forward in the
leading layer and in every later one a sparse mixture of SwiGLU experts
beside a shared expert; an untied output head.

The equations (ISSUE 49, "The equations"), ``x`` a token's normed hidden
state, ``N`` an RMS norm with a gain:

* attention: ``cq = N_q(W_dq x)``; ``[q_nope_h | q_rope_h] = W_uq,h cq``;
  ``[ckv | kr] = W_dkv x``; ``c = N_kv(ckv)``; ``k_nope_h = W_uk,h c``,
  ``v_h = W_uv,h c``; ``q_rope_h`` and ``kr`` rotated at the token's
  position (rotate-half pairing), ``kr`` one for all heads; the score of
  query t on key s <= t is ``(q_nope . k_nope + q_rope . kr) * scale`` with
  ``scale = (128 + 64)^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2``;
  softmax; ``o_h = sum p v_h``; ``W_o [o_1 .. o_H]``. The rotary
  frequencies are YaRN's: ``f_i = theta^(-2i/64)`` blended with ``f_i /
  factor`` by the linear ramp between the correction dimensions of
  ``beta_fast`` and ``beta_slow`` over the original positions; cos and sin
  unscaled (mscale = mscale_all_dim). EXPANDED form: K and V a head are
  formed for every token; no absorption, no cache.
* expert layers: ``s = sigmoid(W_g x)`` over all the router's experts, in
  float32; groups of consecutive experts, a group scored by its largest
  ``s``; the ``topk_group`` best groups kept; among their experts the
  ``num_experts_per_tok`` largest ``s`` (ties to the lower index); ``w_e =
  routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``; ``y = sum
  over chosen AND HELD e of w_e SwiGLU_e(x) + SwiGLU_shared(x)``.

Departures, each the configuration's (``benchmarks/configs/a.x-k1.json``):
the weights' expert stacks hold ``experts_held`` (router ids 0-11 of 192)
and only those are summed, with ``w_e`` normalised over all 8 chosen: what
the absent chips' experts would add is left out, here as in the program;
the vocabulary is the slice the weights hold; ``kv_b_proj`` arrives as its
two parts ``w_uk`` / ``w_uv``; DeepSeek's de-interleave before the rotation
is a relabelling under seeded weights and is not done.

Every held expert is computed for every token, one at a time, and the
unchosen weighted zero (as ``reference/mixtral.py``); sequences are run one
at a time and attention a group of heads at a time, so that the float32
copies fit beside the served model. No code of ``deepspeed_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import mistral as base

F32 = jnp.float32
HEAD_GROUP = 8        # heads whose [s, s] scores are live together

_KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "rms_norm_eps", "first_k_dense_replace", "n_group", "topk_group",
         "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
         "scoring_func")


def _static_cfg(cfg) -> tuple:
    rs = cfg["rope_scaling"]
    return tuple((k, cfg[k]) for k in _KEYS) + (
        ("experts_held", tuple(cfg["experts_held"])),
        ("yarn", tuple(rs[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim"))))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """[dim / 2] rotary frequencies under YaRN."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):   # the dimension that turns this often
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f / factor * ramp + f * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg) -> float:
    factor, _, _, _, mscale, mscale_all = cfg["yarn"]
    if mscale != mscale_all:
        raise NotImplementedError("cos and sin scaled by mscale / mscale_all_dim")
    m = 0.1 * mscale_all * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rotate(x, positions, inv_freq):
    """x [b, s, h, d], rotate-half pairing, at ``positions`` [b, s]."""
    d = x.shape[-1]
    ang = positions.astype(F32)[..., None] * inv_freq      # [b, s, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lw, cfg, quant):
    b, s, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    factor, original, fast, slow, _, _ = cfg["yarn"]
    inv_freq = jnp.asarray(yarn_inv_freq(dr, cfg["rope_theta"], factor,
                                         original, fast, slow))
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    cq = base.rms_norm(base.linear(x, lw["w_dq"], quant),
                       lw["q_lora_norm_w"], eps)
    q = base.linear(cq, lw["w_uq"], quant).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], pos, inv_freq)
    down = base.linear(x, lw["w_dkv"], quant)
    c = base.rms_norm(down[..., :r], lw["kv_lora_norm_w"], eps)
    kr = rotate(down[..., None, r:], pos, inv_freq)[:, :, 0]   # [b, s, dr]
    k_nope = base.linear(c, lw["w_uk"], quant).reshape(b, s, h, dn)
    v = base.linear(c, lw["w_uv"], quant).reshape(b, s, h, dv)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scale = softmax_scale(cfg)

    def heads(args):      # a group of heads at a time
        qn, qr, kn, vv = args                              # [b, s, g, *]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
                  + jnp.einsum("bqhd,bkd->bhqk", qr, kr)) * scale
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vv)

    g = min(HEAD_GROUP, h)
    split = lambda a: jnp.moveaxis(
        a.reshape(b, s, h // g, g, a.shape[-1]), 2, 0)
    out = jax.lax.map(heads, tuple(map(split, (q_nope, q_rope, k_nope, v))))
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, h * dv)
    return base.linear(out, lw["wo"], quant)


def router_weights(x, wg, cfg):
    """[b, s, E] float32: ``w_e`` at the chosen experts, 0 elsewhere."""
    if cfg["scoring_func"] != "sigmoid":
        raise NotImplementedError(cfg["scoring_func"])
    scores = jax.nn.sigmoid(x.astype(F32) @ wg.astype(F32))     # [b, s, E]
    E, G = scores.shape[-1], cfg["n_group"]
    by_group = jnp.max(scores.reshape(scores.shape[:-1] + (G, E // G)), -1)
    _, best = jax.lax.top_k(by_group, cfg["topk_group"])   # ties: lower index
    kept = jnp.sum(jax.nn.one_hot(best, G, dtype=F32), -2) > 0
    inside = jnp.where(jnp.repeat(kept, E // G, axis=-1), scores, -1.0)
    top, idx = jax.lax.top_k(inside, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * top[..., None], -2)


def expert_mlp(x, lw, cfg, quant):
    weight = router_weights(x, lw["wg"], cfg)
    first, past = cfg["experts_held"]

    def one(acc, e):      # e: the expert's place in the held stacks
        out = base.swiglu(x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e],
                          quant)
        w = jax.lax.dynamic_index_in_dim(weight, first + e, -1)
        return acc + out * w, None

    acc, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32),
                          jnp.arange(past - first))
    return acc + base.swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"],
                             quant)


ATTN = ("attn_norm_w", "mlp_norm_w", "w_dq", "q_lora_norm_w", "w_uq", "w_dkv",
        "kv_lora_norm_w", "w_uk", "w_uv", "wo")
MOE = ("wg", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down")


def block(x, layers, li, dense: bool, cfg, quant):
    """Layer ``li`` (traced) of the stacked tree; ``dense`` (static): one
    of the leading dense layers, whose feed-forward sits in its own stack,
    the expert layers' leaves being stacked over the expert layers alone."""
    eps = cfg["rms_norm_eps"]
    lw = {k: layers[k][li] for k in ATTN}
    x = x + attention(base.rms_norm(x, lw["attn_norm_w"], eps), lw, cfg,
                      quant)
    h = base.rms_norm(x, lw["mlp_norm_w"], eps)
    if dense:
        ffn = {k: v[li] for k, v in layers["dense"].items()}
        return x + base.dense_mlp(h, ffn, cfg, quant)
    at = li - cfg["first_k_dense_replace"]
    return x + expert_mlp(h, {k: layers[k][at] for k in MOE}, cfg, quant)


@functools.lru_cache(maxsize=None)
def _jitted_block(static_cfg: tuple, quant, dense: bool):
    cfg = dict(static_cfg)

    def run(x, layers, li):
        with jax.default_matmul_precision("highest"):
            return block(x, layers, li, dense, cfg, quant)

    return jax.jit(run)


def hidden(weights, tokens, cfg, n_layers: int, quant=None):
    """tokens [b, s] -> final hidden states [b, s, d] in float32, a
    sequence at a time."""
    static = _static_cfg(cfg)
    nd = cfg["first_k_dense_replace"]
    out = []
    for i in range(tokens.shape[0]):
        x = weights["tok_embed"][tokens[i][None]].astype(F32)
        for li in range(n_layers):
            x = _jitted_block(static, quant, li < nd)(
                x, weights["layers"], jnp.int32(li))
        out.append(x)
    return jnp.concatenate(out, 0)


def logits_at(weights, tokens, rows, cols, cfg, n_layers: int, quant=None):
    """Logits [n, vocab] of the full forward over ``tokens`` [b, s] at the
    positions (rows[i], cols[i])."""
    x = hidden(weights, tokens, cfg, n_layers, quant)[rows, cols]
    return base._jitted_head(cfg["rms_norm_eps"], quant)(
        x, weights["final_norm_w"], weights["lm_head"])
