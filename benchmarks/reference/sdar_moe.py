"""Plain reference for the SDAR-MoE architecture (``model_type: sdar_moe``,
JetLM/SDAR-30B-A3B-Chat): Qwen3-MoE's decoder layer, as ``transformers``'
``Qwen3MoeDecoderLayer`` has it, under SDAR's block-causal mask.

  h = x + attn(rms(x));  out = h + moe(rms(h))
  attn: q = W_q u (heads x head_dim), k = W_k u, v = W_v u, no bias;
        q = rope(rms_hd(q) * g_q), k = rope(rms_hd(k) * g_k), a head at a
        time with one gain vector for all heads (rotate-half pairing);
        softmax(q k^T / sqrt(head_dim) + M) v; W_o
  moe:  p = softmax(W_r u) over all experts in float32; the
        num_experts_per_tok largest, divided by their sum;
        sum_e p_e W_down,e (silu(W_gate,e u) * W_up,e u); no shared expert
  M:    position i sees position j iff j // block <= i // block: causal
        over blocks of ``block_length``, both ways inside a block
  a final RMSNorm and an untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the full forward of a token
matrix, no cache, no kernels, no batching, and no code of
``deepspeed_tpu``. Every expert is computed for every token and the
unchosen ones weighted zero, one expert at a time (a scan), so that one
expert's float32 copy is live; the rows of ``tokens`` go through in blocks
of ``ROWS`` so that 128 experts and a [rows, heads, s, s] score fit beside
the program's weights.

A pass of the program is one row of ``tokens``: the sequence as it stood at
that pass (decided tokens, the mask id at the undecided positions of the
block under way; whatever follows is invisible to the block), and ``cols``
the block's positions.

``decide`` is the plain form of a denoise pass's rule
(``low_confidence_static``): what ``correct`` holds the program's decisions
to. ``router_margins`` says how close each position's routing came to a
tie (the gap between the last expert taken and the first left out), for
the question of why a position's logits moved.

Controls of ``correct`` (each must fail a limit):
  quant  - "fp8" / "int8": every linear layer's operands rounded
           (``reference/mistral.py``'s control)
  mask   - "causal": the plain causal mask in M's place
  stale  - (tokens [b, s] with every generated block before the one under
           way as it stood at its own last denoise pass, the first [b] and
           the end [b] of those blocks' columns, first -1 where there is
           none): their K and V are taken, in every layer, from the
           forward over those earlier states, not from the finished blocks:
           a cache whose blocks were never committed
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.mistral import (F32, linear, rms_norm, rotary,
                                          swiglu)

ROWS = 8


def block_length(cfg) -> int:
    return int(cfg.get("block_length",
                       cfg.get("assumed", {}).get("block_length", 4)))


def keys_values(x, lw, cfg, quant):
    b, s, _ = x.shape
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    k = linear(x, lw["wk"], quant).reshape(b, s, hkv, hd)
    v = linear(x, lw["wv"], quant).reshape(b, s, hkv, hd)
    k = rotary(rms_norm(k, lw["k_norm_w"], cfg["rms_norm_eps"]), pos,
               cfg["rope_theta"])
    return k, v


def attention(x, k, v, lw, cfg, quant, mask: str):
    """x [b, s, d] against the keys and values given (its own, or with a
    stale block's put in their place)."""
    b, s, _ = x.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q = linear(x, lw["wq"], quant).reshape(b, s, hq, hd)
    q = rotary(rms_norm(q, lw["q_norm_w"], cfg["rms_norm_eps"]), pos,
               cfg["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    blk = 1 if mask == "causal" else cfg["block_length"]
    seen = j // blk <= i // blk
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return linear(out.reshape(b, s, hq * hd), lw["wo"], quant)


def expert_mlp(x, lw, cfg, quant):
    """-> (the layer's output, [b, s] the router's margin: log p of the
    last expert taken less log p of the first left out)."""
    n_experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x.astype(F32) @ lw["wg"].astype(F32), -1)
    top_p, top_idx = jax.lax.top_k(probs, k + 1)             # [b, s, k + 1]
    margin = jnp.log(top_p[..., k - 1]) - jnp.log(top_p[..., k])
    top_p, top_idx = top_p[..., :k], top_idx[..., :k]
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)        # norm_topk_prob
    weight = jnp.sum(jax.nn.one_hot(top_idx, n_experts, dtype=F32)
                     * top_p[..., None], axis=-2)            # [b, s, E]

    def one(acc, e):
        out = swiglu(x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], quant)
        return acc + out * weight[..., e, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32), jnp.arange(n_experts))
    return acc, margin


def block(x, xs, sel, layers, li, cfg, quant, mask):
    """Layer ``li`` on the states ``x`` and, where a stale block is asked
    for, on the earlier states ``xs`` too: ``sel`` [b, s] marks the
    positions whose K and V the first forward takes from the second.
    -> (x, xs, the router's margin [b, s] on ``x``)."""
    eps = cfg["rms_norm_eps"]
    lw = jax.tree_util.tree_map(lambda a: a[li], layers)

    def rest(x, attn):
        x = x + attn
        out, margin = expert_mlp(rms_norm(x, lw["mlp_norm_w"], eps), lw, cfg,
                                 quant)
        return x + out, margin

    u = rms_norm(x, lw["attn_norm_w"], eps)
    k, v = keys_values(u, lw, cfg, quant)
    if xs is not None:
        us = rms_norm(xs, lw["attn_norm_w"], eps)
        ks, vs = keys_values(us, lw, cfg, quant)
        k = jnp.where(sel[..., None, None], ks, k)
        v = jnp.where(sel[..., None, None], vs, v)
        xs, _ = rest(xs, attention(us, ks, vs, lw, cfg, quant, mask))
    x, margin = rest(x, attention(u, k, v, lw, cfg, quant, mask))
    return x, xs, margin


def _static_cfg(cfg: Dict[str, Any]) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps", "num_experts",
            "num_experts_per_tok")
    return tuple((k, cfg[k]) for k in keys) \
        + (("block_length", block_length(cfg)),)


@functools.lru_cache(maxsize=None)
def _jitted_block(static_cfg: tuple, quant, mask):
    cfg = dict(static_cfg)

    def run(x, xs, sel, layers, li):
        with jax.default_matmul_precision("highest"):
            return block(x, xs, sel, layers, li, cfg, quant, mask)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, quant):
    def run(x, gain, head):
        with jax.default_matmul_precision("highest"):
            return linear(rms_norm(x, gain, eps), head, quant)

    return jax.jit(run)


def hidden(weights, tokens, cfg, n_layers: int, quant=None,
           mask: str = "block", stale=None, margins=None):
    """tokens [b, s] -> final hidden states [b, s, d] in float32; a list
    ``margins`` gains each layer's router margins [b, s]."""
    run = _jitted_block(_static_cfg(cfg), quant, mask)
    x = weights["tok_embed"][tokens].astype(F32)
    xs = sel = None
    if stale is not None:
        earlier, first, end = stale
        xs = weights["tok_embed"][earlier].astype(F32)
        col = jnp.arange(tokens.shape[1])[None, :]
        first, end = jnp.asarray(first)[:, None], jnp.asarray(end)[:, None]
        sel = (first >= 0) & (col >= first) & (col < end)
    for li in range(n_layers):
        x, xs, margin = run(x, xs, sel, weights["layers"], li)
        if margins is not None:
            margins.append(margin)
    return x


def logits_at(weights, tokens, rows, cols, cfg, n_layers: int, quant=None,
              mask: str = "block", stale=None):
    """Logits [n, vocab] of the full forward over ``tokens`` [b, s] at the
    positions (rows[i], cols[i]), the rows of ``tokens`` ``ROWS`` at a
    time."""
    tokens, rows, cols = np.asarray(tokens), np.asarray(rows), np.asarray(cols)
    # whole blocks of rows: one shape of the layer's program, whatever the
    # number of passes a seed's prompts make (a row of zeros is a sequence)
    pad = ((0, -len(tokens) % ROWS), (0, 0))
    tokens = np.pad(tokens, pad)
    if stale is not None:
        stale = (np.pad(np.asarray(stale[0]), pad),
                 np.pad(np.asarray(stale[1]), pad[0], constant_values=-1),
                 np.pad(np.asarray(stale[2]), pad[0]))
    head = _jitted_head(cfg["rms_norm_eps"], quant)
    out = [None] * len(rows)
    for lo in range(0, tokens.shape[0], ROWS):
        mine = np.nonzero((rows >= lo) & (rows < lo + ROWS))[0]
        if not len(mine):
            continue
        part = None if stale is None else (
            jnp.asarray(np.asarray(stale[0])[lo:lo + ROWS]),
            np.asarray(stale[1])[lo:lo + ROWS],
            np.asarray(stale[2])[lo:lo + ROWS])
        x = hidden(weights, jnp.asarray(tokens[lo:lo + ROWS]), cfg, n_layers,
                   quant, mask, part)[rows[mine] - lo, cols[mine]]
        got = head(x, weights["final_norm_w"], weights["lm_head"])
        for n, i in enumerate(mine):
            out[i] = got[n]
    return jnp.stack(out)


def router_margins(weights, tokens, rows, cols, cfg, n_layers: int):
    """[n, n_layers]: at the positions (rows[i], cols[i]) of the full
    forward over ``tokens``, each layer's router margin (``expert_mlp``):
    where it is within bfloat16's rounding of the router's input, the
    program may take another eighth expert than the reference."""
    tokens, rows, cols = np.asarray(tokens), np.asarray(rows), np.asarray(cols)
    tokens = np.pad(tokens, ((0, -len(tokens) % ROWS), (0, 0)))
    out = np.zeros((len(rows), n_layers), np.float32)
    for lo in range(0, tokens.shape[0], ROWS):
        mine = np.nonzero((rows >= lo) & (rows < lo + ROWS))[0]
        if not len(mine):
            continue
        margins = []
        hidden(weights, jnp.asarray(tokens[lo:lo + ROWS]), cfg, n_layers,
               margins=margins)
        got = np.stack([np.asarray(m) for m in margins], -1)  # [b, s, L]
        out[mine] = got[rows[mine] - lo, cols[mine]]
    return out


def decide(logits, masked, n: int, mask_id: int):
    """A denoise pass's rule on one block: ``logits`` [B, vocab] at the
    block's positions, ``masked`` [B] bool. Every masked position predicts
    its own token, the argmax of its own logits with the mask id left out,
    with confidence that token's softmax probability; the ``min(n,
    masked)`` masked positions of highest confidence are decided, ties to
    the lower position. -> ([(lane, token)] in lane order, the relative gap
    in confidence between the last position decided and the first left
    masked: inf where none is left)."""
    l = np.array(logits, np.float64)
    l[:, mask_id] = -np.inf
    x0 = np.argmax(l, -1)
    conf = 1.0 / np.sum(np.exp(l - l.max(-1, keepdims=True)), -1)
    lanes = [i for i in np.argsort(-conf, kind="stable") if masked[i]]
    took, left = lanes[:n], lanes[n:]
    gap = (conf[took[-1]] - conf[left[0]]) / conf[took[-1]] \
        if took and left else np.inf
    return sorted((int(i), int(x0[i])) for i in took), float(gap)
