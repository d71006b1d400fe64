"""Attention ops.

Replaces the reference's attention kernel zoo — fused softmax/attention CUDA
kernels (``csrc/transformer/*.cu``), inference ``softmax_context``
(``ops/transformer/inference/op_binding/softmax_context.py``), the Evoformer
CUTLASS fMHA (``csrc/deepspeed4science/evoformer_attn/``) — with one
TPU-first surface:

* :func:`dot_product_attention` — jnp reference path; XLA already produces a
  flash-style fused softmax on TPU for moderate sequence lengths.
* :func:`flash_attention` — Pallas blocked/online-softmax kernel
  (``ops/pallas/flash_attention.py``) for long sequences; falls back to the
  jnp path off-TPU or for tiny shapes.
* GQA/MQA handled by K/V head broadcasting (n_kv_heads <= n_heads).
"""

from __future__ import annotations

import collections
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# Trace-time ledger of which implementation each dispatcher call picked
# ("flash_pallas" | "flash_pallas_padded" | "flash_jnp"): the Python below
# only runs while JAX traces, so a count is a program construction, not a
# step. chip_smoke.py reads it to fail when the kernel path did not run.
DISPATCH: "collections.Counter[str]" = collections.Counter()


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def dot_product_attention(q, k, v, *, causal: bool = True,
                          mask: Optional[jnp.ndarray] = None,
                          bias: Optional[jnp.ndarray] = None,
                          scale: Optional[float] = None,
                          logits_dtype=jnp.float32,
                          window: int = 0, attn_block: int = 1):
    """Reference attention. q: [b, sq, hq, d]; k/v: [b, skv, hkv, d].

    Softmax in fp32 (the reference kernels do the same via float accumulators
    in attn_softmax_v2). Causal masking uses absolute positions aligned to
    the *end* of the KV sequence so decode (sq=1, skv=cache_len) works.
    ``bias``: optional additive logit bias broadcastable to [b, h, sq, skv]
    (ALiBi). ``window`` > 0 bands causal attention to the trailing
    ``window`` keys (k > q - window). ``attn_block`` > 1 (a power of two;
    block diffusion) makes the causal mask one over blocks of that many
    positions: the query at position p sees keys up to p | (attn_block - 1).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0, f"query heads {hq} not a multiple of kv heads {hkv}"
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(logits_dtype) * scale
    if bias is not None:
        logits = logits + bias.astype(logits_dtype)
    if causal:
        q_pos = jnp.arange(sq)[:, None] + (skv - sq)
        if attn_block > 1:
            q_pos = q_pos | (attn_block - 1)
        k_pos = jnp.arange(skv)[None, :]
        causal_mask = q_pos >= k_pos  # [sq, skv]
        if window > 0:
            causal_mask = causal_mask & (k_pos > q_pos - window)
        logits = jnp.where(causal_mask[None, None], logits, jnp.finfo(logits_dtype).min)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits_dtype).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024, window: int = 0):
    """Blocked flash attention. Dispatches to the Pallas TPU kernel when
    running on TPU with compatible shapes (padding odd causal self-attention
    lengths up to a lane multiple); jnp reference otherwise. ``window`` > 0
    (static; requires causal) bands attention to the trailing ``window``
    keys — the kernel skips tiles fully below the band (Mistral sliding
    window at O(s*window) compute)."""
    if window > 0 and not causal:
        raise ValueError("window > 0 requires causal attention")
    # kernel-tuning lever for the on-chip sweeps: override the tile shape
    # without touching call sites (traced once per shape, zero step cost)
    block_q = int(os.environ.get("DST_FLASH_BLOCK_Q", block_q))
    block_k = int(os.environ.get("DST_FLASH_BLOCK_K", block_k))
    if _use_pallas(q, k, block_q, block_k):
        from .pallas.flash_attention import flash_attention as _pallas_flash

        DISPATCH["flash_pallas"] += 1
        return _pallas_flash(q, k, v, causal, scale, block_q, block_k,
                             window=window)
    if _use_pallas_padded(q, k, causal):
        from .pallas.flash_attention import flash_attention_padded

        DISPATCH["flash_pallas_padded"] += 1
        return flash_attention_padded(q, k, v, causal, scale,
                                      block_q, block_k, window=window)
    DISPATCH["flash_jnp"] += 1
    return dot_product_attention(q, k, v, causal=causal, scale=scale,
                                 window=window)


def _on_tpu() -> bool:
    """Shared platform probe for Pallas kernel dispatch. A backend that
    fails to initialize raises here — it is not "not a TPU"."""
    return jax.devices()[0].platform == "tpu"


def _use_pallas(q, k, block_q: int, block_k: int) -> bool:
    if not _on_tpu():
        return False
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    bq, bk = min(block_q, sq), min(block_k, skv)
    # clamped blocks must stay lane-aligned (Mosaic (8,128) tiles): a seq
    # like 264 would otherwise clamp to an untested non-multiple-of-128 block
    return (sq % bq == 0 and skv % bk == 0 and bq % 128 == 0 and bk % 128 == 0
            and d in (64, 128, 256) and hq % hkv == 0 and skv >= sq)


def _use_pallas_padded(q, k, causal: bool) -> bool:
    """Odd causal self-attention lengths go through the pad-to-lane wrapper
    (kernel coverage for s not divisible by 128, e.g. 1000)."""
    if not (_on_tpu() and causal):
        return False
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    return (sq == skv and sq > 128 and d in (64, 128, 256)
            and hq % hkv == 0)
