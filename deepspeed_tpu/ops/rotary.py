"""Rotary position embeddings.

Replaces the reference's CUDA rotary kernels
(``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu`` and FastGen's
``linear_blocked_kv_rotary``). Pure jnp: XLA fuses the sin/cos modulation
into the QK projection epilogue.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_ramp(head_dim: int, theta: float, original: int, beta_fast: float,
              beta_slow: float):
    """YaRN's blend [head_dim/2], 1 where a frequency is kept and 0 where it
    is divided by the factor: a linear ramp between the correction
    dimensions, the dimensions whose wavelength turns ``beta_fast`` and
    ``beta_slow`` times over ``original`` positions (Peng et al. 2023;
    DeepSeek-V2's ``yarn_find_correction_range``)."""
    def correction_dim(turns):
        return head_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) \
        / max(high - low, 1e-3)
    return 1.0 - jnp.clip(ramp, 0.0, 1.0)


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     yarn=None):
    """Precompute [max_len, head_dim/2] angle table. ``yarn``: (factor,
    original positions, beta_fast, beta_slow), each frequency then blended
    with itself over the factor by :func:`yarn_ramp`."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is not None:
        factor, original, beta_fast, beta_slow = yarn
        keep = yarn_ramp(head_dim, theta, original, beta_fast, beta_slow)
        inv_freq = inv_freq / factor * (1.0 - keep) + inv_freq * keep
    t = jnp.arange(max_len, dtype=jnp.float32)
    return jnp.outer(t, inv_freq)  # [max_len, head_dim//2]


def apply_rotary(x, angles, positions=None, rotary_dim=None,
                 interleaved=False):
    """Apply RoPE. x: [..., seq, n_heads, head_dim]; angles:
    [max_len, rotary_dim/2]; positions: optional [..., seq] int32 (for
    KV-cache decode offsets).

    ``rotary_dim`` < head_dim rotates only the leading dims (GPT-NeoX
    ``rotary_pct``); ``interleaved`` uses the GPT-J pairing — (x[2i],
    x[2i+1]) rotate together — instead of the Llama/NeoX half-split."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
        xr = apply_rotary(xr, angles, positions, interleaved=interleaved)
        return jnp.concatenate([xr, xp], axis=-1)
    if positions is None:
        seq = x.shape[-3]
        ang = angles[:seq]  # [seq, rd/2]
        ang = ang[(None,) * (x.ndim - 3) + (slice(None), None, slice(None))]
    else:
        ang = angles[positions]  # [..., seq, rd/2]
        ang = ang[..., None, :]  # broadcast over heads
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        r1, r2 = x1 * cos - x2 * sin, x1 * sin + x2 * cos
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
    return out.astype(x.dtype)


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (Press et al. 2022; Bloom's position scheme —
    reference module_inject/containers/bloom.py consumes torch's
    build_alibi_tensor). Standard geometric construction incl. the
    non-power-of-two fixup."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        slopes = pow2_slopes(n_heads)
    else:
        base = 2 ** math.floor(math.log2(n_heads))
        slopes = pow2_slopes(base)
        extra = pow2_slopes(2 * base)[0::2][: n_heads - base]
        slopes += extra
    return jnp.asarray(slopes, jnp.float32)
