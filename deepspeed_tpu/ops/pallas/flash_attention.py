"""Blocked flash attention (Pallas TPU kernel), forward + backward.

Subsumes the reference's attention kernel surface: the fused training
softmax kernels (``csrc/transformer/softmax.cu``,
``general_kernels.cu``), the Evoformer CUTLASS fMHA
(``csrc/deepspeed4science/evoformer_attn/``), and the inference
``softmax_context`` path's core attention math
(``csrc/transformer/inference/csrc/softmax.cu``) — one online-softmax
kernel family instead of a per-era zoo.

Design (standard flash attention 2 on the MXU):
* forward: grid ``(batch, q_heads, q_blocks, kv_blocks)`` with the kv axis
  innermost; running row-max / row-sum / output accumulator live in VMEM
  scratch across kv steps; logits and softmax in fp32, output in the input
  dtype. Emits LSE (``m + log l``) residuals for the backward.
* causal masking skips fully-masked kv blocks via ``pl.when`` (no MXU work
  in the upper triangle) and applies the per-element mask on the diagonal
  blocks only.
* GQA/MQA: kv-head index derived in the BlockSpec index maps
  (``q_head // group``) — K/V are never materialized per-q-head in the
  forward.
* backward: two kernels — dq over ``(b, h, nq, nk)`` and dk/dv over
  ``(b, h, nk, nq)`` — both recompute probabilities from the LSE residual
  (flash-2 style: no stored attention matrix, ``delta = rowsum(dout*out)``
  precomputed outside).

Off-TPU the caller (``ops/attention.py``) uses the jnp reference path;
tests run these kernels in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: avoids NaN from (-inf)-(-inf)
LANES = 128


def _dot(a, b, dims):
    """MXU matmul, fp32 accumulation, at the operands' own precision.
    Pinned rather than left to ``jax_default_matmul_precision``: under
    "highest" every dot here would ask for an fp32 contract precision, and
    Mosaic refuses that on bf16 operands ("Bad lhs type")."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)


def _causal_mask(qi, ki, block_q: int, block_k: int, sq: int, skv: int,
                 window: int = 0):
    """[block_q, block_k] bool mask for the (qi, ki) tile; query positions are
    aligned to the END of the kv sequence (decode parity with
    ops/attention.py dot_product_attention). ``window`` > 0 additionally
    bands the mask to the trailing ``window`` keys (k > q - window)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + (skv - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    m = q_pos >= k_pos
    if window > 0:
        m = jnp.logical_and(m, k_pos > q_pos - window)
    return m


def _tile_runs(qi, ki, block_q: int, block_k: int, diag_offset: int,
               causal: bool, window: int):
    """Whether the (qi, ki) tile intersects the (banded) causal region:
    skip above the diagonal (causal) AND fully below the band (window)."""
    run = (not causal) or (ki * block_k <= qi * block_q + (block_q - 1) + diag_offset)
    if window > 0:
        run = jnp.logical_and(
            run, ki * block_k + (block_k - 1) > qi * block_q + diag_offset - window)
    return run


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                sq: int, skv: int, window: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # skip tiles above the causal diagonal / fully below the window band
    diag_offset = skv - sq
    run = _tile_runs(qi, ki, block_q, block_k, diag_offset, causal, window)

    @pl.when(run)
    def _step():
        # matmul inputs stay in the storage dtype (bf16 on the training
        # path): the MXU takes bf16 operands with fp32 accumulation natively;
        # upcasting first would force fp32 MXU passes (~8x slower)
        q = q_ref[0, 0]                              # [bq, d]
        k = k_ref[0, 0]                              # [bk, d]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal and window > 0:
            # banded tiles can be partial on both edges — mask every
            # running tile (windowed models only pay this)
            s = jnp.where(_causal_mask(qi, ki, block_q, block_k, sq, skv,
                                       window), s, NEG_INF)
        elif causal:
            # apply the element mask only on blocks crossing the diagonal
            partial = ki * block_k + (block_k - 1) > qi * block_q + diag_offset
            s = jnp.where(
                jnp.logical_and(partial,
                                jnp.logical_not(_causal_mask(qi, ki, block_q,
                                                             block_k, sq, skv))),
                NEG_INF, s)
        m_prev = m_scr[:, :1]                        # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [bq, bk]
        corr = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]                              # [bk, d]
        pv = _dot(p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _final():
        # fully-masked rows (possible when causal and skv < sq): m stays at
        # NEG_INF but p = exp(NEG_INF - NEG_INF) = 1 polluted l/acc, so
        # detect via m, zero the output, and push lse to +inf so the
        # backward's exp(s - lse) is 0 for these rows.
        masked = m_scr[:, :1] <= NEG_INF / 2
        l = l_scr[:, :1]
        l_safe = jnp.where(jnp.logical_or(masked, l == 0.0), 1.0, l)
        o_ref[0, 0] = jnp.where(masked, 0.0, acc_scr[:] / l_safe).astype(o_ref.dtype)
        # LSE is emitted lane-replicated as [block_q, LANES]: Mosaic requires
        # the last two block dims to tile (8, 128), so a rank-3 (1, 1, bq)
        # block is not lowerable; callers slice [..., 0].
        lse = jnp.where(masked, -NEG_INF, m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _kv_tile_clamp(causal: bool, window: int, block_q: int, block_k: int,
                   diag_offset: int):
    """Clamp a skipped tile's kv-block index onto the nearest RUNNING
    tile's index. Pallas elides the DMA when an input's block index
    repeats across grid steps, so tiles whose compute is pl.when-skipped
    (above the causal diagonal, or fully below the window band) stop
    costing K/V traffic too — the same dedup the paged kernel uses. For
    banded attention this turns K/V traffic from O(s^2/bk) into
    O(s * window / bk), matching the compute bound."""
    def clamp(qi, ki):
        j = ki
        if causal:
            # fully-masked q tiles (possible when skv < sq) make last_run
            # negative — pin to block 0, never a negative DMA index
            last_run = (qi * block_q + block_q - 1 + diag_offset) // block_k
            j = jnp.maximum(0, jnp.minimum(j, last_run))
        if window > 0:
            first_run = jnp.maximum(
                0, (qi * block_q + diag_offset - window + 1) // block_k)
            j = jnp.maximum(j, first_run)
        return j
    return clamp


def _q_tile_clamp(causal: bool, window: int, block_q: int, block_k: int,
                  diag_offset: int, nq: int):
    """The dkv-side twin of :func:`_kv_tile_clamp`: clamp a skipped tile's
    q-block index (derived from the fused (group, q_block) grid dim) onto
    the nearest RUNNING tile — same band inequalities solved for qi."""
    def clamp(ki, gq):
        qi = jax.lax.rem(gq, nq)
        if causal:
            # first running q tile for this kv block: qi*bq+bq-1+diag >= ki*bk
            qi = jnp.maximum(qi, jnp.maximum(
                0, (ki * block_k - diag_offset) // block_q))
        if window > 0:
            # last running q tile: qi*bq+diag-window < ki*bk+bk-1
            t = ki * block_k + block_k - 1 + window - diag_offset
            qi = jnp.minimum(qi, jnp.maximum(0, (t - 1) // block_q))
        return qi
    return clamp


def _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret,
                   window=0):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq, nk = sq // block_q, skv // block_k
    # [b, h, s, d] layout: heads as a grid axis, seq contiguous for tiling
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    clamp = _kv_tile_clamp(causal, window, block_q, block_k, skv - sq)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, sq=sq, skv=skv, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, clamp(qi, ki), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, clamp(qi, ki), 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale: float, causal: bool,
               block_q: int, block_k: int, sq: int, skv: int, window: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    diag_offset = skv - sq
    run = _tile_runs(qi, ki, block_q, block_k, diag_offset, causal, window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                              # storage dtype (bf16)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]                   # [bq, 1]
        delta = delta_ref[0, 0][:, :1]               # [bq, 1]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, block_q, block_k, sq, skv,
                                       window), s, NEG_INF)
        p = jnp.exp(s - lse)                         # [bq, bk] fp32
        dp = _dot(do, v, (((1,), (1,)), ((), ())))
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        acc_scr[:] += _dot(ds, k, (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale: float, causal: bool,
                block_q: int, block_k: int, sq: int, skv: int, nq: int,
                window: int):
    # last grid dim fuses (q-head group, q block): dk/dv accumulate across
    # the whole group in scratch without materializing per-q-head K/V
    ki, gq = pl.program_id(2), pl.program_id(3)
    n_gq = pl.num_programs(3)
    qi = jax.lax.rem(gq, nq)

    @pl.when(gq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    diag_offset = skv - sq
    run = _tile_runs(qi, ki, block_q, block_k, diag_offset, causal, window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                              # storage dtype (bf16)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = _dot(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, block_q, block_k, sq, skv,
                                       window), s, NEG_INF)
        p = jnp.exp(s - lse)                         # [bq, bk] fp32
        # dv += P^T @ dO
        dv_scr[:] += _dot(p.astype(do.dtype), do, (((0,), (0,)), ((), ())))
        dp = _dot(do, v, (((1,), (1,)), ((), ())))
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [bq, bk]
        # dk += dS^T @ Q
        dk_scr[:] += _dot(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(gq == n_gq - 1)
    def _final():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _seq_spec(block: int, d: int, index_map):
    return pl.BlockSpec((1, 1, block, d), index_map, memory_space=pltpu.VMEM)


def _row_spec(block: int, index_map):
    # Row statistics (LSE, delta) travel lane-replicated as
    # [..., block_q, LANES] — see _fwd_kernel._final for why.
    return pl.BlockSpec((1, 1, block, LANES), index_map,
                        memory_space=pltpu.VMEM)


def _flash_backward(q, k, v, out, lse, do, scale, causal, block_q, block_k,
                    interpret, window=0):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq, nk = sq // block_q, skv // block_k
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    dot = do.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)
    # lane-replicate row stats for Mosaic-tileable [bq, LANES] blocks
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))

    # dq: grid (b, q_head, q_block, kv_block); K/V indexed per kv-head group
    # (same trick as the forward — never expanded to q-heads). Skipped
    # tiles clamp their K/V index onto a running tile so they cost no DMA
    # (see _kv_tile_clamp).
    clamp = _kv_tile_clamp(causal, window, block_q, block_k, skv - sq)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sq=sq, skv=skv,
                          window=window),
        grid=(b, hq, nq, nk),
        in_specs=[
            _seq_spec(block_q, d, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            _seq_spec(block_k, d,
                      lambda bi, hi, qi, ki, g=group: (bi, hi // g, clamp(qi, ki), 0)),
            _seq_spec(block_k, d,
                      lambda bi, hi, qi, ki, g=group: (bi, hi // g, clamp(qi, ki), 0)),
            _seq_spec(block_q, d, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            _row_spec(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            _row_spec(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=_seq_spec(block_q, d, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # dk/dv: grid (b, kv_head, kv_block, group*q_block) — the fused last dim
    # walks every q-head of the group then every q block, accumulating into
    # one [block_k, d] scratch per kv head (no hq-sized dk/dv intermediates).
    # Skipped q tiles (above the diagonal for this kv block, or fully past
    # the window band) clamp their q-side index onto a running tile so
    # they cost no q/do/lse/delta DMA.
    def qhead(hk, gq, g=group):
        return hk * g + gq // nq

    q_clamp = _q_tile_clamp(causal, window, block_q, block_k, skv - sq, nq)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sq=sq, skv=skv,
                          nq=nq, window=window),
        grid=(b, hkv, nk, group * nq),
        in_specs=[
            _seq_spec(block_q, d,
                      lambda bi, hk, ki, gq: (bi, qhead(hk, gq), q_clamp(ki, gq), 0)),
            _seq_spec(block_k, d, lambda bi, hk, ki, gq: (bi, hk, ki, 0)),
            _seq_spec(block_k, d, lambda bi, hk, ki, gq: (bi, hk, ki, 0)),
            _seq_spec(block_q, d,
                      lambda bi, hk, ki, gq: (bi, qhead(hk, gq), q_clamp(ki, gq), 0)),
            _row_spec(block_q,
                      lambda bi, hk, ki, gq: (bi, qhead(hk, gq), q_clamp(ki, gq), 0)),
            _row_spec(block_q,
                      lambda bi, hk, ki, gq: (bi, qhead(hk, gq), q_clamp(ki, gq), 0)),
        ],
        out_specs=[
            _seq_spec(block_k, d, lambda bi, hk, ki, gq: (bi, hk, ki, 0)),
            _seq_spec(block_k, d, lambda bi, hk, ki, gq: (bi, hk, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False, window: int = 0):
    """q: [b, sq, hq, d]; k/v: [b, skv, hkv, d] -> [b, sq, hq, d].

    ``sq``/``skv`` must divide by the (clamped) block sizes; the dispatcher
    in ``ops/attention.py`` falls back to the jnp path otherwise.
    ``window`` > 0 (static, requires causal) bands attention to the
    trailing ``window`` keys: tiles fully below the band are skipped, so
    compute is O(s * window) instead of O(s^2 / 2) (Mistral sliding
    window).
    """
    assert window <= 0 or causal, "window requires causal attention"
    scale_v = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, _ = _flash_forward(q, k, v, scale_v, causal, block_q, block_k,
                            interpret, window)
    return out


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret, window):
    scale_v = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, lse = _flash_forward(q, k, v, scale_v, causal, block_q, block_k,
                              interpret, window)
    # Name the kernel residuals so remat policies can SAVE them:
    # checkpoint_dots ("selective") does not match a pallas_call, so under
    # plain selective remat the backward replays this whole forward kernel
    # per layer just to regenerate (out, lse). The "selective_flash" policy
    # (runtime/activation_checkpointing.py) saves these names instead —
    # one flash forward per layer per step, ~33 MB/layer at the bench
    # shape. q/k/v are projection dot outputs, already policy-saved.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    scale_v = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    dq, dk, dv = _flash_backward(q, k, v, out, lse, g, scale_v, causal,
                                 block_q, block_k, interpret, window)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_padded(q, k, v, causal: bool = True,
                           scale: Optional[float] = None,
                           block_q: int = 1024, block_k: int = 1024,
                           interpret: bool = False, window: int = 0):
    """Arbitrary-length causal SELF-attention via symmetric zero-padding to
    a lane multiple. Exact: with sq == skv and causal masking, a real query
    i attends keys <= i, so padded keys (> real length) are always masked
    out; padded query rows produce garbage that the final slice drops, and
    their cotangent is zero so dk/dv stay exact through the backward.
    (Banding by ``window`` composes: the band only removes keys.)"""
    assert causal and q.shape[1] == k.shape[1], \
        "padding trick requires causal self-attention (sq == skv)"
    s = q.shape[1]
    pad = (-s) % LANES
    if pad == 0:
        return flash_attention(q, k, v, causal, scale, block_q, block_k,
                               interpret, window)
    widths = ((0, 0), (0, pad), (0, 0), (0, 0))
    out = flash_attention(jnp.pad(q, widths), jnp.pad(k, widths),
                          jnp.pad(v, widths), causal, scale,
                          block_q, block_k, interpret, window)
    return out[:, :s]
