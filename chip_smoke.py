#!/usr/bin/env python3
"""chip_smoke.py — the main path on the chip, once, in one process.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 against ZeRO-0 only

The quickest proof that the system still starts on the accelerator: the
trainer takes a few steps and the server answers a few requests, through
the entry points a user calls (``dst.initialize`` / ``train_batch`` /
``train_steps``; ``RaggedInferenceEngine`` behind ``ServingEngine``), on
Mistral-7B-v0.1 at its published widths (hidden 4096, 32 query / 8 KV
heads of dim 128, feed-forward 14336, vocab 32000, RMSNorm, RoPE, SwiGLU).
No width is cut; depth is cut to what one 16 GB chip holds and printed;
weights are random, made from ``--seed``.

Contract: one JSON object per phase on stdout, then as the LAST line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Anything else is a failure with a non-zero exit code: no accelerator, a
``device_kind`` without published peaks, a phase that raised, a result
off its reference, or a Pallas kernel path that did not run. No fallback
to the CPU, no interpret mode, no child process that touches JAX, no
``try/except`` around a phase. Wall times here are fenced with
``block_until_ready`` but are smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# stated bf16 tolerances: kernel outputs against an fp32 "highest"
# reference, relative to the reference's largest magnitude; and the ragged
# engine's logits against model.apply's full forward (two bf16 programs)
KERNEL_REL_TOL = 0.03
# a recurrent layer's step (the delta rule's, Mamba-2's) is float32
# throughout; only its sums' order differs
STEP_REL_TOL = 1e-5
LOGITS_REL_TOL = 0.05
# ZeRO-3 on four chips against ZeRO-0 on one: same math, different
# reduction order in bf16 — per-step loss agreement
ZERO3_LOSS_TOL = 0.05


@dataclass
class Sizes:
    """What a run is sized by. Defaults: Mistral-7B-v0.1 published widths;
    depths sized with ``compiled.memory_analysis()`` for a described v5e
    (tests/test_tpu_compile.py compiles the same programs). The tier-1
    rehearsal (tests/test_chip_smoke_rehearsal.py) passes tiny ones."""

    vocab_size: int = 32000
    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 32768
    sliding_window: int = 4096
    norm_eps: float = 1e-5
    # train: ~16 B/param of fp32 master, gradients and AdamW state
    train_layers: int = 2
    train_batch: int = 2
    train_seq: int = 2048
    train_steps: int = 3          # engine.train_batch calls
    train_block: int = 4          # one engine.train_steps block
    # serve: bf16 weights + paged KV pool
    serve_layers: int = 16
    token_budget: int = 2048
    max_seqs: int = 64
    kv_block_size: int = 16
    max_context: int = 4096
    max_kv_blocks: int = 4096     # cap; sized below to the HBM left
    # the 16-layer step program's temporaries: 2.8 GB by memory_analysis()
    kv_reserve_bytes: int = 4 << 30
    prompt_lens: Tuple[int, ...] = (64, 200, 1500, 333, 700, 1100)
    shared_prefix: int = 512      # one extra pair of prompts shares this
    probe_len: int = 96           # the request checked against model.apply
    new_tokens: int = 32
    # kernels phase
    kernel_seq: int = 2048
    kernel_pages_per_seq: int = 128
    kernel_n_seqs: int = 64
    # a second head shape for the paged kernel: Olmo-Hybrid's full layers
    kernel_alt_heads: Tuple[int, int] = (30, 30)
    # KV heads of the leaves the row writer is checked at: Mistral's,
    # Ouro's, Olmo-Hybrid's
    kernel_writer_heads: Tuple[int, ...] = (8, 16, 30)
    # heads under 128 wide that share a 128-lane row of the pool:
    # granite-4.0-h-micro's (query heads, KV heads, head size), for the
    # paged kernel's tiled grid and the row writer
    kernel_shared_heads: Tuple[int, int, int] = (32, 8, 64)
    # the delta-rule step kernel's state: Olmo-Hybrid's (heads, key, value)
    kernel_delta_state: Tuple[int, int, int] = (30, 96, 192)
    # the state-space step kernel's: granite-4.0-h-micro's (heads, channels
    # a head, state channels, groups), on a leaf of four periods' runs
    kernel_ssd_state: Tuple[int, int, int, int] = (64, 64, 128, 1)
    kernel_ssd_periods: int = 4
    # the latent kernel's: A.X-K1's (heads, cache row, latent) in its
    # absorbed form, the row 512 + 64 values padded to whole lanes
    kernel_latent: Tuple[int, int, int] = (64, 640, 512)
    # ... and the pages of its longest context in `a.x-k1.docs` (16,128
    # tokens: 63 of the kernel's chunks), which kernel_pages_per_seq's 8
    # chunks never walk
    kernel_latent_pages: int = 1008
    # --chips 4: global batch, split four ways under ZeRO-3
    zero3_layers: int = 1
    zero3_batch: int = 4
    zero3_steps: int = 3

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def smoke_model(sz: Sizes, n_layers: int):
    """The smoke model at ``n_layers``: the translation
    ``checkpoint/hf.py`` makes of a ``mistral`` config.json, with random
    weights instead of a checkpoint."""
    from deepspeed_tpu.models import Llama

    return Llama("7b", vocab_size=sz.vocab_size, d_model=sz.d_model,
                 n_layers=n_layers, n_heads=sz.n_heads,
                 n_kv_heads=sz.n_kv_heads, d_ff=sz.d_ff,
                 max_seq_len=sz.max_seq_len,
                 attn_windows=(sz.sliding_window,) * n_layers,
                 norm_eps=sz.norm_eps, rope_theta=10000.0,
                 tie_embeddings=False, use_flash=True)


def _widths(sz: Sizes) -> Dict[str, int]:
    return {"d_model": sz.d_model, "n_heads": sz.n_heads,
            "n_kv_heads": sz.n_kv_heads, "head_dim": sz.head_dim,
            "d_ff": sz.d_ff, "vocab_size": sz.vocab_size}


def train_config(sz: Sizes, batch: int, zero_stage: int = 0) -> Dict[str, Any]:
    return {
        "train_batch_size": batch,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 2e-5, "weight_decay": 0.1}},
        "zero_optimization": {"stage": zero_stage,
                              "stage3_param_persistence_threshold": 0},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 1_000_000,
    }


# ----------------------------------------------------------------------
# reporting
def emit(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj), flush=True)


class CompileLedger:
    """Compile seconds per program from JAX's own monitoring events, and
    whether each came from the persistent cache (warm) or the compiler
    (cold). ``take()`` reports what happened since the last ``take()``."""

    def __init__(self):
        import jax

        self._programs: List[Tuple[str, float]] = []
        self._hits = self._misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self._programs.append((str(kw.get("fun_name", "?")), seconds))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._misses += 1

    def take(self) -> Dict[str, Any]:
        progs, hits, misses = self._programs, self._hits, self._misses
        self._programs, self._hits, self._misses = [], 0, 0
        slow: Dict[str, float] = {}
        for name, s in progs:
            if s >= 0.5:
                slow[name] = round(slow.get(name, 0.0) + s, 2)
        return {"programs": len(progs),
                "seconds": round(sum(s for _, s in progs), 2),
                "cache_hits": hits, "cache_misses": misses,
                "cache": ("off" if not hits + misses else
                          "warm" if not misses else
                          "cold" if not hits else "mixed"),
                "over_half_a_second": slow}


@contextlib.contextmanager
def phase(name: str, ledger: CompileLedger):
    """Names the phase in flight and emits its record when it ends. A
    failure is announced and re-raised — never swallowed."""
    import jax

    rec: Dict[str, Any] = {"phase": name}
    print(f"chip_smoke: phase {name!r} ...", file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    try:
        yield rec
    except BaseException:
        print(f"chip_smoke: phase {name!r} FAILED", file=sys.stderr,
              flush=True)
        raise
    rec["phase_seconds"] = round(time.perf_counter() - t0, 2)
    rec["compile"] = ledger.take()
    rec["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()]
    emit(rec)


def _dir_bytes(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(e.stat().st_size for e in os.scandir(path) if e.is_file())


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ----------------------------------------------------------------------
# phase: kernels — each Pallas kernel the two main phases use, against
# its jnp reference at these widths (first, so a miscompile is named
# before it shows up as a wrong loss)
def phase_kernels(sz: Sizes, seed: int, rec: Dict[str, Any],
                  interpret: bool = False) -> None:
    """``interpret`` exists for the CPU rehearsal only; main() never
    passes it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import dot_product_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.paged_attention import (
        heads_a_row, latent_attention, latent_attention_reference,
        paged_attention, paged_attention_reference, share_rows, work_list,
        write_kv_pages, write_kv_rows)

    hq, hkv, hd, S = sz.n_heads, sz.n_kv_heads, sz.head_dim, sz.kernel_seq
    kq, kk, kv, kw, kp = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(kq, (1, S, hq, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, S, hkv, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, S, hkv, hd), jnp.bfloat16)
    w = jax.random.normal(kw, (1, S, hq, hd), jnp.float32)

    # every array is an argument: a captured one is baked into the
    # program as a constant (first chip runs: 159 s of compile and 1 GB
    # executables for two captured pools; 90 MB cache entries for ``w``)
    def flash_loss(q, k, v, w):
        out = flash_attention(q, k, v, True, None, 1024, 1024, interpret)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def ref_loss(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            out = dot_product_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True)
        return jnp.sum(out * w), out

    grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))
    got_g, got_o = jax.block_until_ready(grad(flash_loss)(q, k, v, w))
    ref_g, ref_o = jax.block_until_ready(grad(ref_loss)(q, k, v, w))
    errs = {"flash_fwd": _rel_err(got_o, ref_o)}
    for name, g, r in zip(("dq", "dk", "dv"), got_g, ref_g):
        errs[f"flash_bwd_{name}"] = _rel_err(g, r)

    # paged attention, per-sequence tables + slot indirection (the ragged
    # engine's call shape) at the serving cells' head shapes: a SplitFuse
    # prefill chunk, a decode step, and a tick that holds both; then the
    # sliding-window band and the int8-KV pool on the Mistral shape
    from deepspeed_tpu.ops.quantizer import quantize_kv

    blk, mp, ns = sz.kv_block_size, sz.kernel_pages_per_seq, sz.kernel_n_seqs
    n_pages = ns * mp
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.permutation(n_pages).reshape(ns, mp), jnp.int32)
    ctx = mp * blk
    half = sz.token_budget // 2
    n_dec = ns // 3
    chunk = half - n_dec - half // 8       # lanes left over are not live
    shapes = {
        # two sequences, one mid-context chunk and one from position 0
        "paged_prefill": (
            np.repeat(np.array([3, 7], np.int32) % ns, half),
            np.concatenate([np.arange(ctx - half, ctx),
                            np.arange(half)]).astype(np.int32)),
        # one token per sequence at mixed context lengths
        "paged_decode": (np.arange(ns, dtype=np.int32),
                         rng.integers(0, ctx, (ns,)).astype(np.int32)),
        # decode lanes, then a prompt chunk mid-context, then lanes of no
        # sequence: what most ticks with a prompt in them look like
        "paged_mixed": (
            np.concatenate([rng.permutation(ns - 1)[:n_dec] + 1,
                            np.zeros(chunk), -np.ones(half - n_dec - chunk)]
                           ).astype(np.int32),
            np.concatenate([rng.integers(0, ctx, (n_dec,)),
                            ctx // 3 + np.arange(chunk),
                            np.zeros(half - n_dec - chunk)]).astype(np.int32)),
    }
    window = min(1024, ctx // 2)
    sq, skv, shd = sz.kernel_shared_heads
    variants = {"": (hq, hkv, hd, 0, False),
                "_h30": (*sz.kernel_alt_heads, hd, 0, False),
                f"_w{window}": (hq, hkv, hd, window, False),
                "_int8": (hq, hkv, hd, 0, True),
                # Granite's: the kernel reads two KV heads a 128-lane row
                # (the pool as kv_cache.pool_leaves lays it out), the
                # oracle the leaf a head a row
                f"_h{skv}x{shd}": (sq, skv, shd, 0, False)}

    def shared(pool):
        nkv, w = pool.shape[1], pool.shape[3]
        return share_rows(pool, nkv // heads_a_row(nkv, w))

    for tag, (nq, nkv, vhd, win, quant) in variants.items():
        kp1, kp2 = jax.random.split(jax.random.fold_in(kp, nkv))
        k_pool = jax.random.normal(kp1, (n_pages + 1, nkv, blk, vhd), jnp.bfloat16)
        v_pool = jax.random.normal(kp2, (n_pages + 1, nkv, blk, vhd), jnp.bfloat16)
        scales = ()
        if quant:
            (k_pool, ks), (v_pool, vs) = (quantize_kv(k_pool, 8),
                                          quantize_kv(v_pool, 8))
            scales = (ks, vs)
        kw = lambda sc: dict(k_scale=sc[0], v_scale=sc[1], kv_bits=8) \
            if sc else {}
        kernel = jax.jit(lambda q, s, p, kp, vp, tb, sc, win=win: paged_attention(
            q, kp, vp, tb, p, seq_slots=s, live_pages=mp, window=win,
            interpret=interpret, **kw(sc)))
        oracle = jax.jit(lambda q, s, p, kp, vp, tb, sc, win=win:
                         paged_attention_reference(q, kp, vp, tb[s], p,
                                                   window=win, **kw(sc)))
        for name, (slots, pos) in shapes.items():
            T = len(slots)
            qd = jax.random.normal(jax.random.fold_in(kq, T), (T, nq, vhd),
                                   jnp.bfloat16)
            got = kernel(qd, jnp.asarray(slots), jnp.asarray(pos),
                         *((k_pool, v_pool) if quant
                           else map(shared, (k_pool, v_pool))),
                         tables, scales)
            # the gather oracle materializes [lanes, ctx, heads, hd] in
            # fp32: check a 32-lane sample spread over the live lanes
            live = np.flatnonzero(slots >= 0)
            lanes = live[np.linspace(0, len(live) - 1, 32).astype(np.int32)]
            want = oracle(qd[lanes], jnp.asarray(slots[lanes]),
                          jnp.asarray(pos[lanes]), k_pool, v_pool, tables,
                          scales)
            errs[name + tag] = _rel_err(got[lanes], want)
            _check(bool(jnp.isfinite(got.astype(jnp.float32)).all()),
                   f"{name + tag}: non-finite kernel output")
    # latent attention (every head over one shared row a token, the sum
    # over the row's latent part) at A.X-K1's shape against its gather
    # oracle, on the same three kinds of lanes
    lh, lrow, lv = sz.kernel_latent
    lpool = jax.random.normal(jax.random.fold_in(kp, 4000),
                              (n_pages + 1, 1, blk, lrow), jnp.bfloat16)
    lscale = lrow ** -0.5
    lkernel = jax.jit(lambda q, s, p, pool, tb: latent_attention(
        q, pool, tb, p, s, scale=lscale, v_dim=lv, interpret=interpret))
    loracle = jax.jit(lambda q, s, p, pool, tb: latent_attention_reference(
        q, pool, tb[s], p, scale=lscale, v_dim=lv))
    # ... and a tick of the cell's: decode lanes at contexts in the upper
    # half of the longest document's, a question's chunk behind that
    # document, and lanes of no sequence, over page lists of that length
    lmp = sz.kernel_latent_pages
    nl = min(8, n_pages // lmp)
    lctx, asked = lmp * blk, min(200, lmp * blk // 8)
    dead = -(nl - 1 + asked) % 64
    lshapes = {name.replace("paged", "latent"): (*lanes, tables)
               for name, lanes in shapes.items()}
    lshapes["latent_long"] = (
        np.concatenate([np.arange(1, nl), np.zeros(asked), -np.ones(dead)]
                       ).astype(np.int32),
        np.concatenate([rng.integers(lctx // 2, lctx, (nl - 1,)),
                        lctx * 3 // 4 + np.arange(asked), np.zeros(dead)]
                       ).astype(np.int32),
        tables.reshape(-1)[:nl * lmp].reshape(nl, lmp))
    for name, (slots, pos, tbl) in lshapes.items():
        T = len(slots)
        qd = jax.random.normal(jax.random.fold_in(kq, 4000 + T),
                               (T, lh, lrow), jnp.bfloat16)
        got = lkernel(qd, jnp.asarray(slots), jnp.asarray(pos), lpool, tbl)
        live = np.flatnonzero(slots >= 0)
        lanes = live[np.linspace(0, len(live) - 1, 32).astype(np.int32)]
        want = loracle(qd[lanes], jnp.asarray(slots[lanes]),
                       jnp.asarray(pos[lanes]), lpool, tbl)
        errs[name] = _rel_err(got[lanes], want)
        _check(bool(jnp.isfinite(got.astype(jnp.float32)).all()),
               f"{name}: non-finite kernel output")
    # the row writer against the scatter it replaced, on a pool that held
    # other values, at the cells' KV-head counts and the same three kinds
    # of lanes: every page but the sink (which only the scatter writes)
    # bit for bit, K and V
    @jax.jit
    def scattered(k, v, nk, nv, s, p, tb):
        page = jnp.where(s >= 0, tb[jnp.maximum(s, 0), p // blk], n_pages)
        return (write_kv_rows(k, page, p % blk, nk),
                write_kv_rows(v, page, p % blk, nv))

    @jax.jit
    def written(k, v, nk, nv, s, p, tb):
        return write_kv_pages(k, v, nk, nv, tb, work_list(s, p, ns),
                              interpret=interpret)

    @jax.jit
    def unequal(got, want, was):
        return sum(jnp.sum(g[:n_pages] != w[:n_pages])
                   + jnp.sum(g[n_pages] != o[n_pages])
                   for g, w, o in zip(got, want, was))

    rows_off = {}
    for nkv, whd in [(n, hd) for n in sz.kernel_writer_heads] + [(skv, shd)]:
        keys = jax.random.split(jax.random.fold_in(kp, 1000 + nkv + whd), 4)
        pools = [shared(jax.random.normal(a, (n_pages + 1, nkv, blk, whd),
                                          jnp.bfloat16)) for a in keys[:2]]
        tag = f"_h{nkv}" + (f"x{whd}" if whd != hd else "")
        for name, (slots, pos) in shapes.items():
            new = [jax.random.normal(a, (len(slots), nkv, whd), jnp.bfloat16)
                   for a in keys[2:]]
            args = (*pools, *new, jnp.asarray(slots), jnp.asarray(pos), tables)
            rows_off[name.replace("paged", "rows") + tag] = int(
                unequal(written(*args), scattered(*args), pools))
    # the delta-rule step kernel against ``delta_step`` in XLA on the same
    # rows: two slots in three decode (every fourth from zeros), the others
    # and the sink keep their bits
    from deepspeed_tpu.ops import gated_delta
    from deepspeed_tpu.ops.pallas.gated_delta import delta_step_slots

    dh, dk, dv = sz.kernel_delta_state
    dkeys = jax.random.split(jax.random.fold_in(kp, 2000), 6)
    stepping = np.flatnonzero(np.arange(ns) % 3 != 1).astype(np.int32)
    nd = len(stepping)
    dpos = np.where(np.arange(nd) % 4 == 0, 0, 7).astype(np.int32)
    runs = jax.jit(gated_delta.runs_of, static_argnums=2)(
        jnp.asarray(stepping), jnp.asarray(dpos), ns)
    dq, dkk = (gated_delta.l2norm(jax.random.normal(a, (nd, dh, dk)))
               for a in dkeys[:2])
    drows = (dq * dk ** -0.5, dkk,
             jax.random.normal(dkeys[2], (nd, dh, dv)),
             -jax.nn.softplus(jax.random.normal(dkeys[3], (nd, dh))),
             2 * jax.nn.sigmoid(jax.random.normal(dkeys[4], (nd, dh))))
    dstate = jax.random.normal(dkeys[5], (ns + 1, dh, dk, dv))
    got_o, got_s = delta_step_slots(*drows, dstate, runs.steps,
                                    interpret=interpret)
    want_o, want_s = jax.jit(gated_delta.delta_step)(
        *drows, jnp.where(jnp.asarray(dpos == 0)[:, None, None, None], 0.0,
                          dstate[stepping]))
    delta_errs = {"delta_step_o": _rel_err(got_o, want_o),
                  "delta_step_state": _rel_err(got_s[stepping], want_s)}
    idle = np.setdiff1d(np.arange(ns + 1), stepping)
    state_off = int(jnp.sum(got_s[idle] != dstate[idle]))
    errs.update(delta_errs)
    # the state-space step kernel against ``ssd_step`` in XLA on the same
    # rows and the same two slots in three, in the third period's run of a
    # rolled leaf (``base`` an argument of the jitted call, so traced):
    # every other row of the leaf, the other periods' too, keeps its bits
    from deepspeed_tpu.ops import mamba2
    from deepspeed_tpu.ops.pallas.mamba2 import ssd_step_slots

    sh, sp, sn, sg = sz.kernel_ssd_state
    periods = sz.kernel_ssd_periods
    base = (periods - 2) * (ns + 1)
    skeys = jax.random.split(jax.random.fold_in(kp, 3000), 6)
    sdt = jax.nn.softplus(jax.random.normal(skeys[3], (nd, sh)))
    srows = (jax.random.normal(skeys[0], (nd, sh, sp)),
             jax.random.normal(skeys[1], (nd, sg, sn)),
             jax.random.normal(skeys[2], (nd, sg, sn)), sdt,
             -jnp.exp(0.3 * jax.random.normal(skeys[4], (sh,))) * sdt)
    sD = jnp.linspace(0.5, 1.5, sh)
    sstate = jax.random.normal(skeys[5], (periods * (ns + 1), sh, sp, sn))
    got_y, got_ss = ssd_step_slots(*srows, sD, sstate, runs.steps,
                                   jnp.int32(base), interpret=interpret)
    per_head = lambda a: jnp.repeat(a, sh // sg, axis=1)
    want_y, want_ss = jax.jit(mamba2.ssd_step)(
        srows[0], per_head(srows[1]), per_head(srows[2]), *srows[3:], sD,
        jnp.where(jnp.asarray(dpos == 0)[:, None, None, None], 0.0,
                  sstate[base + stepping]))
    ssd_errs = {"ssd_step_y": _rel_err(got_y, want_y),
                "ssd_step_state": _rel_err(got_ss[base + stepping], want_ss)}
    sidle = np.setdiff1d(np.arange(periods * (ns + 1)), base + stepping)
    ssd_off = int(jnp.sum(got_ss[sidle] != sstate[sidle]))
    errs.update(ssd_errs)
    rec.update(shape={"flash": [1, S, f"{hq}/{hkv}", hd],
                      "delta_state": [ns + 1, dh, dk, dv],
                      "ssd_state": [periods * (ns + 1), sh, sp, sn],
                      "latent": [lh, lrow, lv],
                      "latent_long": {"lanes": len(lshapes["latent_long"][0]),
                                      "seqs": nl, "context": lctx},
                      "paged": {n: len(s[0]) for n, s in shapes.items()},
                      "paged_variants": {t or "bf16": list(v[:3])
                                         for t, v in variants.items()},
                      "pages_per_seq": mp, "kv_block": blk},
               rel_err={n: round(e, 5) for n, e in errs.items()},
               tolerance=KERNEL_REL_TOL, rows_unequal=rows_off)
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_REL_TOL}
    _check(not bad, f"kernels off their jnp reference: {bad}")
    _check(not any(rows_off.values()),
           f"write_kv_pages is not bit-equal to the scatter: {rows_off}")
    rec.update(state_unequal=state_off)
    _check(max(delta_errs.values()) <= STEP_REL_TOL,
           f"the delta-rule step kernel is off delta_step: {delta_errs}")
    _check(state_off == 0, f"the delta-rule step kernel changed {state_off} "
           f"elements of slots that do not decode")
    rec.update(ssd_state_unequal=ssd_off)
    _check(max(ssd_errs.values()) <= STEP_REL_TOL,
           f"the state-space step kernel is off ssd_step: {ssd_errs}")
    _check(ssd_off == 0, f"the state-space step kernel changed {ssd_off} "
           f"elements of slots that do not decode")


# ----------------------------------------------------------------------
# phase: train
def _train_batch(sz: Sizes, seed: int, batch: int, topo):
    import numpy as np

    from deepspeed_tpu.runtime.dataloader import shard_batch

    tokens = np.random.default_rng(seed).integers(
        0, sz.vocab_size, (batch, sz.train_seq)).astype(np.int32)
    return shard_batch({"input_ids": tokens}, topo)


def _fenced_steps(engine, batch, n: int) -> Tuple[List[float], List[float]]:
    import jax

    losses, ms = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        jax.block_until_ready(m["loss"])
        ms.append(round((time.perf_counter() - t0) * 1e3, 1))
        losses.append(float(m["loss"]))
    return losses, ms


def _check_losses(losses: Sequence[float], vocab: int) -> None:
    _check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    _check(abs(losses[0] - math.log(vocab)) < 1.0,
           f"first loss {losses[0]:.3f} is not near ln(vocab)="
           f"{math.log(vocab):.3f}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def phase_train(sz: Sizes, seed: int, rec: Dict[str, Any]) -> None:
    import jax

    import deepspeed_tpu as dst
    from deepspeed_tpu.ops.attention import DISPATCH
    from deepspeed_tpu.parallel import mesh as mesh_mod

    DISPATCH.clear()
    mesh_mod.reset_topology()
    model = smoke_model(sz, sz.train_layers)
    engine, _, _, _ = dst.initialize(
        model=model, config=train_config(sz, sz.train_batch),
        rng=jax.random.PRNGKey(seed))
    batch = _train_batch(sz, seed, sz.train_batch, engine.topo)
    t0 = time.perf_counter()
    _check(engine.warmup(batch), "AOT warmup of the train step failed")
    warmup_s = time.perf_counter() - t0
    step_hlo = engine._train_step_aot.as_text()
    losses, step_ms = _fenced_steps(engine, batch, sz.train_steps)
    t0 = time.perf_counter()
    out = engine.train_steps([batch] * sz.train_block)
    block = [float(l) for l in jax.block_until_ready(out["losses"])]
    block_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = engine.train_steps([batch] * sz.train_block)
    block += [float(l) for l in jax.block_until_ready(out["losses"])]
    block_ms = (time.perf_counter() - t0) * 1e3 / sz.train_block
    rec.update(
        widths=_widths(sz), n_layers=sz.train_layers,
        params=model.config.param_count(),
        batch=[sz.train_batch, sz.train_seq],
        attention_dispatch=dict(DISPATCH),
        tpu_custom_calls_in_step=step_hlo.count("tpu_custom_call"),
        warmup_lower_and_compile_s=round(warmup_s, 2),
        train_batch_losses=[round(l, 4) for l in losses],
        train_batch_ms_smoke_timing=step_ms,
        train_steps_losses=[round(l, 4) for l in block],
        train_steps_first_block_s=round(block_s, 2),
        train_steps_ms_per_step_smoke_timing=round(block_ms, 1))
    _check_losses(losses + block, sz.vocab_size)
    engine.close()
    del engine, out, batch
    gc.collect()


def require_flash_kernel(rec: Dict[str, Any]) -> None:
    d = rec["attention_dispatch"]
    _check(d.get("flash_pallas", 0) > 0 and not d.get("flash_jnp")
           and rec["tpu_custom_calls_in_step"] > 0,
           f"train did not run the Pallas flash kernel: dispatch={d}, "
           f"tpu_custom_call x{rec['tpu_custom_calls_in_step']}")


# ----------------------------------------------------------------------
# phase: serve
def _prompts(sz: Sizes, seed: int) -> List[List[int]]:
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    draw = lambda n: rng.integers(1, sz.vocab_size, (n,)).tolist()
    prompts = [draw(n) for n in sz.prompt_lens]
    prefix = draw(sz.shared_prefix)
    return prompts + [prefix + draw(100), prefix + draw(60)]


def _serve_once(sz: Sizes, model, params, n_kv_blocks: int,
                prompts: List[List[int]]) -> Dict[str, Any]:
    """A fresh engine and server over ``params``: every request through
    submit (one through stream), greedy, then drain and the page audit."""
    from deepspeed_tpu.inference.kv_cache import assert_block_balance
    from deepspeed_tpu.inference.ragged import (
        RaggedConfig,
        RaggedInferenceEngine,
    )
    from deepspeed_tpu.serving import ServingEngine

    engine = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=sz.token_budget,
                            max_seqs=sz.max_seqs,
                            kv_block_size=sz.kv_block_size,
                            n_kv_blocks=n_kv_blocks,
                            max_context=sz.max_context,
                            enable_prefix_cache=True),
        params=params)
    server = ServingEngine(engine, {"policy": "slo"})
    t0 = time.perf_counter()
    reqs = [server.submit(p, max_new_tokens=sz.new_tokens)
            for p in prompts[1:]]
    streamed = list(server.stream(prompts[0], max_new_tokens=sz.new_tokens))
    streams = [streamed] + [r.result(timeout=900) for r in reqs]
    wall = time.perf_counter() - t0
    _check(server.drain(timeout=60), "drain() left requests unfinished")
    server.close()
    assert_block_balance(engine)
    held_by_seqs = sum(len(s.blocks) for s in engine.seqs.values())
    engine.prefix_cache.drop_all(engine.allocator)
    out = {"streams": streams, "wall_s": wall,
           "pages_held_by_sequences": held_by_seqs,
           "pages_free_after_cache_drop": engine.allocator.free_blocks,
           "attention_path": engine.attention_path}
    del server, engine
    gc.collect()
    return out


def phase_serve(sz: Sizes, seed: int, rec: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.kv_cache import kv_blocks_for_bytes
    from deepspeed_tpu.inference.ragged import (
        RaggedConfig,
        RaggedInferenceEngine,
    )
    from deepspeed_tpu.ops import ragged_host
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_topology()
    model = smoke_model(sz, sz.serve_layers)
    params = jax.block_until_ready(jax.jit(
        lambda key: model.init(key, dtype=jnp.bfloat16))(
            jax.random.PRNGKey(seed)))
    rcfg = RaggedConfig(kv_block_size=sz.kv_block_size)
    stats = jax.devices()[0].memory_stats()
    n_kv_blocks = sz.max_kv_blocks
    if stats:  # size the pool to the HBM the weights left
        left = stats["bytes_limit"] - stats["bytes_in_use"] \
            - sz.kv_reserve_bytes
        n_kv_blocks = min(n_kv_blocks,
                          kv_blocks_for_bytes(left, model.config, rcfg))
    prompts = _prompts(sz, seed)
    need = sum(-(-(len(p) + sz.new_tokens) // sz.kv_block_size)
               for p in prompts)
    _check(n_kv_blocks >= need,
           f"KV pool of {n_kv_blocks} pages cannot hold the requests "
           f"({need} pages)")

    # prefill-then-decode logits of one short request against the model's
    # own full forward (model.apply: scan over layers, dense attention)
    probe = np.random.default_rng(seed + 2).integers(
        1, sz.vocab_size, (sz.probe_len,)).tolist()
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=sz.token_budget,
                            max_seqs=sz.max_seqs,
                            kv_block_size=sz.kv_block_size,
                            n_kv_blocks=256, max_context=sz.max_context),
        params=params)
    prefill = eng.put([1], [probe])[0]
    first_tok = nxt = int(np.argmax(prefill))
    decode = eng.put([1], [[nxt]])[0]
    tick_ms = []  # put() ends in a host fetch of the logits: fenced
    for _ in range(4):
        t0 = time.perf_counter()
        tok = int(np.argmax(eng.put([1], [[nxt]])[0]))
        tick_ms.append(round((time.perf_counter() - t0) * 1e3, 1))
        nxt = tok
    eng.flush([1])
    full = np.asarray(jax.jit(model.apply)(
        params, jnp.asarray([probe + [first_tok]], jnp.int32)))[0]
    logit_err = {"prefill": _rel_err(prefill, full[-2]),
                 "decode": _rel_err(decode, full[-1])}
    del eng
    gc.collect()

    first = _serve_once(sz, model, params, n_kv_blocks, prompts)
    second = _serve_once(sz, model, params, n_kv_blocks, prompts)
    rec.update(
        widths=_widths(sz), n_layers=sz.serve_layers,
        params=model.config.param_count(), dtype="bfloat16",
        kv_pool={"pages": n_kv_blocks, "block": sz.kv_block_size,
                 "tokens": n_kv_blocks * sz.kv_block_size},
        token_budget=sz.token_budget, max_seqs=sz.max_seqs,
        max_context=sz.max_context,
        requests=len(prompts), prompt_lens=[len(p) for p in prompts],
        new_tokens=sz.new_tokens,
        attention_path=first["attention_path"],
        host_packer=ragged_host.packer(),
        logits_rel_err_vs_model_apply={k: round(v, 5)
                                       for k, v in logit_err.items()},
        logits_tolerance=LOGITS_REL_TOL,
        decode_tick_ms_one_sequence_smoke_timing=tick_ms,
        first_engine_s_with_compiles=round(first["wall_s"], 2),
        second_engine_s_programs_from_cache=round(second["wall_s"], 2),
        pages_held_by_sequences_after_drain=first["pages_held_by_sequences"],
        pages_free_after_cache_drop=first["pages_free_after_cache_drop"])
    for run in (first, second):
        _check(all(len(s) == sz.new_tokens for s in run["streams"]),
               f"a request ended short of {sz.new_tokens} tokens: "
               f"{[len(s) for s in run['streams']]}")
        _check(run["pages_held_by_sequences"] == 0
               and run["pages_free_after_cache_drop"] == n_kv_blocks,
               f"KV pages leaked after drain: {run}")
    _check(first["streams"] == second["streams"],
           "a second engine over the same weights gave different streams")
    bad = {k: v for k, v in logit_err.items() if not v <= LOGITS_REL_TOL}
    _check(not bad, f"ragged logits off model.apply: {bad}")
    del params
    gc.collect()


def require_paged_kernel(rec: Dict[str, Any]) -> None:
    _check(rec["attention_path"] == "pallas",
           f"serve did not run the Pallas paged kernel: "
           f"attention_path={rec['attention_path']!r}")


# ----------------------------------------------------------------------
# phase: zero3 (--chips 4) — ZeRO-3 over data=4 against ZeRO-0 on one
# device: same model, seed and global batch, in this one process
def _zero_run(sz: Sizes, seed: int, mesh_sizes: Dict[str, int],
              zero_stage: int) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu as dst
    from deepspeed_tpu.ops.attention import DISPATCH
    from deepspeed_tpu.parallel import mesh as mesh_mod

    DISPATCH.clear()
    mesh_mod.reset_topology()
    topo = mesh_mod.Topology.build_virtual(mesh_sizes)
    model = smoke_model(sz, sz.zero3_layers)
    engine, _, _, _ = dst.initialize(
        model=model, config=train_config(sz, sz.zero3_batch, zero_stage),
        topology=topo, rng=jax.random.PRNGKey(seed))
    batch = _train_batch(sz, seed, sz.zero3_batch, engine.topo)
    _check(engine.warmup(batch), "AOT warmup of the train step failed")
    hlo = engine._train_step_aot.as_text()
    losses, step_ms = _fenced_steps(engine, batch, sz.zero3_steps)
    state = [x for x in jax.tree_util.tree_leaves(
        (engine.params, engine.opt_state)) if getattr(x, "ndim", 0) >= 1]
    distinct_shards = [len({str(s.index) for s in x.addressable_shards})
                       for x in state]
    state_bytes = [0] * len(jax.devices())
    for x in state:
        for s in x.addressable_shards:
            state_bytes[s.device.id] += s.data.nbytes
    out = {
        "mesh": mesh_sizes, "zero_stage": zero_stage,
        "losses": [round(l, 4) for l in losses],
        "step_ms_smoke_timing": step_ms,
        "attention_dispatch": dict(DISPATCH),
        "hlo": {op: hlo.count(op) for op in
                ("all-gather", "reduce-scatter", "all-reduce",
                 "tpu_custom_call")},
        "state_leaves": len(state),
        "fewest_distinct_shards_of_a_leaf": min(distinct_shards),
        "state_bytes_per_device": state_bytes,
        "bytes_in_use_per_device": [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()],
        "params": model.config.param_count(),
    }
    engine.close()
    del engine, batch, state
    gc.collect()
    return out


def phase_zero3(sz: Sizes, seed: int, rec: Dict[str, Any]) -> None:
    one = _zero_run(sz, seed, {"data": 1}, zero_stage=0)
    four = _zero_run(sz, seed, {"data": 4}, zero_stage=3)
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    rec.update(widths=_widths(sz), n_layers=sz.zero3_layers,
               global_batch=[sz.zero3_batch, sz.train_seq],
               zero0_one_device=one, zero3_four_devices=four,
               loss_abs_diff_per_step=[round(d, 4) for d in diffs],
               loss_tolerance=ZERO3_LOSS_TOL)
    _check_losses(one["losses"], sz.vocab_size)
    _check_losses(four["losses"], sz.vocab_size)
    _check(max(diffs) <= ZERO3_LOSS_TOL,
           f"ZeRO-3 x4 losses left the ZeRO-0 x1 run: {diffs}")
    _check(four["fewest_distinct_shards_of_a_leaf"] == 4,
           "a parameter or optimizer leaf is not sharded over four devices")
    total = sum(one["state_bytes_per_device"])
    worst = max(four["state_bytes_per_device"])
    _check(worst <= 0.30 * total,
           f"state piled up on one chip: {four['state_bytes_per_device']} "
           f"of {total} bytes")
    rec["state_share_of_fullest_device"] = round(worst / total, 4)


def require_zero3_program(rec: Dict[str, Any]) -> None:
    four = rec["zero3_four_devices"]
    hlo, d = four["hlo"], four["attention_dispatch"]
    _check(hlo["all-gather"] > 0 and hlo["reduce-scatter"] > 0,
           f"the ZeRO-3 step shows no all-gather/reduce-scatter: {hlo}")
    _check(hlo["tpu_custom_call"] > 0 and d.get("flash_pallas", 0) > 0
           and not d.get("flash_jnp"),
           f"the flash kernel is not inside the sharded step: {hlo}, {d}")
    used, alone = four["bytes_in_use_per_device"], \
        rec["zero0_one_device"]["bytes_in_use_per_device"][0]
    _check(max(used) <= 0.35 * alone,
           f"bytes_in_use per chip {used} is not about a quarter of the "
           f"one-chip run's {alone}")


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ZeRO-3 phase and its one-device "
                         "comparison, across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX reports platform "
                 f"{dev.platform!r} ({dev.device_kind}); this script runs "
                 f"on the accelerator or not at all")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX reports "
                 f"{len(devices)} device(s)")

    from deepspeed_tpu.profiling.flops_profiler import device_peaks
    from deepspeed_tpu.runtime.compile_cache import place_compile_cache

    peaks = device_peaks(dev)  # raises for a TPU kind without peaks
    cache_dir = place_compile_cache(
        default_dir=os.path.join(HERE, ".jax_cache"))
    ledger = CompileLedger()
    emit({"phase": "env", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__,
          "libtpu": importlib.metadata.version("libtpu"),
          "device_kind": dev.device_kind, "devices": len(devices),
          "peaks": peaks, "seed": args.seed,
          "compile_cache_dir": cache_dir,
          # an LRU cap (JAX_COMPILATION_CACHE_MAX_SIZE) below what one run
          # writes evicts every entry before its reuse: a rerun stays cold
          "compile_cache_max_bytes": jax.config.jax_compilation_cache_max_size,
          "compile_cache_bytes_at_start": _dir_bytes(cache_dir)})

    sz = Sizes()
    if args.chips == 4:
        with phase("zero3", ledger) as rec:
            phase_zero3(sz, args.seed, rec)
            require_zero3_program(rec)
    else:
        with phase("kernels", ledger) as rec:
            phase_kernels(sz, args.seed, rec)
        with phase("train", ledger) as rec:
            phase_train(sz, args.seed, rec)
            require_flash_kernel(rec)
        with phase("serve", ledger) as rec:
            phase_serve(sz, args.seed, rec)
            require_paged_kernel(rec)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
