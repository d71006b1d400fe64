"""Hybrid engine: one model flipping between training and generation
(RLHF inner loop).

Parity with reference ``runtime/hybrid_engine.py:32``
(DeepSpeedHybridEngine — ``generate`` :174 runs inference with injected
kernels on the SAME weights ZeRO-3 trains, ``_zero3_forward`` :363 gathers
partitions for generation, LoRA fuse/unfuse :138-:152). The reference's
hard part — unpartitioning ZeRO-3 weights into inference containers and
back — is free in JAX: the training params ARE the inference params (same
arrays, different jitted programs); GSPMD re-lays them out per program.
So the hybrid engine is composition:

* ``train_batch`` / ``backward`` / ``step`` delegate to the TrainEngine;
* ``generate`` runs the decode program against the CURRENT fp32 master
  params cast to the inference dtype — no copy, no gather choreography,
  no separate weight store;
* the per-call cast is the only overhead (the analog of the reference's
  fuse/unfuse), and XLA dedupes it across decode steps within a call.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.engine import InferenceConfig, InferenceEngine
from ..inference.sampling import sample
from ..utils.logging import log_dist
from .engine import TrainEngine


class HybridEngine:
    """Wraps a TrainEngine; adds generate() on live training weights."""

    def __init__(self, train_engine: TrainEngine,
                 inference_config: Optional[InferenceConfig] = None):
        if train_engine.model is None:
            raise ValueError("HybridEngine needs a model-backed TrainEngine")
        self.engine = train_engine
        self.icfg = inference_config or InferenceConfig(
            dtype="bfloat16" if train_engine.config.bf16.enabled else "float32")
        self._prefill_fn = None
        self._decode_fn = None
        log_dist("HybridEngine: generation shares live training parameters")

    # -- training surface (delegation) ----------------------------------
    def train_batch(self, batch):
        return self.engine.train_batch(batch)

    def backward(self, batch):
        return self.engine.backward(batch)

    def step(self):
        return self.engine.step()

    @property
    def params(self):
        return self.engine.params

    # -- generation surface ---------------------------------------------
    def _infer_params(self):
        dtype = self.icfg.jnp_dtype
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating) else x,
            self.engine.params)

    def generate(self, input_ids, max_new_tokens: int = 64,
                 eos_token_id: Optional[int] = None) -> np.ndarray:
        """Decode with the current training weights (reference generate
        :174 — eval-mode forward through the injected containers)."""
        model = self.engine.model
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        max_len = s + max_new_tokens
        assert max_len <= model.config.max_seq_len

        if self._prefill_fn is None:
            def prefill(params, tokens, caches):
                logits, caches = model.apply(params, tokens, kv_caches=caches,
                                             cache_pos=0)
                return logits[:, -1, :], caches

            def decode(params, caches, last_tokens, cache_pos, rng):
                logits, caches = model.apply(
                    params, last_tokens[:, None],
                    positions=cache_pos[None, None],
                    kv_caches=caches, cache_pos=cache_pos)
                nxt = sample(logits[:, 0, :], rng, self.icfg.temperature,
                             self.icfg.top_k, self.icfg.top_p)
                return caches, nxt

            self._prefill_fn = jax.jit(prefill, donate_argnums=(2,))
            self._decode_fn = jax.jit(decode, donate_argnums=(1,))

        c = model.config
        params = self._infer_params()
        shape = (c.n_layers, b, max_len, c.n_kv_heads, c.head_dim)
        caches = (jnp.zeros(shape, self.icfg.jnp_dtype),
                  jnp.zeros(shape, self.icfg.jnp_dtype))
        rng = jax.random.PRNGKey(self.icfg.seed + self.engine.global_steps)
        logits, caches = self._prefill_fn(params, input_ids, caches)
        next_tok = sample(logits, rng, self.icfg.temperature,
                          self.icfg.top_k, self.icfg.top_p)
        out = [np.asarray(next_tok)]
        finished = np.zeros((b,), bool)
        if eos_token_id is not None:
            finished |= out[0] == eos_token_id
        for i in range(max_new_tokens - 1):
            if finished.all():
                break
            rng, sub = jax.random.split(rng)
            caches, next_tok = self._decode_fn(
                params, caches, next_tok, jnp.asarray(s + i, jnp.int32), sub)
            step_toks = np.asarray(next_tok)
            if eos_token_id is not None:
                step_toks = np.where(finished, eos_token_id, step_toks)
                finished |= step_toks == eos_token_id
                next_tok = jnp.asarray(step_toks)
            out.append(step_toks)
        return np.concatenate([np.asarray(input_ids), np.stack(out, 1)], axis=1)

    # reference API stubs kept for parity
    def fuse_lora_weight(self):
        log_dist("fuse_lora_weight: no-op (no separate inference weight store)")

    def unfuse_lora_weight(self):
        log_dist("unfuse_lora_weight: no-op")
