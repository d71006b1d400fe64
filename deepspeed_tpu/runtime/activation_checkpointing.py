"""Activation checkpointing (rematerialization).

Parity with the reference's Megatron-compatible
``runtime/activation_checkpointing/checkpointing.py`` (``checkpoint()``
:485, ``configure()`` :1065, partitioned/CPU-checkpointed activations,
``CudaRNGStatesTracker`` :122). On TPU the whole subsystem maps onto
``jax.checkpoint`` policies:

* ``checkpoint(fn)``                    -> ``jax.checkpoint`` (recompute in bwd)
* partition_activations across MP ranks -> a sharding constraint on the
  saved residuals (GSPMD shards what IS saved; nothing to partition by hand)
* cpu_checkpointing                     -> ``offload_checkpoint`` policy
  (saved residuals parked in host memory)
* contiguous_memory_optimization       -> n/a (XLA's allocator)
* RNG-state tracking                   -> n/a (functional PRNG keys thread
  through ``fn`` explicitly; replaying is deterministic by construction)

``selective`` policy implements "checkpoint everything except matmul
outputs" (jax's ``checkpoint_dots``) — the sweet spot on TPU where
recomputing elementwise ops is free but recomputing MXU work is not.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from ..config import ActivationCheckpointingConfig
from ..utils.logging import log_dist

_POLICIES = {
    "full": None,  # save nothing, recompute all
    "selective": jax.checkpoint_policies.checkpoint_dots,
    # selective + the flash kernel's named residuals (out, lse): without
    # this, checkpoint_dots can't see inside the opaque pallas_call and
    # the backward replays the whole flash forward per layer (one extra
    # fwd-attention pass per layer per step) just to rebuild them
    "selective_flash": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots,
        jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")),
    "dots_with_no_batch_dims": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "nothing": jax.checkpoint_policies.everything_saveable,
}

_config = ActivationCheckpointingConfig()
_configured = False


def configure(config: Optional[ActivationCheckpointingConfig] = None, **kwargs) -> None:
    """Reference configure() parity: set the process-wide default policy."""
    global _config, _configured
    if config is not None:
        _config = config
    for k, v in kwargs.items():
        if hasattr(_config, k):
            setattr(_config, k, v)
    _configured = True
    log_dist(f"activation checkpointing configured: {_config}")


def is_configured() -> bool:
    return _configured


def checkpoint(fn: Callable, *args, policy: Optional[str] = None,
               offload: Optional[bool] = None) -> Any:
    """Reference ``checkpoint(function, *args)`` parity: run ``fn`` under
    remat. When called with args, applies immediately (Megatron style);
    with no args, returns the wrapped function."""
    wrapped = checkpoint_wrapper(fn, policy=policy, offload=offload)
    if args:
        return wrapped(*args)
    return wrapped


def checkpoint_wrapper(fn: Callable, policy: Optional[str] = None,
                       offload: Optional[bool] = None) -> Callable:
    policy = policy if policy is not None else _config.policy
    if policy in (None, "none"):
        return fn
    offload = offload if offload is not None else _config.cpu_checkpointing
    if offload:
        from .engine import host_memory_kind

        # matmul outputs (no batch dims) are saved to host memory instead
        # of being recomputed; everything else rematerializes
        pol = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", host_memory_kind())
        return jax.checkpoint(fn, policy=pol)
    if policy not in _POLICIES:
        raise ValueError(f"unknown remat policy {policy!r}; have {sorted(_POLICIES)}")
    pol = _POLICIES[policy]
    return jax.checkpoint(fn, policy=pol) if pol is not None else jax.checkpoint(fn)


# Megatron-parity aliases (reference exposes these module-level)
def model_parallel_cuda_manual_seed(seed: int) -> None:
    """No-op shim: JAX PRNG keys are explicit; kept for API parity with
    megatron-style callers (reference checkpointing.py RNG tracker)."""
    log_dist(f"model_parallel_cuda_manual_seed({seed}): functional PRNG — no-op")


def get_rng_state_tracker():
    return None
