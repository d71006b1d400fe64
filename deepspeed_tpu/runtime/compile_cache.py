"""Persistent XLA compilation-cache placement (docs/performance.md).

JAX ships a content-addressed on-disk cache of compiled executables; with
it enabled, time-to-first-step across process restarts (elastic resume,
preemption comebacks, dev iteration) drops from a full XLA compile to a
cache deserialize. The directory is part of the cache key, so it has to
be the same path on every run: :func:`place_compile_cache` is the one
place that decides it, for the engine and the repo's entry points alike.

The cache also turns AOT warmup (``TrainEngine.warmup``) into a strict
win even when the jit call path later re-requests the program: the warmup
compile writes the cache entry and the jit call reads it back instead of
compiling a second time.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from ..utils.logging import logger

_LOCK = threading.Lock()
_PLACED_DIR: Optional[str] = None


def place_compile_cache(config_dir: Optional[str] = None,
                        default_dir: Optional[str] = None) -> Optional[str]:
    """Decide where JAX's persistent compilation cache lives and return
    that directory (None = no cache).

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, so no code
    sets the directory and ``config_dir`` (``compile.cache_dir``) is
    ignored with one log line. Otherwise ``config_dir`` if given, else
    ``default_dir`` — the fixed ``<checkout>/.jax_cache`` the repo's own
    entry points (``chip_smoke.py``, ``benchmarks/run.py``) pass. Every program is
    cached, however small or quick to compile. Idempotent: the first
    placement of the process stands."""
    global _PLACED_DIR
    with _LOCK:
        if _PLACED_DIR is not None:
            return _PLACED_DIR
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env_dir:
            if config_dir and config_dir != env_dir:
                logger.info(f"compile.cache_dir={config_dir} ignored: "
                            f"JAX_COMPILATION_CACHE_DIR={env_dir} is set")
            cache_dir = env_dir
        else:
            cache_dir = config_dir or default_dir
            if not cache_dir:
                return None
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # the operations' metadata (jax.named_scope: embed, attn, ffn, ...)
        # is part of the key, a fixed setting: by default JAX leaves it
        # out, and a cache that holds the same program from before a scope
        # was added or renamed hands back an executable whose operations
        # carry the old names into every profiler trace (PERF.md, PR 25)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        # JAX latches the cache as initialized-disabled at the FIRST compile
        # of the process; any compile before this call (sharded param init,
        # another engine) would make the updates above a silent no-op.
        # Resetting makes the next compile re-initialize against them.
        compilation_cache.reset_cache()
        _PLACED_DIR = cache_dir
        logger.info(f"persistent XLA compilation cache at {cache_dir}")
        return cache_dir
