"""Host-side ragged batch building: native C++ with a numpy fallback.

The reference keeps this on the native side
(``inference/v2/ragged/csrc/fast_host_buffer.cpp`` builds the flattened
buffers its ragged kernels consume); here the same construction backs
``inference/ragged.py``'s SplitFuse step. The C++ path loads lazily via
the op_builder registry; environments without a toolchain fall back to
the equivalent numpy loops (bit-identical outputs — tested).
"""

from __future__ import annotations

import ctypes
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..utils.logging import logger

_LIB = None
_TRIED = False


def _lib():
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        try:
            from .op_builder import get_op_builder

            _LIB = get_op_builder("ds_ragged_host").load()
        except Exception as e:  # no toolchain / build failure: numpy path
            logger.warning(f"ds_ragged_host native build unavailable ({e}); "
                           "using numpy fallback")
            _LIB = None
    return _LIB


def packer() -> str:
    """Which implementation builds the step batches: "native" (the C++
    library, built on first use) or "numpy" (the fallback)."""
    return "native" if _lib() is not None else "numpy"


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def build_batch(chunks: Sequence[Sequence[int]], seens: Sequence[int],
                slots: Sequence[int], T: int, pad_slot: int = -1,
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Flatten scheduled per-sequence token chunks into the step batch.

    Returns (flat_tokens [T], flat_slot [T] (= pad_slot on unused lanes),
    flat_pos [T], last_index [n] — flat index of each chunk's final token).
    """
    n = len(chunks)
    lens = np.fromiter((len(c) for c in chunks), np.int32, count=n)
    offsets = np.zeros((n + 1,), np.int32)
    np.cumsum(lens, out=offsets[1:])
    if n and int(offsets[-1]) > T:
        raise ValueError(
            f"scheduled tokens {int(offsets[-1])} exceed batch width {T}")
    # one C-level conversion per chunk (not per token), then one concat
    concat = np.concatenate(
        [np.asarray(c, np.int32) for c in chunks]) if n else \
        np.zeros((0,), np.int32)
    seens = np.asarray(seens, np.int32)
    slots_a = np.asarray(slots, np.int32)
    flat_tokens = np.zeros((T,), np.int32)
    flat_slot = np.full((T,), pad_slot, np.int32)
    flat_pos = np.zeros((T,), np.int32)
    last_index = np.zeros((n,), np.int32)

    lib = _lib()
    if lib is not None:
        lib.ds_ragged_build_batch(
            np.int32(n), _i32p(concat), _i32p(offsets), _i32p(seens),
            _i32p(slots_a), _i32p(flat_tokens), _i32p(flat_slot),
            _i32p(flat_pos), _i32p(last_index))
        return flat_tokens, flat_slot, flat_pos, last_index

    cursor = 0
    for i in range(n):
        take = int(offsets[i + 1] - offsets[i])
        flat_tokens[cursor:cursor + take] = concat[offsets[i]:offsets[i + 1]]
        flat_slot[cursor:cursor + take] = slots_a[i]
        flat_pos[cursor:cursor + take] = np.arange(
            seens[i], seens[i] + take, dtype=np.int32)
        cursor += take
        last_index[i] = cursor - 1
    return flat_tokens, flat_slot, flat_pos, last_index


def fill_tables(block_lists: Sequence[Sequence[int]], slots: Sequence[int],
                max_seqs: int, max_pages: int) -> np.ndarray:
    """Scatter per-sequence block lists into the dense [max_seqs,
    max_pages] table (zero-padded rows). A sequence owning more than
    max_pages blocks is an engine invariant violation — raise loudly
    rather than truncate into silent wrong attention reads."""
    n = len(block_lists)
    tables = np.zeros((max_seqs, max_pages), np.int32)
    lens = np.fromiter((len(b) for b in block_lists), np.int32, count=n)
    if n and int(lens.max()) > max_pages:
        raise ValueError(
            f"sequence owns {int(lens.max())} blocks > max_pages {max_pages}")
    offsets = np.zeros((n + 1,), np.int32)
    np.cumsum(lens, out=offsets[1:])
    concat = np.concatenate(
        [np.asarray(b, np.int32) for b in block_lists]) if n else \
        np.zeros((0,), np.int32)
    slots_a = np.asarray(slots, np.int32)

    lib = _lib()
    if lib is not None:
        overflowed = lib.ds_ragged_fill_tables(
            np.int32(n), _i32p(concat), _i32p(offsets), _i32p(slots_a),
            np.int32(max_pages), _i32p(tables))
        if overflowed:  # unreachable past the pre-check; belt and braces
            raise ValueError(f"{overflowed} block lists exceed max_pages")
        return tables

    for i in range(n):
        blks = concat[offsets[i]:offsets[i + 1]]
        tables[slots_a[i], : len(blks)] = blks
    return tables
