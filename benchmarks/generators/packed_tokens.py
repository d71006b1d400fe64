"""Packed pre-training batches: every row full, token ids uniform from the
seed; batch ``step`` of a seed is always the same."""

from typing import Any, Dict

import numpy as np


def batch(mix: Dict[str, Any], seed: int, step: int, vocab_size: int
          ) -> np.ndarray:
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab_size, (mix["global_batch"], mix["seq_len"]),
                        dtype=np.int32)
