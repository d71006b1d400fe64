"""Seeded weights, made by the benchmark and handed to the program.

The tree's names and shapes are the program's interface (``jax.eval_shape``
of the model's ``init``: shapes only, no value of the program's). The
values come from ``--seed`` in one jitted call on the device, in the type
they are used in. The reference reads the same tree.
"""

from __future__ import annotations

import zlib
from typing import Any

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape, dtype, n_layers: int):
    name = path.split("/")[-1]
    if "norm" in name:                      # gains near one, not all ones
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name.startswith("b"):              # biases (none at these configs)
        w = jnp.zeros(shape, jnp.float32)
    elif name == "tok_embed":
        w = jax.random.normal(key, shape, jnp.float32)
    else:                                   # matrices [..., fan_in, fan_out]
        scale = shape[-2] ** -0.5
        if name in ("wo", "w_down"):        # residual branches, as GPT-2 init
            scale *= (2 * n_layers) ** -0.5
        w = scale * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def make(shapes: Any, seed: int, dtype, n_layers: int, out_shardings=None):
    """One jitted call: every leaf of ``shapes`` from ``seed``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in paths]

    def build(key):
        leaves = [
            _leaf(jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF),
                  n, s.shape, dtype, n_layers)
            for n, (_, s) in zip(names, paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return jax.block_until_ready(
        jax.jit(build, out_shardings=out_shardings)(key))
