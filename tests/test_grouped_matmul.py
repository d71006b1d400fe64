"""``ops/pallas/grouped_matmul.py`` in interpret mode on the CPU against
``jax.lax.ragged_dot`` on the same operands: the schedule (``visits``), the
masked stores of a tile that two groups share, the zeros past the last
group's end, the layer's place in the stored stack, the tiled forms of a
matrix that does not fit one block, the gated form, and ``no_drop_moe``
with the kernel against ``no_drop_moe`` without it. The chip's half (the
same comparison at the cells' shapes, and the times) is
``scripts/ragged_dot_bench.py``; what Mosaic accepts,
``tests/test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.parallel import moe

SDAR = (2048, 768)          # K, N of sdar-30b-a3b's up product


def _sizes(how: str, rows: int, E: int) -> np.ndarray:
    if how == "even":
        return np.full((E,), rows // E, np.int32)
    if how == "multinomial":
        rng = np.random.default_rng(rows + E)
        return np.bincount(rng.integers(0, E, rows), minlength=E
                           ).astype(np.int32)
    if how == "empty_groups":       # every other group, and the first two
        g = _sizes("multinomial", rows, E)
        g[::2] = 0
        g[:2] = 0
        return g
    if how == "one_group":          # one group holds every row
        g = np.zeros((E,), np.int32)
        g[E // 2] = rows
        return g
    if how == "three_tiles":        # 100 .. 400 of 512: tiles 0, 1, 2, 3
        assert rows == 512 and E >= 3
        g = np.zeros((E,), np.int32)
        g[0], g[1], g[E - 1] = 100, 300, 112
        return g
    if how == "rows_past_the_end":  # the last 1.5 tiles belong to no group
        return _sizes("multinomial", rows // 2 - 37, E)
    if how == "nothing":
        return np.zeros((E,), np.int32)
    raise ValueError(how)


def _operands(rows, E, K, N, L=1, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                             / np.sqrt(shape[-2])).astype(dtype)
    return (jax.random.normal(k1, (rows, K), jnp.float32).astype(dtype),
            draw(k2, (L * E, K, N)), draw(k3, (L * E, K, N)))


def _product(xs, w, sizes, layer=0, w2=None):
    """The kernel as ``no_drop_moe`` calls it: the layer's schedule, then
    the product."""
    sched = gm.visits(jnp.asarray(sizes), xs.shape[0],
                      layer * len(sizes))
    return gm.grouped_matmul(xs, w, sched, w2, interpret=True)


def _close(got, want, dtype):
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    # float32: the same products in another order of sums; bfloat16: the
    # CPU's ragged_dot rounds its own way, two units in the last place
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -6
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("how,rows,E", [
    ("even", 256, 4), ("even", 64, 4), ("multinomial", 256, 8),
    ("multinomial", 200, 5), ("multinomial", 48, 16), ("empty_groups", 256, 8),
    ("one_group", 384, 4), ("three_tiles", 512, 4),
    ("rows_past_the_end", 512, 6), ("nothing", 256, 4)])
def test_matches_ragged_dot(how, rows, E):
    K, N = 256, 128
    xs, w, _ = _operands(rows, E, K, N)
    sizes = _sizes(how, rows, E)
    want = jax.lax.ragged_dot(xs, w, jnp.asarray(sizes))
    got = _product(xs, w, sizes)
    total = int(sizes.sum())
    _close(got[:total], want[:total], jnp.float32)
    # rows of no group: zeros, whatever ragged_dot leaves there
    assert not np.asarray(got[total:]).any()


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("gated", [False, True], ids=["one", "gated"])
def test_reads_only_its_layer_of_the_stack(layer, gated):
    """The stack [L * E, K, N] with every other layer's matrices NaN: the
    layer's place is an offset in the weight's index map, and no other
    layer's block is fetched."""
    L, E, rows, K, N = 3, 4, 256, 256, 128
    xs, w, w2 = _operands(rows, E, K, N, L)
    mine = (jnp.arange(L * E) // E == layer)[:, None, None]
    sizes = jnp.asarray(_sizes("multinomial", rows, E))
    own = slice(layer * E, (layer + 1) * E)
    want = jax.lax.ragged_dot(xs, w[own], sizes)
    if gated:
        want = jax.nn.silu(want) * jax.lax.ragged_dot(xs, w2[own], sizes)
    got = _product(xs, jnp.where(mine, w, jnp.nan), sizes, layer,
                   jnp.where(mine, w2, jnp.nan) if gated else None)
    _close(got, want, jnp.float32)


@pytest.mark.parametrize("K,N,tiles", [
    (SDAR[0], SDAR[1], (2048, 768)),        # SDAR's up: the whole matrix
    (SDAR[1], SDAR[0], (768, 2048)),        # ... and down
    (1024, 3584, (1024, 1792)),             # Mixtral's / 4, up: N tiled
    (3584, 1024, (3584, 512)),              # ... down: N tiled, K whole
    (8192, 512, (4096, 512)),               # K tiled: a float32 accumulator
], ids=["sdar_up", "sdar_down", "mixtral4_up", "mixtral4_down", "k_tiled"])
@pytest.mark.parametrize("gated", [False, True], ids=["one", "gated"])
def test_weight_tiles(K, N, tiles, gated):
    """bfloat16 at the cells' widths (few experts: the CPU multiplies
    them), the block the shape alone decides, both forms."""
    assert gm.weight_tiles(K, N, jnp.bfloat16) == tiles
    E, rows = 3, 160
    xs, w, w2 = _operands(rows, E, K, N, dtype=jnp.bfloat16)
    sizes = jnp.asarray([70, 0, 81], jnp.int32)     # 9 rows of no group
    f32 = lambda a: a.astype(jnp.float32)
    want = jax.lax.ragged_dot(f32(xs), f32(w), sizes)
    if gated:
        want = jax.nn.silu(want) * jax.lax.ragged_dot(f32(xs), f32(w2), sizes)
    got = _product(xs, w, sizes, 0, w2 if gated else None)
    assert got.dtype == jnp.bfloat16
    _close(got[:151], want[:151].astype(jnp.bfloat16), jnp.bfloat16)
    assert not np.asarray(f32(got[151:])).any()


def test_weight_tiles_at_the_cells_shapes():
    bf16 = jnp.bfloat16
    assert gm.weight_tiles(2048, 768, bf16) == (2048, 768)
    assert gm.weight_tiles(4096, 14336, bf16) == (4096, 512)
    assert gm.weight_tiles(14336, 4096, bf16) == (2048, 1024)
    assert gm.tile_rows(8192) == 128 and gm.tile_rows(40) == 48


@pytest.mark.parametrize("how,rows,E,tm", [
    ("even", 256, 4, 128), ("multinomial", 8192, 128, 128),
    ("empty_groups", 512, 128, 128), ("one_group", 384, 4, 128),
    ("rows_past_the_end", 512, 6, 128), ("nothing", 256, 4, 128),
    ("multinomial", 48, 16, 48)])
def test_visits(how, rows, E, tm):
    """The schedule against a plain loop over tiles and groups."""
    sizes = _sizes(how, rows, E)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    tiles = -(-rows // tm)
    want = [(t, 7 * E + g, starts[g], ends[g])
            for g in range(E) for t in range(tiles)
            if max(starts[g], t * tm) < min(ends[g], (t + 1) * tm)]
    last = want[-1][1:] if want else (7 * E, 0, 0)
    want += [(t,) + last for t in range(-(-int(ends[-1]) // tm), tiles)]
    assert gm.tile_rows(rows) == tm
    got = np.asarray(gm.visits(jnp.asarray(sizes), rows, 7 * E))
    n = got[4, 0]
    assert (got[4] == n).all() and n == len(want) <= got.shape[1]
    assert got.shape[1] == tiles + min(E, rows) - 1
    assert [tuple(c) for c in got[:4, :n].T] == want
    # sorted by row; a dead step repeats the last live one
    assert (np.diff(got[0, :n]) >= 0).all()
    assert (got[:4, n:] == got[:4, n - 1:n]).all()


@pytest.mark.parametrize("path,rows,E,K,N,want", [
    ("gather", 512, 128, 2048, 768, "ragged_dot"),
    ("pallas", 512, 128, 2048, 768, "kernel"),
    ("pallas", 2048, 128, 768, 2048, "kernel"),
    ("pallas", 8192, 128, 2048, 768, "kernel"),
    ("pallas_interpret", 8192, 128, 2048, 768, "kernel"),
    ("pallas", 128 * 240, 128, 2048, 768, "ragged_dot"),    # the ridge
    ("pallas", 128, 8, 1024, 2048, "kernel"),       # 4 MiB: one block
    ("pallas", 128, 8, 4096, 14336, "kernel"),      # Mixtral's: tiled
    ("pallas", 512, 8, 14336, 4096, "kernel"),
    ("pallas", 4096, 8, 4096, 14336, "ragged_dot")])    # past the ridge
def test_the_rule(path, rows, E, K, N, want):
    """Which product a program holds: the path and the rows an expert
    (under the ridge), whatever the expert matrix's tiling: Mixtral's 64-
    and 256-lane programs (128 and 512 pairs) hold the kernel over a
    matrix that is no single block, its 2,048-lane one keeps
    ``ragged_dot``."""
    assert moe.expert_product(path, rows, E) == want
    if (K, N) in ((4096, 14336), (14336, 4096)):
        assert gm.weight_tiles(K, N, jnp.bfloat16) != (K, N)


@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
@pytest.mark.parametrize("layer", [0, 2])
def test_no_drop_moe_with_the_kernel(activation, layer):
    """``no_drop_moe`` on the stored stack, the kernel's path against
    ``ragged_dot``'s: SwiGLU (gate and up in one call) and GELU with the
    experts' biases."""
    L, E, d, f, S, k = 3, 8, 128, 256, 40, 2
    bank = moe.MoELayer(d, f, moe.GateConfig(n_experts=E, top_k=k),
                        activation=activation, use_bias=activation == "gelu")
    params = bank.init(jax.random.PRNGKey(1), jnp.float32, n_layers=L)
    for i, b in enumerate(n for n in ("b_up", "b_down") if n in params):
        params[b] = jax.random.normal(jax.random.PRNGKey(2 + i),
                                      params[b].shape)
    lp = {n: a if n in moe.RAGGED_OPERANDS else a[layer]
          for n, a in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (S, d))
    idx = jax.random.randint(jax.random.PRNGKey(5), (S, k), 0, E)
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(6), (S, k)))
    want = moe.no_drop_moe(x, probs, idx, lp, activation, layer)
    hlo = jax.jit(lambda *a: moe.no_drop_moe(
        *a, lp, activation, layer, "pallas_interpret")).lower(x, probs, idx)
    assert "ragged_dot" not in hlo.as_text()
    got = moe.no_drop_moe(x, probs, idx, lp, activation, layer,
                          "pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # without the stack in place the products stay ragged_dot's
    own = {n: a[layer] for n, a in params.items()}
    np.testing.assert_array_equal(
        np.asarray(moe.no_drop_moe(x, probs, idx, own, activation, None,
                                   "pallas_interpret")),
        np.asarray(moe.no_drop_moe(x, probs, idx, own, activation)))
