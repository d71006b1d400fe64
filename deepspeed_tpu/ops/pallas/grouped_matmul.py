"""The experts' grouped product (Pallas TPU): rows sorted by expert against
a stack of expert matrices, each touched expert's matrix streamed once.

The TPU's stand-in for the reference's
``inference/v2/kernels/cutlass_ops/moe_gemm``, and ``jax.lax.ragged_dot``'s
operands as ``parallel/moe.py::no_drop_moe`` hands them over: ``xs``
[rows, K] sorted by expert, the stored stack viewed as [L * E, K, N] (a
bitcast, no slice), one layer's E group sizes and the layer's place.
``ragged_dot`` on a v5e costs some 15 us a group whatever the group holds
(PERF.md, PR 45: 204-239 GB/s of expert bytes at 128 experts of 3.1 MB,
where the group's bytes need 3.8 us); here the ordinary pipeline fetches a
group's matrix while the group before it is multiplied.

The schedule is ``visits``: one grid step a (row tile, group) pair that
share a row, in row order, built on the device once a layer from the group
sizes, shared by the layer's products (they multiply the same rows by the
same groups) and prefetched as scalars (what ``work_list`` is to the paged
kernel and ``runs_of``'s ``steps`` to the two recurrent ones; megablox's
``make_group_metadata`` is the same idea). A tile of ``tm`` rows that holds
the end of one group and the start of the next is visited once for each:
the output block stays in VMEM between the two visits and each stores only
the rows that are its group's. A tile's first visit zeroes it, and the
tiles past the last group's end are visited once with no row to store, so
rows that belong to no group come back as zeros (what ``ragged_dot`` leaves
there). An empty group has no visit and its matrix is not read. Steps past
the live count name the last visit's blocks again, so no copy is issued for
them, and ``pl.when`` skips their work.

The weight's block is the whole [K, N] matrix where it fits
``WEIGHT_BLOCK_BYTES`` (SDAR's 2048 x 768 bf16: 3 MiB, two buffers 6 MiB):
successive visits of one group name the same block and it is fetched once,
so the product's HBM traffic is each touched matrix once plus the rows in
and out. Where it does not fit (Mixtral's 4096 x 14336) N is tiled, the
grid's outermost axis, and then K, its innermost, under a float32
accumulator: ``weight_tiles`` decides from K, N and the dtype alone.
bfloat16 operands, float32 accumulation, the result in the rows' dtype as
``ragged_dot`` gives it. With a second stack (``w2``: SwiGLU's gate beside
its up projection) one call reads a row tile once and forms
``silu(xs @ w) * (xs @ w2)`` in float32 before the one rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import LANES

F32 = jnp.float32
#: rows a grid step multiplies (fewer where the product has fewer)
TILE_ROWS = 128
#: what one buffer of one weight block may take
WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024
#: the widest N tile of a matrix that is also tiled over K
TILED_N = 1024


def _divisor(n: int, most: int) -> int:
    """The largest multiple of ``LANES`` that divides ``n`` and is at most
    ``most``; ``n`` itself where none does (a block may always be whole)."""
    fits = [t for t in range(LANES, min(n, most) + 1, LANES) if n % t == 0]
    return fits[-1] if fits else n


def weight_tiles(K: int, N: int, dtype) -> tuple[int, int]:
    """(tk, tn), a weight block, from the matrix's shape and dtype alone:
    the whole matrix where it fits ``WEIGHT_BLOCK_BYTES``; else all of K
    and the widest N tile that fits, if that is 512 columns or more (runs
    of 16 KiB in the tiled layout); else N in tiles of ``TILED_N`` at most
    and K in the deepest that fits. (2048, 768) at SDAR's up product,
    (4096, 512) and (2048, 1024) at Mixtral's up and down."""
    size = jnp.dtype(dtype).itemsize
    most = WEIGHT_BLOCK_BYTES // size
    if K * N <= most:
        return K, N
    tn = _divisor(N, most // K)
    if K * tn <= most and tn >= min(N, 4 * LANES):
        return K, tn
    tn = _divisor(N, TILED_N)
    return _divisor(K, max(LANES, most // tn)), tn


def tile_rows(rows: int) -> int:
    """Rows a grid step takes: ``TILE_ROWS``, or all of a smaller product's
    (in whole sublane tiles of 16, a bfloat16 block's least)."""
    return min(TILE_ROWS, -(-rows // 16) * 16)


# jitted on its own for ``grouped_matmul``'s reason: traced once a process
# and lowered once a program, whatever the layers
@functools.partial(jax.jit, static_argnames=("rows",))
def visits(group_sizes, rows: int, base=0):
    """A layer's grid steps from its group sizes [E], for products of
    ``rows`` rows (tiles of ``tile_rows(rows)``) -> int32 [5, n_max], one
    column a step, live steps first and in row order:

      0  the step's row tile
      1  its group's matrix in the stack: ``base`` + the group
      2  the group's first row
      3  the row after the group's last
      4  the count of live steps, in every column

    A live step is a (tile, group) pair that share a row, then one step
    for each tile past the last group's end (rows 2 and 3 of a group that
    ends before it: nothing to store but the tile's zeros). Steps past the
    count repeat the last live one. ``n_max`` = tiles + min(E, rows) - 1:
    each group after a tile's first adds one pair."""
    E = group_sizes.shape[0]
    tm = tile_rows(rows)
    tiles = -(-rows // tm)
    n_max = tiles + min(E, rows) - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(count)                # pairs up to and with group g
    paired = upto[-1]
    covered = -(-ends[-1] // tm)            # tiles that hold a group's row
    n = paired + tiles - covered
    v = jnp.arange(n_max, dtype=jnp.int32)
    # all compares at once, as work_list does: a binary search is a loop
    g = jnp.searchsorted(upto, jnp.minimum(v, paired - 1), side="right",
                         method="compare_all").astype(jnp.int32)
    g = jnp.clip(g, 0, E - 1)
    tile = jnp.where(v < paired, first[g] + v - (upto[g] - count[g]),
                     covered + v - paired)
    col = jnp.stack([tile, jnp.asarray(base, jnp.int32) + g, starts[g],
                     ends[g], jnp.broadcast_to(n, v.shape)]).astype(jnp.int32)
    return jnp.where(v < n, col, col[:, jnp.maximum(n - 1, 0)][:, None])


def _kernel(visit_ref, x_ref, *refs, tm: int, nk: int, glu: bool):
    """One grid step = one visit's rows against one block of its group's
    matrix (of both its matrices under ``glu``)."""
    nw = 2 if glu else 1
    w_refs, o_ref, accs = refs[:nw], refs[nw], refs[nw + 1:]
    v, k = pl.program_id(1), pl.program_id(2)
    tile = visit_ref[0, v]

    @pl.when(v < visit_ref[4, 0])
    def _visit():
        first = (v == 0) | (visit_ref[0, jnp.maximum(v - 1, 0)] != tile)

        @pl.when(first if nk == 1 else first & (k == 0))
        def _fresh_tile():      # rows of no group read zero
            o_ref[...] = jnp.zeros_like(o_ref)

        x = x_ref[...]
        # the products in bfloat16 passes with a float32 sum, whatever
        # jax_default_matmul_precision says (Mosaic refuses bfloat16
        # operands under "highest")
        prods = [jnp.dot(x, w[...], preferred_element_type=F32,
                         precision=jax.lax.Precision.DEFAULT)
                 for w in w_refs]
        if nk > 1:
            @pl.when(k == 0)
            def _zero():
                for acc in accs:
                    acc[...] = jnp.zeros_like(acc)

            for acc, p in zip(accs, prods):
                acc[...] += p

        def store():
            out = [acc[...] for acc in accs] if nk > 1 else prods
            y = jax.nn.silu(out[0]) * out[1] if glu else out[0]
            row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
            mine = (row >= visit_ref[2, v]) & (row < visit_ref[3, v])
            o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])

        if nk == 1:
            store()
        else:
            pl.when(k == nk - 1)(store)


# jitted on its own, as ``paged_attention._tiled`` and for its reason: the
# layers of a step call it with the same shapes (the layer's place is in
# the schedule), so it is traced and lowered once a program
@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(xs, w, sched, w2=None, *, interpret: bool = False):
    """``jax.lax.ragged_dot(xs, w[layer * E:(layer + 1) * E], group_sizes)``
    without the slice. xs [rows, K], sorted by group; w [L * E, K, N];
    sched: ``visits(group_sizes, rows, layer * E)``, the layer's schedule,
    one for all its products of ``rows`` rows. Returns [rows, N] in xs'
    dtype; rows past the last group's end are zeros. With ``w2``, a stack
    shaped like ``w``: ``silu(xs @ w) * (xs @ w2)`` group by group, formed
    in float32."""
    rows, K = xs.shape
    N = w.shape[-1]
    glu = w2 is not None
    tm = tile_rows(rows)
    tk, tn = weight_tiles(K, N, w.dtype)
    nk, nj = K // tk, N // tn
    # a dead step names the last live visit's blocks again (its column of
    # ``sched`` repeats it) and the reduction's last block
    deep = lambda v, k, s: jnp.where(v < s[4, 0], k, nk - 1)
    x_spec = pl.BlockSpec((tm, tk), lambda j, v, k, s: (s[0, v],
                                                         deep(v, k, s)))
    w_spec = pl.BlockSpec((None, tk, tn), lambda j, v, k, s: (
        s[1, v], deep(v, k, s), j))
    o_spec = pl.BlockSpec((tm, tn), lambda j, v, k, s: (s[0, v], j))
    ws = (w, w2) if glu else (w,)
    accs = [pltpu.VMEM((tm, tn), F32)] * (len(ws) if nk > 1 else 0)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, nk=nk, glu=glu),
        out_shape=jax.ShapeDtypeStruct((rows, N), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nj, sched.shape[1], nk),
            in_specs=[x_spec] + [w_spec] * len(ws),
            out_specs=o_spec, scratch_shapes=accs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="grouped_matmul",
        interpret=interpret,
    )(sched, xs, *ws)
