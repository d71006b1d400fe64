"""Mamba-2's one-token step over the slots that decode (Pallas TPU): one
call a Mamba layer, a grid over the step's single-lane runs.

``ops/mamba2.py`` has the mathematics (``ssd_step``) and
``ops/gated_delta.py`` the list this walks (``runs_of``'s ``steps``, the
one ``ops/pallas/gated_delta.py::delta_step_slots`` walks: one schedule,
two kernels that differ by their kind's mathematics and state shape). The
state leaf [S + 1, H, P, N] float32 (under a rolled stack
[periods x (S + 1), H, P, N], this period's run of slots starting at the
prefetched scalar ``base``) is an operand aliased to a result: entry ``i``
names block ``state[base + slot_i]`` (whole [P, N] planes, ``hb`` heads a
grid step: all 64 of Granite's, a slot's 2 MiB), which the ordinary
pipeline brings into VMEM, the kernel rewrites and the pipeline sends
back. Each state element of a slot that decodes is read once and written
once, where it lies in the leaf; a slot with no single-lane run is neither
read nor written. Entries past the live count name the last live block
again, so no copy is issued for them, and ``pl.when`` skips their work.
With no live entry every step names the period's sink slot, which the
first step copies through unchanged.

Inside a grid step the heads go one at a time through registers: a [P, N]
float32 plane is 8 vregs at (64, 128). ``S <- exp(g) S + (dt x) B^T;
y = S C + D x`` letter for letter as ``ssd_step`` writes it, in float32,
nothing on the MXU; only the order of the sum over ``N`` is the kernel's
own. x comes in as [entries, P, H] so that a head's vector is a column
over ``P``, the plane's sublanes (picked by a lane mask and a lane
reduction), and y leaves the same way; B and C are rows over ``N``, one a
group, shared by its heads; dt and D are rows over the heads beside x;
exp(g) is a scalar in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import head_block

F32 = jnp.float32
#: heads a turn of the kernel's loop takes, the most that divides a block's:
#: one head a turn waits on its two lane reductions (11.5 us a slot at
#: Granite's shape, my chip run, PR 44), four overlap them with the next
#: heads' products and leave the copies as the bound (6.3 us; eight: 6.4)
UNROLL = (4, 2, 1)


def _step_kernel(steps_ref, base_ref, a_ref, x_ref, dt_ref, d_ref, b_ref,
                 c_ref, s_in, y_ref, s_out, *, hb: int, rep: int,
                 unroll: int):
    """One grid step = ``hb`` heads of one entry's slot."""
    i, j = pl.program_id(0), pl.program_id(1)
    n = steps_ref[3, 0]
    P, H = x_ref.shape[1:]

    @pl.when((n == 0) & (i == 0))
    def _nothing_decodes():     # the one block every step names: as it was
        s_out[...] = s_in[...]

    @pl.when(i < n)
    def _entry():
        fresh = steps_ref[2, i] > 0
        x_all = x_ref[0]                                  # [P, H]
        xdt = dt_ref[0] * x_all                           # dt x, every head
        heads = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)

        def head(h, y_all):
            at = j * hb + h
            mine = heads == at
            col = jnp.sum(jnp.where(mine, xdt, 0.0), 1, keepdims=True)
            group = at // rep
            # a run that starts a sequence takes a zero state
            state = jnp.where(fresh, 0.0, s_in[0, h])     # [P, N]
            new = a_ref[i, at] * state \
                + col * b_ref[0, pl.ds(group, 1), :]
            s_out[0, h] = new
            y = jnp.sum(new * c_ref[0, pl.ds(group, 1), :], 1,
                        keepdims=True)                    # [P, 1]
            return jnp.where(mine, y, y_all)

        def several(k, y_all):      # ``UNROLL``
            for u in range(unroll):
                y_all = head(k * unroll + u, y_all)
            return y_all

        # the block of y is one entry's, whatever ``j``: a later block of
        # heads finds the earlier ones' columns in it, and the last adds D x
        y_all = jax.lax.fori_loop(
            0, hb // unroll, several, jnp.where(j == 0, 0.0, y_ref[0]))
        y_ref[0] = y_all + jnp.where(j == pl.num_programs(1) - 1,
                                     d_ref[...] * x_all, 0.0)


# jitted on its own, as ``delta_step_slots`` and for its reason: the layers
# of a step call it with the same shapes, so it is traced and lowered once
# a program
@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_slots(x, B, C, dt, g, D, state, steps, base=None, *,
                   interpret: bool = False):
    """``ssd_step`` for the entries of ``steps`` (int32 [4, E]: an entry's
    slot, its lane, whether its run starts a sequence, and the count of
    live entries in every column; ``gated_delta.runs_of``). x [E, H, P];
    B, C [E, G, N] (a group's, not repeated to its heads); dt, g (log a)
    [E, H]: the rows of the entries' lanes, float32; D [H]; state
    [R, H, P, N] float32, a slot a row, the entries' slots counted from
    row ``base`` (a traced scalar under a rolled stack, whose leaf holds a
    run of slots a period; None = 0). Returns (y [E, H, P], state): ``y``'s
    rows past the live count hold nothing, and ``state`` is the operand's
    buffer where the caller donates it."""
    E, H, P = x.shape
    n_groups, N = B.shape[1:]
    hb = head_block(H, P, N)
    nj = H // hb
    base = jnp.asarray(0 if base is None else base, jnp.int32).reshape(1)
    # a dead entry names the last live step's blocks again
    entry = lambda i, st: jnp.maximum(jnp.minimum(i, st[3, 0] - 1), 0)
    row = lambda i, j, st, base: (entry(i, st), 0, 0)
    plane = lambda i, j, st, base: (
        base[0] + st[0, i], jnp.where(i < st[3, 0], j, nj - 1), 0, 0)
    cols = pl.BlockSpec((1, P, H), row)
    gates = pl.BlockSpec((1, 1, H), row)
    rows = pl.BlockSpec((1, n_groups, N), row)
    planes = pl.BlockSpec((1, hb, P, N), plane)
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, rep=H // n_groups,
                          unroll=next(u for u in UNROLL if hb % u == 0)),
        out_shape=(jax.ShapeDtypeStruct((E, P, H), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(E, nj),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), cols, gates,
                      pl.BlockSpec((1, H), lambda i, j, st, base: (0, 0)),
                      rows, rows, planes],
            out_specs=[cols, planes]),
        # the state is written where it lies: operand 8 (after the two
        # prefetched scalars, the decay, x, dt, D, B and C) is result 1
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="ssd_step",
        interpret=interpret,
    )(steps, base, jnp.exp(g), x.transpose(0, 2, 1), dt[:, None, :],
      D[None, :], B, C, state)
    return y.transpose(0, 2, 1), state
