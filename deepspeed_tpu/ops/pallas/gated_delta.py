"""The gated delta rule's one-token step over the slots that decode (Pallas
TPU): one call a recurrent layer, a grid over the step's single-lane runs.

``ops/gated_delta.py`` has the mathematics (``delta_step``) and the list
this walks (``runs_of``'s ``steps``: the slots whose run this step is one
lane long, live entries first, built on the device once a step and shared
by the layers, as ``work_list`` is for the paged kernel). The state leaf
[S + 1, H, dk, dv] float32 is an operand aliased to a result: entry ``i``
of the list names block ``state[slot_i]`` (whole [dk, dv] planes, ``hb``
heads a grid step), which the ordinary pipeline brings into VMEM, the
kernel rewrites and the pipeline sends back. Each state element of a slot
that decodes is read once and written once; a slot with no single-lane
run is neither read nor written. Entries past the live count name the last
live block again, so no copy is issued for them, and ``pl.when`` skips
their work (the pattern of ``paged_attention._tile_kernel``). With no live
entry every step names the sink slot's last block, which the first step
copies through unchanged.

Inside a grid step the heads go one at a time through registers: a
[dk, dv] float32 plane is 24 vregs at (96, 192). ``S <- alpha S;
u = beta (v - S^T k); S <- S + k u^T; o = S^T q`` letter for letter as
``delta_step`` writes it, in float32, nothing on the MXU; only the order of
the two sums over ``dk`` is the kernel's own. q and k come in as
[N, dk, H] so that a head's vector is a column over ``dk``, the state
plane's sublanes (picked by a lane mask and a lane reduction: 12 vregs a
vector); v and o are rows over ``dv``; the two gates are scalars in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: VMEM the state blocks may take, input and output, two buffers each
STATE_VMEM_BYTES = 12 * 1024 * 1024


def head_block(n_heads: int, k_dim: int, v_dim: int) -> int:
    """Heads a grid step holds: the most (a divisor of ``n_heads``) whose
    planes, padded to the (8, 128) tiling, fit :data:`STATE_VMEM_BYTES`
    four times over. 30 at Olmo-Hybrid's (96, 192): a slot's 2.95 MB."""
    plane = 4 * (-(-k_dim // 8) * 8) * (-(-v_dim // 128) * 128)
    fit = max(1, STATE_VMEM_BYTES // (4 * plane))
    return max(h for h in range(1, n_heads + 1)
               if n_heads % h == 0 and h <= fit)


def _step_kernel(steps_ref, a_ref, b_ref, q_ref, k_ref, v_ref, s_in, o_ref,
                 s_out, *, hb: int):
    """One grid step = ``hb`` heads of one entry's slot."""
    i, j = pl.program_id(0), pl.program_id(1)
    n = steps_ref[3, 0]
    dk, H = q_ref.shape[1:]

    @pl.when((n == 0) & (i == 0))
    def _nothing_decodes():     # the one block every step names: as it was
        s_out[...] = s_in[...]

    @pl.when(i < n)
    def _entry():
        fresh = steps_ref[2, i] > 0
        q_all, k_all = q_ref[0], k_ref[0]                 # [dk, H]
        heads = jax.lax.broadcasted_iota(jnp.int32, (dk, H), 1)

        def head(h, carry):
            at = j * hb + h
            mine = heads == at
            col = lambda x: jnp.sum(jnp.where(mine, x, 0.0), 1, keepdims=True)
            q, k = col(q_all), col(k_all)                 # [dk, 1]
            a, beta = a_ref[i, at], b_ref[i, at]
            # a run that starts a sequence takes a zero state
            state = jnp.where(fresh, 0.0, s_in[0, h])     # [dk, dv]
            ks = jnp.sum(k * state, 0, keepdims=True) * a   # (alpha S)^T k
            qs = jnp.sum(q * state, 0, keepdims=True) * a
            u = beta * (v_ref[0, pl.ds(at, 1), :] - ks)   # [1, dv]
            s_out[0, h] = a * state + k * u
            o_ref[0, pl.ds(at, 1), :] = \
                qs + jnp.sum(q * k, 0, keepdims=True) * u
            return carry

        jax.lax.fori_loop(0, hb, head, 0)


# jitted on its own, as ``paged_attention._tiled`` and for its reason: the
# layers of a step call it with the same shapes, so it is traced and
# lowered once a program
@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_step_slots(q, k, v, g, beta, state, steps, *,
                     interpret: bool = False):
    """``delta_step`` for the entries of ``steps`` (int32 [4, N]: an
    entry's slot, its lane, whether its run starts a sequence, and the
    count of live entries in every column; ``gated_delta.runs_of``). q, k
    [N, H, dk]; v [N, H, dv]; g (log alpha), beta [N, H]: the rows of the
    entries' lanes, float32; state [S + 1, H, dk, dv] float32. Returns
    (o [N, H, dv], state): ``o``'s rows past the live count hold nothing,
    and ``state`` is the operand's buffer where the caller donates it."""
    N, H, dk = q.shape
    dv = v.shape[-1]
    hb = head_block(H, dk, dv)
    nj = H // hb
    # a dead entry names the last live step's blocks again
    entry = lambda i, st: jnp.maximum(jnp.minimum(i, st[3, 0] - 1), 0)
    row = lambda i, j, st: (entry(i, st), 0, 0)
    plane = lambda i, j, st: (st[0, i], jnp.where(i < st[3, 0], j, nj - 1),
                              0, 0)
    cols = pl.BlockSpec((1, dk, H), row)
    rows = pl.BlockSpec((1, H, dv), row)
    planes = pl.BlockSpec((1, hb, dk, dv), plane)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct((N, H, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N, nj),
            in_specs=[smem, smem, cols, cols, rows, planes],
            out_specs=[rows, planes]),
        # the state is written where it lies: operand 6 (after the
        # prefetched list, the gates, q, k and v) is result 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="delta_step",
        interpret=interpret,
    )(steps, jnp.exp(g), beta, q.transpose(0, 2, 1), k.transpose(0, 2, 1), v,
      state)
