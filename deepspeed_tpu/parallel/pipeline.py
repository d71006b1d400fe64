"""Pipeline-parallel executor: rotating-microbatch SPMD pipeline.

TPU-native redesign of the reference's pipeline engine
(``runtime/pipe/engine.py:55`` PipelineEngine + ``runtime/pipe/p2p.py``
send/recv + ``runtime/pipe/schedule.py`` instruction schedules). The
reference drives one process per stage through an interpreted instruction
list (ForwardPass / SendActivation / RecvActivation / BackwardPass / ...)
with explicit point-to-point sends. On TPU the whole schedule compiles into
ONE program:

* the ``pipe`` mesh axis holds one stage per device group,
* stage parameters are *stacked* on a leading axis sharded over ``pipe``,
* a ``lax.scan`` over clock ticks moves micro-batch activations between
  stages with ``lax.ppermute`` (the p2p.send/recv equivalent, riding ICI),
* ``jax.checkpoint`` on the stage body keeps live memory at one activation
  per stage boundary (the reason the reference implements 1F1B),
* reverse-mode autodiff of the scan yields the backward pipeline — the
  drain/fill structure of 1F1B falls out of the chain rule instead of an
  instruction interpreter.

Ticks run ``M + P - 1`` times (M micro-batches, P stages): the classic
fill/steady/drain profile with bubble fraction ``(P-1)/(M+P-1)`` forward —
identical to the reference's TrainSchedule (schedule.py:189).

The executor is *partial-manual*: only ``pipe`` is a manual axis; data /
model / seq / expert axes stay under GSPMD so tensor-parallel matmuls and
ZeRO shardings inside the stage body keep working unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# stage_fn(stage_params, x, consts, rng, valid) -> (y, aux_scalar)
StageFn = Callable[[Any, jnp.ndarray, Any, jnp.ndarray, jnp.ndarray],
                   Tuple[jnp.ndarray, jnp.ndarray]]


def pipeline_apply(stage_fn: StageFn,
                   stage_params: Any,
                   xs: jnp.ndarray,
                   rng: jnp.ndarray,
                   mesh: Mesh,
                   *,
                   consts: Any = None,
                   axis: str = "pipe",
                   remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run ``xs`` (``[M, mb, ...]`` micro-batched activations) through the
    pipelined stack.

    ``stage_params``: pytree whose leaves are stacked per-stage with leading
    dim P sharded over ``axis`` (each device sees its own stage's slice).
    ``consts``: pytree of stage-invariant inputs (RoPE angle tables, masks)
    replicated over the pipe axis and handed to every ``stage_fn`` call.
    Returns ``(ys, aux)`` where ``ys`` has the shape of ``xs`` (final-stage
    outputs, broadcast over the pipe axis) and ``aux`` is the mean per-
    microbatch auxiliary loss accumulated across stages (MoE load balancing).
    """
    n_stages = mesh.shape[axis]
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def spmd(params, xs, consts, rng):
        # params leaves: [1, ...] local stage slice; drop the stage dim.
        params = jax.tree_util.tree_map(lambda x: x[0], params)
        stage = jax.lax.axis_index(axis)
        n_mb = xs.shape[0]
        ticks = n_mb + n_stages - 1
        state = jnp.zeros_like(xs[0])
        ys = jnp.zeros_like(xs)

        def tick(carry, t):
            state, ys, aux_acc = carry
            # stage 0 loads micro-batch t from the data feed; later stages
            # take the activation rotated in from the previous stage
            # (reference: LoadMicroBatch vs RecvActivation, schedule.py:332).
            mb_in = jnp.clip(t, 0, n_mb - 1)
            inp = jnp.where(stage == 0,
                            jax.lax.dynamic_index_in_dim(xs, mb_in, keepdims=False),
                            state)
            # this stage is computing micro-batch (t - stage); it is real
            # work (not fill/drain bubble) iff 0 <= t - stage < M.
            mb_here = t - stage
            valid = jnp.logical_and(mb_here >= 0, mb_here < n_mb)
            sub = jax.random.fold_in(jax.random.fold_in(rng, t), stage)
            out, aux = body(params, inp, consts, sub, valid)
            aux_acc = aux_acc + jnp.where(valid, aux.astype(jnp.float32), 0.0)
            # final stage banks its finished micro-batch (t - (P-1)).
            mb_out = t - (n_stages - 1)
            write = jnp.logical_and(stage == n_stages - 1, mb_out >= 0)
            idx = jnp.clip(mb_out, 0, n_mb - 1)
            cur = jax.lax.dynamic_index_in_dim(ys, idx, keepdims=False)
            ys = jax.lax.dynamic_update_index_in_dim(
                ys, jnp.where(write, out, cur), idx, 0)
            # rotate activations one stage forward (p2p send/recv analog).
            state = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (state, ys, aux_acc), None

        init = (state, ys, jnp.zeros([], jnp.float32))
        (state, ys, aux_acc), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
        # outputs live on the last stage only; broadcast to every stage so
        # the (replicated-over-pipe) head/loss can run under plain GSPMD.
        # psum in fp32: fp32 collective accumulation discipline (and XLA's
        # CPU backend miscompiles sub-fp32 psum under partial-manual
        # shard_map — "Invalid binary instruction opcode copy").
        ys_dtype = ys.dtype
        ys = jax.lax.psum(
            jnp.where(stage == n_stages - 1, ys, jnp.zeros_like(ys))
            .astype(jnp.float32), axis).astype(ys_dtype)
        aux = jax.lax.psum(aux_acc, axis) / jnp.maximum(n_mb, 1)
        return ys, aux

    return jax.shard_map(
        spmd, mesh=mesh, axis_names={axis},
        in_specs=(P(axis), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(stage_params, xs, consts, rng)


def forward_tick_plan(micro_batches: int, stages: int):
    """The executor's forward work map: ``plan[t]`` is the list of
    ``(stage, micro_batch)`` pairs doing *real* work at clock tick ``t``.

    Derived from the SAME predicate the compiled scan body uses
    (``mb_here = t - stage``, valid iff ``0 <= mb_here < M`` — see ``tick``
    above), so tests can assert this plan is equivalent to the reference-
    shaped instruction schedules in ``pipe/schedule.py``: tick-for-step equal
    to InferenceSchedule's ForwardPass stream, and per-stage order-equal to
    TrainSchedule's forward stream (1F1B re-times backward, never forward
    order). That assertion is what makes ``pipe/schedule.py`` a *wired*
    specification of this executor rather than a standalone model.
    """
    n_mb, n_stages = micro_batches, stages
    plan = []
    for t in range(n_mb + n_stages - 1):
        work = [(s, t - s) for s in range(n_stages) if 0 <= t - s < n_mb]
        plan.append(work)
    return plan


def stack_stage_params(layer_params: Any, n_stages: int) -> Any:
    """Reshape stacked-layer params ``[n_layers, ...]`` into per-stage
    ``[n_stages, n_layers/n_stages, ...]``. A metadata-only reshape when the
    leading dim is already sharded over the pipe axis."""

    def reshape(x):
        n = x.shape[0]
        assert n % n_stages == 0, (
            f"layer count {n} not divisible by pipeline stages {n_stages}")
        return x.reshape((n_stages, n // n_stages) + x.shape[1:])

    return jax.tree_util.tree_map(reshape, layer_params)


def microbatch(batch: Any, num_microbatches: int) -> Any:
    """Split a global batch ``[B, ...]`` into ``[M, B/M, ...]`` along dim 0
    (reference: PipelineEngine micro-batch iterator over the data loader)."""

    def split(x):
        b = x.shape[0]
        assert b % num_microbatches == 0, (
            f"batch {b} not divisible by {num_microbatches} microbatches")
        return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)
