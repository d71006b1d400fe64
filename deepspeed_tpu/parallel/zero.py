"""ZeRO redundancy elimination as sharding rules.

This module is the TPU-native redesign of the reference's
``runtime/zero/stage_1_and_2.py`` (DeepSpeedZeroOptimizer: flattened bit16
partitions + IPG bucketing + hook-driven reduce-scatter) and
``runtime/zero/stage3.py`` (DeepSpeedZeroOptimizer_Stage3: partitioned
parameters with fetch/release hooks + PartitionedParameterCoordinator
prefetching). Under XLA/GSPMD the entire hook/stream machinery collapses
into *placement*: we emit a ``NamedSharding`` for every parameter, gradient
and optimizer-state leaf, and the compiler inserts + schedules the
all-gathers and reduce-scatters (with latency hiding) that the reference
implements by hand.

Stage semantics (config parity with runtime/zero/config.py):
  stage 0 — params/grads/opt replicated over the ZeRO axes; grads psum.
  stage 1 — optimizer state sharded over the ZeRO axes; grads arrive as
            reduce-scattered shards for the update, updated params
            all-gathered (XLA emits the same reduce-scatter + all-gather
            schedule the reference builds with IPG buckets,
            stage_1_and_2.py:889,:999).
  stage 2 — identical compiled program to stage 1 on TPU (gradient shards
            are never materialized unsharded anyway); kept distinct for
            config parity.
  stage 3 — parameters themselves stored sharded (FSDP); forward/backward
            all-gathers are inserted by GSPMD exactly where the reference's
            pre/post-module hooks fetch/release partitions
            (parameter_offload.py:391, partitioned_param_coordinator.py:256).

Small parameters stay replicated below ``stage3_param_persistence_threshold``
— same knob, same motivation (avoid tiny all-gathers) as the reference's
persistence thresholds (stage3.py / partition_parameters.py).

The ZeRO axes come from :meth:`Topology.zero_partition_axes` — ('data',) or
('data','seq'), mirroring the reference's use of the sequence-data-parallel
group as ZeRO's process group when Ulysses is active (engine.py:1122).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..config import ZeroConfig
from .mesh import Topology


def _spec_to_list(spec: Optional[PartitionSpec], ndim: int) -> list:
    out: list = [None] * ndim
    if spec is None:
        return out
    for i, entry in enumerate(spec):
        if i < ndim:
            out[i] = entry
    return out


def _axes_size(topo: Topology, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= topo.axis_size(a)
    return n


def shard_leaf_spec(shape: Tuple[int, ...],
                    zero_axes: Tuple[str, ...],
                    base_spec: Optional[PartitionSpec] = None,
                    threshold: int = 0,
                    axes_size: int = 1,
                    axis_sizes: Optional[dict] = None) -> PartitionSpec:
    """Compute the PartitionSpec for one leaf: start from the tensor-parallel
    spec (if any) and fold the ZeRO axes onto still-unsharded, divisible
    dimensions. Falls back to replicated when nothing fits (tiny or
    odd-shaped leaves — the analog of the reference's persistent params).

    Multi-axis placement puts EACH zero axis on its OWN dimension (largest
    axes first, largest dims first) and NEVER fuses several axes onto one
    dim: XLA's SPMD partitioner cannot efficiently reshard an activation
    tiled over two distinct dims (batch x seq) onto a tensor dim carrying
    the fused product — it falls back to replicate-then-reshard
    ("Involuntary full rematerialization", xla b/433785288), and the
    hazard fires for fused 1-D vector grads just as for fused weight
    grads (an [d] norm grad fused over (data,seq) pressures the [b,s,d]
    cotangent into a feature-dim resharding). Axes that can't get their
    own dim are simply dropped for that leaf (it stays replicated over
    them) — for the 1-D leaves this costs a vector's worth of memory on
    one axis, nothing at scale.
    """
    ndim = len(shape)
    spec = _spec_to_list(base_spec, ndim)
    if ndim == 0 or axes_size == 1:
        return PartitionSpec(*spec)
    if int(np.prod(shape)) < threshold:
        return PartitionSpec(*spec)
    sizes = dict(axis_sizes or {})
    # without per-axis sizes we can only do the fused placement
    live = [] if axis_sizes is None else [a for a in zero_axes if sizes[a] > 1]
    if len(live) > 1:
        placed = 0
        for a in sorted(live, key=lambda a: -sizes[a]):
            n = sizes[a]
            cands = [i for i in range(ndim)
                     if spec[i] is None and shape[i] % n == 0 and shape[i] >= n]
            if cands:
                spec[max(cands, key=lambda i: shape[i])] = a
                placed += 1
        if placed:
            return PartitionSpec(*spec)
        # nothing placeable at all: replicated
        return PartitionSpec(*_spec_to_list(base_spec, ndim))
    # single axis / fused fallback: the product on one divisible dim
    candidates = [i for i in range(ndim) if spec[i] is None and shape[i] % axes_size == 0 and shape[i] >= axes_size]
    if not candidates:
        return PartitionSpec(*spec)
    dim = max(candidates, key=lambda i: shape[i])
    spec[dim] = zero_axes if len(zero_axes) > 1 else zero_axes[0]
    return PartitionSpec(*spec)


class ZeroShardingRules:
    """Produces sharding pytrees for params / grads / optimizer state.

    ``tp_specs`` is an optional pytree (matching params) of PartitionSpecs
    carrying tensor/expert-parallel placement from the model definition; ZeRO
    sharding composes on top (never double-shards a dim).
    """

    def __init__(self, topo: Topology, zero_config: Optional[ZeroConfig] = None):
        self.topo = topo
        self.config = zero_config or ZeroConfig()
        # MiCS (reference runtime/zero/mics.py:55): everything shards within
        # the sub-group (the fast-ICI 'zshard' factor) and REPLICATES across
        # the outer 'data' factor; XLA then emits the hierarchical
        # reduce-scatter(zshard) + all-reduce(data) gradient schedule that
        # mics.py:227 builds by hand.
        self.mics = (self.config.mics_shard_size or 0) > 0
        if self.mics and topo.zero_secondary_size > 1:
            self.zero_axes = topo.zero_secondary_axes()
        else:
            self.zero_axes = topo.zero_partition_axes()
        self.zero_size = _axes_size(topo, self.zero_axes)
        # hpZ (reference partition_parameters.py:883): primary partition over
        # the full ZeRO group (opt state / master params / grads), secondary
        # bf16 compute copy sharded over 'zshard' only so per-layer forward
        # all-gathers never cross the outer axis. The engine applies
        # secondary_param_shardings at the compute-cast boundary.
        self.hpz = (not self.mics
                    and self.config.zero_hpz_partition_size > 1
                    and topo.zero_secondary_size > 1
                    and self.config.stage >= 3)
        self.secondary_axes = topo.zero_secondary_axes()
        self.secondary_size = _axes_size(topo, self.secondary_axes)

    def _axis_sizes(self, axes: Tuple[str, ...]) -> dict:
        return {a: self.topo.axis_size(a) for a in axes}

    # -- per-leaf specs -------------------------------------------------
    def param_spec(self, shape: Tuple[int, ...], base_spec: Optional[PartitionSpec] = None) -> PartitionSpec:
        if self.config.stage < 3:
            return base_spec if base_spec is not None else PartitionSpec()
        return shard_leaf_spec(
            shape, self.zero_axes, base_spec,
            threshold=self.config.stage3_param_persistence_threshold,
            axes_size=self.zero_size, axis_sizes=self._axis_sizes(self.zero_axes),
        )

    def state_spec(self, shape: Tuple[int, ...], base_spec: Optional[PartitionSpec] = None) -> PartitionSpec:
        """Optimizer-state / gradient-shard spec: sharded from stage 1 up."""
        if self.config.stage < 1:
            return base_spec if base_spec is not None else PartitionSpec()
        return shard_leaf_spec(shape, self.zero_axes, base_spec, threshold=0,
                               axes_size=self.zero_size,
                               axis_sizes=self._axis_sizes(self.zero_axes))

    # -- pytree-level ---------------------------------------------------
    def _tree_specs(self, shapes: Any, tp_specs: Optional[Any], leaf_fn) -> Any:
        if tp_specs is None:
            return jax.tree_util.tree_map(lambda s: leaf_fn(tuple(s.shape), None), shapes)
        return jax.tree_util.tree_map(lambda s, t: leaf_fn(tuple(s.shape), t), shapes, tp_specs)

    def secondary_param_spec(self, shape: Tuple[int, ...],
                             base_spec: Optional[PartitionSpec] = None) -> PartitionSpec:
        """hpZ secondary-copy spec: sharded over the inner axes only."""
        return shard_leaf_spec(
            shape, self.secondary_axes, base_spec,
            threshold=self.config.stage3_param_persistence_threshold,
            axes_size=self.secondary_size,
            axis_sizes=self._axis_sizes(self.secondary_axes),
        )

    def param_shardings(self, param_shapes: Any, tp_specs: Optional[Any] = None) -> Any:
        mesh = self.topo.mesh
        specs = self._tree_specs(param_shapes, tp_specs, self.param_spec)
        return jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), specs,
                                      is_leaf=lambda x: isinstance(x, PartitionSpec))

    def secondary_param_shardings(self, param_shapes: Any,
                                  tp_specs: Optional[Any] = None) -> Any:
        """hpZ secondary (compute-copy) shardings — replicated over the outer
        'data' factor, sharded over 'zshard' (+ seq)."""
        mesh = self.topo.mesh
        specs = self._tree_specs(param_shapes, tp_specs, self.secondary_param_spec)
        return jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), specs,
                                      is_leaf=lambda x: isinstance(x, PartitionSpec))

    def grad_shardings(self, param_shapes: Any, tp_specs: Optional[Any] = None) -> Any:
        """Gradient placement: sharded like optimizer state from stage 2 up
        (reduce-scatter), like params otherwise (psum)."""
        mesh = self.topo.mesh
        if self.config.stage >= 2:
            specs = self._tree_specs(param_shapes, tp_specs, self.state_spec)
        else:
            specs = self._tree_specs(param_shapes, tp_specs, self.param_spec)
        return jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), specs,
                                      is_leaf=lambda x: isinstance(x, PartitionSpec))

    def opt_state_shardings(self, opt_state_shapes: Any,
                            param_shapes: Any = None,
                            tp_specs: Optional[Any] = None) -> Any:
        """Sharding pytree for an optax-style optimizer state.

        A moment lies as its gradient does. Every subtree of the state
        that is shaped like the parameters (the parameters' tree structure
        and leaf shapes: ``AdamState.mu`` / ``.nu``, a momentum, an
        accumulator, an ``optax`` state's likewise — found by structure,
        not by a class's name) takes, leaf by leaf, ``state_spec(shape,
        tp_spec)``: the rule ``grad_shardings`` applies from stage 2 up, and
        the master's own wherever the master is partitioned (``param_spec``
        differs only under ``stage3_param_persistence_threshold``). The
        elementwise update then reads ``g``, ``mu``, ``nu`` and the master
        in one layout and holds no collective of its own. A model's spec
        occupies its dimension even where its axis has size 1, so a rule
        that sees shapes alone cuts another dimension than the gradient's,
        and the update pays a float32 all-to-all a disagreeing leaf a
        moment (docs/communication.md "Where a moment lies").

        Every other leaf keeps the shape-only rule: any leaf whose shape
        can host the ZeRO axes is sharded (the big consumers the reference
        partitions in stage_1_and_2.py:97), scalars (step counts, loss
        scale) replicate. Without ``tp_specs`` that is the rule for every
        leaf: there is nothing to disagree with.
        """
        mesh = self.topo.mesh
        shape_of = lambda x: tuple(getattr(x, "shape", ()))

        def place(leaf, base_spec=None):
            return NamedSharding(mesh, self.state_spec(shape_of(leaf), base_spec))

        if tp_specs is None or param_shapes is None:
            return jax.tree_util.tree_map(place, opt_state_shapes)
        treedef = jax.tree_util.tree_structure(param_shapes)
        shapes = [shape_of(p) for p in jax.tree_util.tree_leaves(param_shapes)]

        def like_params(node):
            return (jax.tree_util.tree_structure(node) == treedef
                    and [shape_of(x) for x in jax.tree_util.tree_leaves(node)]
                    == shapes)

        return jax.tree_util.tree_map(
            lambda node: (jax.tree_util.tree_map(place, node, tp_specs)
                          if like_params(node) else place(node)),
            opt_state_shapes, is_leaf=like_params)


def compute_param_bytes(param_shapes: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(param_shapes):
        total += int(np.prod(leaf.shape)) * jax.numpy.dtype(leaf.dtype).itemsize
    return total


# ======================================================================
# T3-style staged ZeRO-3 overlap schedule (docs/communication.md)
#
# GSPMD inserts ZeRO-3's all-gathers and reduce-scatters wherever the
# sharding constraints demand them, but the whole backward is one opaque
# jax.grad: the compiler sees one giant gather-everything /
# reduce-everything dataflow and its latency-hiding scheduler has nothing
# block-shaped to pipeline. The staged schedule splits the model into
# sequential blocks and issues each block's collectives EAGERLY — block
# i+1's weight all-gather before block i's forward compute, block i+1's
# gradient reduce-scatter deferred behind block i's backward — which is
# exactly the software-pipelined schedule T3 (arxiv 2401.16677) fuses in
# hardware and the reference builds with fetch/release hooks + prefetch
# (partitioned_param_coordinator.py:256). Same dataflow, per-block
# granularity, overlap-friendly issue order; serial mode issues every
# collective immediately at its consumer for the A/B.

@dataclass
class BlockProgram:
    """A model decomposed into sequential blocks for the staged ZeRO-3
    schedule. ``block_fns[i](p_i, h) -> h'`` consumes the FULL (gathered)
    params of block i; ``h0`` is the first block's input (derived from
    the batch); ``loss_tail(h) -> scalar loss`` closes over batch/rng;
    ``merge(block_trees) -> params_tree`` reassembles per-block pytrees
    (e.g. gradients) into the model's parameter-tree structure. A model
    opts into the staged engine path by exposing
    ``zero3_blocks(params, batch, rng) -> BlockProgram``; the params
    argument must be handled structurally (the engine also calls it on a
    PartitionSpec tree to learn per-block shardings)."""

    block_fns: List[Callable[[Any, Any], Any]]
    blocks: List[Any]
    h0: Any
    loss_tail: Callable[[Any], Any]
    merge: Callable[[List[Any]], Any]


def _probed(probe, phase: str, i: int, fn):
    """Measurement seam for the schedule's per-block phases. ``probe``
    is a plain callable ``(phase, block_index, thunk) -> thunk()``
    installed ONLY by the host-side overlap profiler
    (profiling/overlap.py), which times each phase around the thunk; in
    every jitted use probe is None and this is a plain call — identical
    dataflow, no trace-time side effects."""
    if probe is None:
        return fn()
    return probe(phase, i, fn)


class Zero3BlockSchedule:
    """Explicit per-block forward/backward with pluggable (compressed)
    collectives. ``gather(i, block_shard) -> block_full`` and
    ``reduce(i, block_grads_full) -> block_grads_reduced`` come from the
    comm facade; ``overlapped`` picks the issue order (True = T3-style
    prefetch/defer, False = serial). Both orders have identical dataflow
    — results are bit-exact to each other by construction, and the tests
    pin that so neither path can drift semantically.

    Memory contract (the stage-3 point): the forward keeps only the
    per-block ACTIVATIONS; full block params are live just for their own
    stage. The backward RE-GATHERS each block and recomputes its forward
    to build the vjp (activation checkpointing at block boundaries —
    the reference's fetch/release + prefetch schedule,
    partitioned_param_coordinator.py:256). That is the 2-gathers + 1-
    reduce per step ``comm.compressed.modeled_exposure`` books; holding
    every vjp residual instead would keep the whole unsharded model
    resident and forfeit ZeRO-3 partitioning at exactly the scale this
    schedule targets."""

    def __init__(self, gather: Callable[[int, Any], Any],
                 reduce: Callable[[int, Any], Any],
                 overlapped: bool = True,
                 probe: Optional[Callable] = None):
        self.gather = gather
        self.reduce = reduce
        self.overlapped = overlapped
        # per-block phase-timing seam (see :func:`_probed`): None on
        # every jitted path; the overlap profiler installs one to time
        # gather/fwd/regather/bwd/reduce per block on the host
        self.probe = probe

    def loss_and_grads(self, prog: BlockProgram, scale) -> Tuple[Any, List[Any]]:
        """(loss, per-block grad trees). Grads are wrt the FULL block
        params (each rank's local-batch contribution, reduced across the
        ZeRO group by ``reduce``); the loss comes back unreduced — the
        caller averages it over the data axes."""
        L = len(prog.block_fns)
        assert L == len(prog.blocks) and L > 0
        probe = self.probe

        def _gather(i, phase="gather"):
            return _probed(probe, phase, i,
                           lambda: self.gather(i, prog.blocks[i]))

        def _reduce(i, g):
            return _probed(probe, "reduce", i, lambda: self.reduce(i, g))

        # -- forward: prefetch next gather, save activations only
        hs: List[Any] = [prog.h0]
        h = prog.h0
        full = _gather(0)
        for i in range(L):
            nxt = None
            if self.overlapped and i + 1 < L:
                # prefetch: next block's gather issued BEFORE this
                # block's compute consumes anything
                nxt = _gather(i + 1)
            h = _probed(probe, "fwd", i,
                        lambda: prog.block_fns[i](full, h))
            hs.append(h)
            if i + 1 < L:
                full = nxt if self.overlapped else _gather(i + 1)
        loss, tail_vjp = jax.vjp(prog.loss_tail, h)
        (g_h,) = tail_vjp(jnp.ones_like(loss) * scale)
        # -- backward: re-gather + recompute each block's vjp; defer the
        # previous block's reduce behind this block's compute
        grads: List[Any] = [None] * L
        pending = None
        pending_i = -1
        full = _gather(L - 1, phase="regather")
        for i in reversed(range(L)):
            nxt = None
            if self.overlapped and i > 0:
                nxt = _gather(i - 1, phase="regather")

            def _bwd(i=i, full=full, g=g_h):
                _, vjp = jax.vjp(prog.block_fns[i], full, hs[i])
                return vjp(g)

            g_full, g_h = _probed(probe, "bwd", i, _bwd)
            if self.overlapped:
                if pending is not None:
                    grads[pending_i] = _reduce(pending_i, pending)
                pending, pending_i = g_full, i
            else:
                grads[i] = _reduce(i, g_full)
            if i > 0:
                full = nxt if self.overlapped else _gather(i - 1,
                                                           phase="regather")
        if pending is not None:
            grads[pending_i] = _reduce(pending_i, pending)
        return loss, grads


class SequentialBlockModel:
    """Reference implementation of the ``zero3_blocks`` protocol: a stack
    of dense layers with a mean-squared-error tail. This is the model
    the staged-schedule tests, the quant-comm smoke and the MULTICHIP
    comm lane drive — small enough to verify bit-level on CPU, block-
    structured enough that every per-block collective is visible.

    ``loss(params, batch, rng)`` is the composed (non-staged) path, used
    for eval parity and as the bit-level reference for the schedule."""

    def __init__(self, dims: Sequence[int], seed: int = 0):
        if len(dims) < 3:
            raise ValueError("SequentialBlockModel needs >= 2 layers")
        self.dims = tuple(int(d) for d in dims)
        self.seed = seed

    @property
    def n_blocks(self) -> int:
        return len(self.dims) - 1

    def init(self, rng) -> Any:
        params = {}
        for i in range(self.n_blocks):
            rng, k = jax.random.split(rng)
            params[f"block_{i}"] = {
                "w": jax.random.normal(
                    k, (self.dims[i], self.dims[i + 1]), jnp.float32) * 0.05,
                "b": jnp.zeros((self.dims[i + 1],), jnp.float32),
            }
        return params

    @staticmethod
    def _apply_block(p: Any, h: Any, last: bool) -> Any:
        y = h @ p["w"] + p["b"]
        return y if last else jnp.tanh(y)

    def loss(self, params, batch, rng=None):
        h = batch["x"]
        for i in range(self.n_blocks):
            h = self._apply_block(params[f"block_{i}"], h,
                                  last=(i == self.n_blocks - 1))
        return jnp.mean((h - batch["y"]) ** 2)

    def zero3_blocks(self, params, batch, rng=None) -> BlockProgram:
        L = self.n_blocks
        blocks = [params[f"block_{i}"] for i in range(L)]

        def block_fn(i):
            last = i == L - 1
            return lambda p, h: self._apply_block(p, h, last)

        def loss_tail(h):
            return jnp.mean((h - batch["y"]) ** 2)

        def merge(trees: List[Any]) -> Any:
            return {f"block_{i}": t for i, t in enumerate(trees)}

        h0 = batch["x"] if isinstance(batch, dict) else batch
        return BlockProgram(block_fns=[block_fn(i) for i in range(L)],
                            blocks=blocks, h0=h0, loss_tail=loss_tail,
                            merge=merge)
