"""Distributed communication facade.

Capability parity with the reference's ``deepspeed/comm/comm.py`` (module-level
``init_distributed`` / ``all_reduce`` / ``all_gather`` / ``reduce_scatter`` /
``all_to_all_single`` / ``barrier`` plus the ``timed_op`` profiling decorator
and CommsLogger), rebuilt for XLA: collectives are ``jax.lax`` primitives that
only exist *inside* a compiled, mesh-mapped program, so this facade has two
faces:

1. **In-program collectives** — thin wrappers over ``jax.lax.psum`` /
   ``all_gather`` / ``psum_scatter`` / ``all_to_all`` / ``ppermute`` taking a
   mesh-axis name where the reference takes a process group. These are what
   engine/MoE/Ulysses code calls inside ``shard_map``. Each call records an
   event with the CommsLogger at trace time (XLA schedules the actual
   transfer; per-op wall times come from the profiler, matching how the
   reference's ``timed_op`` numbers are produced by CUDA events).

2. **Host-level process management** — ``init_distributed`` maps to
   ``jax.distributed.initialize`` (rendezvous via coordinator address instead
   of MASTER_ADDR/NCCL), ``get_rank``/``get_world_size`` map to
   ``jax.process_index``/``process_count``, and ``barrier`` outside jit is a
   tiny psum across all devices.

Reference: deepspeed/comm/comm.py:604 (init_distributed), :483 (all_reduce),
:228 (all_gather), :446 (reduce_scatter), :331 (all_to_all_single),
:406 (barrier), :101 (timed_op), utils/comms_logging.py:67 (CommsLogger).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import log_dist, logger


class ReduceOp(Enum):
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


# ----------------------------------------------------------------------
# Comms logging (reference utils/comms_logging.py)

def _get_bw(comm_op: str, size_bytes: int, duration_s: float, n: int) -> tuple:
    """Algorithmic and bus bandwidth in GB/s. Mirrors reference
    ``calc_bw_log`` (utils/comms_logging.py:34)."""
    if duration_s <= 0:
        return 0.0, 0.0
    size_gb = size_bytes / 1e9
    algbw = size_gb / duration_s
    if comm_op in ("all_reduce", "reduce"):
        busbw = algbw * (2 * (n - 1) / n) if n > 0 else algbw
    elif comm_op in ("all_gather", "reduce_scatter", "all_to_all", "gather",
                     "sparse_allreduce"):
        busbw = algbw * ((n - 1) / n) if n > 0 else algbw
    else:
        busbw = algbw
    return algbw, busbw


@dataclass
class CommsLogger:
    """Records per-op counts/sizes at trace time; real latencies come from
    :func:`measure_comm_latencies`, which replays every recorded
    (op, size, axis) as a standalone timed program on the live mesh — the
    TPU analog of the reference's CUDA-event ``timed_op`` (comm.py:101),
    since XLA collectives only execute inside compiled programs.

    ``log_summary()`` prints the table like ``dist.log_summary`` in the
    reference (comm/comm.py:422), with algbw/busbw once measured.
    """

    enabled: bool = False
    verbose: bool = False
    records: Dict[str, Dict[int, List[float]]] = field(default_factory=dict)
    axes: Dict[tuple, str] = field(default_factory=dict)
    worlds: Dict[tuple, int] = field(default_factory=dict)
    # bytes-on-wire ledger (docs/communication.md): cumulative PHYSICAL
    # bytes per (op, logical_size) — differs from the logical payload only
    # for compressed collectives (comm/compressed.py), where the wire
    # carries int8/int4 + scales instead of the fp tensor
    wire: Dict[tuple, float] = field(default_factory=dict)

    def append(self, op_name: str, size_bytes: int, duration_s: float,
               world: int, axis_name: Optional[str] = None,
               wire_bytes: Optional[int] = None) -> None:
        if not self.enabled:
            return
        per_op = self.records.setdefault(op_name, {})
        per_op.setdefault(size_bytes, []).append(duration_s)
        if axis_name is not None:
            self.axes[(op_name, size_bytes)] = axis_name
        if world:
            self.worlds[(op_name, size_bytes)] = world
        wire = size_bytes if wire_bytes is None else int(wire_bytes)
        key = (op_name, size_bytes)
        self.wire[key] = self.wire.get(key, 0.0) + wire
        # unified telemetry: every recorded collective also lands in the
        # shared metrics registry, so comm volume shows up next to step
        # time in the exporters without a separate pipeline
        from ..telemetry.registry import get_registry

        reg = get_registry()
        reg.counter(f"comm/{op_name}/calls").inc()
        reg.counter(f"comm/{op_name}/bytes").inc(size_bytes)
        reg.counter(f"comm/{op_name}/wire_bytes").inc(wire)
        if wire < size_bytes:
            # compression ratio is a trace-time static (shapes + dtypes),
            # safe to observe here; per-op history for the exporters
            reg.histogram(f"comm/{op_name}/compression_ratio").observe(
                size_bytes / max(wire, 1))
        if self.verbose:
            algbw, busbw = _get_bw(op_name, size_bytes, duration_s, world)
            log_dist(
                f"comm op: {op_name} | msg size: {size_bytes} B | time: {duration_s * 1e3:.3f} ms"
                f" | algbw: {algbw:.2f} GB/s | busbw: {busbw:.2f} GB/s"
            )

    def backfill(self, op_name: str, size_bytes: int, duration_s: float) -> None:
        """Replace trace-time placeholder durations with a measured one."""
        durs = self.records.get(op_name, {}).get(size_bytes)
        if durs:
            self.records[op_name][size_bytes] = [duration_s] * len(durs)

    def log_summary(self) -> str:
        lines = [f"{'Comm. Op':<20}{'Message Size':>16}{'Count':>8}"
                 f"{'Total Lat(ms)':>16}{'Avg Lat(ms)':>14}"
                 f"{'algbw(GB/s)':>14}{'busbw(GB/s)':>14}"]
        for op, sizes in self.records.items():
            lines.append(op)
            for size, durs in sorted(sizes.items()):
                total = sum(durs) * 1e3
                avg = total / len(durs)
                world = self.worlds.get((op, size), 0)
                algbw, busbw = _get_bw(op, size, avg / 1e3, world)
                lines.append(f"{'':<20}{size:>16}{len(durs):>8}{total:>16.2f}"
                             f"{avg:>14.3f}{algbw:>14.2f}{busbw:>14.2f}")
        table = "\n".join(lines)
        logger.info(table)
        return table

    def snapshot_totals(self) -> Dict[str, Dict[str, float]]:
        """Aggregate per-op totals for StepStats: {op: {count, bytes,
        wire_bytes, time_s}}. Counts/bytes are trace-time facts (the
        collectives the compiled program contains); ``wire_bytes`` is the
        physical volume after compression (== ``bytes`` for uncompressed
        ops — the v2 schema field; archived v1 snapshots without it keep
        validating, see telemetry.spans.validate_step_record); time_s sums
        the recorded durations, which are real only after
        :func:`measure_comm_latencies` backfills them."""
        out: Dict[str, Dict[str, float]] = {}
        for op, sizes in self.records.items():
            count = bytes_total = wire_total = time_total = 0.0
            for size, durs in sizes.items():
                count += len(durs)
                bytes_total += size * len(durs)
                wire_total += self.wire.get((op, size), size * len(durs))
                time_total += sum(durs)
            out[op] = {"count": count, "bytes": bytes_total,
                       "wire_bytes": wire_total, "time_s": time_total}
        return out

    def reset(self) -> None:
        self.records.clear()
        self.axes.clear()
        self.worlds.clear()
        self.wire.clear()


_COMMS_LOGGER = CommsLogger()


def get_comms_logger() -> CommsLogger:
    return _COMMS_LOGGER


def configure_comms_logger(enabled: bool, verbose: bool = False) -> None:
    _COMMS_LOGGER.enabled = enabled
    _COMMS_LOGGER.verbose = verbose


def log_summary() -> str:
    return _COMMS_LOGGER.log_summary()


def _nbytes(x: Any) -> int:
    try:
        return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    except Exception:
        return 0


# chaos hook: resilience.chaos.install_fault_injector points this at the
# installed FaultInjector's on_collective (delay/fail injection for the
# fault-tolerance tests). None = zero overhead on every facade call.
_CHAOS_HOOK = None


def _record(op: str, x: Any, axis_name: Optional[str]) -> None:
    # Inside jit the transfer can't be timed at the call site (XLA schedules
    # it); record op/size/axis now, measure_comm_latencies() backfills real
    # durations via timed standalone replays.
    if _CHAOS_HOOK is not None:
        _CHAOS_HOOK(op)
    _COMMS_LOGGER.append(op, _nbytes(x), 0.0, 0, axis_name)


def record_collective(op: str, logical_bytes: int, wire_bytes: int,
                      axis_name: Optional[str] = None, world: int = 0) -> None:
    """Bytes-on-wire ledger entry for a facade-issued collective
    (comm/compressed.py): ``logical_bytes`` is what the uncompressed path
    would move per rank, ``wire_bytes`` the physical payload actually on
    the wire (quantized + scales). Routes through the same chaos hook and
    CommsLogger as the thin lax wrappers above."""
    if _CHAOS_HOOK is not None:
        _CHAOS_HOOK(op)
    _COMMS_LOGGER.append(op, int(logical_bytes), 0.0, world, axis_name,
                         wire_bytes=int(wire_bytes))


def measure_comm_latencies(mesh=None, iters: int = 10) -> str:
    """Replay every recorded collective on the live mesh and backfill real
    per-op latencies (reference timed_op comm.py:101 / comms benchmark
    suite). Each replay chains ``iters`` data-dependent repetitions inside
    ONE jitted shard_map and fences with ``block_until_ready`` — dispatch
    overhead is amortized away. Returns the updated summary table.
    """
    from ..parallel.mesh import get_topology

    mesh = mesh if mesh is not None else get_topology().mesh
    log = _COMMS_LOGGER

    def collective(op, axis):
        if op in ("all_reduce", "reduce",
                  # facade dense reduce hops (comm/compressed.py): the
                  # wire is a psum/pmean over the axis
                  "qgz_intra_reduce", "qgz_inter_reduce_dense"):
            return lambda x: jax.lax.psum(x, axis)
        if op in ("all_gather", "gather", "sparse_allreduce",
                  # facade gather hops: the wire is an all_gather of the
                  # (quantized) payload — the replay buffer is sized by
                  # the recorded WIRE bytes below, so latency reflects
                  # what the compressed program actually moves
                  "qwz_all_gather", "hpz_all_gather",
                  "qgz_inter_all_gather", "qgz_intra_all_gather"):
            # sparse_allreduce's wire cost IS its all_gathers (rows+indices,
            # recorded as one combined payload); the scatter-add is local
            return lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True)
        if op in ("reduce_scatter", "qgz_intra_reduce_scatter"):
            return lambda x: jax.lax.psum_scatter(x, axis, tiled=True)
        if op in ("all_to_all",
                  # facade quantized reduce-scatter hop: the wire is a
                  # chunk exchange (all_to_all) of the quantized payload
                  "qgz_inter_reduce_scatter"):
            return lambda x: jax.lax.all_to_all(x, axis, 0, 0, tiled=True)
        if op in ("broadcast", "scatter"):
            # scatter's wire IS a broadcast (see scatter()); replay as one
            return lambda x: jax.lax.psum(
                jnp.where(jax.lax.axis_index(axis) == 0, x, jnp.zeros_like(x)),
                axis)
        if op == "ppermute":
            return None  # perm is call-specific; skip replay
        return None

    from jax.sharding import PartitionSpec as P

    for op, sizes in list(log.records.items()):
        for size in list(sizes):
            axis = log.axes.get((op, size))
            if axis is None or axis not in mesh.axis_names:
                continue
            world = mesh.shape[axis]
            log.worlds[(op, size)] = world
            fn = collective(op, axis)
            # replay the PHYSICAL payload: for compressed facade ops the
            # wire ledger's per-call bytes, for dense ops wire == logical
            durs = log.records[op][size]
            wire_pc = log.wire.get((op, size), size * len(durs))
            wire_pc = wire_pc / max(len(durs), 1)
            n = max(int(wire_pc) // 4, world)
            n -= n % world or 0
            if fn is None or n < world:
                continue

            def replay(x, fn=fn):
                def body(_, x):
                    y = fn(x)
                    return x + 1e-30 * jnp.sum(y)  # data dep: no DCE/overlap
                return jax.lax.fori_loop(0, iters, body, x)

            spmd = jax.shard_map(replay, mesh=mesh, axis_names={axis},
                                 in_specs=P(axis), out_specs=P(axis),
                                 check_vma=False)
            run = jax.jit(lambda x: jnp.sum(spmd(x)))
            x = jnp.zeros((world * n,), jnp.float32)
            jax.block_until_ready(run(x))  # compile + warm
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(run(x))
                best = min(best, time.perf_counter() - t0)
            log.backfill(op, size, best / iters)
    return log.log_summary()


# ----------------------------------------------------------------------
# Host-level process management

_INITIALIZED = False


def init_distributed(dist_backend: str = "xla",
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout: Optional[float] = None,
                     **_: Any) -> None:
    """Initialize multi-process JAX. Parity with reference
    ``init_distributed`` (comm/comm.py:604): idempotent, env-var driven.

    Single-process (one host owning its devices, incl. a full TPU slice via
    one controller) needs no rendezvous at all — matching how a TPU pod slice
    under a single JAX controller has no NCCL-style bootstrap.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes if num_processes is not None else int(os.environ.get("NUM_PROCESSES", "0") or 0)
    if coordinator_address and num_processes > 1:
        pid = process_id if process_id is not None else int(os.environ.get("PROCESS_ID", "0"))
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=pid,
        )
        log_dist(f"jax.distributed initialized: process {pid}/{num_processes} @ {coordinator_address}")
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank() -> int:
    return jax.process_index()


def get_world_size() -> int:
    return jax.process_count()


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", "0"))


def barrier() -> None:
    """Cross-process barrier (reference comm/comm.py:406). A tiny all-reduce
    over every addressable device forces synchronization."""
    if _CHAOS_HOOK is not None:
        _CHAOS_HOOK("barrier")
    x = jnp.ones((jax.device_count(),))
    jax.block_until_ready(
        jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(x.reshape(jax.local_device_count(), -1)[:, 0])
        if jax.process_count() > 1
        else x.sum()
    )


# ----------------------------------------------------------------------
# In-program collectives (call inside shard_map/jit over a Mesh)

def all_reduce(x, axis_name: str, op: ReduceOp = ReduceOp.SUM):
    """lax.psum/pmax/... over a named mesh axis. Reference: comm.py:483."""
    _record("all_reduce", x, axis_name)
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        y = jax.lax.psum(x, axis_name)
        if op == ReduceOp.AVG:
            y = y / jax.lax.psum(1, axis_name)
        return y
    if op == ReduceOp.MAX:
        return jax.lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(x, axis_name)
    raise NotImplementedError(f"reduce op {op}")


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """lax.all_gather over a named axis. Reference: comm.py:228."""
    _record("all_gather", x, axis_name)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, scatter_dimension: int = 0):
    """lax.psum_scatter. Reference: comm.py:446 (reduce_scatter_tensor)."""
    _record("reduce_scatter", x, axis_name)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int, tiled: bool = True):
    """lax.all_to_all. Reference: comm.py:331 (all_to_all_single)."""
    _record("all_to_all", x, axis_name)
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def broadcast(x, axis_name: str, src_index: int = 0):
    """Broadcast the src shard's value to every member of the axis.

    Reference: comm.py:217 (broadcast). Implemented as select+psum so it
    lowers to one collective.
    """
    _record("broadcast", x, axis_name)
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src_index, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def reduce(x, axis_name: str, dst_index: int = 0,
           op: ReduceOp = ReduceOp.SUM):
    """Reduce-to-one (reference comm.py reduce): every member computes the
    reduction, non-dst members get zeros — under SPMD a true single-owner
    reduce is a psum plus a mask, same wire cost."""
    _record("reduce", x, axis_name)
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        y = jax.lax.psum(x, axis_name)
        if op == ReduceOp.AVG:
            y = y / jax.lax.psum(1, axis_name)
    elif op == ReduceOp.MAX:
        y = jax.lax.pmax(x, axis_name)
    elif op == ReduceOp.MIN:
        y = jax.lax.pmin(x, axis_name)
    else:
        raise NotImplementedError(f"reduce op {op}")
    idx = jax.lax.axis_index(axis_name)
    return jnp.where(idx == dst_index, y, jnp.zeros_like(y))


def gather(x, axis_name: str, dst_index: int = 0, axis: int = 0):
    """Gather-to-one (reference comm.py gather): all_gather, masked off on
    non-dst members."""
    _record("gather", x, axis_name)
    y = jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)
    idx = jax.lax.axis_index(axis_name)
    return jnp.where(idx == dst_index, y, jnp.zeros_like(y))


def scatter(x, axis_name: str, src_index: int = 0, axis: int = 0):
    """Scatter-from-one (reference comm.py scatter): each member ends up
    with its chunk of the src member's tensor along ``axis``.

    NB: pure-SPMD collectives cannot express an asymmetric one-to-many
    send, so the wire carries a broadcast; the recorded payload is the
    algorithmic per-member chunk (what a point-to-point scatter would
    move)."""
    world = jax.lax.axis_size(axis_name)  # static inside shard_map
    if x.shape[axis] % world:
        raise ValueError(
            f"scatter: dim {axis} size {x.shape[axis]} not divisible by "
            f"axis size {world} (torch scatter errors on unequal chunks too)")
    if _CHAOS_HOOK is not None:
        _CHAOS_HOOK("scatter")
    _COMMS_LOGGER.append("scatter", max(_nbytes(x) // world, 1), 0.0, 0,
                         axis_name)
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src_index, x, jnp.zeros_like(x))
    full = jax.lax.psum(masked, axis_name)
    chunk = x.shape[axis] // world
    return jax.lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis=axis)


def sparse_allreduce(rows, indices, axis_name: str, dense_dim: int):
    """Sparse (embedding-)gradient allreduce: each rank contributes only the
    rows its batch touched — ``rows [k, d]`` at ``indices [k]`` — and the
    wire moves ``world*k*d`` elements instead of the dense ``vocab*d``.

    Reference: ``runtime/engine.py`` ``sparse_allreduce_bucket`` /
    ``sparse_gradients_enabled`` (torch SparseTensor allreduce for
    ``nn.Embedding``). Returns the dense [dense_dim, d] reduced gradient.
    Must run inside shard_map with ``axis_name`` manual; ``k`` must be
    equal across ranks (pad with a repeated index — scatter-add makes
    duplicate indices safe)."""
    # wire payload = rows AND indices (both all_gathered below)
    if _CHAOS_HOOK is not None:
        _CHAOS_HOOK("sparse_allreduce")
    _COMMS_LOGGER.append("sparse_allreduce",
                         _nbytes(rows) + _nbytes(indices), 0.0, 0, axis_name)
    rows_all = jax.lax.all_gather(rows, axis_name, axis=0, tiled=True)
    idx_all = jax.lax.all_gather(indices, axis_name, axis=0, tiled=True)
    dense = jnp.zeros((dense_dim,) + rows.shape[1:],
                      jnp.promote_types(rows.dtype, jnp.float32))
    return dense.at[idx_all].add(rows_all.astype(dense.dtype))


def ppermute(x, axis_name: str, perm):
    """Point-to-point shifts (send/recv parity for pipeline stages).

    Reference: send/recv in comm.py:356-:374 and runtime/pipe/p2p.py — on TPU
    neighbor exchange is a collective-permute riding ICI.
    """
    _record("ppermute", x, axis_name)
    return jax.lax.ppermute(x, axis_name, perm)
