"""Measured (not modeled) ZeRO-3 comm-overlap accounting.

The comm-overlap claims of the compressed-collectives facade are
certified by ``comm.compressed.modeled_exposure`` — an
*analytic* T3 model (bytes / bandwidth vs uniform compute windows).
This module is the layer that keeps those claims honest:
:func:`overlap_report` drives the REAL :class:`~deepspeed_tpu.parallel
.zero.Zero3BlockSchedule` eagerly on the host — every per-block phase
(weight gather, forward, backward re-gather, backward, gradient
reduce) is its own jitted program, timed fence-to-fence through the
schedule's probe seam — and then applies the schedule's own issue-order
semantics to the **measured** durations:

* ``serial comm``   = every gather/regather/reduce fully exposed;
* ``overlapped``    = pipeline fill (block 0's gather, block L-1's
  re-gather) + drain (block 0's reduce) + per-block excess where a
  block's comm outruns the compute window it hides behind — exactly the
  accounting ``modeled_exposure`` books, but with per-block measured
  times instead of uniform bytes-over-bandwidth estimates.

The comparison against the model is apples-to-apples by construction:
the link bandwidth fed to ``modeled_exposure`` is *calibrated* so the
model's serial comm time equals the measured serial comm time, and the
model's compute budget is the measured compute total — so any
measured-vs-modeled disagreement isolates exactly the model's
uniformity assumptions (equal per-block comm, fwd:bwd = 1:2 windows),
which is what the trace lane's agreement band gates
(``scripts/trace_smoke.py`` → ``TIMELINE_r01.json``).

Wire bytes are joined from the CommsLogger ledger: each per-block
collective program books its (logical, wire) bytes at trace time, so
the report carries the physical volume behind every measured duration.

Timelines land in the tracer (telemetry/tracing.py) on two tracks —
the real serial drive as it executed, and the accounted overlapped
schedule at its computed offsets — exportable as Chrome-trace JSON next
to the serving request trees. Under any ``jax.profiler`` session the
measured phases also appear on the profiler host track (scoped tracer
spans go through ``profiling.trace.annotate``).
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Optional

__all__ = ["overlap_report", "PhaseTimings"]


class PhaseTimings:
    """The schedule probe: times each (phase, block) thunk fence-to-
    fence on the host clock and forwards the result unchanged. Installed
    on :class:`~deepspeed_tpu.parallel.zero.Zero3BlockSchedule` via its
    ``probe`` seam — only ever on the eager measurement drive, never
    inside jit."""

    def __init__(self, clock=None, tracer=None, track: str = "zero3"):
        from ..resilience.clock import get_clock

        self.clock = clock if clock is not None else get_clock()
        self.tracer = tracer
        self.track = track
        self.durations: Dict[tuple, List[float]] = {}

    def __call__(self, phase: str, i: int, fn: Callable[[], Any]) -> Any:
        import jax

        sp = None
        if self.tracer is not None and self.tracer.enabled:
            sp = self.tracer.span(f"zero3/{phase}", track=self.track,
                                  block=i)
            sp.__enter__()
        try:
            t0 = self.clock.now()
            out = fn()
            # fence: jitted programs return before the work completes
            jax.tree_util.tree_map(
                lambda x: x.block_until_ready()
                if hasattr(x, "block_until_ready") else x, out)
            self.durations.setdefault((phase, i), []).append(
                self.clock.now() - t0)
            return out
        finally:
            # a raising program must not leave the span open on the
            # thread-local stack (later spans would mis-parent under it)
            # nor leak an active profiler annotation
            if sp is not None:
                sp.__exit__(None, None, None)

    def reset(self) -> None:
        self.durations.clear()

    def median(self, phase: str, i: int) -> float:
        durs = self.durations.get((phase, i), [])
        return statistics.median(durs) if durs else 0.0


def _tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))


def _tree_numel(tree) -> int:
    import jax

    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))


def _ledger_delta(before: Dict[str, Dict[str, float]],
                  after: Dict[str, Dict[str, float]]
                  ) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for op, cur in after.items():
        prev = before.get(op, {})
        d = {k: cur[k] - prev.get(k, 0.0) for k in cur}
        if any(d.get(k) for k in ("count", "bytes", "wire_bytes")):
            out[op] = d
    return out


def overlap_report(engine, batch, *, repeats: int = 3,
                   agreement_band: float = 3.0,
                   tracer=None, clock=None) -> Dict[str, Any]:
    """Measure per-block ZeRO-3 phase timelines on ``engine``'s model
    and compare measured comm exposure against ``modeled_exposure``.

    ``engine`` must be a staged-capable TrainEngine (its model exposes
    ``zero3_blocks``); ``batch`` a host batch like ``train_batch``
    takes. Runs one warmup drive (compiles every per-block program, and
    books their ledger rows) plus ``repeats`` timed drives; per-phase
    durations are medians. Returns the report dict (see
    docs/performance.md "Measured vs modeled exposure"); raises
    ``ValueError`` on unmeasurable geometry. The ``agreement_band`` is
    recorded in the report; gating is the caller's job (the trace lane
    gates measured/modeled within the documented band)."""
    import jax
    import jax.numpy as jnp

    from ..comm import compressed as ccomm
    from ..comm.comm import configure_comms_logger, get_comms_logger
    from ..parallel.zero import Zero3BlockSchedule
    from ..resilience.clock import get_clock
    from ..telemetry.tracing import get_tracer

    if not hasattr(engine.model, "zero3_blocks"):
        raise ValueError("overlap_report needs a model exposing "
                         "zero3_blocks (the staged ZeRO-3 protocol)")
    clock = clock if clock is not None else get_clock()
    tracer = tracer if tracer is not None else get_tracer()
    PartitionSpec = jax.sharding.PartitionSpec

    env = engine._facade_prelude(engine.params, batch)
    prog_struct = engine.model.zero3_blocks(env["pc_specs"], None)
    block_specs = prog_struct.blocks
    prog = engine.model.zero3_blocks(env["pc"], batch, None)
    L = len(prog.block_fns)
    world = env["outer_world"] * env["inner_world"]
    rep = PartitionSpec()
    is_spec = env["is_spec"]

    def rep_tree(i):
        return jax.tree_util.tree_map(lambda _: rep, block_specs[i],
                                      is_leaf=is_spec)

    # per-block collectives as standalone jitted shard_map programs —
    # XLA collectives only run inside compiled programs, so (like
    # measure_comm_latencies) each phase is its own fenced executable
    def make_gather(i):
        def g(blk):
            return jax.tree_util.tree_map(
                lambda x, spec: ccomm.gather_param_leaf(
                    x, spec,
                    outer_axes=(env["outer"],) if env["outer"] else (),
                    qspec=env["wq"]),
                blk, block_specs[i], is_leaf=is_spec)

        return jax.jit(jax.shard_map(
            g, mesh=engine.topo.mesh, axis_names=set(env["axes"]),
            in_specs=(block_specs[i],), out_specs=rep_tree(i),
            check_vma=False))

    def make_reduce(i):
        def r(gtree):
            return ccomm.tree_hierarchical_pmean(
                gtree, outer_axis=env["outer"],
                outer_world=env["outer_world"], inner_axis=env["inner"],
                inner_world=env["inner_world"], qspec=env["gq"])

        return jax.jit(jax.shard_map(
            r, mesh=engine.topo.mesh, axis_names=set(env["axes"]),
            in_specs=(rep_tree(i),), out_specs=rep_tree(i),
            check_vma=False))

    gathers = [make_gather(i) for i in range(L)]
    reduces = [make_reduce(i) for i in range(L)]
    jit_fns = [jax.jit(f) for f in prog.block_fns]
    prog.block_fns = jit_fns

    log = get_comms_logger()
    was_enabled = log.enabled
    configure_comms_logger(True)
    probe = PhaseTimings(clock=clock, tracer=tracer,
                         track="zero3/measured")
    sched = Zero3BlockSchedule(
        gather=lambda i, blk: gathers[i](blk),
        reduce=lambda i, g: reduces[i](g),
        overlapped=False, probe=probe)
    scale = jnp.ones([], jnp.float32)

    # warmup drive: compiles every program and books its ledger rows
    # (record_collective fires at trace time); the per-block wire join
    # is the ledger delta across each phase's first execution
    wire: Dict[tuple, Dict[str, Dict[str, float]]] = {}

    def warm_probe(phase, i, fn):
        before = log.snapshot_totals()
        out = probe(phase, i, fn)
        wire[(phase, i)] = _ledger_delta(before, log.snapshot_totals())
        return out

    try:
        sched.probe = warm_probe
        sched.loss_and_grads(prog, scale)
        probe.reset()
        sched.probe = probe
        for _ in range(max(1, int(repeats))):
            loss, _ = sched.loss_and_grads(prog, scale)
    finally:
        # a raising drive must not leave the process-global ledger
        # enabled on callers that never asked for it
        if not was_enabled:
            configure_comms_logger(False)

    g = [probe.median("gather", i) for i in range(L)]
    f = [probe.median("fwd", i) for i in range(L)]
    rg = [probe.median("regather", i) for i in range(L)]
    b = [probe.median("bwd", i) for i in range(L)]
    r = [probe.median("reduce", i) for i in range(L)]
    compute_s = sum(f) + sum(b)

    def wire_sum(phase, i):
        return sum(d.get("wire_bytes", 0.0)
                   for d in wire.get((phase, i), {}).values())

    blocks = [{
        "block": i,
        "gather_s": g[i], "fwd_s": f[i], "regather_s": rg[i],
        "bwd_s": b[i], "reduce_s": r[i],
        "gather_wire_bytes": wire_sum("gather", i),
        # the backward re-gather hits the SAME compiled program as the
        # forward gather (jit cache), so its trace-time ledger delta is
        # empty — it moves the gather's wire again
        "regather_wire_bytes": (wire_sum("regather", i)
                                or wire_sum("gather", i)),
        "reduce_wire_bytes": wire_sum("reduce", i),
    } for i in range(L)]

    # the schedule's issue-order overlap accounting over MEASURED times:
    # fwd — gather(i) hides behind fwd(i-1), gather(0) is the fill;
    # bwd — regather(i-1) and reduce(i) hide behind bwd(i), regather of
    # block L-1 is the fill and block 0's reduce the drain
    fwd_fill = g[0]
    fwd_excess = sum(max(0.0, g[i] - f[i - 1]) for i in range(1, L))
    bwd_fill = rg[L - 1]
    drain = r[0]
    bwd_excess = sum(max(0.0, rg[i] + r[i + 1] - b[i + 1])
                     for i in range(L - 1))
    measured_overlapped = fwd_fill + fwd_excess + bwd_fill + drain \
        + bwd_excess
    measured_serial = sum(g) + sum(rg) + sum(r)

    # calibrated model comparison (see module docstring): bandwidth such
    # that the model's serial comm equals the measured serial comm
    param_bytes = _tree_bytes(env["pc"])
    numel_w = _tree_numel(env["pc"])
    w_itemsize = max(1, param_bytes // max(1, numel_w))
    grad_itemsize = w_itemsize          # grads reduce in compute dtype
    grad_bytes = numel_w * grad_itemsize
    wq, gq = env["wq"], env["gq"]
    w_wire = wq.wire_nbytes(numel_w) if wq else param_bytes
    g_wire = gq.wire_nbytes(numel_w) if gq else grad_bytes
    frac = (world - 1) / world if world > 1 else 0.0
    modeled = None
    agreement = None
    link_bps = None
    if frac > 0.0 and measured_serial > 0.0:
        link_bps = (2 * w_wire + g_wire) * frac / measured_serial
        modeled = ccomm.modeled_exposure(
            param_bytes=param_bytes, grad_bytes=grad_bytes, n_blocks=L,
            compute_s=compute_s, link_bps=link_bps, world=world,
            weight_qspec=wq, grad_qspec=gq,
            weight_itemsize=w_itemsize, grad_itemsize=grad_itemsize)
        if modeled["overlapped_compressed_s"] > 0.0:
            agreement = (measured_overlapped
                         / modeled["overlapped_compressed_s"])

    # assembled overlapped forward timeline on its own tracer track:
    # gather(i) drawn concurrent with fwd(i-1) exactly as the schedule
    # issues it, next to the measured serial drive — one Chrome export
    # shows the real phases and where the accounting hides them
    if tracer.enabled:
        t0 = clock.time()
        fwd_start = [0.0] * L
        fwd_start[0] = g[0]
        tracer.span_complete("zero3/gather[0]", t0, t0 + g[0],
                             track="zero3/accounted", block=0)
        for i in range(1, L):
            g_start = fwd_start[i - 1]          # issued with fwd(i-1)
            tracer.span_complete(f"zero3/gather[{i}]", t0 + g_start,
                                 t0 + g_start + g[i],
                                 track="zero3/accounted", block=i)
            fwd_start[i] = max(fwd_start[i - 1] + f[i - 1],
                               g_start + g[i])
        for i in range(L):
            tracer.span_complete(f"zero3/fwd[{i}]", t0 + fwd_start[i],
                                 t0 + fwd_start[i] + f[i],
                                 track="zero3/accounted", block=i)

    ledger_totals: Dict[str, Dict[str, float]] = {}
    for d in wire.values():
        for op, entry in d.items():
            cur = ledger_totals.setdefault(
                op, {"count": 0.0, "bytes": 0.0, "wire_bytes": 0.0})
            for k in cur:
                cur[k] += entry.get(k, 0.0)

    report = {
        "n_blocks": L,
        "world": world,
        "axes": list(env["axes"]),
        "repeats": int(repeats),
        "loss": float(jax.device_get(loss)),
        "blocks": blocks,
        "compute_s": compute_s,
        "measured": {
            "serial_comm_s": measured_serial,
            "overlapped_exposed_s": measured_overlapped,
            "fwd_fill_s": fwd_fill, "fwd_excess_s": fwd_excess,
            "bwd_fill_s": bwd_fill, "drain_s": drain,
            "bwd_excess_s": bwd_excess,
        },
        "modeled": modeled,
        "calibrated_link_bps": link_bps,
        "agreement_ratio": agreement,
        "agreement_band": float(agreement_band),
        "wire": {
            "param_bytes": param_bytes, "grad_bytes": grad_bytes,
            "w_wire_model": w_wire, "g_wire_model": g_wire,
            "ledger": ledger_totals,
        },
    }
    return report
