"""Request-scoped distributed tracing + flight recorder
(telemetry/tracing.py, docs/observability.md).

Covers: tracer core semantics (disabled no-op, ring bounds, canonical-
hash determinism, Chrome-trace export/validation, tree audits), the
flight recorder (ring, dumps, auto-dump triggers), the serving request
path (one connected tree across queue/prefill/decode and across
replicas under failover), schema compatibility of the new optional
trace_id/span_id record fields, heartbeat recorder-health fields, the
zero-overhead-when-off contract on the fused train_steps scan, and the
measured overlap_report (profiling/overlap.py).
"""

import json

import jax
import numpy as np
import pytest

import deepspeed_tpu as dst_pkg
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.zero import SequentialBlockModel
from deepspeed_tpu.resilience.clock import SimClock, use_clock
from deepspeed_tpu.resilience.dst import (Schedule, SimConfig, SimEngine,
                                          SimEvent, _CaptureTelemetry,
                                          generate_schedule, run_schedule)
from deepspeed_tpu.telemetry import (REQUEST_RECORD_SCHEMA, RequestStats,
                                     StepStats, Tracer, get_tracer,
                                     set_telemetry, trace_tree_problems,
                                     use_tracer, validate_chrome_trace,
                                     validate_request_record,
                                     validate_step_record)
from deepspeed_tpu.telemetry.tracing import FlightRecorder


# ---------------------------------------------------------------- core
def test_default_tracer_disabled_and_noop():
    tr = get_tracer()
    assert not tr.enabled
    before = (len(tr.spans()), tr.flight.depth)
    root = tr.new_trace("request")
    assert root.is_noop
    tr.event(root, "x")                     # no-op, no raise
    tr.finish_span(root)
    with tr.span("scoped") as sp:
        assert sp.is_noop
    # nothing accumulated (the shared singleton may predate this test)
    assert (len(tr.spans()), tr.flight.depth) == before
    fresh = Tracer(enabled=False)
    fresh.new_trace("x")
    assert fresh.spans() == [] and fresh.flight.depth == 0


def test_scoped_spans_nest_and_parent():
    tr = Tracer(enabled=True)
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]
    assert all(s.t_end is not None for s in spans)
    assert trace_tree_problems(spans) == []


def test_explicit_segments_cross_frame():
    tr = Tracer(enabled=True)
    root = tr.new_trace("request", prompt_tokens=3)
    seg = tr.begin_span("queue", root, track="replica-0")
    tr.event(root, "preempt", replica="replica-0")
    tr.finish_span(seg)
    tr.finish_span(root, state="finished")
    spans = tr.spans_for_trace(root.trace_id)
    assert trace_tree_problems(spans) == []
    assert {s.name for s in spans} == {"request", "queue"}
    [r] = [s for s in spans if s.name == "request"]
    assert r.attrs["state"] == "finished"
    assert [e[1] for e in r.events] == ["preempt"]


def test_ring_bound_and_dropped_count():
    tr = Tracer(enabled=True, ring_size=4)
    for i in range(7):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 4
    assert tr.dropped == 3
    assert [s.name for s in tr.spans()] == ["s3", "s4", "s5", "s6"]


def test_canonical_hash_deterministic_across_fresh_tracers():
    def run(tracer):
        clock = SimClock()
        with use_clock(clock):
            root = tracer.new_trace("request", prompt_tokens=5,
                                    uid=object())   # volatile: excluded
            clock.advance(1.0)
            seg = tracer.begin_span("queue", root, track="replica-0")
            clock.advance(2.0)
            tracer.finish_span(seg)
            tracer.finish_span(root, state="finished")
        return tracer.canonical_hash()

    h1, h2 = run(Tracer(enabled=True)), run(Tracer(enabled=True))
    assert h1 == h2
    # a structural difference must change the hash
    t3 = Tracer(enabled=True)
    with use_clock(SimClock()):
        tr_root = t3.new_trace("request", prompt_tokens=5)
        t3.finish_span(tr_root, state="finished")
    assert t3.canonical_hash() != h1


def test_chrome_export_validates_and_carries_tree(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", track="replica-0") as outer:
        tr.event(outer, "mark", k=1)
        with tr.span("inner"):
            pass
    path = tmp_path / "trace.json"
    doc = tr.export_chrome_trace(str(path))
    assert validate_chrome_trace(doc) == []
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    assert any(e["ph"] == "i" and e["name"] == "mark"
               for e in doc["traceEvents"])
    assert any(e["ph"] == "M" and e["args"]["name"] == "replica-0"
               for e in doc["traceEvents"])
    # the parent edge survives the flat event list
    [inner] = [e for e in xs if e["name"] == "inner"]
    [outer_ev] = [e for e in xs if e["name"] == "outer"]
    assert inner["args"]["parent_id"] == outer_ev["args"]["span_id"]


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    bad = {"traceEvents": [{"ph": "X", "name": "a", "pid": 0, "tid": 0,
                            "ts": 1.0}]}          # no dur/args
    assert validate_chrome_trace(bad) != []


def test_trace_tree_problems_flags_orphans_and_open_spans():
    tr = Tracer(enabled=True)
    root = tr.new_trace("request")
    child = tr.begin_span("queue", root)
    tr.finish_span(child)
    # root never finished -> open-span problem
    spans = tr.spans_for_trace(root.trace_id)
    assert any("never finished" in p for p in trace_tree_problems(spans))
    tr.finish_span(root)
    assert trace_tree_problems(tr.spans_for_trace(root.trace_id)) == []
    # orphan: fabricate a span whose parent is missing
    from deepspeed_tpu.telemetry.tracing import Span

    orphan = Span("tX", "s999", "s998", "ghost", None, 0.0)
    orphan.t_end = 1.0
    assert any("orphan" in p
               for p in trace_tree_problems([orphan]))


# ------------------------------------------------------ flight recorder
def test_flight_recorder_ring_and_file_dump(tmp_path):
    fr = FlightRecorder(capacity=3, dump_dir=str(tmp_path))
    for i in range(5):
        fr.note("tick", n=i)
    assert fr.depth == 3
    assert fr.dropped == 2
    path = fr.dump("test-reason")
    assert path is not None and path.startswith(str(tmp_path))
    payload = json.loads(open(path).read())
    assert payload["reason"] == "test-reason"
    assert [r["n"] for r in payload["records"]] == [2, 3, 4]
    assert fr.last_dump_path == path
    assert fr.dumps == 1


def test_flight_recorder_in_memory_dump():
    fr = FlightRecorder(capacity=8)
    fr.note("tick")
    assert fr.dump("no-dir") is None
    assert fr.last_dump is not None
    assert fr.last_dump["reason"] == "no-dir"
    assert fr.dumps == 1 and fr.last_dump_path is None


def test_heartbeat_reports_flight_recorder_health(tmp_path):
    from deepspeed_tpu.telemetry.heartbeat import Heartbeat

    tr = Tracer(enabled=True, flight_capacity=4)
    tr.flight.note("x")
    with use_tracer(tr):
        hb = Heartbeat(str(tmp_path / "hb.json"))
        hb.beat(7)
    payload = json.loads((tmp_path / "hb.json").read_text())
    assert payload["step"] == 7 and payload["state"] == "running"
    assert payload["flight_depth"] == 1
    assert payload["flight_dropped"] == 0
    assert payload["flight_dumps"] == 0
    assert payload["flight_last_dump"] is None


# ------------------------------------------------------------- schemas
def test_archived_records_without_trace_ids_still_validate():
    # a pre-tracing ("v1/v2") request record: no trace_id/span_id
    archived = {"schema_version": 1, "uid": 3, "state": "finished",
                "priority": 0, "prompt_tokens": 4, "new_tokens": 2,
                "timestamp": 123.0, "preemptions": 0, "retries": 0}
    assert validate_request_record(archived) == []
    archived_step = {"schema_version": 1, "step": 1, "timestamp": 1.0,
                     "wall_time_s": 0.1, "tokens_per_s": 1.0,
                     "samples_per_s": 1.0, "mfu": 0.0, "comm": {},
                     "memory": {}, "stalled": False}
    assert validate_step_record(archived_step) == []


def test_records_with_trace_ids_validate_and_type_check():
    rec = RequestStats(uid=1, state="finished", trace_id="t1",
                       span_id="s1").to_record()
    assert rec["trace_id"] == "t1" and rec["span_id"] == "s1"
    assert validate_request_record(rec) == []
    rec["trace_id"] = 7
    assert any("trace_id" in e for e in validate_request_record(rec))
    srec = StepStats(step=1, wall_time_s=0.1, trace_id="t2",
                     span_id="s9").to_record()
    assert validate_step_record(srec) == []
    srec["span_id"] = 1.5
    assert any("span_id" in e for e in validate_step_record(srec))
    assert "trace_id" in REQUEST_RECORD_SCHEMA


# ------------------------------------------------- serving request path
def _drive(serving, clock, reqs, max_ticks=60):
    for _ in range(max_ticks):
        if all(r.is_terminal for r in reqs):
            return
        serving.step()
        clock.advance(1.0)
    raise AssertionError(
        f"requests not terminal: {[r.state for r in reqs]}")


def test_single_engine_request_tree():
    from deepspeed_tpu.serving.server import ServingEngine

    from deepspeed_tpu.telemetry import get_registry, set_registry

    clock = SimClock()
    tracer = Tracer(enabled=True)
    capture = _CaptureTelemetry()
    # set_telemetry(capture) also swaps the process-default registry;
    # restore BOTH or later tests read the capture's registry (the
    # run_schedule restore-discipline, docs/dst.md)
    prev_registry = get_registry()
    prev_t = set_telemetry(capture)
    try:
        with use_clock(clock), use_tracer(tracer):
            serving = ServingEngine(
                SimEngine(SimConfig()),
                {"policy": "fcfs", "stuck_tick_timeout_s": 0.0},
                start=False, replica_id="replica-0")
            req = serving.submit([1, 2, 3], max_new_tokens=3)
            _drive(serving, clock, [req])
            serving.close(timeout=5.0)
    finally:
        set_telemetry(prev_t if prev_t is not None
                      and prev_t.enabled else None)
        set_registry(prev_registry)
    root = req._trace_root
    assert root is not None and root.t_end is not None
    spans = tracer.spans_for_trace(root.trace_id)
    assert trace_tree_problems(spans) == []
    names = [s.name for s in spans]
    for expected in ("request", "queue", "prefill", "decode"):
        assert expected in names, names
    # lifecycle segments are children of the root, on the replica track
    segs = [s for s in spans if s.name in ("queue", "prefill", "decode")]
    assert all(s.parent_id == root.span_id for s in segs)
    assert all(s.track == "replica-0" for s in segs)
    # causal order: queue ends when prefill begins, prefill before decode
    by = {s.name: s for s in segs}
    assert by["queue"].t_end <= by["prefill"].t_start + 1e-9
    assert by["prefill"].t_end <= by["decode"].t_start + 1e-9
    # the emitted request record joins back to this trace
    [span_rec] = [s for s in capture.spans if s.uid == req.uid]
    assert span_rec.trace_id == root.trace_id
    assert span_rec.span_id == root.span_id
    assert root.attrs["state"] == "finished"


def _schedule(events, *, fleet=None, serving=None, seed=0, horizon=40.0):
    fleet_cfg = {"replicas": 2, "router": "least_loaded",
                 "failover": True, "respawn": False, "autoscale": False,
                 "min_replicas": 1, "max_replicas": 4}
    serving_cfg = {"policy": "fcfs", "max_queue": 16,
                   "tick_retry_limit": 1, "stuck_tick_timeout_s": 0.0,
                   "drain_timeout_s": 600.0, "poll_interval_s": 0.25}
    fleet_cfg.update(fleet or {})
    serving_cfg.update(serving or {})
    return Schedule(seed=seed, horizon=horizon,
                    engine_cfg=SimConfig().to_dict(),
                    fleet_cfg=fleet_cfg, serving_cfg=serving_cfg,
                    events=events)


def test_failover_request_stays_one_connected_tree():
    """A replica dies mid-flight; its requests fail over — the spans of
    every terminal request must still form one connected closed tree
    (the DST auditor's trace-tree invariant, exercised directly)."""
    events = [SimEvent(t=1.0, kind="submit",
                       payload={"ix": i, "prompt": [5 + i, 6, 7],
                                "max_new": 6})
              for i in range(4)]
    events.append(SimEvent(t=3.0, kind="replica_death",
                           payload={"which": 0}))
    report = run_schedule(_schedule(events))
    assert report.ok, report.violations
    assert report.finished == 4
    # determinism: the same schedule replays to the same span hash
    assert run_schedule(_schedule(events)).span_hash == report.span_hash
    assert report.n_spans > 0


def test_disaggregated_handoff_tree_spans_two_replicas():
    events = [SimEvent(t=1.0, kind="submit",
                       payload={"ix": 0, "prompt": [9, 8, 7, 6],
                                "max_new": 5})]
    report = run_schedule(_schedule(
        events, fleet={"disaggregated": True, "prefill_replicas": 1,
                       "replicas": 1}))
    assert report.ok, report.violations
    assert report.finished == 1


def test_tick_fault_retry_exhaustion_dumps_flight_recorder():
    events = [
        SimEvent(t=1.0, kind="submit",
                 payload={"ix": 0, "prompt": [3, 4, 5], "max_new": 4}),
        SimEvent(t=2.0, kind="tick_fault", payload={"n": 3}),
    ]
    sched = _schedule(events, fleet={"replicas": 1},
                      serving={"tick_retry_limit": 0})
    clock = SimClock()
    tracer = Tracer(enabled=True)
    # run under OUR tracer so the auto-dump is observable: run_schedule
    # installs its own, so drive the fleet directly here
    from deepspeed_tpu.resilience.chaos import install_fault_injector
    from deepspeed_tpu.resilience.dst import _ScheduledFaultInjector
    from deepspeed_tpu.serving.fleet import ServingFleet

    injector = _ScheduledFaultInjector()
    with use_clock(clock), use_tracer(tracer):
        install_fault_injector(injector)
        try:
            fleet = ServingFleet(lambda: SimEngine(SimConfig()),
                                 dict(sched.fleet_cfg),
                                 dict(sched.serving_cfg), start=False)
            req = fleet.submit([3, 4, 5], max_new_tokens=4)
            injector.arm(3)
            for _ in range(30):
                if req.is_terminal:
                    break
                fleet.step()
                clock.advance(1.0)
            fleet.close(timeout=10.0)
        finally:
            install_fault_injector(None)
    assert req.state.value == "cancelled"
    assert tracer.flight.dumps >= 1
    assert tracer.flight.last_dump_reason == "tick-fault-exhausted"
    kinds = {r["kind"] for r in tracer.flight.last_dump["records"]}
    assert "tick_fault_retry_exhausted" in kinds
    assert "injected_fault" in kinds       # chaos notes land in the ring
    # the retry is visible on the request's root span
    root = req._trace_root
    assert any(e[1] == "tick_fault" for e in root.events)


def test_dst_repro_dump_carries_timeline(tmp_path):
    """A failing run's repro JSON ships the span timeline."""
    from deepspeed_tpu.resilience.dst import dump_repro

    events = [SimEvent(t=1.0, kind="submit",
                       payload={"ix": 0, "prompt": [1, 2], "max_new": 2})]
    sched = _schedule(events, fleet={"replicas": 1})
    report = run_schedule(sched)
    assert report.ok and report.spans is None   # passing runs stay light
    path = str(tmp_path / "repro.json")
    dump_repro(sched, ["synthetic violation"], path,
               timeline=[{"name": "request", "t_start": 0.0}])
    payload = json.loads(open(path).read())
    assert payload["timeline"][0]["name"] == "request"


def test_generated_schedules_span_hash_deterministic():
    for seed in (5, 17):
        s = generate_schedule(seed)
        r1, r2 = run_schedule(s), run_schedule(s)
        assert r1.ok, r1.violations
        assert r1.span_hash == r2.span_hash
        assert r1.trace_hash == r2.trace_hash


# --------------------------------------------- zero overhead / training
def _batch(n=32, in_dim=64, out_dim=64, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(n, in_dim)).astype(np.float32),
            "y": rng.normal(size=(n, out_dim)).astype(np.float32)}


def _staged_engine(cc_cfg, dims=(64, 256, 256, 64), seed=0):
    mesh_mod.reset_topology()
    model = SequentialBlockModel(dims)
    engine, _, _, _ = dst_pkg.initialize(model=model, config={
        "train_batch_size": 32,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "comm_compression": cc_cfg,
        "steps_per_print": 1000,
    }, rng=jax.random.PRNGKey(seed))
    return engine


def test_tracing_off_zero_spans_and_no_recompiles_in_fused_scan():
    """The acceptance pin: with tracing off (the default), the fused
    train_steps scan traces once, the recompile guard stays silent, and
    the tracer ring stays empty — no span, clock read, or flight append
    rides the hot path."""
    from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                         set_registry)

    assert not get_tracer().enabled
    before = (len(get_tracer().spans()), get_tracer().flight.depth)
    old_reg = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        batch = _batch()
        e = _staged_engine({"enabled": True, "grad_bits": 4})
        e.train_steps([batch, batch])
        e.train_steps([batch, batch])
        assert e.trace_count("train_steps_2") == 1
        assert reg.counter("train/recompiles").value == 0
        # the disabled tracer accumulated NOTHING across the scan
        assert (len(get_tracer().spans()),
                get_tracer().flight.depth) == before
    finally:
        set_registry(old_reg)


def test_step_stats_carry_trace_ids_when_tracer_on():
    tracer = Tracer(enabled=True)
    with use_tracer(tracer):
        e = _staged_engine({"enabled": False, "overlap": "serial"})
        stats = e._build_step_stats({"loss": 1.0, "grad_norm": 0.0},
                                    wall_time_s=0.01)
    assert stats.trace_id is not None and stats.span_id is not None
    spans = tracer.spans()
    assert any(s.name == "train/step" for s in spans)
    assert validate_step_record(stats.to_record()) == []


# -------------------------------------------------- measured overlap
def test_overlap_report_structure_and_agreement():
    e = _staged_engine({"enabled": True, "weight_bits": 8,
                        "grad_bits": 4, "overlap": "staged"})
    tracer = Tracer(enabled=True, ring_size=65536)
    with use_tracer(tracer):
        rep = e.overlap_report(_batch(), repeats=2)
    L = rep["n_blocks"]
    assert L == 3 and rep["world"] == 8
    assert len(rep["blocks"]) == L
    for row in rep["blocks"]:
        for k in ("gather_s", "fwd_s", "regather_s", "bwd_s",
                  "reduce_s"):
            assert row[k] > 0.0, (k, row)
        assert row["gather_wire_bytes"] > 0
        assert row["reduce_wire_bytes"] > 0
        assert row["regather_wire_bytes"] == row["gather_wire_bytes"]
    m = rep["measured"]
    # the accounting identities
    assert m["overlapped_exposed_s"] <= m["serial_comm_s"] + 1e-9
    assert m["overlapped_exposed_s"] >= m["fwd_fill_s"] + m["bwd_fill_s"]
    # calibration: the model's serial comm equals the measured serial
    assert rep["modeled"] is not None
    assert rep["modeled"]["serial_compressed_s"] == pytest.approx(
        m["serial_comm_s"], rel=1e-6)
    assert rep["agreement_ratio"] is not None
    # wire join: the quantized weight gather is on the ledger
    assert "qwz_all_gather" in rep["wire"]["ledger"]
    # measured phase spans landed on the tracer (both tracks) and the
    # export validates
    tracks = {s.track for s in tracer.spans()}
    assert "zero3/measured" in tracks and "zero3/accounted" in tracks
    assert validate_chrome_trace(tracer.export_chrome_trace()) == []


def test_overlap_report_requires_staged_path():
    e = _staged_engine({"enabled": False, "overlap": "off"})
    with pytest.raises(ValueError, match="staged"):
        e.overlap_report(_batch())


def test_overlap_report_does_not_perturb_training():
    """The measurement drive must not touch the jitted step programs:
    a train_batch after overlap_report is bit-identical to one
    without it."""
    batch = _batch()
    e1 = _staged_engine({"enabled": True, "weight_bits": 8,
                        "grad_bits": 4, "overlap": "staged"}, seed=3)
    l_ref = float(e1.train_batch(batch)["loss"])
    e2 = _staged_engine({"enabled": True, "weight_bits": 8,
                        "grad_bits": 4, "overlap": "staged"}, seed=3)
    e2.overlap_report(batch, repeats=1)
    assert float(e2.train_batch(batch)["loss"]) == l_ref


def test_schedule_probe_seam_bit_exact():
    """Zero3BlockSchedule with a pass-through probe is bit-identical to
    probe=None — the seam is pure indirection."""
    from deepspeed_tpu.parallel.zero import Zero3BlockSchedule
    import jax.numpy as jnp

    model = SequentialBlockModel((8, 16, 16, 4))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": jnp.asarray(np.random.default_rng(0).normal(
                 size=(4, 8)), jnp.float32),
             "y": jnp.asarray(np.random.default_rng(1).normal(
                 size=(4, 4)), jnp.float32)}
    prog = model.zero3_blocks(params, batch)
    ident = lambda i, x: x                     # noqa: E731
    calls = []

    def probe(phase, i, fn):
        calls.append((phase, i))
        return fn()

    for overlapped in (False, True):
        prog_a = model.zero3_blocks(params, batch)
        prog_b = model.zero3_blocks(params, batch)
        l_a, g_a = Zero3BlockSchedule(ident, ident,
                                      overlapped=overlapped
                                      ).loss_and_grads(prog_a, 1.0)
        l_b, g_b = Zero3BlockSchedule(ident, ident,
                                      overlapped=overlapped,
                                      probe=probe
                                      ).loss_and_grads(prog_b, 1.0)
        assert float(l_a) == float(l_b)
        for a, b in zip(jax.tree_util.tree_leaves(g_a),
                        jax.tree_util.tree_leaves(g_b)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    phases = {p for p, _ in calls}
    assert phases == {"gather", "fwd", "regather", "bwd", "reduce"}
    del prog
