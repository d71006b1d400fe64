"""Unified telemetry subsystem: registry/percentiles, JSONL step-record
schema from a tiny train loop, stall detection, exporters, monitor handle
caching + close, resilience counters, cached log rank."""

import json
import logging
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.telemetry import (
    Histogram,
    JsonlSink,
    MetricsRegistry,
    StallDetector,
    StepStats,
    Telemetry,
    get_telemetry,
    render_prometheus,
    set_registry,
    set_telemetry,
    validate_step_record,
)
from simple_model import init_mlp_params, make_batch, mlp_loss


@pytest.fixture(autouse=True)
def _isolate_global_telemetry():
    """Each test gets a fresh default registry and no global pipeline."""
    old = set_registry(MetricsRegistry())
    set_telemetry(None)
    yield
    set_registry(old)
    set_telemetry(None)


# ----------------------------------------------------------------------
# MetricsRegistry
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("a/b")
    c.inc()
    c.inc(2.5)
    assert reg.counter("a/b").value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    assert g.value is None
    g.set(7)
    assert reg.gauge("g").value == 7.0


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_percentiles_known_data():
    h = Histogram("h")
    for v in range(100):  # 0..99
        h.observe(float(v))
    assert h.count == 100
    assert h.min == 0.0 and h.max == 99.0
    assert h.mean == pytest.approx(49.5)
    # linear interpolation over the sorted window
    assert h.percentile(0) == 0.0
    assert h.percentile(100) == 99.0
    assert h.percentile(50) == pytest.approx(49.5)
    assert h.percentile(90) == pytest.approx(89.1)
    assert h.percentile(99) == pytest.approx(98.01)


def test_histogram_window_keeps_recent():
    h = Histogram("h", window=10)
    for v in range(100):
        h.observe(float(v))
    # exact aggregates cover everything; percentiles only the window
    assert h.count == 100
    assert h.percentile(0) >= 90.0
    summ = h.summary()
    assert summ["count"] == 100 and summ["max"] == 99.0


def test_snapshot_shapes():
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(3.0)
    snap = reg.snapshot()
    assert snap["c"] == 2.0 and snap["g"] == 1.5
    assert snap["h"]["count"] == 1 and snap["h"]["p50"] == 3.0


# ----------------------------------------------------------------------
# SketchHistogram: relative-error bound + merge algebra
def test_sketch_relative_error_bound():
    from deepspeed_tpu.telemetry import SketchHistogram

    s = SketchHistogram("s", alpha=0.01)
    # values across 8 orders of magnitude plus negatives and zero
    vals = ([10.0 ** k for k in range(-4, 5)]
            + [-(10.0 ** k) for k in range(-2, 3)] + [0.0])
    for v in vals:
        s.observe(v)
    assert s.count == len(vals)
    assert s.min == min(vals) and s.max == max(vals)
    # every percentile estimate lands within alpha of SOME true value
    exact = sorted(vals)
    for p in (0, 10, 25, 50, 75, 90, 99, 100):
        est = s.percentile(p)
        rank = int((p / 100.0) * (len(exact) - 1) + 1e-9)
        true = exact[rank]
        if true == 0.0:
            assert abs(est) <= SketchHistogram.ZERO_EPS
        else:
            assert abs(est - true) <= abs(true) * (s.alpha + 1e-9), (
                p, est, true)


def test_sketch_merge_algebra():
    from deepspeed_tpu.telemetry import SketchHistogram

    def fill(name, vals):
        s = SketchHistogram(name, alpha=0.02)
        for v in vals:
            s.observe(v)
        return s

    a_vals = [0.5, 1.0, 3.0, -2.0]
    b_vals = [100.0, 0.001, 7.0]
    c_vals = [0.0, 42.0]

    # commutative: a+b == b+a
    ab = fill("ab", a_vals)
    ab.merge(fill("b", b_vals))
    ba = fill("ba", b_vals)
    ba.merge(fill("a", a_vals))
    assert ab.serialize()["pos"] == ba.serialize()["pos"]
    assert ab.serialize()["neg"] == ba.serialize()["neg"]
    assert ab.count == ba.count and ab.sum == ba.sum

    # associative: (a+b)+c == a+(b+c)
    left = fill("l", a_vals)
    left.merge(fill("b", b_vals))
    left.merge(fill("c", c_vals))
    bc = fill("bc", b_vals)
    bc.merge(fill("c", c_vals))
    right = fill("r", a_vals)
    right.merge(bc)
    ls, rs = left.serialize(), right.serialize()
    for k in ("count", "zero", "pos", "neg", "min", "max"):
        assert ls[k] == rs[k], k

    # identity: merging an empty sketch changes nothing
    before = fill("i", a_vals).serialize()
    ident = fill("i2", a_vals)
    ident.merge(SketchHistogram("empty", alpha=0.02))
    assert ident.serialize() == dict(before, alpha=ident.alpha)

    # merged == union observed directly (sketch is a true monoid hom);
    # sum is float-addition-order sensitive, so approx for that field
    union = fill("u", a_vals + b_vals + c_vals)
    us = union.serialize()
    for k in ("count", "zero", "pos", "neg", "min", "max"):
        assert ls[k] == us[k], k
    assert ls["sum"] == pytest.approx(us["sum"])

    # alpha mismatch is a hard error, not silent precision loss
    with pytest.raises(ValueError):
        left.merge(SketchHistogram("other", alpha=0.01))


def test_sketch_serde_roundtrip():
    from deepspeed_tpu.telemetry import SketchHistogram

    s = SketchHistogram("s", alpha=0.01)
    for v in (0.0, 1e-6, 0.5, 2.0, -3.5, 1e4):
        s.observe(v)
    d = s.serialize()
    # serialized form is json-stable (sorted bucket lists)
    assert d == json.loads(json.dumps(d))
    s2 = SketchHistogram.deserialize("s2", d)
    assert s2.serialize() == d
    for p in (1, 50, 99):
        assert s2.percentile(p) == s.percentile(p)


# ----------------------------------------------------------------------
# exporters
def test_prometheus_render():
    reg = MetricsRegistry()
    reg.counter("train/steps").inc(5)
    reg.gauge("inference/kv_occupancy").set(0.25)
    reg.histogram("train/step_time_s").observe(0.1)
    text = render_prometheus(reg)
    assert "# TYPE dst_train_steps counter" in text
    assert "dst_train_steps 5.0" in text
    assert "dst_inference_kv_occupancy 0.25" in text
    assert 'dst_train_step_time_s{quantile="0.5"} 0.1' in text
    assert "dst_train_step_time_s_count 1" in text


def test_prometheus_renders_sketch_as_native_histogram():
    reg = MetricsRegistry()
    s = reg.sketch("serving/ttft_s", alpha=0.01)
    for v in (0.05, 0.1, 0.1, 2.0):
        s.observe(v)
    text = render_prometheus(reg)
    assert "# TYPE dst_serving_ttft_s histogram" in text
    # cumulative le-series: monotone counts ending at the +Inf total
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith("dst_serving_ttft_s_bucket")]
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert counts == sorted(counts)
    assert 'le="+Inf"' in bucket_lines[-1] and counts[-1] == 4
    assert "dst_serving_ttft_s_count 4" in text
    # every upper bound is >= the values it covers (log-bucket bounds)
    ubs = [float(ln.split('le="')[1].split('"')[0])
           for ln in bucket_lines[:-1]]
    assert all(u > 0 for u in ubs) and max(ubs) >= 2.0


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "out.jsonl")
    sink = JsonlSink(path)
    sink.write({"a": 1, "np": np.float32(2.5)})
    sink.close()
    rec = json.loads(open(path).read())
    assert rec == {"a": 1, "np": 2.5}


# ----------------------------------------------------------------------
# stall detector
def test_stall_detector_flags_slow_step():
    det = StallDetector(window=10, factor=3.0, warmup_steps=2)
    flagged = []
    for i in range(10):
        assert det.observe(i, 0.1) is False
    assert det.observe(99, 0.5) is True  # 5x the 0.1 median
    assert det.stall_count == 1
    # within-factor step after the stall is clean
    assert det.observe(100, 0.15) is False


def test_stall_detector_warmup_absorbs_compile():
    det = StallDetector(window=10, factor=3.0, warmup_steps=2)
    # compile steps: huge, but inside warmup -> never flagged, never
    # polluting the window
    assert det.observe(0, 30.0) is False
    assert det.observe(1, 25.0) is False
    for i in range(2, 8):
        assert det.observe(i, 0.1) is False
    assert det.observe(8, 1.0) is True


def test_stall_factor_validation():
    with pytest.raises(ValueError):
        StallDetector(factor=1.0)


# ----------------------------------------------------------------------
# schema
def test_validate_step_record_catches_violations():
    good = StepStats(step=1, wall_time_s=0.1).to_record()
    assert validate_step_record(good) == []
    bad = dict(good)
    del bad["wall_time_s"]
    bad["comm"] = {"all_reduce": {"count": 1}}  # missing bytes/time_s
    errs = validate_step_record(bad)
    assert any("wall_time_s" in e for e in errs)
    assert any("all_reduce" in e for e in errs)
    assert validate_step_record({"step": "x"})  # junk record -> errors


# ----------------------------------------------------------------------
# golden: 3-step tiny train loop emits schema-valid records
def _train_with_telemetry(tmp_path, steps=3, extra_cfg=None, tag="t"):
    out = str(tmp_path / tag)
    cfg = {
        "train_batch_size": 16,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "steps_per_print": 1000,
        "telemetry": {"enabled": True, "output_dir": out,
                      "prometheus_path": os.path.join(out, "metrics.prom"),
                      "export_every": 1},
    }
    for k, v in (extra_cfg or {}).items():
        cfg[k] = v
    params = init_mlp_params(jax.random.PRNGKey(0))
    engine, _, _, _ = dst.initialize(loss_fn=mlp_loss, params=params, config=cfg)
    batch = make_batch(16)
    for _ in range(steps):
        engine.train_batch(batch)
    engine.close()
    lines = open(os.path.join(out, "steps.jsonl")).read().splitlines()
    return engine, [json.loads(ln) for ln in lines], out


def test_train_loop_jsonl_schema(tmp_path):
    engine, records, out = _train_with_telemetry(
        tmp_path, steps=3, extra_cfg={"zero_optimization": {"stage": 1}})
    assert len(records) == 3
    for i, rec in enumerate(records):
        assert validate_step_record(rec) == [], validate_step_record(rec)
        assert rec["step"] == i + 1
        assert rec["wall_time_s"] > 0
        assert rec["tokens_per_s"] > 0
        assert rec["loss"] is not None
        # dp=8 stage-1: the grad reduction shows up in the comm breakdown
        assert "reduce_scatter" in rec["comm"]
        assert rec["comm"]["reduce_scatter"]["bytes"] > 0
    # prometheus file exported and parseable
    prom = open(os.path.join(out, "metrics.prom")).read()
    assert "dst_train_steps 3.0" in prom
    # close() is idempotent and cleared the global pipeline
    engine.close()
    assert get_telemetry().enabled is False


def test_telemetry_off_keeps_engine_lean(tmp_path):
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "steps_per_print": 1000}
    params = init_mlp_params(jax.random.PRNGKey(0))
    engine, _, _, _ = dst.initialize(loss_fn=mlp_loss, params=params, config=cfg)
    assert engine.telemetry.wants_step_records is False
    assert engine.telemetry.sinks == []
    engine.train_batch(make_batch(16))
    engine.close()


def test_compat_path_phase_times(tmp_path):
    out = str(tmp_path / "compat")
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
           "steps_per_print": 1000,
           "telemetry": {"enabled": True, "output_dir": out}}
    params = init_mlp_params(jax.random.PRNGKey(0))
    engine, _, _, _ = dst.initialize(loss_fn=mlp_loss, params=params, config=cfg)
    batch = make_batch(16)
    engine.backward(batch)
    engine.step()
    jsonl = os.path.join(out, "steps.jsonl")
    engine.close()
    recs = [json.loads(ln) for ln in open(jsonl).read().splitlines()]
    assert len(recs) == 1
    rec = recs[0]
    assert validate_step_record(rec) == []
    assert rec["backward_s"] > 0 and rec["optimizer_s"] > 0


# ----------------------------------------------------------------------
# monitor satellite
def test_csv_monitor_caches_handles(tmp_path):
    from deepspeed_tpu.monitor.monitor import CsvMonitor

    mon = CsvMonitor(str(tmp_path), "job")
    mon.write_events([("Train/loss", 1.0, 1), ("Train/loss", 0.5, 2)])
    mon.write_events([("Train/loss", 0.25, 3)])
    assert len(mon._files) == 1  # one cached handle, not one per event
    mon.close()
    assert mon._files == {}
    lines = open(os.path.join(str(tmp_path), "job",
                              "Train_loss.csv")).read().splitlines()
    assert lines[0].startswith("step")
    assert len(lines) == 4  # header + 3 events, single header


def test_monitor_master_close_idempotent(tmp_path):
    from deepspeed_tpu.config import MonitorConfig
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    cfg = MonitorConfig(csv_enabled=True, csv_output_path=str(tmp_path),
                        csv_job_name="job")
    m = MonitorMaster(cfg)
    m.write_events([("Train/loss", 1.0, 1)])
    m.close()
    m.close()  # second close is a no-op
    assert m.writers == []


def test_monitor_is_a_telemetry_sink(tmp_path):
    from deepspeed_tpu.config import MonitorConfig
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    mon = MonitorMaster(MonitorConfig(csv_enabled=True,
                                      csv_output_path=str(tmp_path),
                                      csv_job_name="job"))
    t = Telemetry(config=None, monitor=mon)
    assert t.wants_step_records  # monitor present => per-step records
    t.record_step(StepStats(step=1, wall_time_s=0.1, loss=2.0))
    t.close()
    loss_csv = os.path.join(str(tmp_path), "job", "Train_loss.csv")
    assert [ln.split(",") for ln in open(loss_csv).read().splitlines()][1] == ["1", "2.0"]


def test_record_request_series(tmp_path):
    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "req")

    r = MetricsRegistry()
    t = Telemetry(config=Cfg(), registry=r)
    t.record_request(latency_s=0.5, ttft_s=0.1, new_tokens=8,
                     decode_tokens_per_s=17.5)
    t.record_request(latency_s=0.7)
    assert r.counter("inference/requests").value == 2
    assert r.counter("inference/generated_tokens").value == 8
    assert r.histogram("inference/ttft_s").count == 1
    assert r.histogram("inference/request_latency_s").percentile(100) == 0.7
    t.close()


def test_disabled_stub_drops_request_metrics(monkeypatch):
    import sys

    # the nothing-configured stub is a lazy process-wide singleton bound
    # to whichever registry was the default at its first use: have it
    # built anew over a registry of this test's own
    reg = set_registry(MetricsRegistry())
    monkeypatch.setattr(sys.modules["deepspeed_tpu.telemetry.telemetry"],
                        "_DISABLED", None)
    stub = get_telemetry()
    assert not stub.enabled and stub.registry is reg
    stub.record_request(latency_s=1.0, ttft_s=0.1, new_tokens=8,
                        decode_tokens_per_s=17.5)
    assert reg.metrics() == {}


# ----------------------------------------------------------------------
# serving-request spans (PR 5): schema, registry series, JSONL stream
def test_validate_request_record_catches_violations():
    from deepspeed_tpu.telemetry import RequestStats, validate_request_record

    good = RequestStats(uid=1, state="finished", prompt_tokens=4,
                        new_tokens=2).to_record()
    assert validate_request_record(good) == []
    bad = dict(good)
    del bad["uid"]
    bad["state"] = "vanished"
    errs = validate_request_record(bad)
    assert any("uid" in e for e in errs)
    assert any("unknown request state" in e for e in errs)
    stale = dict(good, schema_version=99)
    assert any("schema_version" in e for e in validate_request_record(stale))
    assert validate_request_record(["junk"])        # non-dict -> errors


def test_record_request_span_series_and_jsonl(tmp_path):
    from deepspeed_tpu.telemetry import RequestStats, validate_request_record

    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "srv")

    t = Telemetry(config=Cfg())
    t.record_request_span(RequestStats(
        uid=1, state="finished", priority=2, prompt_tokens=8, new_tokens=4,
        queue_wait_s=0.01, ttft_s=0.05, latency_s=0.2, tokens_per_s=20.0,
        in_slo=True))
    t.record_request_span(RequestStats(uid=2, state="rejected",
                                       error="queue full", in_slo=False))
    r = t.registry
    assert r.counter("serving/generated_tokens").value == 4
    assert r.counter("serving/slo_judged").value == 2
    assert r.counter("serving/slo_met").value == 1
    # serving hot-path latency series are sketch-backed (mergeable,
    # bounded-memory) — exact-window histograms stay for training
    assert r.sketch("serving/ttft_s").count == 1
    assert r.sketch("serving/queue_wait_s").count == 1
    t.close()
    # requests get their OWN jsonl stream (one file, one schema) and every
    # line validates
    recs = [json.loads(ln) for ln in
            open(os.path.join(str(tmp_path / "srv"),
                              "requests.jsonl")).read().splitlines()]
    assert [rec["state"] for rec in recs] == ["finished", "rejected"]
    for rec in recs:
        assert validate_request_record(rec) == [], rec
    assert recs[1]["error"] == "queue full"
    # step-record validation must NOT accept a request record (separate
    # schemas guard the one-file-one-schema contract)
    assert validate_step_record(recs[0])


def test_serving_engine_exports_gauges_and_spans(tmp_path):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.telemetry import validate_request_record

    class Cfg:
        enabled = True
        output_dir = str(tmp_path / "serve")

    t = Telemetry(config=Cfg())
    set_telemetry(t)
    model = Llama("tiny", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                  vocab_size=64, max_seq_len=64, use_flash=False, remat=False)
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=16, max_seqs=2, kv_block_size=8,
                            n_kv_blocks=16, max_context=32,
                            dtype=jnp.float32))
    srv = ServingEngine(eng, {"max_queue": 1}, start=False)
    ok = srv.submit([1, 2, 3, 4], max_new_tokens=3, ttft_deadline_s=60.0)
    rejected = srv.submit([5, 6, 7], max_new_tokens=3)    # queue full
    while not ok.is_terminal:
        srv._tick()
    r = t.registry
    assert r.counter("serving/admitted").value == 1
    assert r.counter("serving/completed").value == 1
    assert r.counter("serving/rejected").value == 1
    assert r.counter("serving/ticks").value >= 3
    assert r.gauge("serving/queue_depth").value == 0
    assert r.gauge("serving/live_requests").value == 0
    assert 0.0 <= r.gauge("serving/kv_occupancy").value <= 1.0
    t.close()
    set_telemetry(None)
    recs = [json.loads(ln) for ln in
            open(os.path.join(str(tmp_path / "serve"),
                              "requests.jsonl")).read().splitlines()]
    assert {rec["state"] for rec in recs} == {"finished", "rejected"}
    for rec in recs:
        assert validate_request_record(rec) == [], rec
    fin = next(rec for rec in recs if rec["state"] == "finished")
    assert fin["new_tokens"] == 3 and fin["ttft_s"] > 0
    assert fin["in_slo"] is True
    assert rejected.state.value == "rejected"


# ----------------------------------------------------------------------
# resilience
def test_retry_call_counts_and_succeeds():
    from deepspeed_tpu.resilience import RetryPolicy, retry_call
    from deepspeed_tpu.telemetry import get_registry

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    out = retry_call(flaky, policy=RetryPolicy(max_attempts=5, backoff_s=0),
                     op="ckpt", sleep=lambda _: None)
    assert out == "ok" and calls["n"] == 3
    assert get_registry().counter("resilience/retries/ckpt").value == 2


def test_retry_call_exhaustion_raises():
    from deepspeed_tpu.resilience import RetryError, RetryPolicy, retry_call
    from deepspeed_tpu.telemetry import get_registry

    def always_fails():
        raise RuntimeError("nope")

    with pytest.raises(RetryError):
        retry_call(always_fails, policy=RetryPolicy(max_attempts=2, backoff_s=0),
                   op="x", sleep=lambda _: None)
    assert get_registry().counter("resilience/failures/x").value == 1


def test_preemption_guard_flag():
    from deepspeed_tpu.resilience import PreemptionGuard

    with PreemptionGuard(signals=()) as guard:
        assert guard.should_stop is False
        guard.request_stop()
        assert guard.should_stop is True


# ----------------------------------------------------------------------
# logging satellite
def test_log_dist_env_override(monkeypatch):
    from deepspeed_tpu.utils import logging as dlog

    monkeypatch.setenv("DST_LOG_RANK", "3")
    records = []
    handler = logging.Handler()
    handler.emit = records.append  # the package logger does not propagate
    dlog.logger.addHandler(handler)
    try:
        dlog.log_dist("only-rank-0")          # filtered: we are "rank 3"
        dlog.log_dist("rank-3-message", ranks=[3])
        dlog.log_dist("everyone", ranks=[-1])
    finally:
        dlog.logger.removeHandler(handler)
    text = "\n".join(r.getMessage() for r in records)
    assert "only-rank-0" not in text
    assert "rank-3-message" in text and "[Rank 3]" in text
    assert "everyone" in text


def test_process_index_cached(monkeypatch):
    from deepspeed_tpu.utils import logging as dlog

    dlog.reset_process_index_cache()
    assert dlog._process_index() == 0
    assert dlog._cached_process_index == 0  # cached after first resolution
