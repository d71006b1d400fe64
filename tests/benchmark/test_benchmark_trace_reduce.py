"""``trace_reduce.py`` and the readers, on a hand-built trace whose busy,
idle and exposed-collective times are known, and on the small trace recorded
on the chip that is kept under ``benchmarks/fixtures/``."""

import os
import types

import pytest

from benchmarks import harness, trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000_000  # picoseconds in a millisecond


def _plane(name, line, events):
    """events: (name, start ms, length ms)."""
    ids = {n: i + 1 for i, n in enumerate(dict.fromkeys(e[0] for e in events))}
    ev = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {int(a * MS)} "
                 f"duration_ps: {int(d * MS)} }}\n" for n, a, d in events)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
                   f"}} }}\n" for n, i in ids.items())
    return (f'planes {{ name: "{name}" lines {{ name: "{line}" '
            f"timestamp_ns: 5000 {ev} }} {meta} }}\n")


OPS = [("fusion.1", 1.5, 1.0), ("all-gather-start.1", 2.5, 0.1),
       ("fusion.2", 2.6, 0.4), ("all-gather-done.1", 3.0, 0.5),
       ("reduce-scatter.2", 3.5, 0.5), ("_fwd_kernel", 4.0, 0.5),
       ("fusion.1", 6.5, 2.0)]
HOST = [("bench_open", 0.0, 0.001), ("next_batch", 0.2, 0.6),
        ("train_batch", 1.0, 4.0), ("train_batch", 6.0, 3.5),
        ("bench_close", 10.0, 0.001), ("something_else", 0.0, 20.0)]


@pytest.fixture(scope="module")
def built():
    from jax.profiler import ProfileData

    text = (_plane("/device:TPU:0", "XLA Ops", OPS)
            + _plane("/device:TPU:0", "XLA Modules", [("jit_step", 1.0, 8.0)])
            + _plane("/host:CPU", "python", HOST))
    # two planes of one name: keep the lines apart as a real trace does
    text = text.replace('planes { name: "/device:TPU:0" lines { name: '
                        '"XLA Modules"', 'planes { name: "/device:TPU:0 x" '
                        'lines { name: "XLA Modules"')
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    trace = tr.from_profile(profile, {"bench_open", "bench_close",
                                      "train_batch", "next_batch"})
    lo = tr.window_of(trace, "bench_open")[0]
    hi = tr.window_of(trace, "bench_close")[0]
    return trace, lo, hi


def test_interval_arithmetic():
    u = tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)])
    assert u == [(0, 2), (3, 4)] and tr.total(u) == 3
    assert tr.clip(u, 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (5, 6)], []) == [(0, 1), (5, 6)]
    assert tr.covered(u, 1.5, 3.5) == pytest.approx(1.0)


def test_busy_and_idle_of_the_hand_built_trace(built):
    trace, lo, hi = built
    assert hi - lo == pytest.approx(10e-3)
    assert sorted(trace.device_ops) == [0]         # modules line not read
    assert {n for n, _, _ in trace.host} == {"bench_open", "bench_close",
                                             "train_batch", "next_batch"}
    assert tr.busy_seconds(trace, lo, hi) == pytest.approx(5e-3)
    gaps = tr.idle_gaps(trace, lo, hi, ("train_batch", "next_batch"), "between")
    assert gaps == pytest.approx({"next_batch": 1.5e-3, "between": 2.0e-3,
                                  "train_batch": 1.5e-3})
    ops = tr.op_seconds(trace, lo, hi)
    assert ops["fusion.1"] == pytest.approx(3e-3)
    assert tr.top(ops, 1)[0][0] == "fusion.1"


def test_exposed_collectives_of_the_hand_built_trace(built):
    trace, lo, hi = built
    flight, exposed = tr.collectives(trace, 0, lo, hi)
    assert tr.total(flight) == pytest.approx(1.5e-3)   # start..done, and sync
    assert tr.total(exposed) == pytest.approx(1.1e-3)  # fusion.2 hides 0.4


def _record(built, **more):
    trace, lo, hi = built
    cell = types.SimpleNamespace(
        config={"num_attention_heads": 32, "num_key_value_heads": 8,
                "head_dim": 128, "sliding_window": 4096},
        traffic={"global_batch": 2, "seq_len": 2048})
    return dict(trace=trace, window=(lo, hi), cell=cell, chips=1, n_layers=2,
                peaks=harness.peaks_of("TPU v5 lite"), **more)


@pytest.mark.parametrize("reader,args,want", [
    ("device_idle", {}, 50.0),
    ("span_stat", {"span": "train_batch", "stat": "duration"}, 3.75),
    ("span_stat", {"span": "train_batch", "stat": "device"}, 2.5),
    ("span_stat", {"span": "train_batch", "stat": "host"}, 1.25),
    ("span_stat", {"span": "put", "stat": "duration"}, None),
    ("collectives", {"what": "ms_per_step"}, 0.75),
    ("collectives", {"what": "exposed_pct"}, 100 * 1.1 / 1.5),
])
def test_trace_readers_on_the_hand_built_trace(built, reader, args, want):
    got = harness.find("readers", reader).read(_record(built), args)
    assert got is None if want is None else got == pytest.approx(want)


def test_flash_roofline_reader_counts_the_kernel_once_a_call(built):
    from benchmarks.ops_bytes import flash_attention as fa

    got = harness.find("readers", "flash_roofline").read(
        _record(built), {"passes": "forward", "kernels": ["_fwd_kernel"]})
    least = fa.forward_flops(2, 2048, 32, 128, 4096) / 197e12
    assert got == pytest.approx(100 * least / 0.5e-3)
    assert harness.find("readers", "flash_roofline").read(
        _record(built), {"passes": "backward", "kernels": ["_dq_kernel"]}) is None


def test_host_clock_readers():
    steps = [{"next_batch": 0.001, "train_batch": 0.100 + 0.01 * i}
             for i in range(3)]
    cell = types.SimpleNamespace(
        config={"hidden_size": 4096, "intermediate_size": 14336,
                "num_attention_heads": 32, "num_key_value_heads": 8,
                "head_dim": 128, "vocab_size": 32000, "sliding_window": 4096},
        traffic={"global_batch": 2, "seq_len": 2048})
    rec = dict(steps=steps, tokens_a_step=4096, cell=cell, chips=1, n_layers=2,
               peaks=harness.peaks_of("TPU v5 lite"))
    assert harness.find("readers", "step_ms").read(rec, {}) == pytest.approx(110)
    from benchmarks.ops_bytes import train_step

    per_token = train_step.flops_per_token(cell.config, 2, 2048)
    # 2 layers x 218M + the 131M output head multiply; the input table does not
    assert train_step.multiplying_params(cell.config, 2) == 567_279_616
    assert 6 * 567_279_616 < per_token < 6.25 * 567_279_616
    want = 100 * 3 * 4096 * per_token / 0.333 / 197e12
    assert harness.find("readers", "mfu").read(rec, {}) == pytest.approx(want)
    assert harness.find("readers", "late").read({"late_s": [0.001] * 99 + [1.0]},
                                                {}) == pytest.approx(1.0, rel=0.1)
    calls = [{"t0": 1.0, "t1": 1.02, "live_after": 2},
             {"t0": 1.023, "t1": 1.04, "live_after": 0},
             {"t0": 2.0, "t1": 2.02, "live_after": 1},
             {"t0": 2.025, "t1": 2.04, "live_after": 1}]
    assert harness.find("readers", "server_gap").read(
        {"calls": calls, "t0": 0.5}, {}) == pytest.approx(4.0)


def test_paged_attention_counts():
    from benchmarks.ops_bytes import paged_attention as pa

    # one decode token at context 1000: reads 1000 keys and values once
    flops, moved = pa.ops_and_bytes([(1, 1000)], 32, 8, 128)
    assert flops == 4 * 32 * 128 * 1000
    assert moved == 2 * 1000 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    # a 3-token chunk from position 0 is causal inside the chunk: 1 + 2 + 3
    assert pa.ops_and_bytes([(3, 3)], 32, 8, 128)[0] == 4 * 32 * 128 * 6
    # a window clips what a query sees and what has to be read
    brute = sum(min(p + 1, 4) for p in range(5, 9))
    flops, moved = pa.ops_and_bytes([(4, 9)], 2, 1, 8, window=4)
    assert flops == 4 * 2 * 8 * brute
    assert moved == 2 * 7 * 1 * 8 * 2 + 2 * 4 * 2 * 8 * 2


FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures", "train_steps.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(FIXTURE, {"bench_open", "bench_close", "train_batch",
                              "next_batch"})
    return trace, tr.window_of(trace, "bench_open")[0], \
        tr.window_of(trace, "bench_close")[0]


def test_recorded_trace_reduces_to_the_numbers_read_when_it_was_cut(recorded):
    trace, lo, hi = recorded
    want = harness.read_json(os.path.join(ROOT, "benchmarks", "fixtures",
                                          "train_steps.expected.json"))
    assert sorted(trace.device_ops) == want["chips"]
    assert len(tr.spans(trace, "train_batch", lo, hi)) == want["steps"]
    assert hi - lo == pytest.approx(want["window_s"], rel=1e-9)
    assert tr.busy_seconds(trace, lo, hi) == pytest.approx(want["busy_s"],
                                                           rel=1e-9)
    assert 0 < tr.busy_seconds(trace, lo, hi) < hi - lo
    ops = tr.op_seconds(trace, lo, hi)
    assert tr.top(ops, 1)[0][0] == want["top_op"]


@pytest.mark.parametrize("metric", ["step_device_ms.train",
                                    "device_idle_pct.train",
                                    "flash_fwd_roofline_pct",
                                    "flash_bwd_roofline_pct"])
def test_readers_on_the_recorded_trace(recorded, metric):
    trace, lo, hi = recorded
    want = harness.read_json(os.path.join(ROOT, "benchmarks", "fixtures",
                                          "train_steps.expected.json"))
    cell = harness.Cell("mistral-7b.train")
    spec = cell.metric_spec(metric)
    rec = dict(trace=trace, window=(lo, hi), cell=cell, chips=1,
               n_layers=cell.n_layers, peaks=harness.peaks_of("TPU v5 lite"))
    got = harness.find("readers", spec["reader"]).read(rec, spec["args"])
    assert got == pytest.approx(want["metrics"][metric], rel=1e-6)
    if "roofline" in metric:
        assert 0 < got <= 100
