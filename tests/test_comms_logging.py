"""CommsLogger with measured latencies (reference utils/comms_logging.py +
comm.py:101 timed_op): trace-time op/size/axis recording, timed standalone
replays backfilling real durations, bandwidth columns in the summary."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.parallel.mesh import Topology, set_topology


@pytest.fixture()
def logger_on():
    comm.configure_comms_logger(enabled=True)
    comm.get_comms_logger().reset()
    yield comm.get_comms_logger()
    comm.get_comms_logger().reset()
    comm.configure_comms_logger(enabled=False)


def _run_collectives(topo):
    mesh = topo.mesh

    def spmd(x):
        y = comm.all_reduce(x, "data")
        g = comm.all_gather(x, "data")
        s = comm.reduce_scatter(y, "data")
        return s + 1e-9 * jnp.sum(g)

    f = jax.shard_map(spmd, mesh=mesh, axis_names={"data"},
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
    x = jnp.arange(64 * 8, dtype=jnp.float32)
    return jax.jit(f)(x)


def test_logger_records_ops_and_axes(logger_on):
    topo = Topology.build_virtual({"data": 8})
    set_topology(topo)
    _run_collectives(topo)
    recs = logger_on.records
    assert {"all_reduce", "all_gather", "reduce_scatter"} <= set(recs)
    # axis recorded for the replay pass
    for op in ("all_reduce", "all_gather", "reduce_scatter"):
        (size,) = recs[op].keys()
        assert logger_on.axes[(op, size)] == "data"
        assert size == 64 * 4  # per-shard operand bytes


def test_measured_latencies_are_real(logger_on):
    topo = Topology.build_virtual({"data": 8})
    set_topology(topo)
    _run_collectives(topo)
    table = comm.measure_comm_latencies(topo.mesh, iters=5)
    # durations backfilled: no op row shows a zero average latency
    for op in ("all_reduce", "all_gather", "reduce_scatter"):
        (size,) = logger_on.records[op].keys()
        durs = logger_on.records[op][size]
        assert all(d > 0 for d in durs), (op, durs)
    # summary has bandwidth columns with nonzero values
    assert "algbw(GB/s)" in table and "busbw(GB/s)" in table
    data_rows = [ln for ln in table.splitlines() if re.match(r"\s+\d+", ln)]
    assert data_rows
    # avg-latency column (third from the right) shows real measured ms
    assert all(float(ln.split()[-3]) > 0 for ln in data_rows)


def test_sparse_allreduce_matches_dense(logger_on):
    """Sparse embedding-grad reduction == dense scatter + psum."""
    topo = Topology.build_virtual({"data": 4})
    set_topology(topo)
    V, d, k = 32, 8, 4
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.normal(size=(4, k, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, V, (4, k)), jnp.int32)

    def spmd(rows, idx):
        return comm.sparse_allreduce(rows[0], idx[0], "data", V)[None]

    got = jax.jit(jax.shard_map(
        spmd, mesh=topo.mesh, axis_names={"data"},
        in_specs=(P("data"), P("data")), out_specs=P("data"),
        check_vma=False))(rows, idx)
    dense = np.zeros((V, d), np.float32)
    for r in range(4):
        for j in range(k):
            dense[int(idx[r, j])] += np.asarray(rows[r, j])
    np.testing.assert_allclose(np.asarray(got)[0], dense, rtol=1e-5)


def test_bw_math_known_payload():
    """algbw/busbw formulas on a known payload (reference calc_bw_log,
    utils/comms_logging.py:34): algbw = size/t; ring all-reduce moves
    2(n-1)/n x the payload over the bus, all-gather/reduce-scatter/
    all-to-all (n-1)/n, broadcast 1x."""
    from deepspeed_tpu.comm.comm import _get_bw

    size, dur, n = 1_000_000_000, 1.0, 8  # 1 GB in 1 s across 8 ranks
    algbw, busbw = _get_bw("all_reduce", size, dur, n)
    assert algbw == pytest.approx(1.0)
    assert busbw == pytest.approx(2 * (n - 1) / n)  # 1.75 GB/s
    for op in ("all_gather", "reduce_scatter", "all_to_all"):
        algbw, busbw = _get_bw(op, size, dur, n)
        assert algbw == pytest.approx(1.0)
        assert busbw == pytest.approx((n - 1) / n)  # 0.875 GB/s
    algbw, busbw = _get_bw("broadcast", size, dur, n)
    assert algbw == busbw == pytest.approx(1.0)
    # half the time => double the bandwidth
    algbw, _ = _get_bw("all_reduce", size, 0.5, n)
    assert algbw == pytest.approx(2.0)
    # degenerate duration reports zeros, never divides by zero
    assert _get_bw("all_reduce", size, 0.0, n) == (0.0, 0.0)


def test_comms_events_flow_into_registry(logger_on):
    """Unified telemetry: every recorded collective also lands in the
    shared metrics registry (comm/<op>/{calls,bytes}), and the aggregate
    snapshot the engine folds into StepStats matches."""
    from deepspeed_tpu.telemetry import MetricsRegistry, get_registry, set_registry

    old = get_registry()
    reg = set_registry(MetricsRegistry())
    try:
        logger_on.append("all_reduce", 256, 0.0, 8, "data")
        logger_on.append("all_reduce", 256, 0.0, 8, "data")
        logger_on.append("all_gather", 128, 0.5, 8, "data")
        # v2 ledger: a compressed op books physical wire bytes separately
        logger_on.append("qwz_all_gather", 256, 0.0, 8, "data",
                         wire_bytes=68)
        assert reg.counter("comm/all_reduce/calls").value == 2
        assert reg.counter("comm/all_reduce/bytes").value == 512
        assert reg.counter("comm/all_gather/calls").value == 1
        # dense ops book wire == logical; compressed ops the quantized
        # payload, and the trace-time-static ratio lands in a histogram
        assert reg.counter("comm/all_reduce/wire_bytes").value == 512
        assert reg.counter("comm/qwz_all_gather/wire_bytes").value == 68
        assert reg.histogram(
            "comm/qwz_all_gather/compression_ratio").mean == \
            pytest.approx(256 / 68)
        totals = logger_on.snapshot_totals()
        assert totals["all_reduce"] == {"count": 2, "bytes": 512,
                                        "wire_bytes": 512, "time_s": 0.0}
        assert totals["all_gather"] == {"count": 1, "bytes": 128,
                                        "wire_bytes": 128,
                                        "time_s": pytest.approx(0.5)}
        assert totals["qwz_all_gather"]["wire_bytes"] == 68
    finally:
        set_registry(old)


def test_reduce_gather_scatter(logger_on):
    topo = Topology.build_virtual({"data": 4})
    set_topology(topo)
    world, n = 4, 8
    x = jnp.arange(world * n, dtype=jnp.float32).reshape(world, n)

    def spmd(x):
        r = comm.reduce(x[0], "data", dst_index=1)
        g = comm.gather(x[0], "data", dst_index=0)
        s = comm.scatter(x[0], "data", src_index=2)
        return r[None], g[None], s[None]

    r, g, s = jax.jit(jax.shard_map(
        spmd, mesh=topo.mesh, axis_names={"data"},
        in_specs=P("data"), out_specs=(P("data"), P("data"), P("data")),
        check_vma=False))(x)
    r, g, s = np.asarray(r), np.asarray(g), np.asarray(s)
    # reduce: only dst row 1 holds the sum
    np.testing.assert_allclose(r[1], np.asarray(x).sum(0))
    assert (r[0] == 0).all() and (r[2] == 0).all()
    # gather: dst row 0 holds the concatenation
    np.testing.assert_allclose(g[0], np.asarray(x).reshape(-1))
    assert (g[1] == 0).all()
    # scatter: member i holds chunk i of src rank 2's tensor
    for i in range(world):
        np.testing.assert_allclose(s[i], np.asarray(x[2, i * 2:(i + 1) * 2]))
