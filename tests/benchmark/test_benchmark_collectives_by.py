"""``benchmarks/readers/collectives_by.py`` on hand-built operation lists
whose every number is known, and its ``HloProto`` reader on a program
recorded here.

One step of 100 ms on two chips (times in ms; chip 1 differs in ``fusion.44``
alone, 30 - 38 where chip 0 has 30 - 36, ``fusion.4`` starting as it ends):

  fusion.1                  0 - 10     compute, forward
  async-collective-start.1  10 - 10.5  opens a gather of 8 MB, forward
  fusion.2                  10.5 - 14  compute beside it
                            14 - 16    nothing: the gather is exposed
  fusion.9                  16 - 18    the flight's continuation: compute
  async-collective-done.1   18 - 19    closes it: in flight 10 - 19
  fusion.3                  19 - 30    compute, backward
  fusion.44                 30 - 36    a fusion that holds a reduce-scatter
  fusion.4                  36 - 50    compute, backward
  all-to-all.7              50 - 58    under optimizer/update
  fusion.5                  58 - 70    optimizer/update
  all-reduce.3              70 - 70.2  optimizer/norm
  fusion.6                  70.2 - 80  optimizer/update

and ``while.1`` 0 - 50 around the first eight. By hand, chip 0: in flight
9 + 6 + 8 + 0.2 = 23.2, exposed 3.5 (0.5 + 2 + 1) + 6 + 8 + 0.2 = 17.7;
chip 1 a reduce-scatter of 8: means 24.2 and 18.7.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace as pt, trace_reduce as tr  # noqa: E402
from benchmarks.readers import collectives_by as cb  # noqa: E402

MS = 1e-3
FWD = "jit(train_step)/jvp()/while/body/closed_call/"
BWD = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
OPT = "jit(train_step)/optimizer/"
MB8 = 8 * 2 ** 20
INFO = {
    "async-collective-start.1": cb.Info("all-gather", MB8, 2, "start",
                                        "async-collective-start.1", True),
    "fusion.9": cb.Info("all-gather", MB8, 2, "mid",
                        "async-collective-start.1", True),
    "async-collective-done.1": cb.Info("all-gather", MB8, 2, "done",
                                       "async-collective-start.1", True),
    "fusion.44": cb.Info("reduce-scatter", 4 * MB8, 2, "whole", "fusion.44",
                         True),
    "all-to-all.7": cb.Info("all-to-all", 3 * MB8, 2, "whole",
                            "all-to-all.7", False),
    # no group given: every chip
    "all-reduce.3": cb.Info("all-reduce", 4, 0, "whole", "all-reduce.3",
                            False),
}
#: what a chip of the two sends a step by the ring's count: half of each
#: payload, the all-reduce's 4 bytes twice halved
SENT = (MB8 + 4 * MB8 + 3 * MB8) // 2 + 4


def _ops(scatter_end=36.0):
    rows = [
        ("while.1", 0, 50, "jit(train_step)/jvp()/while"),
        ("fusion.1", 0, 10, FWD + "ffn/dot_general"),
        ("async-collective-start.1", 10, 10.5, FWD + "attn/dot_general"),
        ("fusion.2", 10.5, 14, FWD + "attn/dot_general"),
        ("fusion.9", 16, 18, FWD + "attn/dot_general"),
        ("async-collective-done.1", 18, 19, FWD + "attn/dot_general"),
        ("fusion.3", 19, 30, BWD + "ffn/dot_general"),
        ("fusion.44", 30, scatter_end, BWD + "ffn/dot_general"),
        ("fusion.4", scatter_end, 50, BWD + "ffn/dot_general"),
        ("all-to-all.7", 50, 58, OPT + "update/add"),
        ("fusion.5", 58, 70, OPT + "update/mul"),
        ("all-reduce.3", 70, 70.2, OPT + "norm/reduce_sum"),
        ("fusion.6", 70.2, 80, OPT + "update/add"),
    ]
    return [pt.Op(n, a * MS, b * MS, op) for n, a, b, op in rows]


def _record(ops, attrs=None, info=INFO, chips=None):
    spans = []
    pt._nest(spans, [("train.step", 0.0, 1 * MS, dict(attrs or {}))])
    trace = tr.Trace(
        device_ops={c: [(o.name + " = f32[] fusion()", o.start, o.end)
                        for o in rows] for c, rows in ops.items()},
        host=[("train_batch", 0.0, 99 * MS)])
    return {"program_trace": pt.ProgramTrace(spans, ops), "trace": trace,
            "window": (0.0, 100 * MS), "hlo_collectives": info,
            "chips": chips or len(ops), "tracer": None}


@pytest.fixture()
def record():
    return _record({0: _ops(), 1: _ops(38.0)},
                   {"step": 7, "k": 1, "plan_bytes": 12_000_000_000,
                    "sent_bytes": SENT})


BY_HAND = [
    ({"what": "exposed_ms", "pass": "fwd"}, 3.5),
    ({"what": "exposed_ms", "pass": "bwd"}, 7.0),
    ({"what": "exposed_ms", "pass": "optimizer"}, 8.2),
    ({"what": "flight_ms", "kinds": ["all-gather"]}, 9.0),
    ({"what": "flight_ms", "kinds": ["reduce-scatter", "all-reduce"]}, 7.2),
    ({"what": "attr_gb", "attr": "sent_bytes"}, SENT / 1e9),
    ({"what": "attr_gb", "attr": "plan_bytes"}, 12.0),
    # the reader's own count of what the events sent, not the attribute
    ({"what": "gbps"}, SENT / 1e9 / 24.2e-3),
    # fusion.5, fusion.6 and the all-to-all that sits under the same path
    ({"what": "scope_ms", "scope": ["optimizer", "update"]}, 29.8),
]


@pytest.mark.parametrize("args,want", BY_HAND,
                         ids=lambda v: "-".join(map(str, v.values()))
                         if isinstance(v, dict) else None)
def test_metric_by_hand(record, args, want):
    assert cb.read(record, dict(args)) == pytest.approx(want, rel=1e-9)


def test_exposed_time_splits_by_pass_and_kind_and_adds_up(record, capsys):
    found = cb.of(record)
    assert found["flight"] == pytest.approx(24.2 * MS)
    assert found["exposed"] == pytest.approx(18.7 * MS)
    assert sum(found["by_pass"].values()) == pytest.approx(found["exposed"])
    assert sum(found["by_kind"].values()) == pytest.approx(found["exposed"])
    assert found["by_kind"] == pytest.approx({
        "all-gather": 3.5 * MS, "reduce-scatter": 7 * MS,
        "all-to-all": 8 * MS, "all-reduce": 0.2 * MS})
    said = capsys.readouterr().out
    assert "in flight 24.200 ms, exposed 18.700 ms" in said
    assert "exposed by pass: fwd 3.500, bwd 7.000, optimizer 8.200" in said
    # bytes by kind from the events, held against the program's own count
    assert found["sent_by_kind"] == pytest.approx({
        "all-gather": MB8 / 2, "reduce-scatter": 2 * MB8,
        "all-to-all": 1.5 * MB8, "all-reduce": 4})
    assert f"{SENT / 1e9:.3f} GB: reduce-scatter 0.017, all-to-all 0.013, " \
        "all-gather 0.004, all-reduce 0.000; train.step's sent_bytes " \
        f"{SENT / 1e9:.3f} GB, the same" in said
    # the old reader, by name: the all-to-all and the all-reduce alone
    assert "by name alone (trace_reduce.collectives): in flight 8.200 ms, " \
        "exposed 8.200 ms" in said
    # the table's first row: most exposed first
    assert "  all-to-all.7: all-to-all 25.166 MB, optimizer/update " \
        "(optimizer), outside the loop, in flight 8.000 ms, exposed " \
        "8.000 ms, waited: fusion.5" in said


@pytest.mark.parametrize("claimed, says", [
    (2 * SENT, "WHICH DIFFERS by 100.0%"), (None, "says nothing of it")],
    ids=["a wrong trip count", "no attribute"])
def test_the_line_says_where_the_program_s_count_parts_from_the_events(
        claimed, says, capsys):
    """The program multiplies a loop body's bytes by a trip count read off
    its text; the reader counts the flights that ran. A catalogue that ran
    a loop twice too often is said, not echoed; ``collective_gbps`` goes by
    the events either way."""
    attrs = {"step": 7, "k": 1}
    if claimed is not None:
        attrs["sent_bytes"] = claimed
    record = _record({0: _ops(), 1: _ops(38.0)}, attrs)
    assert cb.read(record, {"what": "gbps"}) \
        == pytest.approx(SENT / 1e9 / 24.2e-3)
    assert says in capsys.readouterr().out


def test_a_fusion_that_holds_a_reduce_scatter_is_counted_with_its_kind(
        record):
    row = cb.of(record)["rows"]["fusion.44"]
    assert (row["kind"], row["bytes"], row["in_loop"]) \
        == ("reduce-scatter", 4 * MB8, True)
    assert row["flight"] == row["exposed"] == pytest.approx(7 * MS)
    assert cb.pass_of(row["op_name"]) == "bwd"
    assert max(row["waited"], key=row["waited"].get) == "fusion.4"


def test_an_asynchronous_pair_is_one_flight_and_its_continuation_compute(
        record):
    flights, compute = cb.flights_of(_ops(), INFO, 0.0, 100 * MS)
    gathers = [f for f in flights if f.kind == "all-gather"]
    assert [(f.name, f.start, f.end) for f in gathers] == [
        ("async-collective-start.1", pytest.approx(10 * MS),
         pytest.approx(19 * MS))]
    assert (16 * MS, 18 * MS) in [(pytest.approx(a), pytest.approx(b))
                                  for a, b in compute]
    row = cb.of(record)["rows"]["async-collective-start.1"]
    assert row["flight"] == pytest.approx(9 * MS)
    assert row["exposed"] == pytest.approx(3.5 * MS)
    # each exposed stretch's waiter is the operation that starts at its
    # end: fusion.2 (0.5 ms), the continuation fusion.9 (2 ms), fusion.3 (1)
    assert row["waited"] == pytest.approx({
        "fusion.2": 0.5 * MS, "fusion.9": 2 * MS, "fusion.3": 1 * MS})


def test_overlapping_flights_share_nothing():
    """Two gathers in flight at once: the first to cover a moment owns it,
    so exposed time is counted once and the pieces add up to the union."""
    info = {n: cb.Info("all-gather", 1, 2, r, f, False) for n, r, f in (
        ("a-start", "start", "a-start"), ("a-done", "done", "a-start"),
        ("b-start", "start", "b-start"), ("b-done", "done", "b-start"))}
    ops = [pt.Op(n, a * MS, b * MS, FWD + "ffn/mul") for n, a, b in (
        ("a-start", 0, 1), ("b-start", 2, 3), ("fusion.1", 3, 5),
        ("a-done", 6, 7), ("b-done", 9, 10))]
    flights, compute = cb.flights_of(ops, info, 0.0, 1.0)
    pieces = cb.stretches(flights, compute)
    assert [(s.flight.name, s.owned) for s in pieces] == [
        ("a-start", pytest.approx(7 * MS)), ("b-start", pytest.approx(3 * MS))]
    assert sum(tr.total(s.exposed) for s in pieces) \
        == pytest.approx((10 - 2) * MS)


def test_plain_names_total_what_trace_reduce_totals():
    """Where every collective carries a plain name the two readers see the
    same operations, and the totals are ``trace_reduce.collectives``'."""
    rows = [("fusion.1", 0, 10), ("all-gather.1", 10, 14),
            ("fusion.2", 14, 20), ("collective-permute-start.1", 20, 20.5),
            ("fusion.3", 20.5, 24), ("collective-permute-done.1", 26, 27),
            ("all-reduce.2", 27, 30), ("fusion.4", 30, 40)]
    info = {
        "all-gather.1": cb.Info("all-gather", 64, 2, "whole",
                                "all-gather.1", False),
        "collective-permute-start.1": cb.Info(
            "collective-permute", 64, 2, "start",
            "collective-permute-start.1", False),
        "collective-permute-done.1": cb.Info(
            "collective-permute", 64, 2, "done",
            "collective-permute-start.1", False),
        "all-reduce.2": cb.Info("all-reduce", 64, 2, "whole",
                                "all-reduce.2", False)}
    ops = {c: [pt.Op(n, a * MS, b * MS, FWD + "ffn/mul") for n, a, b in rows]
           for c in (0, 1)}
    record = _record(ops, info=info)
    found = cb.of(record)
    flight, exposed = tr.collectives(record["trace"], 0, 0.0, 100 * MS)
    assert found["flight"] == pytest.approx(tr.total(flight)) \
        == pytest.approx(14 * MS)
    assert found["exposed"] == pytest.approx(tr.total(exposed)) \
        == pytest.approx((4 + 0.5 + 3 + 3) * MS)


@pytest.mark.parametrize("what", [a for a, _ in BY_HAND],
                         ids=lambda a: "-".join(map(str, a.values())))
def test_one_chip_or_a_program_without_the_names_reads_nothing(what):
    """One chip holds no collective; the parent of the PR that brought the
    attributes and the scope ``optimizer/update`` has neither."""
    one = _record({0: [o for o in _ops() if o.name not in INFO]}, info={})
    if what["what"] == "scope_ms":     # one chip updates its parameters too
        assert cb.read(one, dict(what)) == pytest.approx(21.8)
    else:
        assert cb.read(one, dict(what)) is None
    bare = [o._replace(op_name=o.op_name.replace("update/", "").replace(
        "norm/", "")) for o in _ops()]
    parent = _record({0: bare, 1: bare}, {"step": 7, "k": 1})
    got = cb.read(parent, dict(what))
    if what["what"] in ("attr_gb", "scope_ms"):
        assert got is None
    else:
        assert got is not None      # the trace's HloProto needs no new name


# ----------------------------------------------------------------------
def test_the_hlo_proto_of_a_recorded_trace_gives_the_program_its_collectives(
        tmp_path):
    """A small sharded program with a loop, run under a profiler session on
    four virtual devices: the ``HloProto`` the trace keeps names its
    collectives with their kinds, bytes and loops, as the program's own
    catalogue of the same compiled step (the text's reader) has them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.profiling import collectives as coll

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    x = jax.device_put(jnp.ones((8, 16)), NamedSharding(mesh, P("data")))

    @jax.jit
    def step(x):
        def body(c, _):
            return c + jnp.sum(c, axis=0, keepdims=True), None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))

    compiled = step.lower(x).compile()
    jax.block_until_ready(step(x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(step(x))
    finally:
        jax.profiler.stop_trace()
    with open(tr.find_xplane(str(tmp_path)), "rb") as f:
        data = f.read()
    catalogue = {c.name: c for c in coll.catalogue(compiled.as_text())}
    # the session may hold other programs too (a worker's earlier tests
    # leave threads behind): the step's is the one with these names
    found = next(f for f in cb.programs(data).values()
                 if set(f) == set(catalogue))
    assert found and set(found) <= set(cb.known(data))
    for name, c in catalogue.items():
        assert (found[name].kind, found[name].bytes, found[name].group,
                found[name].in_loop, found[name].role) \
            == (c.kind, c.bytes, c.group, c.in_loop, "whole")
        assert cb.sent_bytes(c.kind, c.bytes, c.group) == c.sent_bytes > 0
    assert {(i.kind, i.bytes, i.in_loop) for i in found.values()} == {
        ("all-reduce", 16 * 4, True), ("all-gather", 8 * 16 * 4, False)}
    assert {c.runs for c in catalogue.values() if c.in_loop} == {3}
    proto = compiled.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    assert cb.module_collectives(proto) == found
