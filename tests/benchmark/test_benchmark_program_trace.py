"""``benchmarks/program_trace.py`` and the three readers over it
(``span_attr_stat``, ``idle_by_span``, ``scope_device``), on hand-built
traces whose every number is known: a serving window with one decode-only
tick and one tick that carries a prefill chunk (idle gaps that start and end
on span edges, operations the compiler made, which take their scope from
their user or their operand in the stored HLO, and one that has neither),
the same trace cut to a decode-only and to a prefill-only window, a two-chip
training window, and a trace of a program that has no such spans or
scopes."""

import os

import pytest

from benchmarks import harness, program_trace as pt, trace_reduce as tr

MS = 1_000_000_000  # picoseconds in a millisecond
STATS = ["tf_op", "lanes", "pages", "seqs", "prefill", "decode", "free",
         "tick", "step", "k", "bytes", "program_id", "Hlo Proto"]
PROGRAM = 7        # the one program of the hand-built device planes


def _plane(name, lines):
    """lines: {line: [(event, start ms, length ms, {stat: int}, op_name)]};
    ``op_name`` goes to the event's metadata as ``tf_op`` beside the
    program's id, the stats to the event, as the profiler writes them."""
    meta, text = {}, ""
    for line, events in lines.items():
        body = ""
        for ev, a, d, stats, op in events:
            i = meta.setdefault((ev, op), len(meta) + 1)
            body += (f"events {{ metadata_id: {i} offset_ps: {int(a * MS)} "
                     f"duration_ps: {int(d * MS)} " + "".join(
                         f"stats {{ metadata_id: {STATS.index(k) + 1} "
                         f"int64_value: {v} }} " for k, v in stats.items())
                     + "}\n")
        text += f'lines {{ name: "{line}" timestamp_ns: 0 {body} }}\n'
    for (ev, op), i in meta.items():
        stat = f"stats {{ metadata_id: 12 uint64_value: {PROGRAM} }} "
        if op is not None:
            stat += f'stats {{ metadata_id: 1 str_value: "{op}" }} '
        text += (f'event_metadata {{ key: {i} value {{ id: {i} name: "{ev}" '
                 f"{stat}}} }}\n")
    for i, n in enumerate(STATS):
        text += (f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                 f'name: "{n}" }} }}\n')
    return f'planes {{ name: "{name}" {text} }}\n'


def _vi(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(field, payload):
    return _vi(field << 3 | 2) + _vi(len(payload)) + payload


def _hlo_plane(instructions):
    """``/host:metadata`` with one program's ``HloProto``: instructions
    are (id, name, op_name, operand ids), all in one computation."""
    body = b"".join(_ld(2, _ld(1, name.encode())
                        + (_ld(7, _ld(2, op.encode())) if op else b"")
                        + _vi(35 << 3) + _vi(i)
                        + (_ld(36, b"".join(map(_vi, operands)))
                           if operands else b""))
                    for i, name, op, operands in instructions)
    proto = "".join("\\%03o" % b for b in _ld(1, _ld(3, body)))
    return (f'planes {{ name: "/host:metadata" event_metadata {{ key: 1 '
            f'value {{ id: 1 name: "jit_step({PROGRAM})" stats {{ '
            f'metadata_id: 13 bytes_value: "{proto}" }} }} }} stat_metadata '
            f'{{ key: 13 value {{ id: 13 name: "Hlo Proto" }} }} }}\n')


def _record(text, lo, hi, names=("put", "train_batch")):
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(text)
    profile = ProfileData.from_serialized_xspace(data)
    return {"program_trace": pt.from_profile(profile, pt.op_names(data)),
            "trace": tr.from_profile(profile, names),
            "window": (lo * 1e-3, hi * 1e-3)}


def _s(name, a, b, **attrs):
    return (name, a, b - a, attrs, None)


J = "jit(step)/"
SERVE_OPS = [  # (instruction, start ms, length ms, {}, op_name)
    ("%copy.1 = f32[8] copy(%p)", 12.4, 0.1, {}, None),
    ("%fusion.1 = bf16[64,64] fusion(%p)", 14, 1, {}, J + "embed/gather:"),
    ("%slice_bitcast_fusion.2 = bf16[64] fusion(%p)", 15, 1, {},
     J + "weights/squeeze:"),
    ("%fusion.3 = bf16[64] fusion(%p)", 16, 2, {}, J + "attn/dot_general:"),
    ("%paged_attention.4 = bf16[64,8,4,128] custom-call(%p)", 18, 2, {},
     J + "attn/paged_attention/pallas_call:"),
    ("%fusion.5 = f32[64,8] fusion(%p)", 20, 0.5, {},
     J + "ffn/router/dot_general:"),
    ("%sort.6 = s32[128] sort(%p)", 20.5, 0.5, {}, J + "ffn/experts/sort:"),
    # XLA's expansion of ragged_dot: no JAX path; the HLO names its user
    ("%ragged-dot-none.7 = bf16[128,64] custom-call(%p)", 21, 2, {},
     "ragged-dot-none:"),
    ("%fusion.8 = bf16[64] fusion(%p)", 23, 0.5, {}, J + "ffn/experts/mul:"),
    ("%fusion.9 = f32[4,256] fusion(%p)", 23.5, 1.5, {},
     J + "head/head/dot_general:"),
    # no user in the HLO, but an operand: the head's
    ("%copy.10 = f32[4,256] copy(%p)", 25, 0.5, {}, None),
    ("%copy.1 = f32[8] copy(%p)", 26.2, 0.1, {}, None),
    ("%copy.1 = f32[8] copy(%p)", 28, 0.1, {}, None),
    ("%fusion.3 = bf16[64] fusion(%p)", 44, 32, {}, J + "attn/dot_general:"),
]
SERVE_HOST = [
    _s("bench_open", 0, 0.001), _s("bench_close", 100, 100.001),
    _s("put", 11.4, 26.6), _s("put", 40.9, 78.1),
    _s("serve.tick", 10, 30, tick=1), _s("serve.admit", 10, 11),
    _s("serve.put", 11, 27),
    _s("ragged.put", 11.5, 26.5, lanes=64, pages=8, seqs=3, prefill=0,
       decode=3, free=100),
    _s("ragged.admit", 11.5, 12), _s("ragged.pack", 12, 13),
    _s("ragged.dispatch", 13, 14), _s("ragged.fetch", 14, 26, bytes=4096),
    _s("ragged.rows", 26, 26.5), _s("serve.emit", 27, 29),
    _s("serve.retire", 29, 30), _s("serve.wait", 30, 40),
    _s("serve.tick", 40, 80, tick=2), _s("serve.put", 40.5, 79),
    _s("ragged.put", 41, 78, lanes=256, pages=16, seqs=4, prefill=253,
       decode=3, free=60),
    _s("ragged.fetch", 43, 77, bytes=4096), _s("serve.wait", 80, 90),
]
SERVE_HLO = [  # (id, name, op_name, operands): copy.1 hangs on nothing
    (1, "copy.1", "", ()), (2, "sort.6", J + "ffn/experts/sort", ()),
    (3, "ragged-dot-none.7", "ragged-dot-none", (2,)),
    (4, "copy-start.2", "", (3,)), (5, "copy-done.2", "", (4,)),
    (6, "fusion.8", J + "ffn/experts/mul", (5,)),
    (7, "fusion.9", J + "head/head/dot_general", (6,)),
    (8, "copy.10", "", (7,)),
]
SERVE = (_plane("/device:TPU:0", {"XLA Ops": SERVE_OPS})
         + _plane("/host:CPU", {"driver": SERVE_HOST})
         + _hlo_plane(SERVE_HLO))

T = "jit(train_step)/"
BACK = T + "transpose(jvp())/while/body/closed_call/checkpoint/"
TRAIN_OPS = [
    ("%while.3 = (f32[]) while(%p)", 11, 29, {}, None),
    ("%fusion.1 = bf16[2] fusion(%p)", 11, 9, {},
     T + "jvp()/while/body/closed_call/attn/dot_general:"),
    ("%flash_attention.2 = bf16[2] custom-call(%p)", 20, 18, {},
     BACK + "rematted_computation/attn/flash_attention/pallas_call:"),
    ("%fusion.4 = f32[2] fusion(%p)", 38, 6, {}, T + "optimizer/add:"),
    ("%fusion.5 = f32[2] fusion(%p)", 44, 1, {},
     T + "jvp(head)/dot_general:"),
    ("%fusion.1 = bf16[2] fusion(%p)", 51, 10, {},
     T + "jvp()/while/body/closed_call/attn/dot_general:"),
    ("%fusion.6 = bf16[2] fusion(%p)", 61, 20, {},
     BACK + "ffn/dot_general:"),
    ("%fusion.4 = f32[2] fusion(%p)", 81, 4, {}, T + "optimizer/add:"),
]
TRAIN_HOST = [_s("train_batch", 9.9, 46), _s("train_batch", 49.9, 86),
              _s("train.step", 10, 12, step=5, k=1), _s("train.pre", 10, 10.5),
              _s("train.dispatch", 10.5, 11.5), _s("train.post", 11.5, 12),
              _s("train.step", 50, 52, step=6, k=1)]
TRAIN = (_plane("/device:TPU:0", {"XLA Ops": TRAIN_OPS})
         + _plane("/device:TPU:1", {"XLA Ops": TRAIN_OPS})
         + _plane("/host:CPU", {"main": TRAIN_HOST}))

#: what the parent of PR 25 writes: the runner's spans, operations without
#: any scope
PARENT = (_plane("/device:TPU:0", {"XLA Ops": [
    ("%fusion.3 = bf16[64] fusion(%p)", 14, 10, {}, "jit(step)/dot_general:"),
    ("%copy.1 = f32[8] copy(%p)", 24, 1, {}, None)]})
    + _plane("/host:CPU", {"driver": [_s("put", 11, 26),
                                      _s("train_batch", 30, 40)]}))

WINDOWS = {"serve": (SERVE, 0, 100), "decode_only": (SERVE, 5, 35),
           "prefill_only": (SERVE, 35, 100), "train": (TRAIN, 0, 100),
           "parent": (PARENT, 0, 100)}
SERVING = ("put_decode_ms", "prefill_tick_pct", "put_prefill_ms",
           "tick_host_ms", "idle_pct.prepare", "idle_pct.fetch",
           "idle_pct.server", "idle_pct.waiting", "attn_device_ms.serve",
           "ffn_device_ms.serve", "head_device_ms.serve")
TRAINING = ("fwd_device_ms", "bwd_device_ms", "optimizer_device_ms")
WANT = {
    # idle: every gap split where a span starts or ends inside it
    "serve": dict(zip(SERVING, (15.0, 50.0, 37.0, 4.0, 5.4, 2.9, 7.9, 40.0,
                                4.0, 3.5, 2.0))),
    # the first tick alone: no tick carries a prefill chunk
    "decode_only": dict(zip(SERVING, (
        15.0, 0.0, None, 5.0, 100 * 2.4 / 30, 100 * 0.9 / 30, 100 * 4.9 / 30,
        100 * 10 / 30, 4.0, 3.5, 2.0))),
    # the second alone: nothing decode-only to read
    "prefill_only": dict(zip(SERVING, (
        None, 100.0, 37.0, 3.0, 100 * 3 / 65, 100 * 2 / 65, 100 * 3 / 65,
        100 * 25 / 65, None, None, None))),
    "train": dict(zip(TRAINING, (10.0, 19.0, 5.0))),
    "parent": dict.fromkeys(SERVING + TRAINING),
}


@pytest.fixture(scope="module")
def records():
    return {k: _record(text, lo, hi) for k, (text, lo, hi) in WINDOWS.items()}


def _read(record, metric):
    spec = harness.read_json(os.path.join(harness.HERE, "metrics",
                                          metric + ".json"))
    return harness.find("readers", spec["reader"]).read(
        record, dict(spec["args"]))


@pytest.mark.parametrize("window,metric", [
    (w, m) for w, want in WANT.items() for m in want])
def test_metric_on_a_hand_built_trace(records, window, metric):
    got = _read(records[window], metric)
    want = WANT[window][metric]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, abs=1e-6)


def test_idle_shares_add_up_to_the_device_idle_share(records):
    for window in ("serve", "decode_only", "prefill_only"):
        r = records[window]
        parts = sum(_read(r, f"idle_pct.{b}") for b in
                    ("prepare", "fetch", "server", "waiting"))
        assert parts == pytest.approx(_read(r, "device_idle_pct.serve"))


def test_scopes_and_the_rest_add_up_to_the_busy_time(records):
    """Every scope with what carried none is the device time of the tick;
    the compiler's ragged-dot takes its user's scope (through two pathless
    copies), a copy without users its operand's, one without either none."""
    from benchmarks.readers import scope_device

    (cell,) = scope_device.per_span(records["serve"], "ragged.put")
    assert cell["busy"] == pytest.approx(11.7e-3)
    assert sum(v for k, v in cell.items() if k != "busy") \
        == pytest.approx(11.7e-3)
    assert cell["ffn/experts", False] == pytest.approx(3.0e-3)
    assert cell["attn/paged_attention", False] == pytest.approx(2.0e-3)
    assert cell[pt.NO_SCOPE, False] == pytest.approx(0.2e-3)
    assert cell["head", False] == pytest.approx(2.0e-3)
    assert cell["embed", False] == pytest.approx(1.0e-3)
    assert cell["weights", False] == pytest.approx(1.0e-3)
    steps = scope_device.per_span(records["train"], "train.step")
    assert [s["busy"] for s in steps] == pytest.approx([34e-3, 34e-3])
    assert steps[0]["attn/flash_attention", True] == pytest.approx(18e-3)
    assert steps[0]["head", False] == pytest.approx(1e-3)


def test_loader_gives_spans_their_attributes_and_parents(records):
    spans = records["serve"]["program_trace"].spans
    assert all(pt.PROGRAM_SPAN.match(s.name) for s in spans)   # no "put"
    by = {(s.name, round(s.start * 1e3, 3)): s for s in spans}
    put = by["ragged.put", 11.5]
    assert put.attrs == {"lanes": 64, "pages": 8, "seqs": 3, "prefill": 0,
                         "decode": 3, "free": 100}
    parent = lambda s: spans[s.parent].name if s.parent is not None else None
    assert parent(put) == "serve.put"
    assert parent(by["ragged.fetch", 14.0]) == "ragged.put"
    assert parent(by["serve.put", 11.0]) == "serve.tick"
    assert parent(by["serve.tick", 10.0]) is None
    assert parent(by["serve.wait", 30.0]) is None
    ops = records["train"]["program_trace"].ops
    assert sorted(ops) == [0, 1] and len(ops[0]) == len(TRAIN_OPS)
    assert ops[1][2] == pt.Op("flash_attention.2", 20e-3, 38e-3,
                              BACK + "rematted_computation/attn/"
                              "flash_attention/pallas_call:")


def test_op_names_reads_a_stat_by_value_or_by_reference():
    """``tf_op`` as a string, and as a reference into the stat names."""
    from jax.profiler import ProfileData

    text = ('planes { name: "/device:TPU:2" '
            'event_metadata { key: 1 value { id: 1 name: "%a" '
            'stats { metadata_id: 7 str_value: "jit(f)/attn/add:" } } } '
            'event_metadata { key: 2 value { id: 2 name: "%b" '
            'stats { metadata_id: 3 uint64_value: 9 } '
            'stats { metadata_id: 7 ref_value: 8 } } } '
            'event_metadata { key: 3 value { id: 3 name: "%c" } } '
            'stat_metadata { key: 3 value { id: 3 name: "flops" } } '
            'stat_metadata { key: 7 value { id: 7 name: "tf_op" } } '
            'stat_metadata { key: 8 value { id: 8 name: "jit(f)/ffn/mul:" } }'
            ' } planes { name: "/host:CPU" }')
    data = ProfileData.text_proto_to_serialized_xspace(text)
    assert pt.op_names(data) == {2: {"%a": "jit(f)/attn/add:",
                                     "%b": "jit(f)/ffn/mul:", "%c": ""}}


def test_hlo_paths_follow_users_then_operands():
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(_hlo_plane(SERVE_HLO))
    assert pt.hlo_paths(data) == {PROGRAM: {
        "copy.1": "", "ragged-dot-none.7": J + "ffn/experts/mul",
        "copy-start.2": J + "ffn/experts/mul",
        "copy-done.2": J + "ffn/experts/mul",
        "copy.10": J + "head/head/dot_general"}}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/attn/paged_attention/pallas_call:",
     ("attn", "attn/paged_attention", False)),
    ("jit(step)/weights/squeeze:", ("weights", "weights", False)),
    ("jit(step)/head/head/dot_general:", ("head", "head", False)),
    ("jit(step)/ffn/experts/jit(argsort)/sort:",
     ("ffn", "ffn/experts", False)),
    ("jit(train_step)/transpose(jvp(head))/jit(log_softmax)/sub:",
     ("head", "head", True)),
    ("jit(train_step)/jvp(embed)/gather:", ("embed", "embed", False)),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/flash_attention/pallas_call:",
     ("attn", "attn/flash_attention", True)),
    ("jit(train_step)/optimizer/add:", ("optimizer", "optimizer", False)),
    ("jit(step)/scatter:", (pt.NO_SCOPE, pt.NO_SCOPE, False)),
    ("ragged-dot-none:", (pt.NO_SCOPE, pt.NO_SCOPE, False)),
    ("", (pt.NO_SCOPE, pt.NO_SCOPE, False)),
])
def test_scope_of(op_name, want):
    assert pt.scope_of(op_name) == want
