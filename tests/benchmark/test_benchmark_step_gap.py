"""``benchmarks/readers/step_gap.py`` on hand-built ``Span`` / ``Op`` lists
whose every number is known: five ticks of one server thread (times in ms)

  A  decode   call returns 13.0   step 13.5 - 27.2   ids on the host 28.0
  B  decode   call returns 33.0   step 32.8 - 48.0   (starts BEFORE its call
                                                      returns)       48.5
  C  prefill  call returns 53.0   step 53.4 - 87.0                   88.0
     serve.wait 90.5 - 100
  D  decode   call returns 102.5  step 103.0 - 118.0                119.0
  E  decode   call returns 122.3  step 122.9 - 138.2                 139.0

so the gaps are A-B and D-E (before a decode-only tick), B-C (before a
prefill tick: in the all-tick medians only) and C-D (a ``serve.wait`` inside:
dropped, but its two ticks still bound the clocks' offset).
"""

import os

import pytest

from benchmarks import harness, program_trace as pt
from benchmarks.readers import idle_by_span, step_gap

MS = 1e-3
#: tick: (put, h2d, call, fetch) each (start, end), prefill lanes, step ops
TICKS = {
    "A": ((10, 30), (11.01, 12.01), (12.02, 13.0), (13.02, 28), 0,
          [(13.5, 20), (20.05, 27.2)]),
    "B": ((30.5, 50), (31, 32.2), (32.21, 33), (33, 48.5), 0,
          [(32.8, 40), (40.02, 48)]),
    "C": ((50.5, 90), (51, 52), (52, 53), (53, 88), 200,
          [(53.4, 70), (70.3, 87)]),
    "D": ((100.5, 120), (101, 102), (102, 102.5), (102.5, 119), 0,
          [(103, 118)]),
    "E": ((120.2, 140), (120.5, 121.5), (121.5, 122.3), (122.3, 139), 0,
          [(122.9, 130), (130.01, 138.2)]),
}
#: gap: (gap, between, h2d, call, handoff) in ms, by hand
BY_HAND = {"AB": (5.6, 3.0, 1.2, 0.79, 0.6), "BC": (5.4, 2.5, 1.0, 1.0, 0.9),
           "DE": (4.9, 1.5, 1.0, 0.8, 1.6)}
DECODE = dict(zip(step_gap.SEGMENTS, (5.25, 2.25, 1.1, 0.795, 1.1)))
ALL = dict(zip(step_gap.SEGMENTS, (5.4, 2.5, 1.0, 0.8, 0.9)))
OFFSETS = (-0.5, 0.59)       # B's way back; B's start after its call began


def _events(names=TICKS, calls=True):
    out = [("serve.wait", 90.5, 100, {})]
    for k in names:
        put, h2d, call, fetch, prefill, _ = TICKS[k]
        out += [("serve.tick", put[0] - 0.3, put[1] + 0.2, {}),
                ("ragged.put", *put, {"prefill": prefill}),
                ("ragged.pack", put[0], h2d[0] - 0.01, {}),
                ("ragged.dispatch", h2d[0] - 0.01, call[1], {}),
                ("ragged.fetch", *fetch, {"bytes": 256}),
                ("ragged.rows", fetch[1], fetch[1] + 0.2, {})]
        if calls:
            out += [("ragged.h2d", *h2d, {"arrays": 5, "bytes": 4096}),
                    ("ragged.call", *call, {"leaves": 40})]
    return [(n, a * MS, b * MS, attrs) for n, a, b, attrs in out]


def _spans(*threads):
    spans = []
    for events in threads:
        pt._nest(spans, events)
    return spans


def _ops(shift=0.0, names=TICKS):
    return [pt.Op(f"fusion.{i}", (a + shift) * MS, (b + shift) * MS, "")
            for k in names for i, (a, b) in enumerate(TICKS[k][5])]


def _pairs(spans, ops):
    return step_gap.gaps_of(
        step_gap.ticks_of(spans, 0, 1),
        sorted(s.start for s in spans if s.name == "serve.wait"),
        [(o.start, o.end) for o in ops])


def _segments(spans, ops, capsys):
    found = step_gap.segments(spans, ops, 0.0, 1.0)
    return found, capsys.readouterr().out


def _identity(capsys):
    """Each gap's five by hand, adding up gap by gap with what
    ``ragged.dispatch`` holds between its children (0.01 ms in A-B)."""
    spans, ops = _spans(_events()), _ops()
    pairs = _pairs(spans, ops)
    kept = {"AB": pairs[0], "BC": pairs[1], "DE": pairs[3]}
    for name, want in BY_HAND.items():
        g = kept[name]
        got = [getattr(g, n) / MS for n in step_gap.SEGMENTS]
        assert got == pytest.approx(list(want), abs=1e-9), name
        assert g.gap == pytest.approx(g.between + g.h2d + g.call + g.handoff
                                      + g.residue, abs=1e-12)
        assert g.handoff == pytest.approx(g.way_back + g.launch, abs=1e-12)
    assert kept["AB"].residue / MS == pytest.approx(0.01)
    found, said = _segments(spans, ops, capsys)
    assert found == pytest.approx(DECODE)
    assert "5 ticks, 4 pairs of them with the chip's gap found, 1 with a " \
        "serve.wait" in said
    assert "3 gaps, 2 of them before a decode-only tick" in said
    assert "largest 0.0100 ms" in said


def _early_start(capsys):
    """B's step starts 0.2 ms before its call returns: the launch is signed
    and the hand-off still the way back plus it, exactly."""
    spans, ops = _spans(_events()), _ops()
    ab = _pairs(spans, ops)[0]
    assert ab.launch / MS == pytest.approx(-0.2)
    assert ab.way_back / MS == pytest.approx(0.8)
    assert ab.handoff / MS == pytest.approx(0.6)
    _, said = _segments(spans, ops, capsys)
    assert "1 of 3 before it" in said
    assert f"[{OFFSETS[0]:.4f}, {OFFSETS[1]:.4f}] ms, holds 0" in said


def _shifted(shift):
    def case(capsys):
        """Every device time moved by ``shift`` ms: the five numbers stay,
        the interval of offsets moves with it and no longer holds 0."""
        found, said = _segments(_spans(_events()), _ops(shift), capsys)
        assert found == pytest.approx(DECODE)
        assert ("medians over all 3: " + ", ".join(
            f"{n} {v:.4f} ms" for n, v in ALL.items())) in said
        assert (f"[{OFFSETS[0] + shift:.4f}, {OFFSETS[1] + shift:.4f}] ms, "
                f"does NOT hold 0") in said
    return case


def _wait_drops_a_gap(capsys):
    """C-D holds a ``serve.wait``: without the span it is a gap of 16 ms
    before a decode-only tick and moves the medians."""
    ops = _ops()
    no_wait = [e for e in _events() if e[0] != "serve.wait"]
    found, said = _segments(_spans(no_wait), ops, capsys)
    assert "0 with a serve.wait" in said and "4 gaps, 3 of them" in said
    assert found["gap"] == pytest.approx(5.6)        # of 4.9, 5.6, 16.0
    found, _ = _segments(_spans(_events()), ops, capsys)
    assert found["gap"] == pytest.approx(5.25)


def _prefill_leaves_the_decode_medians(capsys):
    """B-C ends in a tick with ``prefill`` 200: in the all-tick medians,
    not in the metric; with no decode-only second tick nothing is read."""
    found, said = _segments(_spans(_events()), _ops(), capsys)
    assert found == pytest.approx(DECODE)
    assert "gap 5.4000 ms, between 2.5000 ms, h2d 1.0000 ms" in said
    only = _spans(_events("BC"))
    found, said = _segments(only, _ops(names="BC"), capsys)
    assert found is None and "1 gaps, 0 of them" in said


def _no_call_reads_none(capsys):
    """The parent's trace: the same ticks without the two spans."""
    found, said = _segments(_spans(_events(calls=False)), _ops(), capsys)
    assert found is None and said == ""
    assert step_gap.segments(_spans(_events()), [], 0, 1) is None


def _children_share_their_parents_bucket(capsys):
    assert idle_by_span.bucket_of("ragged.h2d") \
        == idle_by_span.bucket_of("ragged.call") \
        == idle_by_span.bucket_of("ragged.dispatch") == "prepare"
    spans = _spans(_events())
    for s in spans:
        if s.name in ("ragged.h2d", "ragged.call"):
            assert spans[s.parent].name == "ragged.dispatch"


def _another_threads_ticks_are_not_paired(capsys):
    """A second thread's ``put`` (the check's, a caller's own) overlaps the
    server's in the list's order and joins no gap."""
    other = [(n, a + 5 * MS, b + 5 * MS, attrs)
             for n, a, b, attrs in _events("A") if n != "serve.wait"]
    found, said = _segments(_spans(_events(), other), _ops(), capsys)
    assert found == pytest.approx(DECODE)
    assert "6 ticks, 4 pairs of them" in said


def _off_by_more_than_a_gap(capsys):
    """The device's line 10 ms late: a hand-over falls inside a step and
    meets at most the step's own short pauses, never an idle stretch as
    long as itself: the pair is left out, not misread. Only C-D's 16 ms
    are found, and they hold a ``serve.wait``."""
    found, said = _segments(_spans(_events()), _ops(10.0), capsys)
    assert found is None
    assert "5 ticks, 1 pairs of them with the chip's gap found, 1 with a " \
        "serve.wait between (dropped), 0 gaps" in said


def _old_readings_do_not_move(capsys):
    """What the benchmark had reads the same with the two children in the
    trace as without: the idle shares by bucket (the children's moments go
    where their parent's went), the decode tick, the server's own time."""
    from benchmarks import trace_reduce as tr
    from benchmarks.readers import span_attr_stat

    got = []
    for calls in (True, False):
        record = {"program_trace": pt.ProgramTrace(
            _spans(_events(calls=calls)), {0: _ops()}), "window": (0.0, 1.0),
            "trace": tr.Trace(device_ops={0: [
                (o.name, o.start, o.end) for o in _ops()]})}
        got.append(
            [idle_by_span.read(record, {"bucket": b})
             for b in ("prepare", "fetch", "server", "waiting")]
            + [span_attr_stat.read(record, dict(a, stat="median_ms"))
               for a in ({"span": "ragged.put", "attr": "prefill",
                          "is": "zero"},
                         {"span": "serve.tick", "less": "ragged.put"})])
    assert got[0] == pytest.approx(got[1], abs=1e-12)
    assert all(v is not None and v > 0 for v in got[0])
    said = capsys.readouterr().out
    assert "ragged.h2d" in said and "ragged.call" in said


CASES = [_identity, _early_start, _shifted(1.0), _shifted(-1.0),
         _wait_drops_a_gap, _prefill_leaves_the_decode_medians,
         _no_call_reads_none, _children_share_their_parents_bucket,
         _another_threads_ticks_are_not_paired, _off_by_more_than_a_gap,
         _old_readings_do_not_move]
IDS = ["identity", "early_start", "device_late_1ms", "device_early_1ms",
       "serve_wait", "prefill", "no_call", "bucket_of", "two_threads",
       "off_by_a_gap", "old_readings"]


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_step_gap_on_a_hand_built_trace(case, capsys):
    case(capsys)


@pytest.mark.parametrize("metric,segment", [
    ("gap_ms", "gap"), ("gap_between_ms", "between"), ("gap_h2d_ms", "h2d"),
    ("gap_call_ms", "call"), ("gap_handoff_ms", "handoff")])
def test_metric_file_reads_its_segment_once_a_record(metric, segment, capsys):
    """Each of the five files through the harness, as ``run.py`` reads it;
    the record keeps what the first read computed."""
    spec = harness.read_json(os.path.join(harness.HERE, "metrics",
                                          metric + ".json"))
    assert spec["reader"] == "step_gap" and spec["args"] == {
        "segment": segment}
    assert spec["unit"] == "ms" and spec["moves"] == "itl_p50_ms"
    record = {"program_trace": pt.ProgramTrace(_spans(_events()),
                                               {0: _ops(), 1: []}),
              "window": (0.0, 1.0)}
    read = harness.find("readers", spec["reader"]).read
    assert read(record, dict(spec["args"])) == pytest.approx(DECODE[segment])
    assert capsys.readouterr().out.count("step gaps over") == 1
    for other in step_gap.SEGMENTS:
        assert read(record, {"segment": other}) == pytest.approx(DECODE[other])
    assert capsys.readouterr().out == ""
    parent = {"program_trace": pt.ProgramTrace(
        _spans(_events(calls=False)), {0: _ops()}), "window": (0.0, 1.0)}
    assert read(parent, dict(spec["args"])) is None
