"""The traced steps' collectives by kind, pass and scope, found by what each
device operation's HLO instruction IS, not by the first word of its name.

``readers/collectives.py`` (``trace_reduce.COLLECTIVE``) knows a collective
by a name that starts with ``all-gather``, ``all-reduce``, ...; the TPU
compiler leaves others under other names: a gradient's reduce-scatter runs
as a fusion ``fusion.N`` over an all-reduce and this chip's slice
(computation ``all-reduce-scatter``), an asynchronous parameter gather as a
pair of fusions ``async-collective-start.N`` / ``async-collective-done.N``
around one channel, with a continuation fusion of compute between them.
The profiler stores each executed program's ``HloProto`` in the trace
(plane ``/host:metadata``, as ``program_trace.hlo_paths`` reads it), and
there an event's instruction says what it is: ``programs`` lists, a
program, every instruction that is a collective or calls a computation
that holds one, with its kind, its bytes (from the instruction's shape),
whether a ``while`` body holds it and its part in a flight (``whole``: a
synchronous collective; ``start`` / ``mid`` / ``done``: an asynchronous
one's).

``flights_of`` then walks one chip's operations: a collective is in flight
from its ``start`` (or ``whole``) event's start to its ``done``'s end; the
part of it with no compute beside it is exposed (compute: every operation
that is neither a collective's ``whole`` / ``start`` / ``done`` nor a
``while`` around others, as ``trace_reduce.collectives`` has it, so the two
agree where they see the same operations); each exposed stretch belongs to
one flight (the first to cover it), hence to one kind and one pass
(``optimizer`` by scope, else ``bwd`` where ``transpose(`` is in the
``op_name``, else ``fwd``), and the operation that starts at its end is the
one that waited.

``args['what']``, all a step (``train_batch`` spans of the window), mean
over chips:
  exposed_ms   - exposed time of the pass ``args['pass']``
  flight_ms    - time a collective of ``args['kinds']`` was in flight
  attr_gb      - ``train.step``'s attribute ``args['attr']`` (``sent_bytes``,
                 ``plan_bytes``) in GB: the program's own count
  gbps         - bytes a chip sent a step, counted here (each flight of the
                 trace by its instruction's shape and replica group, by the
                 ring's count, as the program counts ``sent_bytes``), over
                 the time any collective was in flight
  scope_ms     - device time under the path ``args['scope']`` (as
                 ``named_scope_device`` finds one) in a ``train.step``,
                 median over the spans
Nothing to read on one chip (no collective), from a program without the
attribute or the scope (the parent of the PR that brought them), or
without a device line. Says on an earlier line the ten collectives with
most exposed time a step and the totals by kind and by pass, beside them
what ``trace_reduce.collectives`` counts of the same window, and the bytes
sent by kind beside ``train.step``'s ``sent_bytes``: the program multiplies
a loop body's bytes by a trip count it reads off its text, the reader counts
the events that ran, and the line says so where the two part.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmarks import harness, program_trace as pt, trace_reduce as tr
from benchmarks.readers import collectives as by_name
from benchmarks.readers.named_scope_device import under

#: HLO opcode -> kind; a ``-done`` closes the flight its operand opened
KINDS = {
    "all-gather": "all-gather", "all-gather-start": "all-gather",
    "all-reduce": "all-reduce", "all-reduce-start": "all-reduce",
    "reduce-scatter": "reduce-scatter",
    "all-to-all": "all-to-all", "ragged-all-to-all": "all-to-all",
    "collective-permute": "collective-permute",
    "collective-permute-start": "collective-permute",
    "collective-broadcast": "collective-broadcast",
}
#: xla_data.proto PrimitiveType -> bytes an element
ITEMSIZE = {1: 1, 2: 1, 3: 2, 4: 4, 5: 8, 6: 1, 7: 2, 8: 4, 9: 8, 10: 2,
            11: 4, 12: 8, 15: 8, 16: 2, 18: 16, 19: 1, 20: 1, 21: 1, 22: 1,
            23: 1, 24: 1, 25: 1}
PASSES = ("fwd", "bwd", "optimizer")


class Info(NamedTuple):
    """What a program's ``HloProto`` says of one instruction."""

    kind: str
    bytes: int          # a gather's result, a reduction's operand
    group: int          # devices in its replica group; 0: every chip
    role: str           # whole | start | mid | done
    flight: str         # the name of the instruction that opens its flight
    in_loop: bool


class Flight(NamedTuple):
    name: str           # the instruction that opened it
    kind: str
    bytes: int
    group: int
    in_loop: bool
    op_name: str
    start: float
    end: float


# ----------------------------------------------------------------------
# the HloProto (hlo.proto: HloModuleProto computations 3, entry_computation_id
# 6; HloComputationProto name 1, instructions 2, id 5; HloInstructionProto
# name 1, opcode 2, shape 3, channel_id 26, id 35, operand_ids 36,
# called_computation_ids 38, replica_groups 49, source_target_pairs 52,
# collective_device_list 87; ShapeProto element_type 2, dimensions 3,
# tuple_shapes 4)
def _shape_bytes(shape) -> List[int]:
    """Bytes of every array of a ``ShapeProto``, a tuple's in order."""
    kind, out = 0, []
    for num, value in pt._fields(shape):
        if num == 2:
            kind = value
        elif num == 4:
            out += _shape_bytes(value)
    if kind == 13:                      # TUPLE
        return out
    n = 1
    for d in pt._ints(shape, 3):
        n *= d
    return [n * ITEMSIZE.get(kind, 0)]


def _group(ins) -> int:
    """Devices in an instruction's (first) replica group: the
    ``collective_device_list`` (a list of ``ReplicaGroup``s, or an iota's
    ``num_devices_per_group``), an older writer's ``replica_groups``, a
    permute's pairs; 0 where none is given: every chip."""
    pairs = set()
    for num, value in pt._fields(ins):
        if num == 87:
            for f, v in pt._fields(value):
                if f == 1:
                    return len(pt._ints(v, 1))
                if f == 2:
                    return dict(pt._fields(v)).get(2, 0)
        elif num == 49:
            return len(pt._ints(value, 1))
        elif num == 52:
            pair = dict(pt._fields(value))
            pairs |= {pair.get(1, 0), pair.get(2, 0)}
    return len(pairs)


def sent_bytes(kind: str, payload: int, group: int) -> int:
    """Bytes a chip sends (= receives) for one collective by the ring's
    count, as ``TrainEngine.zero_plan`` and ``train.step``'s ``sent_bytes``
    count: (n-1)/n of the payload, twice that for an all-reduce, the whole
    payload for a permute."""
    if kind == "collective-permute":
        return payload
    share = payload * (group - 1) // group if group > 1 else 0
    return 2 * share if kind == "all-reduce" else share


def module_collectives(module) -> Dict[str, Info]:
    """{instruction name: Info} of one ``HloModuleProto``'s collectives: the
    instructions of its scheduled computations (the entry, ``while`` bodies
    and conditions, branches, calls) that are one or fold one in (a
    fusion's body, an async wrapper)."""
    comps: Dict[int, List[dict]] = {}
    entry = None
    for num, value in pt._fields(module):
        if num == 6:
            entry = value
        if num != 3:
            continue
        comp_id, instrs = None, []
        for f, v in pt._fields(value):
            if f == 5:
                comp_id = v
            elif f == 2:
                parts = {k: x for k, x in pt._fields(v)
                         if k in (1, 2, 3, 26, 35)}
                instrs.append({
                    "name": pt._text(parts.get(1, b"")),
                    "opcode": pt._text(parts.get(2, b"")),
                    "shape": parts.get(3, b""), "channel": parts.get(26, 0),
                    "id": parts.get(35, 0), "operands": pt._ints(v, 36),
                    "calls": pt._ints(v, 38), "proto": v})
        comps[comp_id] = instrs
    if entry is None:
        return {}
    held: Dict[int, Optional[Tuple[dict, List[dict]]]] = {}

    def holds(comp: int):
        if comp not in held:
            held[comp] = None
            for ins in comps.get(comp, ()):
                held[comp] = inner_of(ins, comp)
                if held[comp] is not None:
                    break
        return held[comp]

    def inner_of(ins: dict, comp: int):
        if ins["opcode"] in KINDS:
            return ins, comps[comp]
        if ins["opcode"] in ("fusion", "async-start", "custom-call"):
            for callee in ins["calls"]:
                if holds(callee) is not None:
                    return holds(callee)
        return None

    out: Dict[str, Info] = {}
    stack, seen = [(entry, False)], set()
    while stack:
        comp, in_loop = stack.pop()
        if (comp, in_loop) in seen:
            continue
        seen.add((comp, in_loop))
        by_start: Dict[int, str] = {}    # a start's (or done's) id -> flight
        by_channel: Dict[int, str] = {}
        members: Dict[str, List[str]] = {}
        for ins in comps.get(comp, ()):
            op = ins["opcode"]
            if op == "while":
                stack.extend((c, True) for c in ins["calls"])
                continue
            if op in ("conditional", "call"):
                stack.extend((c, in_loop) for c in ins["calls"])
                continue
            if op.endswith("-done") or op == "async-update":
                flight = by_start.get(ins["operands"][0]) \
                    if ins["operands"] else None
                if flight is not None:
                    by_start[ins["id"]] = flight
                    out[ins["name"]] = out[flight]._replace(
                        role="done" if op.endswith("-done") else "mid")
                continue
            got = inner_of(ins, comp)
            if got is None:
                continue
            inner, siblings = got
            channel = inner["channel"]
            if channel and channel in by_channel:
                # a later fusion around a channel already open: compute
                # beside the flight, until the last one closes it
                flight = by_channel[channel]
                members[flight].append(ins["name"])
                out[ins["name"]] = out[flight]._replace(role="mid")
                continue
            kind, size = _kind_and_bytes(inner, siblings)
            out[ins["name"]] = Info(
                kind, size, _group(inner["proto"]),
                "start" if op.endswith("-start") else "whole",
                ins["name"], in_loop)
            members[ins["name"]] = [ins["name"]]
            by_start[ins["id"]] = ins["name"]
            if channel:
                by_channel[channel] = ins["name"]
        for flight, names in members.items():
            if len(names) > 1:
                out[flight] = out[flight]._replace(role="start")
                out[names[-1]] = out[names[-1]]._replace(role="done")
    return out


def _kind_and_bytes(inner: dict, siblings: List[dict]) -> Tuple[str, int]:
    kind = KINDS[inner["opcode"]]
    arrays = _shape_bytes(inner["shape"])
    if inner["opcode"] == "all-gather-start" and len(arrays) >= 2:
        arrays = arrays[len(arrays) // 2:]       # (operands, results)
    elif inner["opcode"] == "collective-permute-start" and len(arrays) >= 2:
        arrays = arrays[1:2]
    size = sum(arrays)
    if kind == "reduce-scatter":
        operands = {i["id"]: i for i in siblings}
        shapes = [_shape_bytes(operands[o]["shape"])
                  for o in inner["operands"] if o in operands]
        size = sum(b for s in shapes for b in s) if shapes else size
    elif kind == "all-reduce" and any(
            i["opcode"] == "dynamic-slice" and i["operands"][:1]
            == [inner["id"]] for i in siblings):
        kind = "reduce-scatter"     # the TPU's ``all-reduce-scatter`` fusion
    return kind, size


def programs(data: bytes) -> Dict[int, Dict[str, Info]]:
    """program id -> its collectives, from the ``HloProto``s a serialized
    ``XSpace`` keeps in ``/host:metadata`` (``program_trace.hlo_paths``
    reads the same stat)."""
    out: Dict[int, Dict[str, Info]] = {}
    for num, plane in pt._fields(memoryview(data)):
        if num != 1 or next((pt._text(v) for f, v in pt._fields(plane)
                             if f == 2), "") != "/host:metadata":
            continue
        for _, meta in pt._map_entries(plane, 4):
            name = next((pt._text(v) for f, v in pt._fields(meta)
                         if f == 2), "")
            m = re.search(r"\((\d+)\)$", name)
            for f, stat in pt._fields(meta):
                proto = dict(pt._fields(stat)).get(6) if f == 5 else None
                if m and proto is not None:
                    found = module_collectives(
                        dict(pt._fields(proto)).get(1, b""))
                    if found:
                        out[int(m.group(1))] = found
    return out


def known(data: bytes) -> Dict[str, Info]:
    """{instruction name: Info} over the traced programs that hold a
    collective. In a window of train steps that is the step's program
    alone; were there two, a name both use keeps the first's."""
    out: Dict[str, Info] = {}
    for _, found in sorted(programs(data).items()):
        for name, info in found.items():
            out.setdefault(name, info)
    return out


# ----------------------------------------------------------------------
# flights, exposure and who waited, on plain lists
def pass_of(op_name: str) -> str:
    top, _, back = pt.scope_of(op_name)
    return "optimizer" if top == "optimizer" else ("bwd" if back else "fwd")


def flights_of(ops: Sequence[pt.Op], info: Dict[str, Info], lo: float,
               hi: float) -> Tuple[List[Flight], List[tr.Interval]]:
    """(the collectives in flight inside [lo, hi], compute intervals) of
    one chip's operations by start time."""
    flights: List[Flight] = []
    open_at: Dict[str, List[pt.Op]] = {}
    compute: List[tr.Interval] = []
    for o in ops:
        i = info.get(o.name)
        if i is None or i.role == "mid":
            if not tr.CONTAINER.match(o.name):
                compute.append((o.start, o.end))
            continue
        if i.role == "start":
            open_at.setdefault(i.flight, []).append(o)
            continue
        first = o
        if i.role == "done":
            began = open_at.get(i.flight)
            first = began.pop(0) if began else o
        if min(o.end, hi) > max(first.start, lo):
            flights.append(Flight(i.flight, i.kind, i.bytes, i.group, i.in_loop,
                                  first.op_name or o.op_name,
                                  max(first.start, lo), min(o.end, hi)))
    flights.sort(key=lambda f: f.start)
    return flights, tr.union(tr.clip(compute, lo, hi))


class Stretch(NamedTuple):
    """A flight's own piece of the timeline (flights that overlap share
    nothing: the first to cover a moment owns it) and its exposed part."""

    flight: Flight
    owned: float
    exposed: List[tr.Interval]


def stretches(flights: Sequence[Flight], compute: Sequence[tr.Interval]
              ) -> List[Stretch]:
    out, covered = [], float("-inf")
    for f in flights:
        a = max(f.start, covered)
        if f.end > a:
            out.append(Stretch(f, f.end - a,
                               tr.subtract([(a, f.end)], compute)))
            covered = f.end
        else:
            out.append(Stretch(f, 0.0, []))
    return out


def waiters(ops: Sequence[pt.Op], info: Dict[str, Info]
            ) -> Tuple[List[float], List[str]]:
    """(starts, names) of the chip's compute operations by start time: the
    one that waited for an exposed stretch is the first to start at or
    after its end."""
    compute = [o for o in ops if not tr.CONTAINER.match(o.name)
               and (o.name not in info or info[o.name].role == "mid")]
    return [o.start for o in compute], [o.name for o in compute]


def reduce(ops: Dict[int, List[pt.Op]], info: Dict[str, Info], lo: float,
           hi: float, n_steps: int) -> Optional[dict]:
    """Seconds a step, mean over chips: ``flight`` (any collective in
    flight), ``exposed``, both also ``by_kind`` and ``by_pass``;
    ``sent_by_kind``: bytes a chip sent a step, each flight the trace shows
    by its instruction's shape and group (the reader's own count: no trip
    count of the program's goes into it); and ``rows``: a collective
    instruction's in-flight and exposed time with the operation that waited
    longest for it."""
    chips = sorted(ops)
    if not chips or not n_steps or not info:
        return None
    scale = 1.0 / (len(chips) * n_steps)
    out = {"flight": 0.0, "exposed": 0.0, "by_kind": {}, "by_pass": {},
           "flight_by_kind": {}, "sent_by_kind": {}, "rows": {}}
    for c in chips:
        flights, compute = flights_of(ops[c], info, lo, hi)
        if not flights:
            continue
        out["flight"] += tr.total(tr.union(
            (f.start, f.end) for f in flights)) * scale
        for kind in {f.kind for f in flights}:
            out["flight_by_kind"][kind] = out["flight_by_kind"].get(
                kind, 0.0) + tr.total(tr.union(
                    (f.start, f.end) for f in flights
                    if f.kind == kind)) * scale
        for f in flights:
            out["sent_by_kind"][f.kind] = out["sent_by_kind"].get(
                f.kind, 0.0) + sent_bytes(f.kind, f.bytes,
                                          f.group or len(chips)) * scale
        starts, names = waiters(ops[c], info)
        for s in stretches(flights, compute):
            f, exposed = s.flight, tr.total(s.exposed)
            row = out["rows"].setdefault(f.name, {
                "kind": f.kind, "bytes": f.bytes, "in_loop": f.in_loop,
                "op_name": f.op_name, "flight": 0.0, "exposed": 0.0,
                "waited": {}})
            row["flight"] += (f.end - f.start) * scale
            row["exposed"] += exposed * scale
            out["exposed"] += exposed * scale
            for key, name in (("by_kind", f.kind),
                              ("by_pass", pass_of(f.op_name))):
                out[key][name] = out[key].get(name, 0.0) + exposed * scale
            for a, b in s.exposed:
                at = bisect.bisect_left(starts, b - 1e-9)
                w = names[at] if at < len(names) else ""
                row["waited"][w] = row["waited"].get(w, 0.0) + (b - a) * scale
    return out if out["flight"] else None


# ----------------------------------------------------------------------
PLUMBING = ("while", "body", "cond", "closed_call", "checkpoint",
            "rematted_computation")


def scope_path(op_name: str) -> str:
    """An ``op_name`` as a line of the table shows it: without the jit, the
    loop's and the checkpoint's plumbing and the primitive at its end."""
    keep = [p for p in op_name.split("/")[:-1]
            if p not in PLUMBING and not p.startswith("jit(")]
    return "/".join(keep) or "(no scope)"


def _say(found: dict, old: Tuple[float, float],
         claimed: Optional[int]) -> None:
    ms = lambda s: f"{s * 1e3:.3f}"
    rows = sorted(found["rows"].items(), key=lambda kv: -kv[1]["exposed"])
    sent = sum(found["sent_by_kind"].values())
    if claimed is None:
        check = "train.step says nothing of it"
    else:
        off = abs(claimed - sent) / max(sent, 1.0)
        check = (f"train.step's sent_bytes {claimed / 1e9:.3f} GB, "
                 + ("the same" if off < 0.01 else
                    f"WHICH DIFFERS by {off:.1%}: the program's catalogue "
                    "counts other runs a step than the device ran"))
    harness.say(
        "collectives a step by HLO instruction, mean over chips: in flight "
        f"{ms(found['flight'])} ms, exposed {ms(found['exposed'])} ms; "
        "in flight by kind: " + ", ".join(
            f"{k} {ms(v)}" for k, v in sorted(
                found["flight_by_kind"].items(), key=lambda kv: -kv[1]))
        + "; exposed by kind: " + ", ".join(
            f"{k} {ms(v)}" for k, v in sorted(
                found["by_kind"].items(), key=lambda kv: -kv[1]))
        + "; exposed by pass: " + ", ".join(
            f"{p} {ms(found['by_pass'].get(p, 0.0))}" for p in PASSES)
        + f"; by name alone (trace_reduce.collectives): in flight "
          f"{ms(old[0])} ms, exposed {ms(old[1])} ms; {len(rows)} "
          f"instructions; sent a chip a step, by the events' shapes and "
          f"groups, {sent / 1e9:.3f} GB: " + ", ".join(
              f"{k} {v / 1e9:.3f}" for k, v in sorted(
                  found["sent_by_kind"].items(), key=lambda kv: -kv[1]))
        + f"; {check}")
    for name, r in rows[:10]:
        waited = max(r["waited"].items(), key=lambda kv: kv[1],
                     default=("", 0.0))[0]
        harness.say(
            f"  {name}: {r['kind']} {r['bytes'] / 1e6:.3f} MB, "
            f"{scope_path(r['op_name'])} ({pass_of(r['op_name'])}), "
            f"{'in the loop' if r['in_loop'] else 'outside the loop'}, "
            f"in flight {ms(r['flight'])} ms, exposed {ms(r['exposed'])} "
            f"ms, waited: {waited or 'nothing'}")


def of(record) -> Optional[dict]:
    """The record's reduction, made and said once."""
    if "collectives_by" in record:
        return record["collectives_by"]
    found = None
    program = pt.of(record)
    lo, hi = record["window"]
    info = record.get("hlo_collectives")
    if info is None and record.get("tracer") is not None:
        with open(tr.find_xplane(record["tracer"].dir), "rb") as f:
            info = known(f.read())
    trace = record["trace"]
    n_steps = len(tr.spans(trace, "train_batch", lo, hi))
    if info and len(program.ops) > 1:
        found = reduce(program.ops, info, lo, hi, n_steps)
    if found is not None:
        flight = by_name.read(record, {"what": "ms_per_step"}) or 0.0
        share = by_name.read(record, {"what": "exposed_pct"}) or 0.0
        _say(found, (flight * 1e-3, flight * share * 1e-5),
             step_attr(record, "sent_bytes"))
    record["collectives_by"] = found
    return found


def step_attr(record, attr: str) -> Optional[int]:
    """``train.step``'s static attribute over the window's spans."""
    spans = pt.of(record).spans
    lo, hi = record["window"]
    values = {spans[i].attrs[attr] for i in pt.inside(spans, "train.step",
                                                      lo, hi)
              if attr in spans[i].attrs}
    return max(values) if values else None


def scope_ms(record, scope: Sequence[str]) -> Optional[float]:
    """Median over the ``train.step`` spans (each to the next one's start,
    as ``scope_device`` cuts them) of the device time under ``scope``."""
    program = pt.of(record)
    lo, hi = record["window"]
    starts = sorted(program.spans[i].start for i in
                    pt.inside(program.spans, "train.step", lo, hi))
    chips = sorted(program.ops)
    rows = {c: [o for o in program.ops[c] if not tr.CONTAINER.match(o.name)
                and under(o.op_name, scope)] for c in chips}
    if not starts or not any(rows.values()):
        return None
    return harness.median([
        sum(o.end - o.start for c in chips for o in rows[c]
            if a <= o.start < b) / len(chips)
        for a, b in zip(starts, starts[1:] + [hi])]) * 1e3


def read(record, args):
    what = args["what"]
    if what == "scope_ms":
        return scope_ms(record, args["scope"])
    if what == "attr_gb":
        value = step_attr(record, args["attr"])
        return None if value is None or record["chips"] < 2 else value / 1e9
    found = of(record)
    if found is None:
        return None
    if what == "exposed_ms":
        return found["by_pass"].get(args["pass"], 0.0) * 1e3
    if what == "flight_ms":
        seconds = sum(v for k, v in found["flight_by_kind"].items()
                      if k in args["kinds"])
        return seconds * 1e3 if seconds else None
    if what == "gbps":
        return sum(found["sent_by_kind"].values()) / 1e9 / found["flight"]
    raise ValueError(f"unknown what {what!r}")
