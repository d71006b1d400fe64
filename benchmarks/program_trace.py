"""The program's own spans and scopes, out of the profiler's trace.

``run.py`` keeps only the runner's span names in ``record["trace"]``. The
program writes host spans of its own (``deepspeed_tpu/profiling/trace.py``
``annotate``: ``ragged.*``, ``serve.*``, ``train.*``, with integer
attributes) and names its device operations with ``jax.named_scope``.
``of(record)`` loads both once from the run's ``.xplane.pb`` and keeps them
on the record for the readers ``span_attr_stat``, ``idle_by_span`` and
``scope_device``. What it returns is plain lists (``Span``, ``Op``), so a
hand-built trace tests the readers as well as a recorded one. A program
without such spans or scopes (the parent of the PR that brought them) gives
empty lists, and the readers then find nothing to read.

What a TPU v5e trace holds (read by hand on PR 24's traces with the raw
protobuf, PR 25):

* A host span's attributes (``TraceAnnotation(name, lanes=64)``, or
  ``set_metadata`` before it ends) are stats of its event, which
  ``jax.profiler.ProfileData`` gives as ``event.stats``; host threads are
  lines of ``/host:CPU``, and a span's parent is the span of its own line
  that encloses it.
* A device event (line ``XLA Ops`` of ``/device:TPU:<n>``) carries only
  ``device_offset_ps``, ``device_duration_ps`` and a time scale as stats:
  nothing of the JAX scope. The scope is in the event's *metadata* (the
  plane's ``event_metadata`` map, one entry an HLO instruction, keyed by the
  event's ``metadata_id`` and named by the instruction's whole text, which
  is what ``ProfileData`` gives as the event's name): its stat ``tf_op``
  holds the instruction's ``op_name``, as in
  ``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/dot_general:``,
  and ``program_id`` the program. ``ProfileData`` does not give metadata
  stats, so ``op_names`` below reads that one map from the file's bytes
  (protobuf wire format, no dependency).
* Instructions the compiler made carry no JAX path: XLA's expansion of
  ``ragged_dot`` is a custom call with the ``op_name`` ``ragged-dot-none:``
  (a quarter of Mixtral's step), a copy of an argument is named after the
  argument (``pools[0][3]:``: the KV pool, copied whole before every
  scatter), many small copies nothing. Their neighbours in time do not tell
  (the scheduler puts the next layer's weight slice between two ragged
  dots), but the HLO does: the profiler stores each executed program's
  ``HloProto`` in the plane ``/host:metadata`` (one event metadata a
  program, named ``jit_step(<program id>)``, stat ``Hlo Proto``), and there
  a pathless instruction's first user with a path (then its first operand
  with one) says what it was made for. ``hlo_paths`` reads that.
* A fusion carries one ``op_name``, that of one instruction in it, so a
  fusion that XLA built across two scopes counts under one of them.
* Backward operations are those whose ``op_name`` holds ``transpose(``.
  What ``jax.checkpoint`` recomputes in the backward pass
  (``.../checkpoint/rematted_computation/...``) sits under the same
  ``transpose(jvp())`` and counts as backward.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

from benchmarks import trace_reduce as tr

PROGRAM_SPAN = re.compile(r"^(ragged|serve|train)\.\w+$")
#: scopes that split a step's device time (docs/observability.md), and the
#: scopes inside them that the readers' tables list on a line of their own
SCOPES = ("embed", "weights", "attn", "ffn", "head", "optimizer")
INNER = ("paged_attention", "flash_attention", "router", "experts")
NO_SCOPE = "(no scope)"
WORD = re.compile(r"[A-Za-z_][\w.\-]*")


class Span(NamedTuple):
    """One host span of the program, seconds on the trace's clock.
    ``parent``: index into the list of the span that encloses it on its
    own thread, or None."""

    name: str
    start: float
    end: float
    attrs: Dict[str, int]
    parent: Optional[int]


class Op(NamedTuple):
    """One executed device operation: its short name, seconds on the
    trace's clock, and the ``op_name`` JAX gave it, or the one it was
    made for (``hlo_paths``), or '' where neither."""

    name: str
    start: float
    end: float
    op_name: str


class ProgramTrace(NamedTuple):
    spans: List[Span]                 # thread after thread, each by start
    ops: Dict[int, List[Op]]          # chip -> operations by start time


# ----------------------------------------------------------------------
# the event metadata's tf_op, from the file's bytes
def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _map_entries(plane, field: int) -> Iterator[Tuple[int, Any]]:
    """(key, value bytes) of a ``map<int64, Message>`` field."""
    for num, entry in _fields(plane):
        if num == field:
            parts = dict(_fields(entry))
            yield parts.get(1, 0), parts.get(2, b"")


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _short(text: str) -> str:
    """An instruction's name out of its whole text (``%fusion.3 = ...``)."""
    return tr.short(text[:200].replace("%", ""))


def _ints(message, field: int) -> List[int]:
    """A ``repeated int64`` field: packed, or one varint an element."""
    out: List[int] = []
    for num, value in _fields(message):
        if num == field and isinstance(value, int):
            out.append(value)
        elif num == field:
            i = 0
            while i < len(value):
                one, i = _varint(value, i)
                out.append(one)
    return out


def has_path(op_name: str) -> bool:
    """Whether JAX named the operation (a scope or a ``jit(`` path)."""
    return "jit(" in op_name or scope_of(op_name)[0] != NO_SCOPE


def _made_for(module) -> Dict[str, str]:
    """{instruction name: the ``op_name`` it counts under} for the
    pathless instructions of one ``HloModuleProto`` (computations 3;
    HloComputationProto instructions 2; HloInstructionProto name 1,
    metadata 7 with op_name 2, id 35, operand_ids 36): the first user,
    through pathless users, that has a path; failing that the first such
    operand."""
    instr: Dict[int, Tuple[str, str, List[int]]] = {}
    for num, comp in _fields(module):
        if num != 3:
            continue
        for f, ins in _fields(comp):
            if f != 2:
                continue
            parts = {k: v for k, v in _fields(ins) if k in (1, 7, 35)}
            op = next((_text(v) for k, v in _fields(parts.get(7, b""))
                       if k == 2), "")
            instr[parts.get(35, 0)] = (_text(parts.get(1, b"")), op,
                                       _ints(ins, 36))
    users: Dict[int, List[int]] = {}
    for i, (_, _, operands) in instr.items():
        for o in operands:
            users.setdefault(o, []).append(i)

    def walk(i: int, step, depth: int) -> str:
        if i not in instr:
            return ""
        if has_path(instr[i][1]):
            return instr[i][1]
        for j in (step(i) if depth else ()):
            found = walk(j, step, depth - 1)
            if found:
                return found
        return ""

    return {name: walk(i, lambda k: users.get(k, ()), 6)
            or walk(i, lambda k: instr[k][2], 6)
            for i, (name, op, _) in instr.items() if not has_path(op)}


def hlo_paths(data: bytes) -> Dict[int, Dict[str, str]]:
    """program id -> {pathless instruction: the ``op_name`` it counts
    under}, from the ``HloProto``s (hlo_module 1) a serialized ``XSpace``
    keeps in its ``/host:metadata`` plane."""
    out: Dict[int, Dict[str, str]] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1 or next((_text(v) for f, v in _fields(plane)
                             if f == 2), "") != "/host:metadata":
            continue
        for _, meta in _map_entries(plane, 4):
            name = next((_text(v) for f, v in _fields(meta) if f == 2), "")
            m = re.search(r"\((\d+)\)$", name)
            for f, stat in _fields(meta):
                proto = dict(_fields(stat)).get(6) if f == 5 else None
                if m and proto is not None:
                    module = dict(_fields(proto)).get(1, b"")
                    out[int(m.group(1))] = _made_for(module)
    return out


def op_names(data: bytes) -> Dict[int, Dict[str, str]]:
    """chip -> {instruction text (the event's name): its ``op_name``}, from
    a serialized ``XSpace``: for every ``/device:TPU:<n>`` plane (XSpace
    field 1; XPlane name 2, event_metadata 4, stat_metadata 5), each event
    metadata's (name 2, stats 5) stat whose metadata is named ``tf_op``
    (XStat metadata_id 1, str_value 5, or ref_value 7 into the stat names);
    for an instruction without a path, what ``hlo_paths`` says of it in its
    program (stat ``program_id``, uint64_value 3 or int64_value 4)."""
    made = hlo_paths(data)
    out: Dict[int, Dict[str, str]] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name = next((_text(v) for f, v in _fields(plane) if f == 2), "")
        m = tr.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_names = {key: next((_text(v) for f, v in _fields(value)
                                 if f == 2), "")
                      for key, value in _map_entries(plane, 5)}
        names = out.setdefault(int(m.group(1)), {})
        for _, meta in _map_entries(plane, 4):
            text, op, program = "", "", None
            for f, v in _fields(meta):
                if f == 2:
                    text = _text(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    kind = stat_names.get(stat.get(1))
                    if kind == "tf_op":
                        op = _text(stat[5]) if 5 in stat \
                            else stat_names.get(stat.get(7), "")
                    elif kind == "program_id":
                        program = stat.get(3, stat.get(4, 0)) % 2 ** 64
            if not has_path(op):
                op = made.get(program, {}).get(_short(text)) or op
            names[text] = op
    return out


# ----------------------------------------------------------------------
def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    return from_profile(ProfileData.from_serialized_xspace(data),
                        op_names(data))


def from_profile(profile, names: Dict[int, Dict[str, str]]) -> ProgramTrace:
    spans: List[Span] = []
    ops: Dict[int, List[Op]] = {}
    for plane in profile.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            known = names.get(chip, {})
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    short: Dict[str, str] = {}
                    ops[chip] = sorted(
                        (Op(short.setdefault(e.name, _short(e.name)),
                            e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            known.get(e.name, ""))
                         for e in line.events), key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                _nest(spans, ((e.name, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9,
                               {k: int(v) for k, v in e.stats
                                if isinstance(v, (int, float))})
                              for e in line.events
                              if PROGRAM_SPAN.match(e.name)))
    return ProgramTrace(spans, ops)


def _nest(out: List[Span], events) -> None:
    """Appends one thread's spans, each with its parent: the last span of
    that thread still open when it starts."""
    stack: List[int] = []
    for name, a, b, attrs in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]].end < b:
            stack.pop()
        out.append(Span(name, a, b, attrs, stack[-1] if stack else None))
        stack.append(len(out) - 1)


def of(record: Dict[str, Any]) -> ProgramTrace:
    """The record's program trace, loaded once."""
    if "program_trace" not in record:
        tracer = record.get("tracer")
        record["program_trace"] = load(tr.find_xplane(tracer.dir)) \
            if tracer is not None else ProgramTrace([], {})
    return record["program_trace"]


# ----------------------------------------------------------------------
# what the readers ask
def inside(spans: List[Span], name: str, lo: float, hi: float) -> List[int]:
    """Indices of the spans of that name lying wholly inside [lo, hi]."""
    return [i for i, s in enumerate(spans)
            if s.name == name and s.start >= lo and s.end <= hi]


def scope_of(op_name: str) -> Tuple[str, str, bool]:
    """(scope, scope or scope/inner, backward) of an ``op_name``: the first
    path component that is one of ``SCOPES`` (a transform may wrap it:
    ``transpose(jvp(attn))``), then the first of ``INNER`` after it."""
    top = key = NO_SCOPE
    for part in op_name.split("/"):
        words = WORD.findall(part)
        word = words[-1] if words else ""
        if top == NO_SCOPE and word in SCOPES:
            top = key = word
        elif word in INNER:
            key = word if top == NO_SCOPE else f"{top}/{word}"
            break
    return top, key, "transpose(" in op_name


def scoped(ops: List[Op]) -> List[Tuple[Op, str, bool]]:
    """(operation, scope or scope/inner, backward) for every operation that
    is not a ``while`` around others."""
    return [(o,) + scope_of(o.op_name)[1:] for o in ops
            if not tr.CONTAINER.match(o.name)]


def device_seconds(rows: List[Tuple[Op, str, bool]], lo: float, hi: float
                   ) -> Dict[Tuple[str, bool], float]:
    """Seconds of the operations of ``scoped`` that start in [lo, hi), by
    (scope or scope/inner, backward)."""
    out: Dict[Tuple[str, bool], float] = {}
    for o, key, back in rows:
        if lo <= o.start < hi:
            out[key, back] = out.get((key, back), 0.0) + (o.end - o.start)
    return out
