"""The collectives of a compiled program, read off its optimized HLO text.

GSPMD places ZeRO's gathers and reductions inside the compiled train step,
where no wrapper of the comm facade sees them. The text of the compiled
program (``compiled.as_text()``) holds every one, under whatever name the
compiler left it: a plain ``all-gather`` / ``all-reduce`` /
``reduce-scatter`` / ``all-to-all`` / ``collective-permute``, the
``-start`` of an asynchronous pair (its ``-done`` is not counted again), an
``async-start`` around a wrapped collective, or a fusion whose body holds
one. :func:`catalogue` lists them, one entry an instruction *a device trace
shows* (the fusion, not the instruction inside it), each with its kind, its
payload in bytes, its (first) replica group, whether it is asynchronous, how
often a step runs it (a ``while`` body's by the loop's trip count, where the
text gives it) and its ``op_name``: the ``jax.named_scope`` path and the
pass it belongs to.

Two counts of bytes, under two names. ``bytes`` is the payload (a gather's
result, a reduction's operand): what the comm ledger books as ``bytes`` and
``wire_bytes`` (there "wire" means after compression, and nothing here is
compressed). ``sent_bytes`` is what one chip sends (and receives) for it by
the ring's count, (n-1)/n of the payload and twice that for an all-reduce:
the convention of ``TrainEngine.zero_plan`` and of ``train.step``'s
``sent_bytes``.

Host-side bookkeeping only: it reads text, once, off the step's path
(``TrainEngine.warmup``), and changes nothing of the program.
``TrainEngine.step_collectives()`` is the operator's way in
(docs/observability.md "Program spans and device scopes").
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import numpy as np

#: an instruction's opcode -> the kind it is booked under
KINDS = {
    "all-gather": "all-gather", "all-gather-start": "all-gather",
    "all-reduce": "all-reduce", "all-reduce-start": "all-reduce",
    "reduce-scatter": "reduce-scatter",
    "all-to-all": "all-to-all", "ragged-all-to-all": "all-to-all",
    "collective-permute": "collective-permute",
    "collective-permute-start": "collective-permute",
    "collective-broadcast": "collective-broadcast",
}
ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
            "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
            "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

# every pattern is anchored where it must be and used with ``search``: dslint's
# call graph goes by method name, and ``match`` is a method of the prefix cache
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_OPCODE = re.compile(r"^([\w\-]+)\(")
_CALLEE = re.compile(
    r"\b(calls|to_apply|body|condition|branch_computations|"
    r"called_computations|true_computation|false_computation)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([\d,]*)\}")
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS = re.compile(r"source_target_pairs=\{([^=]*?)\}\}")
_PARTITIONS = re.compile(r"\bnum_partitions=(\d+)")
_REPLICAS = re.compile(r"\breplica_count=(\d+)")
#: attributes that call a computation which folds into the caller's one
#: instruction (a fusion's body, an async wrapper); a ``while``'s body, a
#: conditional's branches and a ``call``'s target run as instructions of
#: their own, which a device trace shows; ``to_apply`` is a reducer
_FOLDED = ("calls", "called_computations")
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")


class Collective(NamedTuple):
    """One collective instruction of a compiled program."""

    kind: str          # all-gather, reduce-scatter, all-reduce, ...
    name: str          # the instruction a device trace shows
    bytes: int         # a gather's result, a reduction's operand
    members: Tuple[int, ...]   # its first replica group, by partition id
    asynchronous: bool
    in_loop: bool      # inside a ``while`` body
    runs: int          # times a call of the program runs it
    runs_known: bool   # False: an enclosing loop's trip count is not given
    op_name: str       # the JAX path: scope and pass
    dtype: str

    @property
    def group(self) -> int:
        """Devices in its replica group."""
        return len(self.members)

    @property
    def sent_bytes(self) -> int:
        """Bytes this chip sends (= receives) for one run, by the ring's
        count: (n-1)/n of the payload, twice that for an all-reduce, the
        whole payload for a permute."""
        return sent_bytes(self.kind, self.bytes, self.group)


def sent_bytes(kind: str, payload: int, group: int) -> int:
    if kind == "collective-permute":
        return payload
    if group <= 1:
        return 0
    share = payload * (group - 1) // group
    return 2 * share if kind == "all-reduce" else share


class _Instr(NamedTuple):
    name: str
    shape: str
    opcode: str
    rest: str          # operands and attributes


def _split_shape(text: str) -> Tuple[str, str]:
    """(shape, what follows it) of an instruction's right-hand side: a
    tuple shape runs to its matching parenthesis, an array's to the first
    blank (a layout holds none)."""
    if text.startswith("("):
        depth = 0
        for i, ch in enumerate(text):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return text[:i + 1], text[i + 1:].lstrip()
    head, _, tail = text.partition(" ")
    return head, tail


def _arrays(shape: str) -> List[Tuple[str, int]]:
    """(dtype, bytes) of every array in a shape's text, a tuple's in
    order; an unknown element type (a token) counts for nothing."""
    out = []
    for dtype, dims in _ARRAY.findall(shape):
        if dtype not in ITEMSIZE:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        out.append((dtype, n * ITEMSIZE[dtype]))
    return out


def _computations(text: str) -> Tuple[Dict[str, List[_Instr]], Optional[str]]:
    comps: Dict[str, List[_Instr]] = {}
    entry = current = None
    for line in text.splitlines():
        if current is None:
            m = _HEADER.search(line)
            if m:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.search(line)
        if not m:
            continue
        shape, rest = _split_shape(m.group(3))
        op = _OPCODE.search(rest)
        if op:
            comps[current].append(_Instr(m.group(2), shape, op.group(1),
                                         rest[op.end():]))
    return comps, entry


def _callees(rest: str) -> Iterable[Tuple[str, str]]:
    """(attribute, computation) for every computation an instruction
    calls."""
    for attr, value in _CALLEE.findall(rest):
        for name in re.findall(r"[\w.\-]+", value):
            yield attr, name


def _members(rest: str, devices: int) -> Tuple[int, ...]:
    """The first replica group of a collective's attributes, by partition
    id: a list ``{{0,1},{2,3}}``, an iota ``[groups,size]<=[dims]T(perm)``
    (the ids 0.. reshaped to ``dims``, transposed, cut into rows), a
    permute's pairs; every device where none is given."""
    m = _GROUPS_LIST.search(rest)
    if m and m.group(1):
        return tuple(int(d) for d in m.group(1).split(","))
    m = _GROUPS_IOTA.search(rest)
    if m:
        dims = [int(d) for d in m.group(3).split(",")]
        perm = [int(d) for d in m.group(4).split(",")] if m.group(4) else None
        ids = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm)
        return tuple(int(i) for i in ids.reshape(-1)[:int(m.group(2))])
    m = _PAIRS.search(rest)
    if m:
        return tuple(sorted({int(d) for d in re.findall(r"\d+", m.group(1))}))
    return tuple(range(devices))


def _trip_count(ins: _Instr, comps: Dict[str, List[_Instr]]
                ) -> Optional[int]:
    """A ``while``'s trip count where the text gives it: the loop's
    ``known_trip_count`` (the CPU's text has it), else a condition that
    compares a counter with an integer constant by ``LT`` (what ``lax.scan``
    and ``fori_loop`` lower to: the counter starts at 0 and steps by 1; the
    TPU's text gives the count no other way: ``mistral-7b.zero3-x4``'s two
    loops carry no ``known_trip_count``). The benchmark's reader checks the
    product against the events a traced step shows
    (``benchmarks/readers/collectives_by.py``, ``sent`` on its line)."""
    m = _TRIPS.search(ins.rest)
    if m:
        return int(m.group(1))
    cond = next((c for a, c in _callees(ins.rest) if a == "condition"), None)
    body = comps.get(cond, [])
    consts = {i.name: i.rest for i in body if i.opcode == "constant"
              and i.shape.startswith(("s32[]", "s64[]", "u32[]", "u64[]"))}
    for i in body:
        if i.opcode == "compare" and "direction=LT" in i.rest:
            operands = re.findall(r"%([\w.\-]+)", i.rest.split(")")[0])
            if len(operands) == 2 and operands[1] in consts:
                n = re.search(r"^(\d+)\)", consts[operands[1]])
                if n:
                    return int(n.group(1))
    return None


def catalogue(hlo_text: str) -> List[Collective]:
    """Every collective of one optimized HLO module, each under the
    instruction a device trace shows, in the order the schedule's
    computations are reached from the entry."""
    comps, entry = _computations(hlo_text)
    if entry is None:
        return []
    head = hlo_text[:hlo_text.find("\n")]
    devices = max([int(m.group(1)) for m in (_PARTITIONS.search(head),
                                             _REPLICAS.search(head)) if m]
                  or [1])

    # the collective a computation holds, itself or through what folds
    # into one of its instructions (a fusion's body, an async wrapper)
    held: Dict[str, Optional[Tuple[_Instr, List[_Instr]]]] = {}

    def holds(comp: str):
        if comp not in held:
            held[comp] = None
            for ins in comps.get(comp, ()):
                held[comp] = inner_of(ins, comp)
                if held[comp] is not None:
                    break
        return held[comp]

    def inner_of(ins: _Instr, comp: str):
        if ins.opcode in KINDS:
            return ins, comps[comp]
        for attr, callee in _callees(ins.rest):
            if attr in _FOLDED and holds(callee) is not None:
                return holds(callee)
        return None

    out: List[Collective] = []
    # the scheduled computations from the entry, each with how often a
    # call of the program runs it and whether a loop encloses it
    stack = [(entry, 1, True, False)]
    seen = set()
    while stack:
        comp, runs, known, in_loop = stack.pop()
        if (comp, runs, in_loop) in seen:
            continue
        seen.add((comp, runs, in_loop))
        flights: Dict[str, int] = {}       # channel -> index into ``out``
        for ins in comps.get(comp, ()):
            if ins.opcode == "while":
                trips = _trip_count(ins, comps)
                for attr, callee in _callees(ins.rest):
                    if attr == "body":
                        stack.append((callee, runs * (trips or 1),
                                      known and trips is not None, True))
                continue
            if ins.opcode in ("conditional", "call"):
                stack.extend((callee, runs, known, in_loop)
                             for attr, callee in _callees(ins.rest)
                             if attr != "to_apply")
                continue
            got = inner_of(ins, comp)
            if got is None:
                continue
            inner, siblings = got
            channel = _CHANNEL.search(inner.rest)
            if channel and channel.group(1) in flights:
                # the continuation or the done of a flight already
                # listed under its start
                at = flights[channel.group(1)]
                out[at] = out[at]._replace(asynchronous=True)
                continue
            if channel:
                flights[channel.group(1)] = len(out)
            out.append(_entry(ins, inner, siblings, devices, runs, known,
                              in_loop))
    return out


def _entry(outer: _Instr, inner: _Instr, siblings: List[_Instr],
           devices: int, runs: int, known: bool, in_loop: bool
           ) -> Collective:
    kind = KINDS[inner.opcode]
    arrays = _arrays(inner.shape)
    if inner.opcode == "all-gather-start" and len(arrays) >= 2:
        arrays = arrays[len(arrays) // 2:]    # (operands, results)
    elif inner.opcode == "collective-permute-start" and len(arrays) >= 2:
        arrays = arrays[1:2]                  # (operand, result, u32, u32)
    payload = sum(b for _, b in arrays)
    members = _members(inner.rest, devices)
    if kind == "reduce-scatter":
        payload *= len(members)               # the operand's
    elif kind == "all-reduce" and any(
            i.opcode == "dynamic-slice"
            and re.search(r"^%" + re.escape(inner.name) + r"[,)]", i.rest)
            for i in siblings):
        # the TPU compiler's reduce-scatter: a fusion (computation
        # ``all-reduce-scatter``) of an all-reduce and this chip's slice
        kind = "reduce-scatter"
    op = _OP_NAME.search(outer.rest) or _OP_NAME.search(inner.rest)
    return Collective(
        kind=kind, name=outer.name, bytes=payload, members=members,
        asynchronous=inner.opcode.endswith("-start")
        or outer.opcode == "async-start",
        in_loop=in_loop, runs=runs, runs_known=known,
        op_name=op.group(1) if op else "",
        dtype=arrays[0][0] if arrays else "")


# ----------------------------------------------------------------------
def totals(entries: Iterable[Collective]) -> Dict[str, Dict[str, int]]:
    """{kind: {count, bytes, sent_bytes}} a call of the program: ``count``
    the runs, ``bytes`` the payloads, ``sent_bytes`` what a chip sends."""
    out: Dict[str, Dict[str, int]] = {}
    for c in entries:
        t = out.setdefault(c.kind, {"count": 0, "bytes": 0, "sent_bytes": 0})
        t["count"] += c.runs
        t["bytes"] += c.bytes * c.runs
        t["sent_bytes"] += c.sent_bytes * c.runs
    return out


def describe(entries: List[Collective], plan: Dict[str, int]) -> str:
    """The one line an operator reads at warm-up: what the step sends a
    chip by kind, beside the stage's plan (``TrainEngine.zero_plan``)."""
    planned = (f"plan {plan['plan_bytes'] / 1e9:.3f} GB (gathers "
               f"{plan['gather_bytes'] / 1e9:.3f}, reductions "
               f"{plan['reduce_bytes'] / 1e9:.3f})")
    if not entries:
        return f"train step collectives: none; {planned}"
    by = totals(entries)
    parts = ", ".join(
        f"{kind} x{t['count']} {t['sent_bytes'] / 1e9:.3f} GB"
        for kind, t in sorted(by.items(), key=lambda kv: -kv[1]["sent_bytes"]))
    sent = sum(t["sent_bytes"] for t in by.values())
    guessed = sum(1 for c in entries if not c.runs_known)
    return (f"train step collectives: {parts}; {sent / 1e9:.3f} GB a chip a "
            f"step on the wire in {len(entries)} instructions"
            + (f" ({guessed} in a loop of unknown length, counted once)"
               if guessed else "")
            + f"; {planned}")
