"""From a compiled step's buffer assignment to where the bytes of
``peak_hbm_gib`` live: occupancy by class, phase and scope at the peak.

``python -m perfbench.memory_reduce <dump dir | file.xplane.pb>`` prints the
``memory:`` table from a file alone; every traced run prints it after the
``scopes:`` table (:func:`for_ctx`).

Two front ends give one structure (:class:`Assignment`): the allocations
of the program, each with the buffers that were given a place in it, and
each buffer's live range in schedule order.

:func:`from_dump`   XLA's text under ``xla_dump_to``:
                    ``*after_optimizations-buffer-assignment.txt`` (the
                    allocation listing and ``BufferLiveRange:``) and
                    ``*after_optimizations.txt`` for the names.
:func:`from_trace`  the ``BufferAssignmentProto`` a trace file carries
                    beside the module (``HloProto`` field 3, on the plane
                    ``/host:metadata``): the program as executed, cache
                    reads included.  Live ranges come from its heap
                    simulator trace, which the v5e's trace carries; a
                    file without one (this JAX's CPU backend) gives
                    allocations and buffers only, and no occupancy.

**Occupancy is counted by slot, not by buffer.**  XLA's own "Live ranges
at <n> (peak)" sums logical buffers, and buffers that share a slot (an
in-place ``dynamic-update-slice`` and its operand) are counted each; here
the bytes occupied at an instant are the union of ``[offset, offset +
size)`` over the live buffers of an allocation, and at the peak instant
each occupied byte is booked once, to the live buffer defined last, by the
rules time uses (``scope_reduce.classify``).  Arguments are a class of
their own (by parameter number, never by their first user), and a
temporary that sits in a donated argument's allocation adds nothing.  Only
the memory space of the HBM temporaries counts (no ``color`` in the text,
0 in the proto): ``color 1`` is VMEM.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import scope_reduce
from perfbench.scope_reduce import (PHASES, _fields, _grouped, classify,
                                    parse_hlo)

GIB = 2.0 ** 30
HBM = 0                  # the memory space (``color``) of the HBM buffers
FWD, BWD, UPDATE = ("fwd",), ("bwd", "remat"), ("grad_mean", "optimizer")

Buffer = collections.namedtuple(
    "Buffer", "name index offset size shape start end")
Buffer.__doc__ = """One value placed in an allocation: the instruction
that defines it and the index into its result (``""`` or ``"1"``), its
bytes ``[offset, offset + size)`` of the allocation, and the first and
last instant of the schedule at which it is live (None where the source
holds no live ranges)."""

Allocation = collections.namedtuple(
    "Allocation", "number size kind parameter color live_out buffers")
Allocation.__doc__ = """``kind`` is ``argument`` (with its ``parameter``
number; ``live_out`` where an output is aliased to it: a donated
argument), ``output`` (live out and no parameter), ``temporary``,
``constant`` or ``thread-local``."""

Assignment = collections.namedtuple(
    "Assignment", "allocations sequence hlo source")
Assignment.__doc__ = """``sequence``: instruction names in schedule
order, called computations flattened in, so that a buffer's ``start`` and
``end`` index it; ``hlo``: :class:`scope_reduce.Hlo` of the same module;
``source``: where it was read from."""

_ALLOCATION = re.compile(r"^allocation (\d+): size (\d+), (.*):$")
_VALUE = re.compile(r"^ value: <\d+ (\S+?)(?:\{([\d,]*)\})? @\d+> "
                    r"\(size=(\d+),offset=(\d+)\): (.*)$")
_RANGE = re.compile(r"^\s+(\S+?)\{([\d,]*)\}:(\d+)-(\d+)$")
_STEP = re.compile(r"^\s+(\d+):(\S+)$")


def _kind(parameter, thread_local, constant, live_out) -> str:
    """An allocation's ``kind`` from its flags, text or proto."""
    if parameter:
        return "argument"
    if thread_local:
        return "thread-local"
    if constant:
        return "constant"
    return "output" if live_out else "temporary"


def _flags(text: str) -> Tuple[str, Optional[int], int, bool]:
    """``(kind, parameter number, color, live out)`` of an allocation's
    line, after its size."""
    parameter = re.search(r"\bparameter (\d+)", text)
    color = re.search(r"\bcolor (\d+)", text)
    live_out = "maybe-live-out" in text
    kind = _kind(parameter, "thread-local" in text,
                 "constant" in text.split("|")[-1], live_out)
    return (kind, int(parameter.group(1)) if parameter else None,
            int(color.group(1)) if color else HBM, live_out)


def parse_assignment(text: str, hlo: scope_reduce.Hlo,
                     source: str = "") -> Assignment:
    """:class:`Assignment` of the text of a
    ``*buffer-assignment.txt``."""
    drafts, sequence, ranges = [], [], {}
    section = "allocations"
    for line in text.splitlines():
        stripped = line.strip()
        if stripped in ("InstructionSequence:", "BufferLiveRange:"):
            section = stripped
            continue
        if stripped.startswith(("Live ranges at", "Used values:",
                                "Stack trace breakdown")):
            section = ""
            continue
        if section == "allocations":
            opened = _ALLOCATION.match(line)
            if opened:
                drafts.append((int(opened.group(1)), int(opened.group(2)),
                               _flags(opened.group(3)), []))
                continue
            value = _VALUE.match(line)
            if value and drafts:
                name, index, size, offset, shape = value.groups()
                drafts[-1][3].append(
                    (name, index or "", int(offset), int(size), shape))
        elif section == "InstructionSequence:":
            step = _STEP.match(line)
            if step:
                sequence.append(step.group(2))
        elif section == "BufferLiveRange:":
            live = _RANGE.match(line)
            if live:
                name, index, start, end = live.groups()
                ranges[(name, index)] = (int(start), int(end))
    allocations = [
        Allocation(number, size, kind, parameter, color, live_out, tuple(
            Buffer(*b, *ranges.get((b[0], b[1]), (None, None)))
            for b in buffers))
        for number, size, (kind, parameter, color, live_out), buffers
        in drafts]
    return Assignment(allocations, sequence, hlo, source)


def _one(pattern: str) -> str:
    files = sorted(glob.glob(pattern), key=os.path.getsize)
    if not files:
        raise FileNotFoundError(f"no file {pattern}")
    return files[-1]        # of several modules the largest: the step


def from_dump(directory: str) -> Assignment:
    """:class:`Assignment` of the largest module dumped under
    ``directory`` (``compiler_options={"xla_dump_to": directory,
    "xla_dump_hlo_as_text": True}``, or the same in ``XLA_FLAGS``)."""
    listing = _one(os.path.join(
        directory, "*after_optimizations-buffer-assignment.txt"))
    module = listing[:-len("-buffer-assignment.txt")] + ".txt"
    with open(module) as f:
        hlo = parse_hlo(f.read())
    with open(listing) as f:
        return parse_assignment(f.read(), hlo, listing)


# --- the trace file's own buffer assignment ---------------------------------
# xla/service/hlo.proto.  HloProto: 1 module, 3 buffer assignment.
# BufferAssignmentProto: 1 logical buffers (1 id, 2 size, 3 defined_at: 4
# instruction id, 3 shape index; 4 color), 3 allocations (1 index, 2 size,
# 3 thread-local, 5 entry parameter, 6 its number, 7 maybe live out, 8
# color, 12 constant, 9 assigned: 1 logical buffer, 2 offset, 3 size), 4
# heap simulator traces (1 events: 1 kind ALLOC/FREE/SHARE_WITH, 2 buffer,
# 4 instruction name; 3 allocation index).  HloModuleProto: 3
# computations, of them 2 instructions (1 name, 35 id).

def _hlo_protos(path: str) -> Iterable[Dict[int, list]]:
    """The ``HloProto`` of every module a trace file describes, each
    :func:`_grouped` (the walk of ``scope_reduce.trace_hlo``)."""
    for plane in scope_reduce._planes(path):
        if plane[2] != [b"/host:metadata"]:
            continue
        for entry in plane[4]:
            metadata = dict(_fields(entry)).get(2, b"")
            for field, stat in _fields(metadata):
                proto = dict(_fields(stat)).get(6) if field == 5 else None
                if proto:
                    yield _grouped(proto)


def _first(grouped: Dict[int, list], field: int, default=0):
    return grouped[field][0] if grouped.get(field) else default


def _shape_index(message: bytes) -> str:
    """``"1,0"`` of a location's repeated ``shape_index``, packed or
    not."""
    out = []
    for field, value in _fields(message):
        if field != 3:
            continue
        if isinstance(value, int):
            out.append(value)
        else:
            at = 0
            while at < len(value):
                item, at = scope_reduce._varint(value, at)
                out.append(item)
    return ",".join(map(str, out))


def _element(shape: str, index: str) -> str:
    """The member of a result shape that a shape index names:
    ``("(f32[8], bf16[4])", "1")`` -> ``bf16[4]``."""
    for step in filter(None, index.split(",")):
        if not shape.startswith("("):
            break
        members, depth, last = [], 0, 1
        for at, ch in enumerate(shape):
            depth += (ch in "([{") - (ch in ")]}")
            if (ch == "," and depth == 1) or depth == 0:
                members.append(shape[last:at].strip())
                last = at + 1
        if int(step) >= len(members):
            break
        shape = members[int(step)]
    return shape


def _heap_ranges(trace: Dict[int, list]):
    """``({logical buffer: [first, last instant]}, [instruction of each
    instant])`` of one heap simulator trace.  The simulator takes the
    schedule's instructions in order and for each first places what it
    defines (ALLOC, SHARE_WITH), then frees what it used last (FREE; an
    event names the instruction that *defines* its buffer).  An instant
    here is one instruction that is given a place in this allocation; an
    instruction that only frees is joined to the one before it, which
    holds the same buffers."""
    ranges: Dict[int, List[int]] = {}
    sequence: List[str] = []
    freed = True
    for event in map(_grouped, trace[1]):
        number = _first(event, 2)
        if _first(event, 1) == 1:           # FREE
            freed = True
            if number in ranges:
                ranges[number][1] = len(sequence) - 1
            continue
        name = _first(event, 4, b"").decode()
        if freed or name != sequence[-1]:
            sequence.append(name)
            freed = False
        ranges[number] = [len(sequence) - 1, len(sequence) - 1]
    return ranges, sequence


def parse_proto(proto: Dict[int, list], hlo: scope_reduce.Hlo,
                source: str = "") -> Assignment:
    """:class:`Assignment` of one ``HloProto``, :func:`_grouped`.  Live
    ranges are those of the heap simulation of the largest HBM temporary
    allocation (:func:`_heap_ranges`: its instants are not the dump's
    schedule indices); what that simulation did not place keeps none.
    On the v5e those are the 512-byte scalars, 0.3 MB of 5.3 GB."""
    names = {}
    for computation in _grouped(proto[1][0])[3]:
        for instruction in _grouped(computation)[2]:
            fields = _grouped(instruction)
            names[_first(fields, 35)] = _first(fields, 1, b"").decode()
    assignment = _grouped(proto[3][0])
    logical = {}
    for message in assignment[1]:
        fields = _grouped(message)
        where = _first(fields, 3, b"")
        location = _grouped(where)
        name = names.get(_first(location, 4)) or _first(
            location, 2, b"").decode()
        logical[_first(fields, 1)] = (name, _shape_index(where))
    drafts = []
    for message in assignment[3]:
        fields = _grouped(message)
        kind = _kind(_first(fields, 5), _first(fields, 3),
                     _first(fields, 12), _first(fields, 7))
        assigned = [(_first(a, 1), _first(a, 2), _first(a, 3))
                    for a in map(_grouped, fields.get(9, ()))]
        drafts.append((_first(fields, 1), _first(fields, 2), kind,
                       _first(fields, 6) if kind == "argument" else None,
                       _first(fields, 8), bool(_first(fields, 7)),
                       assigned))
    largest = max((d for d in drafts if d[2] == "temporary"
                   and d[4] == HBM), key=lambda d: d[1], default=None)
    ranges, sequence = {}, []
    if largest:
        inside = {number for number, _, _ in largest[6]}
        for trace in map(_grouped, assignment.get(4, ())):
            found, instants = _heap_ranges(trace)
            if found and set(found) <= inside:
                ranges, sequence = found, instants
                break
    allocations = []
    for number, size, kind, parameter, color, live_out, assigned in drafts:
        buffers = []
        for buffer, offset, held in assigned:
            name, index = logical.get(buffer, ("", ""))
            shape = _element(hlo.instructions[name].shape, index) if (
                name in hlo.instructions) else ""
            buffers.append(Buffer(name, index, offset, held, shape,
                                  *ranges.get(buffer, (None, None))))
        allocations.append(Allocation(number, size, kind, parameter, color,
                                      live_out, tuple(buffers)))
    return Assignment(allocations, sequence, hlo, source)


def from_trace(path: str) -> Optional[Assignment]:
    """:class:`Assignment` of the largest module of a trace file (the
    step); None where the file holds no buffer assignment."""
    protos = [p for p in _hlo_protos(path) if p.get(1) and p.get(3)]
    if not protos:
        return None
    proto = max(protos, key=lambda p: len(p[1][0]))
    return parse_proto(proto, parse_hlo(*scope_reduce.trace_hlo(path)),
                       path)


def has_live_ranges(assignment: Assignment) -> bool:
    return any(b.start is not None for a in hbm_temporaries(assignment)
               for b in a.buffers)


# --- occupancy --------------------------------------------------------------

def hbm_temporaries(assignment: Assignment) -> List[Allocation]:
    return [a for a in assignment.allocations
            if a.kind == "temporary" and a.color == HBM]


def _union(spans: Iterable[Tuple[int, int]]) -> int:
    """Bytes covered by half-open ``(low, high)`` intervals."""
    total, reach = 0, None
    for low, high in sorted(spans):
        if reach is None or low > reach:
            total += high - low
            reach = high
        elif high > reach:
            total += high - reach
            reach = high
    return total


def occupancy(allocation: Allocation, instants: int) -> List[int]:
    """Bytes of ``allocation`` occupied at every instant ``0 ..
    instants - 1``: the union of the live buffers' bytes."""
    starts = collections.defaultdict(list)
    ends = collections.defaultdict(list)
    for buffer in allocation.buffers:
        if buffer.start is not None and buffer.size:
            starts[buffer.start].append(buffer)
            ends[buffer.end].append(buffer)
    live, out, dirty, last = set(), [], True, 0
    for instant in range(instants):
        if starts.get(instant):
            live.update(starts[instant])
            dirty = True
        if dirty:
            last = _union((b.offset, b.offset + b.size) for b in live)
            dirty = False
        out.append(last)
        if ends.get(instant):
            live.difference_update(ends[instant])
            dirty = True
    return out


def _instants(assignment: Assignment) -> int:
    ends = [b.end for a in hbm_temporaries(assignment) for b in a.buffers
            if b.end is not None]
    return max(ends + [len(assignment.sequence) - 1, -1]) + 1


def total_occupancy(assignment: Assignment) -> List[int]:
    """Occupancy summed over the HBM temporary allocations, by instant."""
    instants = _instants(assignment)
    total = [0] * instants
    for allocation in hbm_temporaries(assignment):
        for instant, used in enumerate(occupancy(allocation, instants)):
            total[instant] += used
    return total


def naive_sums(assignment: Assignment) -> List[int]:
    """XLA's way ("Live ranges at <n> (peak)"), for the temporaries: the
    sizes of the live buffers summed by instant, shared slots counted
    each."""
    total = [0] * (_instants(assignment) + 1)
    for allocation in hbm_temporaries(assignment):
        for buffer in allocation.buffers:
            if buffer.start is not None:
                total[buffer.start] += buffer.size
                total[buffer.end + 1] -= buffer.size
    for instant in range(1, len(total)):
        total[instant] += total[instant - 1]
    return total[:-1]


def live_at(allocation: Allocation, instant: int) -> List[Buffer]:
    return [b for b in allocation.buffers if b.size and b.start is not None
            and b.start <= instant <= b.end]


def booked(allocation: Allocation, instant: int) -> Dict[Buffer, int]:
    """{buffer: bytes} at ``instant``: each occupied byte once, to the
    live buffer that covers it and was defined last."""
    live = live_at(allocation, instant)
    cuts = sorted({b.offset for b in live}
                  | {b.offset + b.size for b in live})
    out: Dict[Buffer, int] = collections.Counter()
    for low, high in zip(cuts, cuts[1:]):
        over = [b for b in live if b.offset <= low and high <= b.offset
                + b.size]
        if over:
            out[max(over, key=lambda b: (b.start, b.offset))] += high - low
    return dict(out)


Slot = collections.namedtuple("Slot", "allocation offset size buffers")
Slot.__doc__ = """Live buffers of one allocation that start at one
offset (an in-place pair, a loop's carried value and its update): one
place in memory; ``buffers`` in the order they were defined."""


def slots_at(assignment: Assignment, instant: int) -> List[Slot]:
    """The slots occupied at ``instant``, largest first."""
    out = []
    for allocation in hbm_temporaries(assignment):
        by_offset = collections.defaultdict(list)
        for buffer in live_at(allocation, instant):
            by_offset[buffer.offset].append(buffer)
        for offset, buffers in by_offset.items():
            buffers.sort(key=lambda b: b.start)
            out.append(Slot(allocation.number, offset,
                            max(b.size for b in buffers), tuple(buffers)))
    return sorted(out, key=lambda s: -s.size)


def chain(assignment: Assignment, slot: Slot) -> List[Buffer]:
    """Every buffer that holds ``slot`` by handing it on: those of the
    slot, and over and over any buffer at the same offset of the same
    allocation that is live together with one of them (an in-place update
    is live with its operand at the one instant that makes it; two
    buffers that merely reuse a place never are)."""
    allocation = next(a for a in assignment.allocations
                      if a.number == slot.allocation)
    there = [b for b in allocation.buffers if b.offset == slot.offset
             and b.start is not None and b.size]
    held = list(slot.buffers)
    grown = True
    while grown:
        grown = False
        for buffer in there:
            if buffer not in held and any(
                    buffer.start <= h.end and h.start <= buffer.end
                    for h in held):
                held.append(buffer)
                grown = True
    return held


def next_plateaus(assignment: Assignment, total: Sequence[int],
                  slots: Sequence[Slot], depth: int = 2):
    """``[(slots left out, greatest occupancy, its instant)]`` over the
    instants at which none of the ``n`` largest slots is held
    (:func:`chain`), for ``n`` = 1 .. ``depth``: what taking those
    buffers away can buy at most.  ``(n, 0, None)`` where they are held
    throughout."""
    out = []
    busy = [False] * len(total)
    for n, slot in enumerate(slots[:depth], 1):
        for buffer in chain(assignment, slot):
            for instant in range(buffer.start, buffer.end + 1):
                busy[instant] = True
        free = [i for i in range(len(total)) if not busy[i]]
        best = max(free, key=lambda i: (total[i], -i), default=None)
        out.append((n, total[best] if best is not None else 0, best))
    return out


def plateaus(total: Sequence[int], within: float = 0.03):
    """Runs of consecutive instants whose occupancy is within ``within``
    of the peak's: ``[(first, last)]``."""
    if not total:
        return []
    floor = max(total) * (1.0 - within)
    runs, start = [], None
    for instant, used in enumerate(list(total) + [-1]):
        if used >= floor and start is None:
            start = instant
        elif used < floor and start is not None:
            runs.append((start, instant - 1))
            start = None
    return runs


# --- the reduction ----------------------------------------------------------

def argument_group(name: str, op_name: str = "") -> str:
    """Which argument of the step a parameter is a leaf of, from the path
    JAX gives it: its ``op_name`` (``opt_state[0].trace['embed']`` ->
    ``opt_state``), else the TPU compiler's name for the instruction
    (``params__layers___0___w1__.1`` -> ``params``, ``tokens.1`` ->
    ``tokens``)."""
    path = re.match(r"\w+", op_name)
    if path and not op_name.startswith("jit("):
        return path.group(0)
    stem = re.sub(r"\.\d+$", "", name)
    head = stem.split("__", 1)[0]
    return re.sub(r"_\d+$", "", head) or "(unnamed)"


def reduce(assignment: Assignment, state_leaves: Optional[int] = None,
           batch_leaves: Optional[int] = None) -> dict:
    """Bytes on one chip, by what holds them.

    ``arguments``   {group: bytes} of the argument allocations, by
                    :func:`argument_group`
    ``state``, ``batch``  the same split by parameter number: the first
                    ``state_leaves`` parameters are the state, the next
                    ``batch_leaves`` the batch (``step(*state, *batch)``);
                    where the counts are not given, ``batch`` holds the
                    arguments no output is aliased to (a step donates its
                    state) and ``state`` the rest
    ``outputs``     live-out allocations that are no argument's
    ``constants``, ``thread_local``, ``other_spaces``  allocations that
                    are none of the above, and temporaries of another
                    memory space than HBM: counted by no row, printed
    ``temp``        size of the HBM temporary allocations; ``unranged``:
                    bytes of their buffers that have no live range in the
                    source (their sizes summed: an upper bound)
    ``peak``        ``(instant, instruction, phase, scope)`` of the
                    greatest occupancy; None without live ranges
    ``occupied``    bytes occupied at it, ``fragmentation`` = ``temp`` -
                    ``occupied``
    ``table``       {(scope, phase): bytes} at the peak instant
    ``phase``       {phase: bytes} of the same
    ``slots``       the ten largest slots at it: ``(bytes, allocation,
                    offset, shape, defining instruction, phase, scope,
                    other buffers in the slot)``
    ``plateaus``    :func:`plateaus`; ``next``: :func:`next_plateaus`
    ``reach``       the highest byte in use at the peak instant: between
                    it and ``occupied`` are holes, between it and ``temp``
                    room that only other instants use
    ``naive``       ``(instant, bytes)`` of the peak counted XLA's way:
                    the live buffers' sizes summed, shared slots each
    """
    hlo = assignment.hlo
    arguments: Dict[str, int] = collections.Counter()
    state = batch = outputs = constants = thread_local = other = 0
    for allocation in assignment.allocations:
        if allocation.kind == "argument":
            defining = next((hlo.instructions[b.name]
                             for b in allocation.buffers
                             if b.name in hlo.instructions and
                             hlo.instructions[b.name].opcode == "parameter"),
                            None)
            arguments[argument_group(
                defining.name if defining else "",
                defining.op_name if defining else "")] += allocation.size
            if state_leaves is None:
                is_batch = not allocation.live_out
            else:
                is_batch = allocation.parameter >= state_leaves
                if (batch_leaves is not None and allocation.parameter
                        >= state_leaves + batch_leaves):
                    raise ValueError(
                        f"parameter {allocation.parameter} of a step with "
                        f"{state_leaves} + {batch_leaves} arguments")
            if is_batch:
                batch += allocation.size
            else:
                state += allocation.size
        elif allocation.kind == "output":
            outputs += allocation.size
        elif allocation.kind == "constant":
            constants += allocation.size
        elif allocation.kind == "thread-local":
            thread_local += allocation.size
        elif allocation.color != HBM:
            other += allocation.size
    temporaries = hbm_temporaries(assignment)
    out = {
        "source": assignment.source, "arguments": dict(arguments),
        "state": state, "batch": batch, "outputs": outputs,
        "constants": constants, "thread_local": thread_local,
        "other_spaces": other,
        "temp": sum(a.size for a in temporaries),
        "buffers": sum(len(a.buffers) for a in temporaries),
        "unranged": sum(b.size for a in temporaries for b in a.buffers
                        if b.start is None),
        "peak": None, "occupied": 0, "table": {},
        "phase": dict.fromkeys(PHASES, 0), "slots": [], "plateaus": [],
        "next": [], "naive": (None, 0), "reach": 0,
    }
    out["fragmentation"] = out["temp"]
    if not has_live_ranges(assignment):
        return out
    total = total_occupancy(assignment)
    instant = max(range(len(total)), key=lambda i: (total[i], -i))
    placed = {}

    def place(name):
        if name not in placed:
            placed[name] = classify(name, hlo)[:2]
        return placed[name]

    table: Dict[Tuple[str, str], int] = collections.Counter()
    for allocation in temporaries:
        for buffer, held in booked(allocation, instant).items():
            phase, scope = place(buffer.name)
            table[(scope or "(no scope)", phase)] += held
            out["phase"][phase] += held
    summed = naive_sums(assignment)
    out["naive"] = max(enumerate(summed), key=lambda kv: (kv[1], -kv[0]))
    at = (assignment.sequence[instant]
          if instant < len(assignment.sequence) else "")
    out["reach"] = sum(
        max((b.offset + b.size for b in live_at(a, instant)), default=0)
        for a in temporaries)
    slots = slots_at(assignment, instant)
    out.update(
        peak=(instant, at) + place(at), occupied=total[instant],
        fragmentation=out["temp"] - total[instant], table=dict(table),
        plateaus=plateaus(total),
        next=next_plateaus(assignment, total, slots),
        slots=[(s.size, s.allocation, s.offset, s.buffers[-1].shape,
                s.buffers[-1].name) + place(s.buffers[-1].name)
               + (tuple(b.name for b in s.buffers[:-1]),)
               for s in slots[:10]])
    return out


def format_table(memory: dict, analysis: Optional[dict] = None) -> str:
    """The ``memory:`` table, GiB on one chip, and what stands around
    it.  ``analysis``: the five terms of ``memory_analysis()`` by their
    names, where a compiled step is at hand."""
    def gib(n):
        return f"{n / GIB:.3f}"

    scopes = sorted({scope for scope, _ in memory["table"]},
                    key=lambda s: -sum(v for (sc, _), v
                                       in memory["table"].items()
                                       if sc == s))
    width = max([len(s) for s in scopes] + [13])
    lines = [f"memory: {memory['source'] or '?'}: GiB on one chip, "
             f"temporaries at the peak instant by phase x scope "
             f"({memory['buffers']} buffers), then what holds the rest",
             "  " + "scope".ljust(width)
             + "".join(p.rjust(13) for p in PHASES) + "total".rjust(9)]

    def row(title, cells):
        return ("  " + title.ljust(width)
                + "".join(f"{c / GIB:13.3f}" for c in cells)
                + f"{sum(cells) / GIB:9.3f}")

    for scope in scopes:
        lines.append(row(scope, [memory["table"].get((scope, p), 0)
                                 for p in PHASES]))
    lines.append(row("occupied", [memory["phase"][p] for p in PHASES]))

    def single(title, n, note=""):
        lines.append("  " + title.ljust(width) + " " * 13 * len(PHASES)
                     + f"{n / GIB:9.3f}" + (f"  {note}" if note else ""))

    single("fragmentation", memory["fragmentation"],
           f"the temporary allocations' {gib(memory['temp'])} less the "
           f"{gib(memory['occupied'])} occupied")
    single("state", memory["state"])
    single("batch", memory["batch"], "arguments by the prefix JAX gives "
           "them: " + ", ".join(f"{k} {gib(v)}" for k, v in sorted(
               memory["arguments"].items(), key=lambda kv: -kv[1])))
    if memory["outputs"]:
        single("outputs", memory["outputs"], "aliased to no argument")
    if analysis:
        counted = (memory["state"] + memory["batch"] + memory["outputs"]
                   + memory["temp"]
                   + analysis["generated_code_size_in_bytes"])
        single("code", analysis["generated_code_size_in_bytes"])
        single("remainder", program_bytes(analysis) - counted,
               f"memory_analysis() less the rows: arguments "
               f"{analysis['argument_size_in_bytes'] - memory['state'] - memory['batch']}"
               f", outputs less aliased "
               f"{analysis['output_size_in_bytes'] - analysis['alias_size_in_bytes'] - memory['outputs']}"
               f", temporaries "
               f"{analysis['temp_size_in_bytes'] - memory['temp']} bytes")
    lines.append(
        f"  counted by no row: constants {memory['constants']}, "
        f"thread-local {memory['thread_local']}, temporaries of other "
        f"memory spaces (VMEM, flags) {memory['other_spaces']} bytes; "
        f"temporaries without a live range in this source "
        f"{memory['unranged']} bytes")
    if memory["peak"]:
        instant, at, phase, scope = memory["peak"]
        lines.append(
            f"  peak: instant {instant} of the schedule, %{at} ({phase}, "
            f"{scope or 'no scope'}): {gib(memory['occupied'])} GiB "
            f"occupied by slot; within 3% of it: " + ", ".join(
                f"{a}-{b}" if a != b else str(a)
                for a, b in memory["plateaus"])
            + f"; the live buffers' sizes summed (XLA's \"Live ranges at "
            f"(peak)\" less the arguments; shared slots counted each) "
            f"peak at instant {memory['naive'][0]}: "
            f"{gib(memory['naive'][1])}")
        for size, allocation, offset, shape, name, phase, scope, rest \
                in memory["slots"]:
            lines.append(
                f"    slot {gib(size)}  allocation {allocation} offset "
                f"{offset}  {shape}  %{name} ({phase}, "
                f"{scope or 'no scope'})"
                + (f"; shares it: {', '.join('%' + r for r in rest)}"
                   if rest else ""))
        lines.append(
            f"  fragmentation: the live buffers reach {gib(memory['reach'])}"
            f" GiB at the peak instant, "
            f"{gib(memory['reach'] - memory['occupied'])} of holes between "
            f"them and {gib(memory['temp'] - memory['reach'])} above them "
            f"that only other instants use")
        for n, best, where in memory["next"]:
            lines.append(
                f"  next plateau, the {n} largest slot"
                f"{'s' if n > 1 else ''} not live: "
                + (f"{gib(best)} GiB at instant {where}, "
                   f"{gib(memory['occupied'] - best)} under the peak"
                   if where is not None else "they are live throughout"))
    else:
        lines.append("  no live ranges in this source: no occupancy, "
                     "peak instant or plateaus")
    return "\n".join(lines)


def metrics(memory: dict) -> Dict[str, float]:
    """The six per-layer metrics, GiB."""
    phase = memory["phase"]
    peak = {"hbm_peak_fwd_gib": sum(phase[p] for p in FWD),
            "hbm_peak_bwd_gib": sum(phase[p] for p in BWD),
            "hbm_peak_update_gib": sum(phase[p] for p in UPDATE)}
    out = {"hbm_state_gib": memory["state"] + memory["batch"],
           "hbm_temp_gib": memory["temp"], **peak,
           "hbm_peak_unplaced_gib": memory["temp"] - sum(peak.values())}
    return {k: v / GIB for k, v in out.items()}


# --- one traced run ---------------------------------------------------------

_ANALYSIS = ("argument_size_in_bytes", "output_size_in_bytes",
             "alias_size_in_bytes", "temp_size_in_bytes",
             "generated_code_size_in_bytes")


def program_bytes(analysis: dict) -> int:
    """What ``run.py::_memory`` calls the step: arguments + outputs -
    aliased + temporaries + code."""
    return (analysis["argument_size_in_bytes"]
            + analysis["output_size_in_bytes"]
            - analysis["alias_size_in_bytes"]
            + analysis["temp_size_in_bytes"]
            + analysis["generated_code_size_in_bytes"])


def analysis_of(compiled) -> dict:
    """The five terms of ``memory_analysis()`` by name, and the
    compiler's own ``peak_memory_in_bytes`` (arguments plus the
    temporaries' peak, without fragmentation)."""
    stats = compiled.memory_analysis()
    out = {name: getattr(stats, name) for name in _ANALYSIS}
    out["peak_memory_in_bytes"] = getattr(stats, "peak_memory_in_bytes", 0)
    return out


def _lower(cell):
    return cell.step.lower(*cell.state_shapes, *cell.batch_shapes)


def compile_with_dump(cell, directory: str):
    """``cell.step`` compiled once more from its shapes with the
    compiler's dump pointed at ``directory``, **with the persistent cache
    off for that one compile**: the cache's key leaves every ``xla_dump_*``
    option out (``jax/_src/cache_key.py``), so a warm run would read the
    executable back and dump nothing."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return _lower(cell).compile(compiler_options={
            "xla_dump_to": directory, "xla_dump_hlo_as_text": True})
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _leaves(shapes) -> int:
    import jax

    return len(jax.tree_util.tree_leaves(shapes))


def _live_bytes_on_fullest_device() -> int:
    """``run.py::_bytes_on_fullest_device``."""
    import jax

    per_device: Dict[object, int] = collections.Counter()
    for array in jax.live_arrays():
        for shard in array.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    return max(per_device.values(), default=0)


_MEMO: Dict[int, Optional[dict]] = {}


def for_ctx(ctx) -> Optional[dict]:
    """The run's :func:`reduce`, made once for all readers; the first
    call prints the ``memory:`` table and the identity (after the
    ``scopes:`` table, which it asks for first).  Prefers the trace
    file's own buffer assignment; where that holds no live ranges,
    compiles the step once more with a dump (:func:`compile_with_dump`).
    None where there is no buffer assignment at all."""
    cell = ctx.get("cell")
    if cell is None:
        return None
    key = id(cell)
    if key in _MEMO:
        return _MEMO[key]
    scope_reduce.for_ctx(ctx)
    started = time.perf_counter()
    _MEMO[key] = None
    path = scope_reduce._trace_file(ctx)
    assignment = from_trace(path) if path else None
    front = "the trace file's HloProto"
    if assignment is None or not has_live_ranges(assignment):
        directory = os.path.join(scope_reduce.ROOT, ".perfbench", "dump")
        shutil.rmtree(directory, ignore_errors=True)
        compile_with_dump(cell, directory)
        assignment = from_dump(directory)
        front = ("a compile with xla_dump_to (the trace file's buffer "
                 "assignment holds no heap simulator trace)")
    dumped = time.perf_counter()
    memory = reduce(assignment, _leaves(cell.state_shapes),
                    _leaves(cell.batch_shapes))
    # A cache read on a traced run: the step was compiled moments ago.
    analysis = analysis_of(_lower(cell).compile())
    _MEMO[key] = memory
    print(format_table(memory, analysis), flush=True)
    program = program_bytes(analysis)
    live = _live_bytes_on_fullest_device()
    others = live - analysis["argument_size_in_bytes"]
    left = (memory["state"] + memory["batch"] + memory["outputs"]
            + memory["temp"] + analysis["generated_code_size_in_bytes"])
    print(f"identity: state + batch + outputs + temporaries (placed "
          f"{sum(memory['phase'].values()) - memory['phase']['unattributed']}"
          f" + unattributed {memory['phase']['unattributed']} + "
          f"fragmentation {memory['fragmentation']}) + code = {left} bytes"
          f"; the bytes of peak_hbm_gib {program + others} less the pool's "
          f"other batches {others} (live arrays {live}) = {program}; "
          f"remainder {program - left} = "
          f"{100.0 * abs(program - left) / program:.4f}%; the compiler's "
          f"peak_memory_in_bytes less the arguments "
          f"{analysis['peak_memory_in_bytes'] - analysis['argument_size_in_bytes']}"
          f" against {memory['occupied']} occupied by slot; front end: "
          f"{front}; {dumped - started:.2f} s to the buffer assignment, "
          f"{time.perf_counter() - dumped:.2f} s to reduce and compile "
          f"again", flush=True)
    return memory


def metric(ctx, name: str) -> Optional[float]:
    """One of :func:`metrics` for a reader; None where there is no buffer
    assignment, and for the four read at the peak instant where there are
    no live ranges."""
    memory = for_ctx(ctx)
    if memory is None or (memory["peak"] is None and "peak" in name):
        return None
    return metrics(memory)[name]


if __name__ == "__main__":
    target = sys.argv[1]
    found = from_dump(target) if os.path.isdir(target) else from_trace(
        target)
    if found is None:
        raise SystemExit("the trace file holds no buffer assignment")
    print(format_table(reduce(found)))
