"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``python -m perfbench.trace_reduce <file.xplane.pb>`` prints what a trace
holds (planes, lines, the commonest event names) and its reduction: look
at one trace by hand before trusting a reader.

What a trace of this program on a TPU v5e holds (looked at by hand, PR 22;
PERF.md, "Reading a trace"):

* one plane per chip, named ``/device:TPU:<n>``.  Its line ``XLA Ops`` has
  one event per executed HLO instruction, and the event's name is the
  instruction's whole text: ``%fusion.180 = (f32[4,2048]{...}, ...)
  fusion(...), kind=kOutput, calls=%fused_computation.110``.  The ops of
  a step follow one another; a container (a ``while``) would enclose its
  children on the same line, so every sum here is of *self* time: an
  event's duration less what its children cover;
* a Pallas kernel is an event whose text holds
  ``custom_call_target="tpu_custom_call"``; the instruction is named from
  JAX's name stack (``%jvp__.7``, ``%transpose_jvp___.13``), not from the
  kernel, so the text is the only stable handle today;
* ``Async XLA Ops`` holds the spans from each ``*-start`` to its
  ``*-done`` (copies, and collectives where the compiler makes them
  asynchronous): data in flight beside the ops, not device work, so it
  is not part of the busy union;
* ``XLA Modules`` has one event per executed program, ``Steps`` one per
  step: both enclose the ops and are not read;
* the host's threads are lines of the plane ``/host:CPU``; the harness's
  ``jax.profiler.TraceAnnotation`` spans (``perfbench:*``) are events of
  the line ``python3``, on the same clock as the device planes.
"""

from __future__ import annotations

import collections
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "perfbench:"
ASYNC_LINE = "Async XLA Ops"
# By opcode: the instruction's name comes from JAX's name stack
# (``%psum_invariant.315 = f32[50257,4096] all-reduce(...)``).
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?$")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start_ns, end_ns


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return [(s, e) for s, e in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that ``b`` does not cover (both as :func:`union`
    gives them)."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def parse(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, result shape)`` of an HLO instruction's whole
    text, layouts dropped: ``("%fusion.180", "fusion", "(f32[4,2048],
    bf16[4,2048,4096])")``.  Text of another form is its own name."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", ""
    rest = _LAYOUT.sub("", rest)
    # The result's shape is a tuple in parentheses or one array; the
    # opcode follows it, up to its "(".
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            break
    return name, rest[i + 1:].partition("(")[0], rest[:i]


def short_name(text: str, width: int = 120) -> str:
    """``%fusion.180 fusion (f32[4,2048], bf16[4,2048,4096])``."""
    return " ".join(part for part in parse(text) if part)[:width]


def is_collective(text: str) -> bool:
    return bool(COLLECTIVE.match(parse(text)[1]))


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, Interval]]:
    """``(name, self_ns, (start, end))`` per event of one line: duration
    less the time its direct children cover.  Events of a line nest or
    follow one another; they do not cross."""
    order = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    self_ns = [e[2] - e[1] for e in order]
    stack: List[int] = []
    for i, (_, start, end) in enumerate(order):
        while stack and order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(end, order[stack[-1]][2]) - start
        stack.append(i)
    return [(e[0], max(s, 0.0), (e[1], e[2])) for e, s in zip(order, self_ns)]


def reduce_events(device_ops: Dict[str, Sequence[Event]],
                  host_spans: Sequence[Event],
                  kernels: Optional[Dict[str, Sequence[str]]] = None,
                  device_async: Optional[Dict[str, Sequence[Event]]] = None
                  ) -> dict:
    """The reduction proper, from plain event lists (so that a hand-built
    list tests it): ``device_ops`` maps a device plane's name to the events
    of its ops line and ``device_async`` to those of its async line,
    ``host_spans`` are the harness's annotations, ``kernels`` maps a
    kernel's name to substrings of its events' text.

    Returns seconds, averaged over the devices:

    ``window_s``      first op's start to last op's end
    ``busy_s``        union of the op intervals
    ``op_s``          {short name of the event: self seconds}
    ``kernel_s``      {kernel name: self seconds}, for the kernels found
    ``collective_s``  union of the collectives' intervals: synchronous
                      ones on the ops line, and from ``*-start`` to
                      ``*-done`` for asynchronous ones
    ``exposed_collective_s``  the part of it in which no other operation
                      runs on that device
    ``idle_gaps``     {host span name: seconds of device idle time that
                      fell inside that span}; ``"(no span)"`` for the rest
    """
    device_ops = {k: v for k, v in device_ops.items() if v}
    kernels, device_async = kernels or {}, device_async or {}
    n = len(device_ops)
    if not n:
        return {}
    total = collections.Counter()
    op_s: Dict[str, float] = collections.Counter()
    kernel_s: Dict[str, float] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    for plane, events in device_ops.items():
        timed = self_times(events)
        busy = union(iv for _, _, iv in timed)
        window = (busy[0][0], busy[-1][1])
        coll = union(
            [iv for name, _, iv in timed if is_collective(name)]
            + [(s, e) for name, s, e in device_async.get(plane, ())
               if is_collective(name)])
        # Only a leaf (nothing inside it) computes; a container merely
        # spans its children, collectives among them.
        other = union(iv for name, self_ns, iv in timed
                      if not is_collective(name)
                      and self_ns >= (iv[1] - iv[0]) * 0.999)
        total["window"] += window[1] - window[0]
        total["busy"] += measure(busy)
        total["collective"] += measure(coll)
        total["exposed"] += measure(subtract(coll, other))
        for name, self_ns, _ in timed:
            op_s[short_name(name)] += self_ns
            for kernel, matches in kernels.items():
                if any(m in name for m in matches):
                    kernel_s[kernel] += self_ns
        # The harness's spans follow one another on one thread, so a
        # moment of a gap lies in at most one of them.
        for g0, g1 in subtract([window], busy):
            rest = g1 - g0
            for name, start, end in host_spans:
                inside = min(end, g1) - max(start, g0)
                if inside > 0:
                    gaps[name] += inside
                    rest -= inside
            gaps["(no span)"] += max(rest, 0.0)
    ns = 1e-9 / n
    return {
        "devices": n,
        "window_s": total["window"] * ns,
        "busy_s": total["busy"] * ns,
        "collective_s": total["collective"] * ns,
        "exposed_collective_s": total["exposed"] * ns,
        "op_s": {k: v * ns for k, v in op_s.items()},
        "kernel_s": {k: v * ns for k, v in kernel_s.items()},
        "idle_gaps": {k: v * ns for k, v in gaps.items() if v > 0},
    }


def read_xplane(path: str):
    """``(device_ops, host_spans, device_async)`` of a trace file, as
    :func:`reduce_events` takes them."""
    from jax.profiler import ProfileData

    lines = {OPS_LINE: {}, ASYNC_LINE: {}}
    host_spans: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return lines[OPS_LINE], host_spans, lines[ASYNC_LINE]


def reduce_file(path: str,
                kernels: Optional[Dict[str, Sequence[str]]] = None) -> dict:
    device_ops, host_spans, device_async = read_xplane(path)
    return reduce_events(device_ops, host_spans, kernels, device_async)


def describe(path: str, top: int = 12) -> str:
    """What the file holds, for a reader's eyes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            by_name = collections.Counter()
            for e in events:
                by_name[e.name] += e.duration_ns
            out.append(f"  line {line.name!r}: {len(events)} events")
            for name, ns in by_name.most_common(top):
                out.append(f"    {ns / 1e6:12.3f} ms  {name}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
    reduced = reduce_file(
        sys.argv[1], {"mosaic": ['custom_call_target="tpu_custom_call"']})
    ops = sorted(reduced.pop("op_s", {}).items(), key=lambda kv: -kv[1])
    print(reduced)
    for name, seconds in ops[:20]:
        print(f"  {seconds * 1e3:10.3f} ms self  {name}")
