"""From a traced step to phase x scope: which part of the model, and which
of forward, backward, recomputation, gradient mean and update, each
executed device op belongs to.

``python -m perfbench.scope_reduce <file.xplane.pb> [steps]`` prints what
the events of a trace carry besides their names, and the ``scopes:`` table.

The join (looked at by hand on PR 22's recorded traces; PERF.md, "Reading
a trace"): an ``XLA Ops`` event is named by its instruction's text and
carries only timing stats.  Its ``op_name`` sits in the event's *metadata*
(stat ``tf_op``), which ``jax.profiler.ProfileData`` does not hand out, and
it is the fusion's own, not that of the matmul inside.  But the plane
``/host:metadata`` of the same file holds the executed module's optimized
HLO (stat ``Hlo Proto``), metadata included.  So the reduction needs the
trace file alone: instruction name (first token of a key of
``trace_reduce``'s ``op_s``) -> instruction in that HLO -> ``op_name``,
and for a fusion the instructions of its fused computation.  It reads the
names as executed, also when the executable came from the compile cache.

The vocabulary below is the benchmark's own copy of
``horovod_tpu/telemetry/scopes.py`` (``tests/test_step_scopes.py`` holds
the two together): the benchmark's files also run over a program that
has no scopes yet, where the scope-keyed numbers are None.
"""

from __future__ import annotations

import collections
import glob
import math
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL_SCOPES = (
    "embed", "attn/qkv", "attn/out", "attn/local_attention",
    "attn/flash_attention", "attn/ring_attention",
    "attn/ring_flash_attention", "attn/ulysses_attention", "mlp", "head",
    "loss")
GRAD_MEAN_SCOPES = ("grad_mean", "loss_mean")
OPTIMIZER_SCOPES = ("optimizer", "grad_reduce_scatter", "param_all_gather",
                    "step_guard")
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
PHASES = ("fwd", "bwd", "remat", "optimizer", "grad_mean", "unattributed")

_VOCABULARY = MODEL_SCOPES + GRAD_MEAN_SCOPES + OPTIMIZER_SCOPES
# A scope is a whole component of the path: between "/" or the brackets
# of jvp(..) and transpose(..), never part of a parameter's name.
_SCOPE = re.compile(
    r"(?:^|(?<=[/(]))("
    + "|".join(sorted(map(re.escape, _VOCABULARY), key=len, reverse=True))
    + r"|layer_\d+)(?=$|[/)])")
_KERNEL = re.compile(r"(?:^|(?<=/))(" + "|".join(KERNEL_NAMES)
                     + r")(?=$|/)")
# flax: ``jvp(ResNet)/BottleneckBlock_3/Conv_0/conv_general_dilated``.
_FLAX = re.compile(r"(?<=[/(])([A-Z]\w*)\)*(?:/(\w+)(?=/))?")
_REMAT_NAME = re.compile(r"\.remat\d*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_ARRAY = re.compile(r"\b([a-z]+)(\d+)?\w*\[([\d,]*)\]")
_REFERENCE = re.compile(r"%([\w.\-]+)")


class Instruction(collections.namedtuple(
        "Instruction", "name opcode shape op_name calls operands")):
    """One line of optimized HLO; ``calls`` names the computations it
    runs: a fusion's fused computation, a loop's body and condition, a
    conditional's branches."""


def _operands(text: str, opcode: str) -> Tuple[str, ...]:
    """Names of the operands: the ``%`` references inside the brackets
    that follow the opcode."""
    start = text.find(" " + opcode + "(")
    if start < 0:
        return ()
    start += len(opcode) + 2
    depth, at = 1, start
    while at < len(text) and depth:
        depth += (text[at] == "(") - (text[at] == ")")
        at += 1
    return tuple(_REFERENCE.findall(text[start:at]))


Hlo = collections.namedtuple("Hlo", "instructions computations successor")
Hlo.__doc__ = """Optimized HLO, parsed: {instruction name: Instruction},
{computation name: [instruction names, in order]}, and {instruction: where
its result is needed next}: its first user in the text's (scheduled)
order; for the root of a loop body or of another called computation, the
instruction that calls it.  Names are kept without ``%``."""


def parse_hlo(*texts: str) -> Hlo:
    """:class:`Hlo` of optimized HLO text as ``compiled.as_text()`` and
    :func:`trace_hlo` print it.  A chip's trace holds the one module that
    ran; of several texts the largest (the step) has the last word on a
    name."""
    instructions: Dict[str, Instruction] = {}
    computations: Dict[str, List[str]] = {}
    for text in sorted(texts, key=len):
        body: Optional[List[str]] = None
        for line in text.splitlines():
            if body is None or not line.startswith(" "):
                opened = _COMPUTATION.match(line)
                body = None
                if opened:
                    body = computations[opened.group(1)] = []
                continue
            stripped = line.strip()
            if stripped.startswith("ROOT "):
                stripped = stripped[5:]
            name, opcode, shape = trace_reduce.parse(stripped)
            if not opcode:
                continue
            name = name.lstrip("%")
            op_name = _OP_NAME.search(stripped)
            instructions[name] = Instruction(
                name, opcode, shape, op_name.group(1) if op_name else "",
                tuple(_CALLS.findall(stripped) + _REFERENCE.findall(
                    "".join(_BRANCHES.findall(stripped)))),
                _operands(stripped, opcode))
            body.append(name)
    successor: Dict[str, str] = {}
    for instruction in instructions.values():
        for operand in instruction.operands:
            successor.setdefault(operand, instruction.name)
    for instruction in instructions.values():
        for computation in instruction.calls:
            for name in computations.get(computation, ()):
                successor.setdefault(name, instruction.name)
    return Hlo(instructions, computations, successor)


def scope_of(op_name: str) -> str:
    """The innermost scope of the vocabulary in ``op_name``, ``layer_<i>``
    dropped; for a flax model its top module and block
    (``ResNet/BottleneckBlock_3``); else ``""``."""
    found = [s for s in _SCOPE.findall(op_name)
             if not s.startswith("layer_")]
    if found:
        return found[-1]
    if _SCOPE.search(op_name):
        return "layer"
    flax = _FLAX.search(op_name)
    return "/".join(filter(None, flax.groups())) if flax else ""


def phase_of(op_name: str, name: str = "", opcode: str = "") -> str:
    """The issue's rules, in order: recomputed (JAX's ``checkpoint`` or
    XLA's own ``.remat``), gradient mean (its scopes, or any collective),
    optimizer (its scopes), backward (``transpose(``), forward (``jvp(``
    or a model scope), unattributed."""
    scopes = set(_SCOPE.findall(op_name))
    if "rematted_computation" in op_name or _REMAT_NAME.search(name):
        return "remat"
    if scopes.intersection(GRAD_MEAN_SCOPES) or trace_reduce.COLLECTIVE.match(
            opcode):
        return "grad_mean"
    if scopes.intersection(OPTIMIZER_SCOPES):
        return "optimizer"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name or scopes:
        return "fwd"
    return "unattributed"


def _fused(instruction, hlo: Hlo) -> List[Instruction]:
    """The instructions of a fusion's computation, nested fusions'
    included, in text order."""
    out = []
    for computation in instruction.calls:
        for name in hlo.computations.get(computation, ()):
            inner = hlo.instructions[name]
            out.append(inner)
            if inner.opcode == "fusion":
                out.extend(_fused(inner, hlo))
    return out


def classify(name: str, hlo: Hlo):
    """``(phase, scope, kernel, phases inside)`` of an executed
    instruction, by the ``op_name`` of:

    * for a fusion, the ``dot``/``convolution`` inside its fused
      computation where it holds one (that instruction sets its time: on
      one chip XLA fuses the SGD update into the weight-gradient matmul),
      else the fusion's own, else the last instruction inside that has
      one;
    * for an instruction the compiler made without one (the
      ``copy-start``/``copy-done`` of a prefetch, a ``copy``, a
      relayout loop), its successor (:class:`Hlo`), by the same rules:
      a wait for data is booked where the data is needed.

    ``phases inside`` are those of every instruction of the executed
    fusion's computation that carries an ``op_name``, ``unattributed``
    left out: more than one makes the fusion ``mixed``."""
    executed = hlo.instructions.get(name)
    if executed is None:
        return "unattributed", "", None, frozenset()

    def carriers(instruction):
        if instruction.opcode != "fusion":
            return [instruction] if instruction.op_name else []
        inner = [i for i in _fused(instruction, hlo) if i.op_name]
        matmuls = [i for i in inner if i.opcode in ("dot", "convolution")]
        own = [instruction] if instruction.op_name else []
        return matmuls + own + inner[::-1]

    def placed(instruction):
        return [c.op_name for c in carriers(instruction)
                if phase_of(c.op_name, opcode=c.opcode) != "unattributed"]

    host, found = executed, placed(executed)
    for _ in range(8):
        then = hlo.successor.get(host.name)
        if found or then is None:
            break
        host = hlo.instructions[then]
        found = placed(host)
    if not found:
        host = executed
        found = [c.op_name for c in carriers(executed)] or [""]
    op_name = found[0]
    inside = frozenset()
    if executed.opcode == "fusion":
        inside = frozenset(
            phase_of(i.op_name, name, i.opcode)
            for i in _fused(executed, hlo) if i.op_name) - {"unattributed"}
    kernel = None
    if executed.opcode == "custom-call":
        named = _KERNEL.search(executed.op_name)
        kernel = named.group(1) if named else None
    remat = name if _REMAT_NAME.search(name) else host.name
    return (phase_of(op_name, remat, executed.opcode), scope_of(op_name),
            kernel, inside)


def attribute(op_s: Dict[str, float], hlo: Hlo) -> dict:
    """Every key of ``trace_reduce``'s ``op_s`` (``%name opcode shape``)
    to one phase and one scope.  Returns seconds:

    ``phase_s``   {phase: s}: the six phases, every op in exactly one
    ``table``     {(scope, phase): s}
    ``mixed_s``   {"bwd+optimizer": s}: fusions whose computation holds
                  more than one phase, by what they hold (they are booked
                  under one phase all the same, see :func:`classify`)
    ``kernel_s``  {kernel name: s} for the custom calls named in
                  ``KERNEL_NAMES``
    ``unjoined_s``  ops whose instruction the HLO does not hold (booked
                  ``unattributed``)
    ``has_scopes``  whether any ``op_name`` of the module holds a scope
                  of the vocabulary (False on a program from before them)
    """
    phase_s = dict.fromkeys(PHASES, 0.0)
    table: Dict[Tuple[str, str], float] = collections.Counter()
    mixed_s: Dict[str, float] = collections.Counter()
    kernel_s: Dict[str, float] = collections.Counter()
    unjoined = 0.0
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        phase, scope, kernel, inside = classify(name, hlo)
        phase_s[phase] += seconds
        table[(scope or "(no scope)", phase)] += seconds
        if len(inside) > 1:
            mixed_s["+".join(p for p in PHASES if p in inside)] += seconds
        if kernel:
            kernel_s[kernel] += seconds
        if name not in hlo.instructions:
            unjoined += seconds
    return {
        "phase_s": phase_s, "table": dict(table), "mixed_s": dict(mixed_s),
        "kernel_s": dict(kernel_s), "unjoined_s": unjoined,
        "has_scopes": any(
            s for i in hlo.instructions.values()
            for s in _SCOPE.findall(i.op_name) if not s.startswith("layer_")
        ),
    }


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO result shape, a tuple's members summed:
    ``(f32[16384,4096], bf16[8])`` -> 268435472."""
    total = 0
    for kind, bits, dims in _ARRAY.findall(shape):
        width = int(bits) if bits else {"pred": 8}.get(kind, 0)
        total += math.prod(int(d) for d in dims.split(",") if d) * width // 8
    return total


def collectives(device_ops: Dict[str, Sequence[trace_reduce.Event]]):
    """``(calls, bytes)`` of the collective events on one device (mean
    over the devices): an asynchronous pair counts once, at its
    ``-done``, whose result is the collective's."""
    calls = total = 0
    planes = [events for events in device_ops.values() if events]
    for events in planes:
        for text, _, _ in events:
            _, opcode, shape = trace_reduce.parse(text)
            if (trace_reduce.COLLECTIVE.match(opcode)
                    and not opcode.endswith("-start")):
                calls += 1
                total += shape_bytes(shape)
    n = max(len(planes), 1)
    return calls / n, total / n


# --- the trace file's own HLO ----------------------------------------------

def _varint(data: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = data[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def _fields(data: bytes) -> Iterable[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: ints for
    varints, bytes for the rest."""
    at = 0
    while at < len(data):
        key, at = _varint(data, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(data, at)
        elif wire == 2:
            size, at = _varint(data, at)
            value, at = data[at:at + size], at + size
        else:
            size = {1: 8, 5: 4}[wire]
            value, at = data[at:at + size], at + size
        yield key >> 3, value


def _grouped(message: bytes) -> Dict[int, list]:
    """{field number: [values]} of one message."""
    grouped = collections.defaultdict(list)
    for field, value in _fields(message):
        grouped[field].append(value)
    return grouped


def _planes(path: str) -> Iterable[Dict[int, list]]:
    """The XPlanes of a trace file, each :func:`_grouped`: 2 name, 3
    lines (of them 2 name, 4 events), 4 event metadata, 5 stat metadata
    (xplane.proto)."""
    with open(path, "rb") as f:
        space = f.read()
    return (_grouped(plane) for number, plane in _fields(space)
            if number == 1)


def trace_hlo(path: str) -> List[str]:
    """The optimized HLO of every module the trace file describes
    (``/host:metadata``: one event metadata per module, its stat ``Hlo
    Proto`` an ``HloProto`` whose field 1 is the module), printed with
    metadata."""
    from jax._src.lib import _jax, xla_client

    options = _jax.HloPrintOptions()
    options.print_metadata = True
    options.print_backend_config = False
    options.print_percent = True
    out = []
    for plane in _planes(path):
        if plane[2] != [b"/host:metadata"]:
            continue
        for entry in plane[4]:
            metadata = dict(_fields(entry)).get(2, b"")
            for field, stat in _fields(metadata):
                proto = dict(_fields(stat)).get(6) if field == 5 else None
                module = dict(_fields(proto)).get(1) if proto else None
                if module:
                    out.append(xla_client.XlaComputation(module)
                               .get_hlo_module().to_string(options))
    return out


def event_stat_names(path: str) -> str:
    """For a reader's eyes: per plane and line, the stats of the first
    event and of that event's metadata (which ``ProfileData`` leaves
    out), by name."""
    out = []
    for plane in _planes(path):
        names = {}
        for entry in plane[5]:
            stat = dict(_fields(dict(_fields(entry))[2]))
            names[stat.get(1)] = stat.get(2, b"").decode()
        metadata = {}
        for entry in plane[4]:
            pair = dict(_fields(entry))
            metadata[pair.get(1)] = pair.get(2, b"")

        def stats(message, field):
            return [names.get(dict(_fields(v)).get(1), "?")
                    for f, v in _fields(message) if f == field]

        out.append(f"plane {plane[2][0].decode()!r}: "
                   f"{len(metadata)} event metadata")
        for line in map(_grouped, plane[3]):
            if not line[4]:
                continue
            title, first = line[2][0].decode(), line[4][0]
            meta = metadata.get(dict(_fields(first)).get(1), b"")
            text = dict(_fields(meta)).get(2, b"").decode(errors="replace")
            out.append(f"  line {title!r}: {len(line[4])} events; first "
                       f"{text[:70]!r}")
            out.append(f"    event stats: {stats(first, 4)}")
            out.append(f"    metadata stats: {stats(meta, 5)}")
    return "\n".join(out)


# --- one traced run ---------------------------------------------------------

def reduce_file(path: str, op_s: Optional[Dict[str, float]] = None):
    """:func:`attribute` of a trace file against its own HLO, plus
    ``collective_calls`` and ``collective_bytes`` per device over the
    window; None where the file holds no HLO."""
    texts = trace_hlo(path)
    if not texts:
        return None
    device_ops, host_spans, device_async = trace_reduce.read_xplane(path)
    if op_s is None:
        op_s = trace_reduce.reduce_events(
            device_ops, host_spans, None, device_async).get("op_s", {})
    out = attribute(op_s, parse_hlo(*texts))
    out["modules"] = [t.split(",", 1)[0].split()[-1]
                      for t in sorted(texts, key=len, reverse=True)]
    out["collective_calls"], out["collective_bytes"] = collectives(
        device_ops)
    return out


def format_table(scopes: dict, steps: int) -> str:
    """The ``scopes:`` table, ms per step, and the ``mixed`` lines."""
    ms = 1e3 / steps
    rows = sorted({scope for scope, _ in scopes["table"]},
                  key=lambda s: -sum(v for (sc, _), v
                                     in scopes["table"].items() if sc == s))
    width = max([len(r) for r in rows] + [12])
    modules = scopes.get("modules") or ["?"]
    lines = [f"scopes: module {modules[0]}"
             + (f" (and {len(modules) - 1} smaller in the file)"
                if len(modules) > 1 else "")
             + ", ms per step on one device, phase x scope",
             "  " + "scope".ljust(width)
             + "".join(p.rjust(13) for p in PHASES) + "total".rjust(11)]

    def row(title, cells):
        return ("  " + title.ljust(width)
                + "".join(f"{c * ms:13.3f}" for c in cells)
                + f"{sum(cells) * ms:11.3f}")

    for scope in rows:
        lines.append(row(scope, [scopes["table"].get((scope, p), 0.0)
                                 for p in PHASES]))
    lines.append(row("all", [scopes["phase_s"][p] for p in PHASES]))
    mixed = sorted(scopes["mixed_s"].items(), key=lambda kv: -kv[1])
    lines.append(
        f"  mixed (fusions holding more than one phase, booked above by "
        f"their dot/convolution, else their root): "
        f"{sum(scopes['mixed_s'].values()) * ms:.3f} ms"
        + "".join(f"; {k} {v * ms:.3f}" for k, v in mixed))
    lines.append("  kernels by name: " + (", ".join(
        f"{k} {v * ms:.3f}" for k, v in sorted(scopes["kernel_s"].items()))
        or "none") + f"; not in the HLO: {scopes['unjoined_s'] * ms:.3f} ms")
    return "\n".join(lines)


_MEMO: Dict[int, Optional[dict]] = {}


def _trace_file(ctx) -> Optional[str]:
    """The run's trace: ``ctx["trace_file"]`` where the harness gives it,
    else the newest under ``.perfbench/trace`` (the run has just written
    it)."""
    if ctx.get("trace_file"):
        return ctx["trace_file"]
    files = glob.glob(os.path.join(ROOT, ".perfbench", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def for_ctx(ctx) -> Optional[dict]:
    """The run's :func:`reduce_file`, made once for all readers; the first
    call prints the ``scopes:`` table and the identity.  None where there
    is nothing to read: no reduction, no trace file, no HLO in it."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = _trace_file(ctx)
        scopes = reduce_file(path, reduced["op_s"]) if path else None
        _MEMO[key] = scopes
        if scopes:
            steps = ctx["trace_steps"]
            print(format_table(scopes, steps), flush=True)
            left = sum(scopes["phase_s"].values())
            # xla_ms_per_step + flash_ms_per_step + collective_ms_per_step
            # as their readers compute them.
            kernels = sum(reduced["kernel_s"].values())
            xla = (sum(reduced["op_s"].values()) - kernels
                   - reduced["collective_s"])
            right = xla + kernels + reduced["collective_s"]
            print(f"identity: fwd + bwd + remat + optimizer + grad_mean + "
                  f"unattributed = {left * 1e3 / steps:.3f} ms; xla + "
                  f"kernels + collective = {right * 1e3 / steps:.3f} ms; "
                  f"difference {100.0 * abs(left - right) / right:.4f}%",
                  flush=True)
    return _MEMO[key]


def phase_ms(ctx, phase: str, needs_scopes: bool = False):
    """Milliseconds per step of one phase; None where there is nothing to
    read, or where ``needs_scopes`` and the program carries none."""
    scopes = for_ctx(ctx)
    if not scopes or (needs_scopes and not scopes["has_scopes"]):
        return None
    return scopes["phase_s"][phase] * 1e3 / ctx["trace_steps"]


def scope_ms(ctx, names: Sequence[str]):
    """Milliseconds per step under the named scopes, every phase; None
    where the program carries no scope."""
    scopes = for_ctx(ctx)
    if not scopes or not scopes["has_scopes"]:
        return None
    seconds = sum(v for (scope, _), v in scopes["table"].items()
                  if scope in names)
    return seconds * 1e3 / ctx["trace_steps"]


def kernel_ms(ctx, kernel: str):
    """Milliseconds per step in the custom calls named ``kernel``; None
    where the trace holds none."""
    scopes = for_ctx(ctx)
    if not scopes or kernel not in scopes["kernel_s"]:
        return None
    return scopes["kernel_s"][kernel] * 1e3 / ctx["trace_steps"]


def collective_per_step(ctx, what: str):
    """``collective_calls`` or ``collective_bytes`` per step on one
    device; None where the trace holds no collective."""
    scopes = for_ctx(ctx)
    if not scopes or not scopes["collective_calls"]:
        return None
    return scopes[what] / ctx["trace_steps"]


if __name__ == "__main__":
    print(event_stat_names(sys.argv[1]))
    result = reduce_file(sys.argv[1])
    if result is None:
        raise SystemExit("the trace file holds no HLO")
    print(format_table(result, int(sys.argv[2]) if len(sys.argv) > 2 else 1))
