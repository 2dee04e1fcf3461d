"""From a profiler trace (``.xplane.pb``) and the compiled program's HLO to
the numbers the per-layer metrics read.

* :func:`load` reads the trace: each device's operations (leaf events
  only: an event that encloses another on its line, such as a while loop
  around its body, is not an operation of its own) and the host's
  annotations, all on the trace's one clock in nanoseconds.  On a TPU the
  operations are the events of each ``/device:TPU:<n>`` plane's
  ``XLA Ops`` line, named by their HLO instruction and tied to the module
  of the ``XLA Modules`` event they fall in; the CPU backend has no device plane, and there they are
  the host events that carry an ``hlo_op`` stat, grouped by
  ``device_ordinal``.
* :func:`busy_ns` / :func:`idle_gaps`: the union of a device's operation
  intervals inside a window, and the holes in it.
* :class:`HloIndex`: every instruction of a compiled module by name, with
  its opcode, custom-call target, ``op_name`` metadata and shapes, so that
  a reader selects events by what the program computed (a Pallas kernel
  from its source, a collective by its opcode) and not by a numbered name.
* :func:`time_by`: device time and number of the events a predicate
  selects.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float  # ns, on the trace's clock
    dur: float    # ns
    module: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict            # device id -> [Op] (leaf operations, by start)
    annotations: list        # [Op] host annotations (name, start, dur)

    def annotation(self, name: str) -> Op | None:
        """The first host annotation called ``name``."""
        return next((a for a in self.annotations if a.name == name), None)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def leaves(ops: list[Op]) -> list[Op]:
    """``ops`` without the events that enclose a later one (sorted)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.dur))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end:
            continue
        out.append(o)
    return out


def _instr_name(text: str) -> str:
    """A TPU event is named by its HLO instruction's text
    (``%name = f32[...] op(...)``): keep the name."""
    m = _NAME.match(text)
    return m[1] if m else text


def _module_at(modules: list, t: float) -> str:
    """Name (without its ``(fingerprint)``) of the module run at ``t``."""
    i = bisect.bisect_right(modules, (t, float("inf"))) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def load(path: str, annotation_prefix: str = "bench/") -> Trace:
    """Read a trace file; host annotations are those whose name starts
    with ``annotation_prefix``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict = defaultdict(list)
    annotations = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
                for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
            for e in lines["XLA Ops"].events:
                devices[plane.name].append(Op(
                    _instr_name(e.name), e.start_ns, e.duration_ns,
                    _module_at(modules, e.start_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(annotation_prefix):
                        annotations.append(Op(e.name, e.start_ns, e.duration_ns))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st:
                        devices[f"/host-device:{st.get('device_ordinal', 0)}"].append(
                            Op(str(st["hlo_op"]), e.start_ns, e.duration_ns,
                               str(st.get("hlo_module", ""))))
    return Trace(
        devices={k: leaves(v) for k, v in sorted(devices.items())},
        annotations=sorted(annotations, key=lambda a: a.start),
    )


def clip(ops: list[Op], t0: float, t1: float) -> list[Op]:
    """The parts of ``ops`` that lie inside [t0, t1]."""
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append(dataclasses.replace(o, start=s, dur=e - s))
    return out


def busy_intervals(ops: list[Op], t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of the intervals of ``ops`` inside [t0, t1], merged, sorted."""
    merged: list[list[float]] = []
    for o in sorted(clip(ops, t0, t1), key=lambda o: o.start):
        if merged and o.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], o.end)
        else:
            merged.append([o.start, o.end])
    return [(s, e) for s, e in merged]


def busy_ns(ops: list[Op], t0: float, t1: float) -> float:
    return sum(e - s for s, e in busy_intervals(ops, t0, t1))


def idle_share(ops: list[Op], t0: float, t1: float) -> float:
    """1 − busy / window, as a fraction."""
    return 1.0 - busy_ns(ops, t0, t1) / (t1 - t0)


def idle_gaps(ops: list[Op], t0: float, t1: float) -> list[tuple[float, float]]:
    """The holes of the busy union inside [t0, t1], longest first."""
    gaps, at = [], t0
    for s, e in busy_intervals(ops, t0, t1):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def label_at(spans: list[Op], t: float) -> str:
    """Name of the shortest span that contains instant ``t`` ("" if none)."""
    inside = [s for s in spans if s.start <= t <= s.end]
    return min(inside, key=lambda s: s.dur).name if inside else ""


def time_by(ops: list[Op], pick) -> tuple[float, int]:
    """(total ns, number of events) of the ops for which ``pick(op)``."""
    sel = [o.dur for o in ops if pick(o)]
    return float(sum(sel)), len(sel)


# ------------------------------------------------------------------ HLO
_NAME = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_SHAPE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    target: str          # custom_call_target, or ""
    op_name: str         # metadata op_name, or ""
    shape: str           # result shape text
    operands: str        # operand shapes: a custom call's layout constraints

    def shapes(self) -> list[tuple[int, ...]]:
        """Array dimensions of the operands."""
        return [tuple(int(d) for d in m["dims"].split(",") if d)
                for m in _SHAPE.finditer(self.operands)]


def _braced(text: str, key: str) -> str:
    """The text inside ``key={...}`` (nested braces allowed), or ""."""
    at = text.find(key + "={")
    if at < 0:
        return ""
    depth, start = 0, at + len(key) + 1
    for i in range(start, len(text)):
        depth += text[i] == "{"
        depth -= text[i] == "}"
        if depth == 0:
            return text[start + 1 : i]
    return ""


def _split_shape(rest: str) -> tuple[str, str]:
    """``"f32[2]{0} add(...)"`` -> (shape text, the remainder)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[: i + 1], rest[i + 1 :].lstrip()
    head, _, tail = rest.partition(" ")
    return head, tail


class HloIndex:
    """Instructions of compiled HLO text, by name (``as_text()`` output;
    names are unique within a module, and the index keeps the modules of
    the text apart)."""

    def __init__(self, text: str):
        self.by_module: dict[str, dict[str, Instr]] = {}
        module = ""
        for line in text.splitlines():
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                self.by_module.setdefault(module, {})
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            shape, tail = _split_shape(m["rest"])
            op = re.match(r"([a-z][\w\-]*)\((.*)", tail)
            if not op:
                continue
            target = re.search(r'custom_call_target="([^"]*)"', tail)
            op_name = re.search(r'op_name="([^"]*)"', tail)
            self.by_module.setdefault(module, {})[m["name"]] = Instr(
                name=m["name"], opcode=op[1],
                target=target[1] if target else "",
                op_name=op_name[1] if op_name else "",
                shape=shape,
                operands=_braced(tail, "operand_layout_constraints"),
            )

    def find(self, op: Op) -> Instr | None:
        """The instruction a trace event ran (None if not of this HLO)."""
        mods = [m for m in self.by_module if op.module == m]
        for m in mods or list(self.by_module):
            hit = self.by_module[m].get(op.name)
            if hit is not None:
                return hit
        return None


def is_pallas(instr: Instr | None, source: str) -> bool:
    """A Pallas TPU kernel whose ``op_name`` path names ``source`` (the
    jitted wrapper that issues the ``pallas_call``)."""
    return (instr is not None and instr.target == "tpu_custom_call"
            and source in instr.op_name)


def is_collective(instr: Instr | None, kind: str) -> bool:
    """``kind`` is an opcode stem: ``"collective-permute"``, ``"all-reduce"``
    (their ``-start``/``-done`` halves count too)."""
    return instr is not None and (
        instr.opcode == kind or instr.opcode.startswith(kind + "-"))
