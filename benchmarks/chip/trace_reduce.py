"""From a profiler trace to the numbers the per-layer metrics read.

Three steps, each a function of plain data, so that the tests can run the
last two on a recorded excerpt:

1. :func:`op_classes` reads the step program's optimized HLO text and
   names the class of every instruction: ``dot`` for a dot or a
   convolution, or a fusion that holds one; ``collective`` for an
   all-gather, reduce-scatter, all-reduce, collective-permute or
   all-to-all (their ``-start`` and ``-done`` halves included), or an
   async or fused op that holds one; ``container`` for a while loop, a
   conditional or a call, whose interval covers the ops of its body;
   ``other`` for the rest.
2. :func:`events_from_xspace` takes, for each ``/device:TPU:<i>`` plane,
   the ops of its "XLA Ops" line and the async ops (start to done) of
   its "Async XLA Ops" line, each by its instruction name, and the
   benchmark's own host spans.
3. :func:`reduce` clips them to the traced window, which runs from the
   start of the first ``engine.step`` span to the end of the last
   ``sync`` span, and sums what the metrics need.  A device is busy
   while any op of its "XLA Ops" line runs.  Class times leave out
   containers.  Collective time is the union of the collective ops and
   of the async collectives in flight; its exposed part is what no
   other op (containers left out) overlaps.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

#: The host spans the benchmark writes around its calls into the program.
SPANS = ("traffic.next", "engine.step", "sync")

_COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
                "collective-permute", "all-to-all")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _opcode(rhs: str) -> str:
    """The opcode of an HLO instruction's right-hand side
    (``bf16[2,3]{1,0} dot(%a, %b), ...`` -> ``dot``)."""
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + _strip_shape(rhs))
    return m.group(1) if m else ""


def _strip_shape(rhs: str) -> str:
    # the result shape may be a tuple with nested parentheses
    depth, i = 0, 0
    if rhs.startswith("("):
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        return rhs[i + 1:]
    return rhs


def _class_of_opcode(op: str) -> str:
    if op in ("dot", "convolution"):
        return "dot"
    if any(op.startswith(c) for c in _COLLECTIVES):
        return "collective"
    if op in ("while", "conditional", "call"):
        return "container"
    return "other"


def op_classes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``dot`` / ``collective`` / ``other``."""
    comp_ops: Dict[str, set] = {}
    instr: List[Tuple[str, str, str]] = []      # (name, opcode, rhs)
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and current is not None:
            name, rhs = m.group(1), m.group(2)
            op = _opcode(rhs)
            comp_ops[current].add(op)
            instr.append((name, op, rhs))
            continue
        c = _COMP.match(line)
        if c:
            current = c.group(1)
            comp_ops.setdefault(current, set())
    out = {}
    for name, op, rhs in instr:
        cls = _class_of_opcode(op)
        if op == "fusion" or op.startswith("async-"):
            called = _CALLS.findall(rhs)
            inner = set().union(*(comp_ops.get(c, set()) for c in called))
            classes = {_class_of_opcode(o) for o in inner}
            cls = "dot" if "dot" in classes else (
                "collective" if "collective" in classes else "other")
        out[name] = cls
    return out


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_labels(hlo_text: str, depth: int = 3) -> Dict[str, str]:
    """Instruction name -> the last ``depth`` parts of the JAX op path in
    its metadata (``.../while/body/dot_general``), for the breakdown."""
    out = {}
    for line in hlo_text.splitlines():
        m, o = _INSTR.match(line), _OP_NAME.search(line)
        if m and o:
            out[m.group(1)] = "/".join(o.group(1).split("/")[-depth:])
    return out


@dataclasses.dataclass
class Events:
    """Device ops and host spans on the profiler's clock (ns)."""

    #: one list per device: (instruction name, start, end)
    devices: List[List[Tuple[str, int, int]]]
    #: (span name, start, end)
    spans: List[Tuple[str, int, int]]
    #: one list per device: async ops, start to done
    async_ops: List[List[Tuple[str, int, int]]] = dataclasses.field(
        default_factory=list)


_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


def instruction_name(event_name: str) -> str:
    """``%fusion.1 = bf16[...] fusion(...)`` -> ``fusion.1``."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name.lstrip("%")


def events_from_xspace(path: str) -> Events:
    """Read the ``.xplane.pb`` under ``path`` (a profiler log dir)."""
    import jax
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, spans = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {"XLA Ops": [], "Async XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [
                        (instruction_name(e.name), int(e.start_ns),
                         int(e.end_ns)) for e in line.events]
            devices.append((int(plane.name.rsplit(":", 1)[1]),
                            lines["XLA Ops"], lines["Async XLA Ops"]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events if e.name in SPANS]
    devices.sort()
    return Events([d[1] for d in devices], spans, [d[2] for d in devices])


@dataclasses.dataclass
class Reduced:
    """What the per-layer metrics read, all in seconds."""

    window_s: float
    steps: int
    #: per device, the time in which any op ran (union of intervals)
    busy_s: List[float]
    #: per device, time in ops of each class
    class_s: List[Dict[str, float]]
    #: per device, collective time during which no other op ran
    exposed_collective_s: List[float]
    #: (op name, seconds summed over devices), longest first
    top_ops: List[Tuple[str, float]]
    #: (host span open during the gap, seconds), longest first
    idle_gaps: List[Tuple[str, float]]

    @property
    def chips(self) -> int:
        return len(self.busy_s)


def _union(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(iv: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in iv)


def _minus(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
           ) -> List[Tuple[int, int]]:
    """Union ``a`` less union ``b`` (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce(ev: Events, classes: Dict[str, str],
           top: int = 10) -> Reduced:
    """Clip ``ev`` to the traced window and sum it."""
    steps = [s for s in ev.spans if s[0] == "engine.step"]
    syncs = [s for s in ev.spans if s[0] == "sync"]
    if not steps or not syncs:
        raise ValueError("the trace holds no engine.step or sync span")
    t0 = min(s[1] for s in steps)
    t1 = max(s[2] for s in syncs)
    busy, class_s, exposed, op_tot = [], [], [], {}
    gaps: List[Tuple[str, float]] = []
    spans = sorted((s for s in ev.spans if s[1] < t1 and s[2] > t0),
                   key=lambda s: s[1])
    async_ops = ev.async_ops or [[] for _ in ev.devices]
    for ops, aops in zip(ev.devices, async_ops):
        clip = lambda xs: [(n, max(s, t0), min(e, t1)) for n, s, e in xs
                           if e > t0 and s < t1]
        clipped = clip(ops)
        u = _union([(s, e) for _, s, e in clipped])
        busy.append(_length(u) / 1e9)
        per: Dict[str, float] = {"dot": 0.0, "collective": 0.0,
                                 "other": 0.0}
        coll, rest = [], []
        for n, s, e in clipped:
            c = classes.get(n, "other")
            if c == "container":
                continue
            per[c] += (e - s) / 1e9
            op_tot[n] = op_tot.get(n, 0.0) + (e - s) / 1e9
            (coll if c == "collective" else rest).append((s, e))
        coll += [(s, e) for n, s, e in clip(aops)
                 if classes.get(n) == "collective"]
        coll = _union(coll)
        per["collective"] = _length(coll) / 1e9
        class_s.append(per)
        exposed.append(_length(_minus(coll, _union(rest))) / 1e9)
        idle = _minus([(t0, t1)], u)
        for s, e in idle:
            gaps.append((_open_span(spans, s, e), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(t1 - t0) / 1e9, steps=len(steps), busy_s=busy,
        class_s=class_s, exposed_collective_s=exposed,
        top_ops=sorted(op_tot.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top])


def _open_span(spans, s: int, e: int) -> str:
    """The benchmark span that covers most of [s, e), or ``none``."""
    best, best_cover = "none", 0
    for name, a, b in spans:
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def events_from_json(d: Dict) -> Events:
    """Events from their JSON form (the test fixture's)."""
    return Events([[tuple(o) for o in ops] for ops in d["devices"]],
                  [tuple(s) for s in d["spans"]],
                  [[tuple(o) for o in ops] for ops in d.get("async_ops", [])])
