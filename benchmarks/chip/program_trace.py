"""The program's own spans and scopes in a ``--trace 1`` run.

``trace_reduce`` reads the benchmark's spans and classes ops by their HLO
opcode.  This module reads what the program writes into the same trace:

* host spans of ``SpmdEngine.step`` (:data:`SPANS`), from the host planes:
  ``spmd.step`` around each call, and inside it ``spmd.grid``,
  ``spmd.put``, ``spmd.dispatch`` and ``spmd.loss_wait``;
* the ``jax.named_scope`` of every device op, from the op's JAX
  ``op_name``.  On a TPU v5e the events of a ``/device:TPU:<i>``
  plane's "XLA Ops" line carry the instruction's HLO text and no
  ``op_name`` stat (their stats are ``device_offset_ps``,
  ``device_duration_ps`` and ``Time Scale Multiplier``), so the
  instruction names are mapped to ``op_name`` through the HLO protos of
  the trace's ``/host:metadata`` plane (:func:`hlo_op_names`), read with
  a small protobuf decoder, since ``ProfileData`` shows no event
  metadata.

The scopes a cell splits its ops by are the ``SCOPE`` names of the
per-layer readers listed for that cell (``registry.scopes``), so a scope
added for one cell cannot move a reading in another.  An op's scope is the
innermost of that set that is a component of its ``op_name`` once
wrappers such as ``transpose(...)`` and ``jvp(...)`` are stripped, so a
backward op (``transpose(jvp(attention))``) and a rematerialized forward
(``.../checkpoint/attention/...``) count with the forward's scope.  An op
with none of them is unscoped.  Containers (``while``, ``conditional``,
``call``) are left out, as ``trace_reduce`` leaves them out of its class
times.

Both are clipped to the window ``trace_reduce`` uses: from the start of
the first ``engine.step`` span to the end of the last ``sync`` span.

The names here are this module's own copies: nothing is imported from the
program, so the yardstick does not move with it.  A name that is not in
the trace reads ``None``, never 0.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import (Collection, Dict, FrozenSet, List, Optional, Sequence,
                    Tuple)

import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
#: where ``run.py`` writes the trace of a ``--trace 1`` run
TRACE_DIR = os.path.join(HERE, ".trace")

#: host spans that ``SpmdEngine.step`` writes
SPANS = ("spmd.step", "spmd.grid", "spmd.put", "spmd.dispatch",
         "spmd.loss_wait")
#: the spans of the host's own work in a step (the loss wait left out)
HOST_WORK = ("spmd.grid", "spmd.put", "spmd.dispatch")
_CONTAINERS = ("while", "conditional", "call")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")

Interval = Tuple[int, int]


def _components(op_name: str) -> List[str]:
    """``a/f(b/c)/d`` -> ``[a, f(b/c), d]``: split at the slashes that
    no parenthesis encloses."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def _unwrap(component: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def scope_of(op_name: str, scopes: Collection[str]) -> Optional[str]:
    """The innermost of ``scopes`` in ``op_name``, or None."""
    for comp in reversed(_components(op_name or "")):
        inner = _unwrap(comp)
        for part in reversed(_components(inner)):
            if part in scopes:
                return part
    return None


def is_container(name: str) -> bool:
    """A while loop, conditional or call, by the opcode of the event's
    HLO text (``%while.3 = (..) while(..), ..``)."""
    rhs = name.split("=", 1)[-1].strip()
    return T._opcode(rhs) in _CONTAINERS


@dataclasses.dataclass
class Events:
    """What the trace holds for this module, on the profiler's clock (ns)."""

    #: one list per device: (op_name, "" where none; start, end),
    #: containers left out
    ops: List[List[Tuple[str, int, int]]]
    #: per device, every op's interval, containers included (busy time)
    busy: List[List[Interval]]
    #: (span name, start, end): the program's and the benchmark's spans
    spans: List[Tuple[str, int, int]]


@dataclasses.dataclass
class ProgramTrace:
    """The program's spans and scopes, clipped to the traced window."""

    window: Interval
    steps: int
    #: per device: scope (None = unscoped) -> ns
    scope_ns: List[Dict[Optional[str], int]]
    #: per device: the union of op intervals
    busy: List[List[Interval]]
    #: span name -> union of its intervals (the program's and the
    #: benchmark's spans)
    span_union: Dict[str, List[Interval]]

    @property
    def chips(self) -> int:
        return len(self.busy)

    def _per_step_ms(self, ns_per_device: Sequence[float]) -> float:
        return sum(ns_per_device) / len(ns_per_device) / self.steps / 1e6

    def scope_ms(self, scope: str) -> Optional[float]:
        """Device time per step in ops of ``scope``, averaged over the
        devices; None where no op has it."""
        if not any(scope in d for d in self.scope_ns):
            return None
        return self._per_step_ms([d.get(scope, 0) for d in self.scope_ns])

    def unscoped_ms(self) -> Optional[float]:
        """Device time per step in ops with no scope; None where no op
        has any scope (a program without them)."""
        if not any(k is not None for d in self.scope_ns for k in d):
            return None
        return self._per_step_ms([d.get(None, 0) for d in self.scope_ns])

    def _spans(self, names: Sequence[str]) -> List[Interval]:
        return T._union([iv for n in names
                         for iv in self.span_union.get(n, [])])

    def host_ms(self) -> Optional[float]:
        """Host time per step under ``spmd.grid``, ``spmd.put`` or
        ``spmd.dispatch`` (their union); None without those spans."""
        work = self._spans(HOST_WORK)
        return T._length(work) / self.steps / 1e6 if work else None

    def idle_ms_under(self, *names: str) -> Optional[float]:
        """Device idle time per step under the union of the spans
        ``names``, averaged over the devices; None where none of them is
        in the trace."""
        under = self._spans(names)
        if not under:
            return None
        return self._per_step_ms([T._length(T._minus(under, b))
                                  for b in self.busy])

    def exposed_host_ms(self) -> Optional[float]:
        """Device idle time per step under the host's own work
        (``spmd.grid``, ``spmd.put``, ``spmd.dispatch``)."""
        return self.idle_ms_under(*HOST_WORK)


def reduce(ev: Events, scopes: Collection[str]) -> ProgramTrace:
    """Clip ``ev`` to the benchmark's window and sum it, each op under
    its innermost scope of ``scopes``."""
    steps = [s for s in ev.spans if s[0] == "engine.step"]
    syncs = [s for s in ev.spans if s[0] == "sync"]
    if not steps or not syncs:
        raise ValueError("the trace holds no engine.step or sync span")
    t0 = min(s[1] for s in steps)
    t1 = max(s[2] for s in syncs)

    def clip(iv):
        return [(max(s, t0), min(e, t1)) for s, e in iv if e > t0 and s < t1]

    scope_ns = []
    scope: Dict[str, Optional[str]] = {}      # per distinct op_name
    for ops in ev.ops:
        per: Dict[Optional[str], int] = {}
        for name, s, e in ops:
            if name not in scope:
                scope[name] = scope_of(name, scopes)
            for a, b in clip([(s, e)]):
                per[scope[name]] = per.get(scope[name], 0) + b - a
        scope_ns.append(per)
    spans: Dict[str, List[Interval]] = {}
    for name, s, e in ev.spans:
        spans.setdefault(name, []).extend(clip([(s, e)]))
    return ProgramTrace(
        window=(t0, t1), steps=len(steps), scope_ns=scope_ns,
        busy=[T._union(clip(b)) for b in ev.busy],
        span_union={n: T._union(iv) for n, iv in spans.items()})


def device_ops(events, op_names: Dict[str, str]
               ) -> Tuple[List[Tuple[str, int, int]], List[Interval]]:
    """One device's "XLA Ops" events, as (name, start, end), to its ops
    by ``op_name`` (containers left out) and the intervals of all.  An
    event's name is its HLO instruction (``%fusion.1 = bf16[..]
    fusion(..)``); ``op_names`` maps instruction names to their JAX
    ``op_name``."""
    ops, busy = [], []
    kinds: Dict[str, Tuple[bool, str]] = {}  # per distinct event
    for name, s, e in events:
        busy.append((s, e))
        if name not in kinds:
            kinds[name] = (is_container(name),
                           op_names.get(T.instruction_name(name), ""))
        container, op_name = kinds[name]
        if not container:
            ops.append((op_name, s, e))
    return ops, busy


def _fields(buf: memoryview):
    """(field number, value) of a protobuf message: an int for a varint,
    a memoryview for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not expected")
        yield key >> 3, v


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _first(buf: memoryview, field: int):
    return next((v for f, v in _fields(buf) if f == field), None)


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Per HLO module in the trace's ``/host:metadata`` plane (its
    ``Hlo Proto`` stats), instruction name -> JAX ``op_name``.

    Field numbers, from ``xplane.proto`` and ``hlo.proto``: XSpace.planes
    1; XPlane.name 2, .event_metadata 4 (map: value 2); XEventMetadata
    .stats 5; XStat.bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.name 1, .computations 3; HloComputationProto
    .instructions 2; HloInstructionProto.name 1, .metadata 7;
    OpMetadata.op_name 2."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        for g, entry in _fields(plane):
            meta = _first(entry, 2) if g == 4 else None
            for h, stat in _fields(meta) if meta is not None else ():
                proto = _first(stat, 6) if h == 5 else None
                module = _first(proto, 1) if proto is not None else None
                if module is None:
                    continue
                names = out.setdefault(bytes(_first(module, 1)).decode(), {})
                for c, comp in _fields(module):
                    for k, ins in _fields(comp) if c == 3 else ():
                        if k != 2:
                            continue
                        md = _first(ins, 7)
                        op = _first(md, 2) if md is not None else None
                        if op is not None:
                            names[bytes(_first(ins, 1)).decode()] = \
                                bytes(op).decode()
    return out


def json_form(xspace: bytes) -> Dict:
    """A serialized ``XSpace`` in the JSON form of
    :func:`events_from_json`: per device, its "XLA Ops" events as [name,
    start, end]; the ``op_name`` of each instruction that ran; the host
    spans of :data:`SPANS` and of the benchmark as [name, start, end]."""
    import jax
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    devices, spans = [], []
    names = set(SPANS) | set(T.SPANS)
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            raw = [[e.name, int(e.start_ns), int(e.end_ns)]
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            devices.append((int(plane.name.rsplit(":", 1)[1]), raw))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, int(e.start_ns), int(e.end_ns)]
                          for e in line.events if e.name in names]
    devices.sort(key=lambda d: d[0])
    raw = [r for _, r in devices]
    return {"devices": raw,
            "op_names": merged_op_names(hlo_op_names(xspace), raw),
            "spans": spans}


def events_from_xspace(path: str) -> Events:
    """Read the ``.xplane.pb`` under ``path`` (a profiler log dir)."""
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    with open(files[-1], "rb") as fh:
        return events_from_json(json_form(fh.read()))


def merged_op_names(modules: Dict[str, Dict[str, str]], devices
                    ) -> Dict[str, str]:
    """One instruction name -> ``op_name`` map for the ops that ran: the
    modules that hold more of the ops' names come first, so the step's
    own module wins where another module has an instruction of the same
    name."""
    ran = {T.instruction_name(n) for raw in devices for n, _, _ in raw}
    out: Dict[str, str] = {}
    for names in sorted(modules.values(),
                        key=lambda m: -len(ran.intersection(m))):
        for k, v in names.items():
            if k in ran:
                out.setdefault(k, v)
    return out


_CACHE: Dict[Tuple[str, float, FrozenSet[str]], ProgramTrace] = {}


def load(path: str = TRACE_DIR, scopes: Collection[str] = ()
         ) -> Optional[ProgramTrace]:
    """The trace under ``path`` split by ``scopes``, read once per
    process, file and scope set; None where there is no trace."""
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    key = (files[-1], os.path.getmtime(files[-1]), frozenset(scopes))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(events_from_xspace(path), scopes)
    return _CACHE[key]


def events_from_json(d: Dict) -> Events:
    """Events from their JSON form (the test fixture's): per device, the
    "XLA Ops" events as [name, start, end]; the instruction name ->
    ``op_name`` map; the host spans as [name, start, end]."""
    per = [device_ops([tuple(o) for o in dev], d["op_names"])
           for dev in d["devices"]]
    return Events([p[0] for p in per], [p[1] for p in per],
                  [tuple(s) for s in d["spans"]])
