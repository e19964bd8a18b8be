"""Read a ``jax.profiler`` device trace against the program's own spans
and named scopes.

An enabled :class:`~repro.obs.spans.SpanRecorder` opens a
``TraceAnnotation`` carrying each span's ``sid``, so every program span
appears on the trace's host plane, on the same clock as the device's
operations (:class:`Mark`). The compiled programs carry ``jax.named_scope``
names in each operation's HLO ``op_name`` (``attn``, ``mlp``, ``layers``,
``head`` in ``transformer.paged_step``; ``sample`` in ``serving.sampler``),
and :func:`scope_of` reads the innermost of them. Operations are assigned
to scopes only through that metadata, never by time overlap with host
spans: the device runs behind the host.

A TPU trace's operation events carry no ``op_name``: an event is named
by its HLO instruction, and the device's ``XLA Modules`` line says which
program it ran in. The programs' HLO is in the trace itself
(:func:`hlo_texts`), so each operation's ``op_name`` is looked up by
program and instruction (:func:`op_names`). A persistent compilation
cache keys a program without its debug information: an executable built
from code without these scopes can serve code with them, and its
operations then read as unscoped.

    tr = devtrace.read(xplane_path)
    devtrace.step_parts(tr, "decode_step")   # per-step ms by scope
    devtrace.idle_gaps(tr, "engine_step")    # gaps by innermost span
"""
from __future__ import annotations

import bisect
import re
import statistics
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

#: Scopes the serving programs set, innermost first where they nest.
SCOPES = ("sample", "head", "attn", "ssm", "mlp", "layers")
#: Time of operations in no scope.
UNSCOPED = "unscoped"
#: Control flow around other operations: busy, but not an operation's own
#: time.
CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Mark:
    """One program span on the profiler's clock (ns)."""
    name: str
    sid: int
    t0: float
    t1: float


@dataclass
class DevOp:
    name: str           # HLO instruction name, as the trace gives it
    t0: float           # ns, profiler clock
    dur: float          # ns
    scope: str          # one of SCOPES, or UNSCOPED

    @property
    def container(self) -> bool:
        return base_name(self.name) in CONTAINERS


@dataclass
class DevTrace:
    marks: List[Mark]                 # sorted by start
    ops: List[DevOp]                  # the first device's, sorted by start
    by_sid: Dict[int, Mark] = field(default_factory=dict)

    def __post_init__(self):
        self.marks.sort(key=lambda m: (m.t0, -m.t1))
        self.ops.sort(key=lambda o: o.t0)
        self.by_sid = {m.sid: m for m in self.marks}

    def named(self, name: str) -> List[Mark]:
        return [m for m in self.marks if m.name == name]

    def ops_in(self, a: float, b: float) -> List[DevOp]:
        return [o for o in self.ops if a <= o.t0 < b]

    def busy(self, a: float, b: float) -> List[Tuple[float, float]]:
        """Union of operation intervals, clipped to [a, b]."""
        out: List[Tuple[float, float]] = []
        for o in self.ops:
            x, y = max(o.t0, a), min(o.t0 + o.dur, b)
            if y <= x:
                continue
            if out and x <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], y))
            else:
                out.append((x, y))
        return out

    def gaps(self, a: float, b: float) -> List[Tuple[float, float]]:
        """Stretches of [a, b] with no operation on the device."""
        out, t = [], a
        for x, y in self.busy(a, b):
            if x > t:
                out.append((t, x))
            t = max(t, y)
        if t < b:
            out.append((t, b))
        return out

    def innermost(self, t: float) -> Optional[Mark]:
        """The latest-starting span open at ``t``."""
        best = None
        for m in self.marks:
            if m.t0 > t:
                break
            if t < m.t1:
                best = m
        return best

    def cover(self, a: float, b: float) -> Dict[str, float]:
        """ns of [a, b] by the innermost span open over it (``host`` where
        no span is open)."""
        cuts = {a, b}
        for m in self.marks:
            if m.t0 >= b:
                break
            if m.t1 > a:
                cuts.update(t for t in (m.t0, m.t1) if a < t < b)
        cuts = sorted(cuts)
        out: Dict[str, float] = {}
        for x, y in zip(cuts, cuts[1:]):
            m = self.innermost(0.5 * (x + y))
            k = m.name if m is not None else "host"
            out[k] = out.get(k, 0.0) + (y - x)
        return out


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; an HLO text line -> its name."""
    head = name.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


def scope_of(op_name: Optional[str]) -> str:
    """The innermost serving scope in an HLO ``op_name`` path."""
    if not op_name:
        return UNSCOPED
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


_HEAD = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) ')
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'(?:calls|body|condition|to_apply|true_computation|'
                    r'false_computation)=%?([\w.\-]+)|'
                    r'branch_computations=\{([^}]*)\}')
_REF = re.compile(r'%([\w.\-]+)')


def op_names(hlo_text: str) -> Dict[str, Optional[str]]:
    """HLO instruction name -> ``op_name`` from a compiled program's text.

    An instruction the compiler added (a layout copy, a loop's carried
    copy, an async copy's halves) carries no metadata: it takes the
    ``op_name`` of its first operand that has one, else that of the
    instruction whose computation it lies in (a ``while`` body's
    instruction, the ``while``'s). Both are read from the program's
    structure, never from time."""
    comp = None
    ins: Dict[str, Tuple[Optional[str], Optional[str], List[str]]] = {}
    caller: Dict[str, str] = {}
    for ln in hlo_text.splitlines():
        if not ln.startswith(" ") and ln.rstrip().endswith("{"):
            m = _HEAD.match(ln)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(ln)
        if not m:
            continue
        body = ln.split(", metadata=", 1)[0]
        called: List[str] = []
        for one, many in _CALLS.findall(body):
            called += ([one] if one else
                       [c.strip().lstrip("%") for c in many.split(",")])
        for c in called:
            caller.setdefault(c, m.group(1))
        own = _OP_NAME.search(ln)
        refs = [r for r in _REF.findall(body[m.end():]) if r not in called]
        ins[m.group(1)] = (comp, own.group(1) if own else None, refs)

    memo: Dict[str, Optional[str]] = {}

    def resolve(name: str) -> Optional[str]:
        if name in memo:
            return memo[name]
        memo[name] = None                 # a cycle resolves to nothing
        comp, own, refs = ins.get(name, (None, None, []))
        if own is None:
            own = next((r for r in map(resolve, (x for x in refs
                                                 if x in ins)) if r), None)
        if own is None and comp in caller:
            own = resolve(caller[comp])
        memo[name] = own
        return own
    return {n: resolve(n) for n in ins}


def _fields(buf):
    """(field number, value) of one protobuf message in wire format: an
    int for varint and fixed fields, a memoryview for length-delimited."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            byte = buf[i]
            i += 1
            v |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return v
    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            v = varint()
        elif kind in (1, 5):
            w = 8 if kind == 1 else 4
            v = int.from_bytes(buf[i:i + w], "little")
            i += w
        elif kind == 2:
            ln = varint()
            v = buf[i:i + ln]
            i += ln
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def hlo_texts(path: str) -> Dict[str, str]:
    """Program name (``jit_paged_step(12)``, as the trace names it) -> the
    compiled program's HLO text, from the ``Hlo Proto`` the profiler keeps
    on the trace's ``/host:metadata`` plane (XSpace.planes = 1; XPlane
    name = 2, event_metadata = 4, stat_metadata = 5; XEventMetadata name =
    2, stats = 5; XStat metadata_id = 1, bytes_value = 6; HloProto
    hlo_module = 1)."""
    from jax._src.lib import xla_client
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, str] = {}
    for num, plane in _fields(data):
        fields = list(_fields(plane)) if num == 1 else []
        if not any(k == 2 and bytes(v) == b"/host:metadata"
                   for k, v in fields):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry[2]))
                stat_names[entry[1]] = bytes(meta.get(2, b"")).decode()
        for k, v in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v))[2]))
            name = next(bytes(x).decode() for f, x in meta if f == 2)
            for f, stat in meta:
                st = dict(_fields(stat)) if f == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto":
                    module = dict(_fields(st[6]))[1]
                    out[name] = xla_client._xla.HloModule \
                        .from_serialized_hlo_module_proto(bytes(module)) \
                        .to_string()
    return out


def from_events(events: Iterable[Tuple[str, str, str, float, float, Dict]],
                names: Optional[Dict[str, Dict[str, Optional[str]]]] = None
                ) -> DevTrace:
    """Build from ``(plane, line, name, start_ns, duration_ns, stats)``:
    host events with a ``sid`` stat are program spans; the first device
    plane's ``XLA Ops`` events are operations, each looked up in
    ``names[program][instruction]`` (:func:`op_names` of each program),
    where its program is the ``XLA Modules`` event it starts in."""
    names = names or {}
    marks: List[Mark] = []
    raw: List[Tuple[str, float, float]] = []
    mods: List[Tuple[float, float, str]] = []
    first_dev = None
    for plane, line, name, start, dur, stats in events:
        if plane.startswith("/device:"):
            if first_dev not in (None, plane):
                continue
            first_dev = plane
            if line == OPS_LINE:
                raw.append((name, start, dur))
            elif line == MODULES_LINE:
                mods.append((start, start + dur, name))
        elif "sid" in stats:
            marks.append(Mark(name, int(stats["sid"]), start, start + dur))
    mods.sort()
    starts = [m[0] for m in mods]
    ops = []
    for name, start, dur in raw:
        i = bisect.bisect_right(starts, start) - 1
        prog = mods[i][2] if i >= 0 and start < mods[i][1] else None
        path = names.get(prog, {}).get(instruction(name))
        ops.append(DevOp(name, start, dur, scope_of(path)))
    return DevTrace(marks, ops)


def instruction(name: str) -> str:
    """The instruction's name from a trace event's name (``fusion.12``, or
    its HLO text ``%fusion.12 = ...``)."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def events(path: str) -> List[Tuple[str, str, str, float, float, Dict]]:
    """The events of an ``.xplane.pb`` that :func:`from_events` reads."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = plane.name.startswith("/device:")
        if not dev and not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            if dev and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in ln.events:
                stats = dict(ev.stats)
                if dev or "sid" in stats:
                    out.append((plane.name, ln.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns),
                                stats))
    return out


def read(path: str) -> DevTrace:
    """A trace file with its operations scoped through the programs' HLO
    that the trace itself holds."""
    names = {prog: op_names(text) for prog, text in hlo_texts(path).items()}
    return from_events(events(path), names)


def step_parts(tr: DevTrace, step: str = "decode_step"
               ) -> Dict[str, float]:
    """Median ms per ``step`` span of device time by scope (operations
    that start inside the span; containers left out), and of device-idle
    time inside the span (``idle``). Empty when no such span was traced."""
    per: Dict[str, List[float]] = {k: [] for k in SCOPES + (UNSCOPED,
                                                            "idle")}
    for m in tr.named(step):
        tot = dict.fromkeys(per, 0.0)
        for o in tr.ops_in(m.t0, m.t1):
            if not o.container:
                tot[o.scope] += o.dur
        tot["idle"] = sum(y - x for x, y in tr.gaps(m.t0, m.t1))
        for k, v in tot.items():
            per[k].append(v * 1e-6)
    return {k: statistics.median(v) for k, v in per.items() if v}


def idle_gaps(tr: DevTrace, within: str = "engine_step", top: int = 10,
              min_ns: float = 0.0) -> List[Dict]:
    """The ``top`` longest device-idle gaps inside ``within`` spans, each
    with its length, the innermost span at its midpoint (``leaf``) and ns
    of it by innermost span (``cover``)."""
    found = []
    for m in tr.named(within):
        for a, b in tr.gaps(m.t0, m.t1):
            if b - a > min_ns:
                found.append((a, b))
    found.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in found[:top]:
        leaf = tr.innermost(0.5 * (a + b))
        out.append({"ms": (b - a) * 1e-6, "at_ns": a,
                    "leaf": leaf.name if leaf else "host",
                    "sid": leaf.sid if leaf else None,
                    "cover": tr.cover(a, b)})
    return out
