"""A device trace of a few seconds inside the window, reduced to what the
per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes (``/device:TPU:n``) carry one event per executed
operation on their ``XLA Ops`` line; the host plane carries the harness's
own ``sb.*`` annotations (``drive.py``). Busy time is the union of the
operation intervals; an idle gap is a stretch of the traced window with no
operation, named by the annotation the host was inside at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
ANNOTATION = "sb."


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip_total(merged: List[Tuple[float, float]], a: float, b: float
               ) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in merged)


@dataclass
class Op:
    name: str
    start: float        # ns, profiler clock
    dur: float          # ns
    device: int


@dataclass
class Profile:
    """One traced sub-window (times in ns on the profiler's clock)."""
    t0: float
    t1: float
    ops: List[Op]
    annotations: List[Tuple[str, float, float]]
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_ns(self, a: float, b: float, device: Optional[int] = None
                ) -> float:
        """Union of operation time inside [a, b], averaged over devices."""
        devs = range(self.n_devices) if device is None else [device]
        tot = 0.0
        for d in devs:
            iv = merge([(o.start, o.start + o.dur) for o in self.ops
                        if o.device == d])
            tot += clip_total(iv, a, b)
        return tot / len(devs)

    @property
    def busy_s(self) -> float:
        return self.busy_ns(self.t0, self.t1) * 1e-9

    def steps(self) -> List[Tuple[float, float]]:
        return [(a, b) for n, a, b in self.annotations if n == "sb.step"]

    def ops_in(self, a: float, b: float) -> List[Op]:
        return [o for o in self.ops if a <= o.start < b]

    def gaps(self) -> List[Tuple[float, float]]:
        busy = merge([(o.start, o.start + o.dur) for o in self.ops
                      if o.device == 0])
        out, t = [], self.t0
        for a, b in busy:
            if a > t:
                out.append((t, min(a, self.t1)))
            t = max(t, b)
            if t >= self.t1:
                break
        if t < self.t1:
            out.append((t, self.t1))
        return [(a, b) for a, b in out if b > a]

    def host_at(self, t: float) -> str:
        inside = [n for n, a, b in self.annotations if a <= t < b]
        return inside[-1] if inside else "host (no harness call)"

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        per: Dict[str, float] = {}
        for o in self.ops:
            if o.name not in CONTAINERS:
                per[o.name] = per.get(o.name, 0.0) + o.dur * 1e-9
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        named = [[self.host_at(0.5 * (a + b)), (b - a) * 1e-9]
                 for a, b in gaps]
        return {"device_ops": [[n, s / self.n_devices] for n, s in ops],
                "idle_gaps": named}


def op_name(text: str) -> str:
    """The instruction's name from its HLO text (``%name.12 = ...``), less
    the numeric suffix: a Pallas kernel's custom call carries the name of
    the function that made it (``paged_gather_pallas``)."""
    head = text.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


#: Control flow around other operations: busy, but not an operation's own
#: time in the breakdown.
CONTAINERS = ("while", "conditional", "call")


def xplane_events(path: str) -> List[Tuple[str, str, str, float, float]]:
    """(plane, line, name, start_ns, duration_ns) of every event the
    reduction reads: device operations and the host's ``sb.*`` marks."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = plane.name.startswith("/device:")
        if not dev and not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            if dev and ln.name != OPS_LINE:
                continue
            for ev in ln.events:
                if dev or ev.name.startswith(ANNOTATION):
                    out.append((plane.name, ln.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def from_events(events) -> Optional[Profile]:
    """The traced sub-window: from the first ``sb.*`` mark to the end of
    the last one, with every device operation overlapping it."""
    ops: List[Op] = []
    notes: List[Tuple[str, float, float]] = []
    devices: Dict[str, int] = {}
    for plane, _line, name, start, dur in events:
        if plane.startswith("/device:"):
            d = devices.setdefault(plane, len(devices))
            ops.append(Op(op_name(name), start, dur, d))
        elif name.startswith(ANNOTATION):
            notes.append((name, start, start + dur))
    if not ops or not notes:
        return None
    notes.sort(key=lambda n: n[1])
    t0, t1 = notes[0][1], max(b for _, _, b in notes)
    ops = [o for o in ops if o.start < t1 and o.start + o.dur > t0]
    return Profile(t0, t1, ops, notes, max(len(devices), 1))


def reduce_xplane(path: str) -> Optional[Profile]:
    return from_events(xplane_events(path))


class WindowTracer:
    """Starts the profiler ``after_s`` into the window and stops it
    ``seconds`` later; ``reduce()`` reads the trace and deletes it."""

    def __init__(self, after_s: float, seconds: float):
        self.after_s, self.seconds = after_s, seconds
        self.t_open: Optional[float] = None
        self.t_start: Optional[float] = None
        self.running = False
        self.done = False
        self.first_step: Optional[int] = None
        self.dir = tempfile.mkdtemp(prefix="servebench-trace-")

    def tick(self, now: float, n_steps: int) -> None:
        import jax
        if self.t_open is None:
            self.t_open = now
        if self.done:
            return
        if not self.running and now >= self.t_open + self.after_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.running, self.t_start = True, now
            self.first_step = n_steps
        elif self.running and now >= self.t_start + self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.running:
            jax.profiler.stop_trace()
            self.running, self.done = False, True

    def reduce(self) -> Optional[Profile]:
        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return reduce_xplane(found[0]) if found else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
