"""Helpers the per-layer metric readers share."""
from __future__ import annotations

from typing import List, Optional, Tuple


def spans_in_window(run, name: str) -> List:
    w = run.window
    return [s for s in run.setup.spans.snapshot()
            if s.name == name and w.t0 <= s.t0 and s.t1 <= w.t1]


def traced_steps(run, kind: str) -> List[Tuple[object, Tuple[float, float]]]:
    """(harness step record, its ``sb.step`` interval on the profiler's
    clock) for every step of ``kind`` inside the traced sub-window. The
    annotations are the trace's own record of the harness's steps, in
    order; they are matched to the step records by their order, counted
    back from the last step the window ran before tracing stopped."""
    prof = run.profile
    if prof is None:
        return []
    marks = prof.steps()
    steps = run.window.steps
    first = run.window.first_traced_step
    if first is None or first + len(marks) > len(steps):
        return []
    pairs = zip(steps[first:first + len(marks)], marks)
    return [(s, m) for s, m in pairs if s.kind == kind]


def share(num: float, den: float) -> Optional[float]:
    return None if den <= 0 else 100.0 * num / den
