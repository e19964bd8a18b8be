"""Roofline share of one Pallas kernel over the traced steps: the sum of
its calls' least times (``work.kernels``) over the sum of the durations of
its events in the device trace. The calls are counted from the traced
steps' shapes; if the trace holds another number of events, the reading
is not sound and nothing is returned."""
from __future__ import annotations

from typing import Callable, List, Tuple

from servebench.metrics.common import share, traced_steps
from servebench.work import kernels


def roofline(run, event: str,
             calls: Callable[[object, str], List[Tuple[float, float]]]):
    if run.profile is None:
        return None
    least = dur = 0.0
    n_ev = n_calls = 0
    for kind in ("prefill", "decode"):
        for s, (a, b) in traced_steps(run, kind):
            evs = [o for o in run.profile.ops_in(a, b) if o.name == event]
            cs = calls(run, kind)
            n_ev += len(evs)
            n_calls += len(cs)
            dur += sum(o.dur for o in evs) * 1e-9
            least += sum(kernels.least_seconds(f, nb, run.peaks)
                         for f, nb in cs)
    if n_calls == 0 or n_ev != n_calls:
        return None
    return share(least, dur)
