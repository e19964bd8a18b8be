"""Share of the engine's step time in the window spent in prefill: the
time of its ``prefill_step`` spans over that of its ``prefill_step`` and
``decode_step`` spans, each clipped to the window. Step time, not the
window, is the denominator: in the traced run the profiler's stop holds
the window's loop for seconds in which no step runs. Nothing to read where
the window holds no step span."""
from servebench.metrics.common import share

STEPS = ("prefill_step", "decode_step")


def read(run):
    w = run.window
    busy = dict.fromkeys(STEPS, 0.0)
    for s in run.setup.spans.snapshot():
        if s.name in busy and s.t1 > w.t0 and s.t0 < w.t1:
            busy[s.name] += min(s.t1, w.t1) - max(s.t0, w.t0)
    return share(busy["prefill_step"], sum(busy.values()))
