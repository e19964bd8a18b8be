"""Median duration of the engine's ``decode_step`` spans in the window."""
from servebench import stats
from servebench.metrics.common import spans_in_window


def read(run):
    v = stats.median([s.dur for s in spans_in_window(run, "decode_step")])
    return None if v is None else 1e3 * v
