"""Least bytes of each traced decode step (the configuration's work
module, ``spec.work_module``) over its device busy time times the peak
HBM bandwidth, summed over the traced decode steps."""
from servebench import spec
from servebench.metrics.common import share, traced_steps


def read(run):
    model = spec.work_module(run.cell.config)
    least = busy = 0.0
    for s, (a, b) in traced_steps(run, "decode"):
        least += model.decode_least_bytes(run.cell.config, s.rows, s.context)
        busy += run.profile.busy_ns(a, b) * 1e-9
    return share(least / run.peaks["hbm_bytes_per_s"], busy)
