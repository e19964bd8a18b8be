"""Share of the traced sub-window in which no operation ran on the
device: 1 - busy union / window."""


def read(run):
    prof = run.profile
    if prof is None or prof.window_s <= 0:
        return None
    return 100.0 * (1.0 - prof.busy_s / prof.window_s)
