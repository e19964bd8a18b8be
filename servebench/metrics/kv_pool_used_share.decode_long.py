"""Pages of the paged KV pool held over the window, as a % of the pages
the pool reserves (time-weighted over the window's steps). Nothing to
read where the configuration has no paged pool."""
from servebench import stats


def read(run):
    if not run.window.steps or not any(s.pages_used
                                       for s in run.window.steps):
        return None
    return stats.kv_used_share(run.window, run.setup.sched.num_pages)
