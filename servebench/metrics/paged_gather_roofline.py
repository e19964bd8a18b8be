"""Roofline share of the paged-gather kernel: every prefill and decode
step gathers K and V of every layer for all ``max_batch`` rows over the
whole ``table_width`` of pages (``models/attention.py:_paged_hist``)."""
from servebench.metrics.kernels import roofline
from servebench.work import kernels

EVENT = "paged_gather_pallas"


def read(run):
    cfg, sc = run.setup.cfg, run.setup.sched
    p = run.cell.config["published"]
    width = p["num_key_value_heads"] * p["head_dim"]

    def calls(run, kind):
        rows = sc.max_batch if kind == "decode" else sc.prefill_batch
        one = kernels.paged_gather(rows, sc.table_width, sc.page_size,
                                   width, 2)
        return [one] * (2 * cfg.n_layers)
    return roofline(run, EVENT, calls)
