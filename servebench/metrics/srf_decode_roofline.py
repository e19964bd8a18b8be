"""Roofline share of the fused SRF decode kernel: one call per layer per
decode step, over all ``max_batch`` rows and every query head, float32."""
from servebench.metrics.kernels import roofline
from servebench.work import kernels

EVENT = "srf_decode_pallas"


def read(run):
    cfg, sc = run.setup.cfg, run.setup.sched
    p = run.cell.config["published"]
    m = run.cell.config["attention"]["srf"]["n_features"]

    def calls(run, kind):
        if kind != "decode":
            return []
        one = kernels.srf_decode(sc.max_batch, p["num_attention_heads"], m,
                                 p["head_dim"])
        return [one] * cfg.n_layers
    return roofline(run, EVENT, calls)
