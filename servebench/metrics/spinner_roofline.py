"""Roofline share of the fused spinner kernel: per layer and step one call
for the query features (rows = batch x query heads per kv head x tokens)
and one for the key features (rows = batch x tokens), grouped by kv head,
bf16 in and out."""
from servebench.metrics.kernels import roofline
from servebench.work import kernels

EVENT = "spinner_project_pallas"


def read(run):
    cfg, sc = run.setup.cfg, run.setup.sched
    p = run.cell.config["published"]
    n, kv = p["head_dim"], p["num_key_value_heads"]
    g = p["num_attention_heads"] // kv
    m = run.cell.config["attention"]["srf"]["n_features"]

    def calls(run, kind):
        b, c = ((sc.max_batch, 1) if kind == "decode"
                else (sc.prefill_batch, sc.prefill_chunk))
        q = kernels.spinner(kv, b * g * c, n, m, 2)
        k = kernels.spinner(kv, b * c, n, m, 2)
        return [q, k] * cfg.n_layers
    return roofline(run, EVENT, calls)
