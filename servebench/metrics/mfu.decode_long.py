"""Model FLOP/s utilization of the whole window: the model FLOPs of every
token processed in it (each prompt whose first token came in the window,
and every output token emitted in it; the configuration's work module,
``spec.work_module``), over the window's length times the chip's peak
bf16 FLOP/s."""
from servebench import spec
from servebench.metrics.common import share


def read(run):
    w, cfg = run.window, run.cell.config
    model = spec.work_module(cfg)
    flops = 0.0
    for r in w.recs:
        p = len(r.prompt)
        for k, t in enumerate(r.token_t):
            if not w.t0 <= t <= w.t1:
                continue
            if k == 0:        # the prompt's prefill ends with token 0
                flops += sum(model.token_flops(cfg, i + 1, False)
                             for i in range(p - 1))
                flops += model.token_flops(cfg, p, True)
            else:
                flops += model.token_flops(cfg, p + k, True)
    n = run.cell.chips
    return share(flops, (w.t1 - w.t0) * n * run.peaks["bf16_flops"])
