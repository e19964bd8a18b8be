"""The reduction from a device trace to per-layer metrics, on one recorded
decode step of the kv cell (``fixtures/trace_kv_decode_step.json``) and on
a small hand-made trace whose answers are known exactly."""
import json
import os
import types

import pytest

from servebench import profile, spec
from servebench.drive import StepRec, Window
from servebench.metrics import common
from servebench.work import kernels

HOST, DEV, OPS = "/host:CPU", "/device:TPU:0", profile.OPS_LINE


def _events(name):
    with open(os.path.join(spec.HERE, "fixtures", name)) as f:
        return json.load(f)["events"]


def _run(prof, steps, config="qwen3-4b"):
    with open(os.path.join(spec.HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    sched = types.SimpleNamespace(max_batch=24, prefill_batch=24,
                                  table_width=320, page_size=16,
                                  prefill_chunk=32)
    setup = types.SimpleNamespace(cfg=types.SimpleNamespace(n_layers=36),
                                  sched=sched)
    win = Window(0.0, 1.0, [], steps, first_traced_step=0)
    cell = types.SimpleNamespace(config=cfg, chips=1)
    return types.SimpleNamespace(cell=cell, setup=setup, window=win,
                                 peaks=spec.load_peaks("TPU v5 lite"),
                                 profile=prof)


def test_hand_made_trace():
    ev = [(HOST, "python3", "sb.step", 0, 100), (HOST, "python3", "sb.wait",
                                                  100, 50),
          (HOST, "python3", "sb.step", 150, 50),
          (DEV, OPS, "%while.1 = (s32[]) while(x)", 10, 60),
          (DEV, OPS, "%fusion.3 = f32[8] fusion(y)", 10, 30),
          (DEV, OPS, "%paged_gather_pallas.7 = bf16[2] custom-call(z)", 40, 30),
          (DEV, OPS, "%copy.2 = bf16[2] copy(z)", 160, 20),
          (DEV, OPS, "%copy.9 = bf16[2] copy(z)", 400, 20)]   # after the window
    prof = profile.from_events(ev)
    assert (prof.t0, prof.t1) == (0, 200)
    assert prof.busy_ns(0, 200) == 60 + 20
    assert prof.busy_s == pytest.approx(80e-9)
    assert prof.steps() == [(0, 100), (150, 200)]
    assert prof.gaps() == [(0, 10), (70, 160), (180, 200)]
    bd = prof.breakdown()
    assert [n for n, _ in bd["device_ops"]] == ["fusion", "paged_gather_pallas",
                                                 "copy"]
    assert bd["idle_gaps"][0] == ["sb.wait", pytest.approx(90e-9)]
    assert len(prof.ops_in(0, 100)) == 3


def test_recorded_decode_step_busy_and_idle():
    prof = profile.from_events(_events("trace_kv_decode_step.json"))
    assert len(prof.steps()) == 1
    idle = 1 - prof.busy_s / prof.window_s
    assert 0.0 < idle < 0.1               # a long step with little host work
    names = [n for n, _ in prof.breakdown()["device_ops"]]
    assert "paged_gather_pallas" in names and "while" not in names


def test_recorded_decode_step_paged_gather_roofline():
    from servebench.metrics import kernels as kreader
    prof = profile.from_events(_events("trace_kv_decode_step.json"))
    gathers = [o for o in prof.ops if o.name == "paged_gather_pallas"]
    assert len(gathers) == 2 * 36                 # K and V of every layer
    run = _run(prof, [StepRec(0, 1, "decode", 24, 24 * 600)])
    reader = spec.metric_reader("paged_gather_roofline")
    got = reader.read(run)
    least = 72 * kernels.paged_gather(24, 320, 16, 1024, 2)[1] / 819e9
    want = 100 * least / (sum(o.dur for o in gathers) * 1e-9)
    assert got == pytest.approx(want) and 0 < got < 100
    # another number of events than of calls is no sound reading
    assert kreader.roofline(run, "paged_gather_pallas",
                            lambda r, k: [(0.0, 1.0)] * 71) is None


def test_recorded_decode_step_bandwidth_share():
    prof = profile.from_events(_events("trace_kv_decode_step.json"))
    run = _run(prof, [StepRec(0, 1, "decode", 24, 24 * 600)])
    got = spec.metric_reader("step_bw_share.decode_long").read(run)
    (a, b), = prof.steps()
    work = spec.work_module(run.cell.config)
    least = work.decode_least_bytes(run.cell.config, 24, 24 * 600)
    assert got == pytest.approx(100 * least / 819e9 / (prof.busy_ns(a, b)
                                                       * 1e-9))
    idle = spec.metric_reader("idle_share.decode_long").read(run)
    assert idle == pytest.approx(100 * (1 - prof.busy_s / prof.window_s))


def test_steps_are_matched_to_records_by_order():
    prof = profile.from_events(_events("trace_kv_decode_step.json"))
    steps = [StepRec(0, 1, "prefill", 24, 0), StepRec(1, 2, "decode", 24, 9)]
    run = _run(prof, steps)
    run.window.first_traced_step = 1
    assert [s.kind for s, _ in common.traced_steps(run, "decode")] == ["decode"]
    run.window.first_traced_step = 0
    assert common.traced_steps(run, "decode") == []
    run.window.first_traced_step = 2              # more marks than records
    assert common.traced_steps(run, "decode") == []
