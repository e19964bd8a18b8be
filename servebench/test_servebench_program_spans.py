"""The program's spans beside the harness's reduction: the prefill share
read from the engine's step spans, and a device trace whose host plane also
holds the program's own annotations (each carrying a ``sid``) reducing to
exactly what it reduced to without them."""
import json
import os
import types

import pytest

from repro.obs.spans import Span
from servebench import profile, spec
from servebench.drive import StepRec, Window

HOST = "/host:CPU"


def _span(name, t0, t1, sid):
    return Span(name, t0, t1, sid, None)


def _run(spans, t0=10.0, t1=20.0, prof=None, steps=()):
    rec = types.SimpleNamespace(snapshot=lambda: list(spans))
    with open(os.path.join(spec.HERE, "configs", "qwen3-4b.json")) as f:
        cfg = json.load(f)
    sched = types.SimpleNamespace(max_batch=24, prefill_batch=24,
                                  table_width=320, page_size=16,
                                  prefill_chunk=32, num_pages=2)
    setup = types.SimpleNamespace(spans=rec, sched=sched,
                                  cfg=types.SimpleNamespace(n_layers=36))
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=cfg, chips=1), setup=setup,
        window=Window(t0, t1, [], list(steps), first_traced_step=0),
        peaks=spec.load_peaks("TPU v5 lite"), profile=prof)


def test_prefill_share_hand_made():
    read = spec.metric_reader("prefill_share.decode_long").read
    spans = [_span("prefill_step", 9.0, 11.0, 1),     # 1 s inside
             _span("decode_step", 11.0, 12.0, 2),
             _span("build", 12.0, 12.5, 3),
             _span("prefill_step", 12.0, 14.5, 4),    # 2.5 s
             _span("prefill_step", 19.5, 21.0, 5),    # 0.5 s inside
             _span("prefill_step", 25.0, 26.0, 6)]    # after the window
    # prefill 1 + 2.5 + 0.5 s, decode 1 s inside the window
    assert read(_run(spans)) == pytest.approx(100 * 4.0 / 5.0)
    assert read(_run(spans[1:2])) == 0.0               # decode only
    assert read(_run([])) is None                      # spans were off
    assert read(_run(spans[-1:])) is None              # none in the window


def _events():
    with open(os.path.join(spec.HERE, "fixtures",
                           "trace_kv_decode_step.json")) as f:
        return json.load(f)["events"]


def _with_program_annotations(events):
    """The fixture's events plus the program's spans on the host plane,
    as an enabled recorder puts them there: inside ``sb.step``, and one
    ``engine_step`` running past its end."""
    (_, _, _, a, d), = [e for e in events if e[2] == "sb.step"]
    extra = [[HOST, "python3", "engine_step", a + 10, d],
             [HOST, "python3", "decode_step", a + 20, d - 40],
             [HOST, "python3", "dispatch", a + 30, 1000],
             [HOST, "python3", "sync", a + d // 2, d // 4],
             [HOST, "python3", "gc", a + d - 30, 20]]
    return events + extra


def test_program_annotations_leave_the_reduction_as_it_was():
    base = profile.from_events(_events())
    both = profile.from_events(_with_program_annotations(_events()))
    assert both.annotations == base.annotations
    assert (both.t0, both.t1, both.n_devices) == (base.t0, base.t1,
                                                  base.n_devices)
    assert both.ops == base.ops
    assert both.steps() == base.steps() and both.gaps() == base.gaps()
    assert both.breakdown() == base.breakdown()
    steps = [StepRec(0, 1, "decode", 24, 24 * 600)]
    for m in ("step_bw_share.decode_long", "idle_share.decode_long",
              "paged_gather_roofline"):
        read = spec.metric_reader(m).read
        assert read(_run([], prof=both, steps=steps)) == \
            read(_run([], prof=base, steps=steps))
