"""Span timelines: recorder semantics, Chrome-trace export (golden
schema pin), multi-replica merge, and the engine/router instrumentation
contract (spans off by default, clock reads unchanged)."""
import gc
import glob
import json

import numpy as np
import pytest

from repro.obs import SpanRecorder, chrome_trace, dump_chrome_trace
from repro.obs import spans as spans_lib
from repro.obs.spans import NOOP


def _recorded(rec):
    """The records of ``rec`` less ``gc`` spans: a collection may run
    anywhere, and an enabled recorder records it."""
    return [s for s in rec.snapshot() if s.name != "gc"]


# ---------------------------------------------------------------------------
# recorder semantics
# ---------------------------------------------------------------------------

def test_begin_end_records_span_with_args():
    rec = SpanRecorder()
    tok = rec.begin("work", uid=7, rows=3)
    tok.args["extra"] = 1
    rec.end(tok)
    (sp,) = _recorded(rec)
    assert sp.name == "work" and sp.uid == 7
    assert sp.args == {"rows": 3, "extra": 1}
    assert sp.t1 >= sp.t0 and sp.kind == "span"


def test_parent_links_follow_open_span_stack():
    rec = SpanRecorder()
    outer = rec.begin("outer")
    inner = rec.begin("inner")
    rec.end(inner)
    rec.end(outer)
    by_name = {s.name: s for s in _recorded(rec)}
    assert by_name["outer"].parent is None
    assert by_name["inner"].parent == by_name["outer"].sid


def test_context_manager_and_instant():
    rec = SpanRecorder(replica=2)
    with rec.span("step", uid=1):
        rec.instant("hit", uid=1, tokens=4)
    kinds = {s.name: s for s in _recorded(rec)}
    assert kinds["hit"].kind == "instant"
    assert kinds["hit"].t0 == kinds["hit"].t1
    assert kinds["hit"].parent == kinds["step"].sid   # nested under step
    assert all(s.replica == 2 for s in rec.snapshot())


def test_ring_bounded_and_dropped_counter():
    rec = SpanRecorder(maxlen=4)
    gc.disable()                # a gc span would take a place in the ring
    try:
        for i in range(10):
            rec.instant(f"e{i}")
    finally:
        gc.enable()
    assert len(rec) == 4
    assert rec.dropped == 6
    assert [s.name for s in rec.snapshot()] == ["e6", "e7", "e8", "e9"]
    rec.clear()
    assert len(rec) == 0


def test_disabled_recorder_is_noop():
    rec = SpanRecorder(enabled=False)
    tok = rec.begin("x", uid=1)
    tok.args["y"] = 2          # absorbed, never recorded
    rec.end(tok)
    with rec.span("z"):
        rec.instant("i")
    assert len(rec) == 0 and rec.snapshot() == []
    assert len(NOOP) == 0      # the module-level shared instance too


def test_sids_unique_across_recorders():
    a, b = SpanRecorder(replica=0), SpanRecorder(replica=1)
    a.instant("x")
    b.instant("x")
    sids = [s.sid for s in _recorded(a) + _recorded(b)]
    assert len(set(sids)) == 2  # process-global counter: merge-safe


# ---------------------------------------------------------------------------
# chrome-trace export: golden schema pin (fixed timestamps via complete())
# ---------------------------------------------------------------------------

def _golden_recorders():
    r0 = SpanRecorder(replica=0)
    root = r0.complete("engine_step", 1.0, 1.5, rows=2)
    r0.complete("prefill_step", 1.1, 1.3, parent=root)
    r0.complete("decode_step", 1.3, 1.5, parent=root)
    r1 = SpanRecorder(replica=1)
    r1.complete("engine_step", 1.2, 1.4, uid=9)
    return [r0, r1]


def test_chrome_trace_golden_schema(tmp_path):
    recs = _golden_recorders()
    path = tmp_path / "trace.json"
    n = dump_chrome_trace(str(path), recs)
    doc = json.loads(path.read_text())       # schema-valid JSON on disk
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert n == len(evs)
    meta = [e for e in evs if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} == {"replica 0", "replica 1"}
    be = [e for e in evs if e["ph"] in "BE"]
    # every B/E event carries the required Chrome trace-event fields
    for e in be:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e)
        assert isinstance(e["ts"], float) and e["ts"] >= 0.0
    # earliest span anchors the merged clock at ts=0
    assert min(e["ts"] for e in be) == 0.0


def test_chrome_trace_begin_end_paired_and_monotonic():
    recs = _golden_recorders()
    doc = chrome_trace(recs)
    for pid in (0, 1):
        seq = [e for e in doc["traceEvents"]
               if e.get("pid") == pid and e["ph"] in "BE"]
        # ts never decreases within one pid row
        assert all(a["ts"] <= b["ts"] for a, b in zip(seq, seq[1:]))
        stack = []
        for e in seq:
            if e["ph"] == "B":
                stack.append(e["name"])
            else:
                assert stack.pop() == e["name"]   # E matches innermost B
        assert stack == []                        # fully paired


def test_chrome_trace_merges_replicas_onto_one_clock():
    recs = _golden_recorders()
    evs = chrome_trace(recs)["traceEvents"]
    b0 = next(e for e in evs if e["pid"] == 0 and e["ph"] == "B"
              and e["name"] == "engine_step")
    b1 = next(e for e in evs if e["pid"] == 1 and e["ph"] == "B")
    # replica 1's step began 0.2s into replica 0's: 200000us on the
    # shared normalized clock, not 0 on a per-replica clock
    assert b1["ts"] - b0["ts"] == 200000.0
    assert b1["args"]["uid"] == 9                 # uid rides into args


def test_chrome_trace_instants():
    r = SpanRecorder(replica=3)
    r.complete("step", 2.0, 3.0)
    r.instant("prefix_hit", uid=5, tokens=8)
    evs = chrome_trace([r])["traceEvents"]
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["s"] == "t" and i["pid"] == 3
    assert i["args"]["uid"] == 5 and i["args"]["tokens"] == 8


def test_chrome_trace_empty_recorder():
    doc = chrome_trace(SpanRecorder())
    assert doc["traceEvents"] == []


# ---------------------------------------------------------------------------
# engine integration: spans record the serving control flow
# ---------------------------------------------------------------------------

def test_engine_records_step_spans_and_export_loads():
    import jax
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine, Request

    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    rec = SpanRecorder(replica=0)
    eng = Engine(cfg, params, batch_slots=2, max_len=64, spans=rec)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=np.arange(4, dtype=np.int32),
                           max_new=4))
    eng.run()
    names = {s.name for s in rec.snapshot()}
    assert {"engine_step", "admit", "prefill_step",
            "decode_step", "sample"} <= names
    by_name = {}
    for s in rec.snapshot():
        by_name.setdefault(s.name, s)
    # nesting: prefill/decode/sample live under an engine_step
    steps = {s.sid for s in rec.snapshot() if s.name == "engine_step"}
    assert by_name["prefill_step"].parent in steps
    assert by_name["decode_step"].parent in steps
    doc = chrome_trace(rec)
    assert json.loads(json.dumps(doc)) == doc     # JSON-serializable
    assert any(e["ph"] == "B" for e in doc["traceEvents"])


def test_engine_without_spans_records_nothing():
    # default Engine uses the shared NOOP recorder: no per-step cost
    import jax
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine, Request

    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    before = len(NOOP)
    eng = Engine(cfg, params, batch_slots=2, max_len=64)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new=2))
    eng.run()
    assert len(NOOP) == before == 0


# ---------------------------------------------------------------------------
# the device trace's clock and gc pauses
# ---------------------------------------------------------------------------

class _CountingNote:
    opened = 0

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        type(self).opened += 1
        return self

    def __exit__(self, *exc):
        return False


def test_enabled_recorder_opens_one_annotation_per_span(monkeypatch):
    monkeypatch.setattr(spans_lib, "TraceAnnotation", _CountingNote)
    _CountingNote.opened = 0
    rec = SpanRecorder()
    gc.disable()
    try:
        tok = rec.begin("outer")
        with rec.span("inner"):
            rec.instant("mark")
        rec.end(tok)
        rec.complete("late", 1.0, 2.0)
    finally:
        gc.enable()
    assert _CountingNote.opened == 2        # begin/end spans only
    assert tok.note.name == "outer" and tok.note.meta == {"sid": tok.sid}


def test_noop_recorder_opens_no_annotation(monkeypatch):
    monkeypatch.setattr(spans_lib, "TraceAnnotation", _CountingNote)
    _CountingNote.opened = 0
    for rec in (NOOP, SpanRecorder(enabled=False)):
        tok = rec.begin("x", rows=3)
        rec.end(tok)
        with rec.span("y"):
            pass
    assert _CountingNote.opened == 0
    assert tok is spans_lib._NOOP_TOKEN and tok.note is None


def test_enabled_spans_reach_the_profiler_trace_by_sid(tmp_path):
    import jax
    from repro.obs import devtrace
    rec = SpanRecorder()
    jax.numpy.ones((8,)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("decode_step", rows=3):
            with rec.span("sync"):
                jax.numpy.ones((8,)).block_until_ready()
        rec.instant("mark")
        rec.complete("late", 1.0, 2.0)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tr = devtrace.read(path)
    mine = {s.sid: s for s in _recorded(rec)}
    joined = [m for m in tr.marks if m.sid in mine]
    assert sorted(m.name for m in joined) == ["decode_step", "sync"]
    for m in joined:
        assert mine[m.sid].name == m.name
    step = next(m for m in joined if m.name == "decode_step")
    assert mine[step.sid].args == {"rows": 3}
    sync = next(m for m in joined if m.name == "sync")
    assert mine[sync.sid].parent == step.sid
    assert step.t0 <= sync.t0 and sync.t1 <= step.t1   # one clock
    leaf = tr.innermost(0.5 * (sync.t0 + sync.t1))
    gcs = {s.sid for s in rec.snapshot()
           if s.name == "gc" and s.parent == sync.sid}
    assert leaf.sid == sync.sid or leaf.sid in gcs


def test_gc_collect_inside_an_enabled_recorder_is_one_gc_span():
    rec = SpanRecorder()
    with rec.span("outer"):
        before = len([s for s in rec.snapshot() if s.name == "gc"])
        gc.collect()
        got = [s for s in rec.snapshot() if s.name == "gc"][before:]
    outer, = [s for s in rec.snapshot() if s.name == "outer"]
    g, = [s for s in got if s.args["generation"] == 2]
    assert g.parent == outer.sid and g.t0 <= g.t1
    assert set(g.args) == {"generation", "collected"}
    assert len(NOOP) == 0                 # the disabled recorder records none


def test_gc_hook_lives_as_long_as_an_enabled_recorder():
    gc.collect()                # recorders earlier tests left in cycles
    base = spans_lib._GC_LIVE[0]
    rec = SpanRecorder()
    assert spans_lib._on_gc in gc.callbacks
    assert spans_lib._GC_LIVE[0] == base + 1
    SpanRecorder(enabled=False)
    assert spans_lib._GC_LIVE[0] == base + 1
    del rec
    gc.collect()
    assert spans_lib._GC_LIVE[0] == base
    assert (spans_lib._on_gc in gc.callbacks) == (base > 0)
    assert gc.callbacks.count(spans_lib._on_gc) <= 1


@pytest.mark.parametrize("attn", ["kv", "srf"])
def test_engine_leaf_spans_cover_each_step(attn):
    import jax
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine, Request

    kw = {"attn_impl": "srf"} if attn == "srf" else {}
    cfg = registry.reduced("qwen3-4b", n_layers=2, **kw)
    params = T.init(jax.random.PRNGKey(0), cfg)
    rec = SpanRecorder()
    eng = Engine(cfg, params, batch_slots=2, max_len=64, spans=rec,
                 quality_every=1)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=np.arange(4, dtype=np.int32),
                           max_new=4))
    eng.run()
    recs = _recorded(rec)
    by_sid = {s.sid: s for s in recs}
    kids = {}
    for s in recs:
        if s.parent in by_sid:
            kids.setdefault(by_sid[s.parent].name, set()).add(s.name)
    leaves = {"build", "dispatch", "sample", "emit"}
    assert leaves <= kids["prefill_step"]
    assert leaves | {"grow"} <= kids["decode_step"]
    assert kids["sample"] == {"sync"}
    if attn == "srf":
        assert {"quality_probe"} <= kids["decode_step"]
        assert {"zero_slot_rows"} <= kids["engine_step"]
    else:
        assert "quality_probe" not in {s.name for s in recs}
    # leaves follow one another inside their step, in order
    for step in (s for s in recs if s.name == "decode_step"):
        seq = sorted((s for s in recs if s.parent == step.sid),
                     key=lambda s: s.t0)
        names = [s.name for s in seq if s.kind == "span"]
        assert names[:5] == ["grow", "build", "dispatch", "sample", "emit"]
        assert all(a.t1 <= b.t0 for a, b in zip(seq, seq[1:]))
