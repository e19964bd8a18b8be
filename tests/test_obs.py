"""Observability subsystem: metrics registry semantics, trace lifecycle
derivations, kernel profiling hooks, the SRF quality probe, the
reporter, and the no-bare-print lint pin over the serving stack."""
import io
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import profiling, quality, report
from repro.obs.metrics import MetricsRegistry, StatsView
from repro.obs.trace import Trace, latency_summary, percentiles

SRC = Path(__file__).resolve().parent.parent / "src"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", ("engine",))
    c.labels(engine="0").inc()
    c.labels(engine="0").inc(2)
    c.labels(engine="1").inc(5)
    assert c.labels(engine="0").value() == 3
    assert c.total() == 8
    assert reg.value_sum("reqs_total") == 8
    with pytest.raises(ValueError):
        c.labels(engine="0").inc(-1)           # counters only go up


def test_unlabelled_metrics_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("steps_total")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    g = reg.gauge("free_pages")
    g.set(7)
    assert g.value() == 7
    gl = reg.gauge("headroom", "", ("replica",))
    gl.labels(replica=0).set(3)
    gl.labels(replica=0).dec()
    gl.labels(replica=1).inc(2)
    assert gl.labels(replica=0).value() == 2
    assert reg.value_sum("headroom") == 4


def test_factory_idempotent_and_type_mismatch_raises():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", ("engine",))
    b = reg.counter("x_total", "different help", ("engine",))
    assert a is b                              # same series, not a fork
    with pytest.raises(ValueError):
        reg.gauge("x_total")                   # type mismatch
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("other",))  # label-set mismatch


def test_histogram_percentiles_and_ring():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "", (), max_observations=8)
    for v in range(100):
        h.observe(float(v))
    bound = h.labels()
    assert bound.count() == 100                # count survives the ring
    assert bound.sum() == sum(range(100))
    assert len(bound.values()) == 8            # observations bounded
    hh = reg.histogram("exact", "")
    for v in (5.0, 1.0, 3.0, 2.0, 4.0):
        hh.observe(v)
    assert hh.labels().percentile(50) == 3.0   # nearest-rank
    assert hh.labels().percentile(99) == 5.0
    assert reg.percentiles("exact")["p50"] == 3.0


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    c.inc(99)
    assert c.value() == 0
    reg.event("queued", uid=1)
    assert reg.events == []
    assert reg.snapshot()["counters"] == {}
    assert reg.value_sum("c_total") == 0
    assert np.isnan(reg.percentiles("nope")["p50"])


def test_events_bounded_and_jsonl_dump():
    reg = MetricsRegistry(max_events=3)
    for i in range(5):
        reg.event("queued", uid=i)
    assert len(reg.events) == 3
    assert reg.events_dropped == 2
    buf = io.StringIO()
    assert reg.dump_events_jsonl(buf) == 3
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert [e["uid"] for e in lines] == [0, 1, 2]
    assert all(e["event"] == "queued" and "t" in e for e in lines)


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("a_total", "things", ("engine",)).labels(engine="0").inc(3)
    reg.gauge("b").set(1.5)
    reg.histogram("c_seconds", "lat", ("engine",)) \
       .labels(engine="0").observe(0.25)
    text = reg.prometheus_text()
    assert "# TYPE a_total counter" in text
    assert 'a_total{engine="0"} 3' in text
    assert "b 1.5" in text
    assert "# TYPE c_seconds summary" in text
    assert 'c_seconds{engine="0",quantile="0.5"} 0.25' in text
    assert 'c_seconds_count{engine="0"} 1' in text


def test_prometheus_label_value_escaping():
    # Prometheus exposition: backslash, newline and double-quote inside
    # a label VALUE must be escaped; ordinary values pass through
    # byte-identical (pinned by test_prometheus_text_format above).
    reg = MetricsRegistry()
    c = reg.counter("esc_total", "", ("tenant",))
    c.labels(tenant='a"b\\c\nd').inc()
    text = reg.prometheus_text()
    assert r'esc_total{tenant="a\"b\\c\nd"} 1' in text
    assert MetricsRegistry._escape_label_value("plain-0") == "plain-0"


def test_prometheus_empty_histogram_and_label_only_series():
    # A histogram that was registered but never observed must still
    # export valid exposition (TYPE line, zero count, no quantile lines
    # that would divide by an empty sample), and a labelled metric with
    # no bound children exports just its header.
    reg = MetricsRegistry()
    reg.histogram("idle_seconds", "never observed")
    reg.counter("unbound_total", "no children yet", ("engine",))
    text = reg.prometheus_text()
    assert "# TYPE idle_seconds summary" in text
    assert "# TYPE unbound_total counter" in text
    lines = [l for l in text.splitlines() if l.startswith("idle_seconds")]
    for line in lines:
        assert "quantile" not in line or not line.endswith("nan")
    h = reg.histogram("idle_seconds", "")
    assert h.labels().count() == 0 and h.labels().sum() == 0.0


def test_noop_registry_snapshot_shape():
    # The disabled registry's snapshot must be shape-compatible with the
    # enabled one (same top-level keys), so reporters can read either.
    live = MetricsRegistry().snapshot()
    noop = MetricsRegistry(enabled=False).snapshot()
    assert set(noop) == set(live)
    assert all(noop[k] in ({}, [], 0) for k in noop)
    reg = MetricsRegistry(enabled=False)
    h = reg.histogram("h_seconds")
    h.observe(1.0)
    assert reg.snapshot()["histograms"] == {}


def test_stats_view_is_read_only_live_mapping():
    reg = MetricsRegistry()
    c = reg.counter("tok_total")
    view = StatsView({"tokens": c.value})
    assert view["tokens"] == 0
    c.inc(4)
    assert view["tokens"] == 4                 # live, not a copy
    assert dict(view) == {"tokens": 4}
    assert "tokens" in view and len(view) == 1
    with pytest.raises(TypeError):
        view["tokens"] = 9                     # Mapping, not MutableMapping


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_trace_derivations_and_monotonic():
    tr = Trace(uid=1)
    tr.stamp("queued", 1.0)
    tr.stamp("admitted", 1.5)
    tr.stamp("prefill", 1.6)
    tr.stamp("first_token", 2.0)
    tr.stamp("preempted", 2.1)
    tr.stamp("restored", 2.2)
    tr.stamp("decode", 2.3)
    tr.stamp("done", 3.0)
    assert tr.queue_time == pytest.approx(0.5)
    assert tr.ttft == pytest.approx(1.0)
    assert tr.e2e == pytest.approx(2.0)
    assert tr.tpot(5) == pytest.approx(1.0 / 4)
    assert tr.tpot(1) is None                  # single token: no TPOT
    assert tr.monotonic()
    assert tr.count("preempted") == 1


def test_trace_detects_out_of_order():
    tr = Trace()
    tr.stamp("queued", 2.0)
    tr.stamp("admitted", 1.0)                  # time goes backwards
    assert not tr.monotonic()
    tr2 = Trace()
    tr2.stamp("first_token", 1.0)
    tr2.stamp("queued", 1.0)                   # milestones out of order
    tr2.stamp("admitted", 1.0)
    assert not tr2.monotonic()


def test_percentiles_nearest_rank_and_empty():
    p = percentiles([10.0, 20.0, 30.0, 40.0], qs=(50, 95, 99))
    assert p == {"p50": 30.0, "p95": 40.0, "p99": 40.0}
    assert all(np.isnan(v) for v in percentiles([]).values())


def test_latency_summary_falls_back_to_stamps():
    class R:
        done = True
        out_tokens = [1, 2, 3]
        t_submit, t_first, t_done = 0.0, 0.5, 1.5
        trace = None
    s = latency_summary([R(), R()])
    assert s["requests"] == 2 and s["tokens"] == 6
    assert s["ttft_s"]["p50"] == pytest.approx(0.5)
    assert s["tpot_s"]["p50"] == pytest.approx(0.5)
    assert s["e2e_s"]["p50"] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# profiling hooks
# ---------------------------------------------------------------------------

def _op_names(fn, *args):
    from repro.obs import devtrace
    return devtrace.op_names(jax.jit(fn).lower(*args).compile().as_text())


def test_dispatch_runs_the_call_eagerly():
    out = profiling.dispatch("toy", lambda: jnp.ones((4,)) * 2)
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_dispatch_scope_reaches_hlo_op_name():
    names = _op_names(lambda x: profiling.dispatch("traced", lambda: x * 3),
                      jnp.ones((2,)))
    assert any("/traced/" in n for n in names.values())


def test_ops_dispatch_scope_names_kernel():
    from repro.kernels import ops
    pool = jnp.zeros((4, 2, 8))
    tables = jnp.zeros((2, 2), jnp.int32)
    names = _op_names(lambda p, t: ops.paged_gather(p, t, use_pallas=False),
                      pool, tables)
    assert any("/paged_gather/" in n for n in names.values())


# ---------------------------------------------------------------------------
# quality probe
# ---------------------------------------------------------------------------

def test_srf_quality_probe():
    from repro.configs import registry
    from repro.models import transformer as T
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    assert quality.srf_quality_probe(cfg, params) is None   # non-SRF

    scfg = registry.reduced("qwen3-4b", n_layers=2, attn_impl="srf")
    sparams = T.init(jax.random.PRNGKey(0), scfg)
    stats = quality.srf_quality_probe(scfg, sparams)
    assert set(stats) == {"srf_row_mean_abs_max", "srf_row_var_err_max"}
    # Def. 1 calibration: freshly initialized rows are near N(0, I) rows
    assert 0 <= stats["srf_row_mean_abs_max"] < 1.0
    assert 0 <= stats["srf_row_var_err_max"] < 1.0


def test_engine_publishes_quality_gauge():
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine, Request
    cfg = registry.reduced("qwen3-4b", n_layers=2, attn_impl="srf")
    params = T.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, batch_slots=2, max_len=64, quality_every=2)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new=6))
    eng.run()
    qual = eng.metrics.snapshot()["gauges"].get("srf_quality", {})
    assert qual, "srf engine never sampled the quality gauge"
    assert all(np.isfinite(v) for v in qual.values())


# ---------------------------------------------------------------------------
# reporter
# ---------------------------------------------------------------------------

def test_reporter_periodic_and_final(tmp_path):
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine, Request
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    reg = MetricsRegistry()
    eng = Engine(cfg, params, batch_slots=4, max_len=64, metrics=reg)
    for i in range(4):
        eng.submit(Request(uid=i, prompt=np.arange(5, dtype=np.int32),
                           max_new=4))
    buf = io.StringIO()
    rep = report.Reporter(stream=buf)
    done = eng.run(on_step=rep.periodic(reg, every_s=0.0))
    dump = tmp_path / "metrics.prom"
    rep.final(reg, done, dump_path=str(dump))
    text = buf.getvalue()
    assert "[metrics] t=" in text              # periodic line fired
    assert "tok/s=" in text
    assert "ttft_ms p50=" in text and "tpot_ms" in text
    assert "requests=4" in text
    assert "engine_requests_total" in dump.read_text()
    events = (tmp_path / "metrics.prom.events.jsonl").read_text()
    assert all(json.loads(l)["event"] for l in events.splitlines())


# ---------------------------------------------------------------------------
# per-tenant accounting
# ---------------------------------------------------------------------------

def test_tenant_accounting_labels_flow_through_engine():
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine, Request
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    reg = MetricsRegistry()
    eng = Engine(cfg, params, batch_slots=4, max_len=64, metrics=reg)
    for i, ns in enumerate(["acme", "acme", "globex", ""]):
        eng.submit(Request(uid=i, prompt=np.arange(5, dtype=np.int32),
                           max_new=4, namespace=ns))
    eng.run()
    lab = {"engine": eng.engine_id}

    def by_tenant(name):
        c = reg.counter(name, "", ("engine", "tenant"))
        return {t: c.labels(**lab, tenant=t).value()
                for t in ("acme", "globex", "-")}

    reqs = by_tenant("tenant_requests_total")
    assert reqs == {"acme": 2, "globex": 1, "-": 1}   # "" renders as "-"
    dec = by_tenant("tenant_decode_tokens_total")
    assert dec["acme"] == 8 and dec["globex"] == 4 and dec["-"] == 4
    pre = by_tenant("tenant_prefill_tokens_total")
    assert sum(pre.values()) == reg.value_sum("engine_prefill_tokens_total")
    assert reg.value_sum("tenant_decode_tokens_total") == \
        reg.value_sum("engine_tokens_total")
    # pages all released after drain: every tenant gauge back at zero
    g = reg.gauge("tenant_pages_held", "", ("engine", "tenant"))
    for t in ("acme", "globex", "-"):
        assert g.labels(**lab, tenant=t).value() == 0


def test_tenant_namespaces_partition_prefix_cache():
    """Two tenants sending the IDENTICAL prompt must not share cached
    pages; two requests of one tenant must."""
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import ChunkConfig, Engine, PrefixConfig, Request
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    reg = MetricsRegistry()
    eng = Engine(cfg, params, batch_slots=2, max_len=64, metrics=reg,
                 prefix=PrefixConfig(chunk=ChunkConfig(chunk_tokens=16)))
    prompt = np.arange(20, dtype=np.int32)

    def serve_one(uid, ns):
        eng.submit(Request(uid=uid, prompt=prompt.copy(), max_new=2,
                           namespace=ns))
        eng.run()
        return reg.value_sum("prefix_hits_total")

    assert serve_one(0, "acme") == 0          # cold
    assert serve_one(1, "globex") == 0        # same tokens, other tenant
    assert serve_one(2, "acme") == 1          # same tenant: hits
    hits = reg.counter("prefix_tenant_hits_total", "",
                       ("engine", "tenant"))
    assert hits.labels(engine=eng.engine_id, tenant="acme").value() == 1
    assert hits.labels(engine=eng.engine_id, tenant="globex").value() == 0


# ---------------------------------------------------------------------------
# lint pin: the serving stack never prints directly
# ---------------------------------------------------------------------------

def test_no_bare_print_in_serving():
    """All human-facing serving output routes through obs.report.Reporter;
    a bare print() in the serving stack, the launchers, or the bench
    harness bypasses the registry and drifts from the metrics report."""
    repo = SRC.parent
    files = sorted((SRC / "repro" / "serving").rglob("*.py"))
    files.append(SRC / "repro" / "launch" / "serve.py")
    files.append(SRC / "repro" / "launch" / "dryrun.py")
    files.append(repo / "benchmarks" / "run.py")
    pat = re.compile(r"(?<![\w.])print\(")
    offenders = []
    for f in files:
        for i, line in enumerate(f.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(
                    f"{f.relative_to(repo)}:{i}: {line.strip()}")
    assert not offenders, "bare print() in the serving stack:\n" + \
        "\n".join(offenders)


def test_metric_name_table_in_readme_is_complete():
    """serving/README.md documents every metric series the stack
    registers. Registered names are collected statically (string-literal
    first argument of counter()/gauge()/histogram() calls under
    src/repro/serving and src/repro/obs), so adding a metric without
    documenting it fails this pin."""
    pat = re.compile(r'\.(?:counter|gauge|histogram)\(\s*"([a-z0-9_]+)"',
                     re.S)
    # registration through the local one-letter factory aliases some
    # modules bind (c = metrics.counter(...).labels(...), etc.)
    alias = re.compile(r'(?<![\w.])[cgh]\(\s*"([a-z0-9_]+)"', re.S)
    names = set()
    for root in (SRC / "repro" / "serving", SRC / "repro" / "obs"):
        for f in sorted(root.rglob("*.py")):
            text = f.read_text()
            names.update(pat.findall(text))
            names.update(alias.findall(text))
    assert len(names) > 20, "metric-name scrape came back implausibly thin"
    readme = (SRC / "repro" / "serving" / "README.md").read_text()
    missing = sorted(n for n in names if n not in readme)
    assert not missing, \
        "metrics registered but undocumented in serving/README.md: " + \
        ", ".join(missing)
