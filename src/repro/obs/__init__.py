"""Serving observability: a labelled metrics registry, per-request
lifecycle traces, kernel profiling hooks, a live embedding-quality
probe, and the launcher's reporter.

The registry (``obs.metrics``) is the single source of truth for every
counter the serving stack used to keep in ad-hoc ``stats`` dicts —
those dicts survive as :class:`~repro.obs.metrics.StatsView` compat
views reading straight from the registry. Traces (``obs.trace``) stamp
each request's queued → admitted → prefill → first-token → decode →
done lifecycle (plus preemption / restore / migration events) and
derive TTFT / TPOT / queue-time / e2e latencies. ``obs.profiling``
annotates kernel dispatches with ``jax.named_scope``. ``obs.quality`` samples
the paper's row-statistics (Def. 1 calibration) from live serving
params. ``obs.report`` owns all human-facing printing for the serving
launcher. ``obs.spans`` records ring-buffered begin/end span timelines
over the serving hot path and ``obs.export`` renders them as
Chrome-trace JSON that Perfetto loads directly. Enabled spans also land on
a running ``jax.profiler`` trace's clock, and ``obs.devtrace`` reads such
a trace against them: device time by named scope, idle gaps by span.
"""
from .metrics import (Counter, Gauge, Histogram,        # noqa: F401
                      MetricsRegistry, StatsView)
from .trace import Trace, latency_summary, percentiles  # noqa: F401
from .profiling import annotate, dispatch               # noqa: F401
from .spans import Span, SpanRecorder                   # noqa: F401
from .export import chrome_trace, dump_chrome_trace     # noqa: F401
