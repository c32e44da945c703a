"""raft_tpu.obs — query-path observability: metrics registry, spans on
the profiler's clock (device-sync-aware when recorded), and
Perfetto/Chrome-trace export.

Facade re-exporting the pieces the instrumented layers use::

    from raft_tpu import obs

    with obs.span("ivf_pq.search", mode=mode) as sp:
        out = run(...)
        sp.sync(out)            # block_until_ready at span end
    if obs.is_enabled():
        obs.inc("ivf_pq.search.calls", mode=mode)

Recording is off by default; enable it with ``RAFT_TPU_OBS=1`` or
``obs.enable()``. Spans also annotate any ``jax.profiler`` session. See
``docs/observability.md`` for the metric/span taxonomy and
``tools/obs_report.py`` for the artifact summarizer.
"""
from raft_tpu.obs.export import (
    chrome_trace,
    load_trace,
    validate_trace,
    write_metrics_jsonl,
    write_trace,
)
from raft_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    disable,
    enable,
    inc,
    is_enabled,
    observe,
    registry,
    set_gauge,
)
from raft_tpu.obs.request import (
    NULL_SCOPE,
    current_trace,
    iter_trace_spans,
    new_trace_id,
    trace_scope,
)
from raft_tpu.obs import recorder, timeseries
from raft_tpu.obs.recorder import FlightRecorder, list_bundles, load_bundle
from raft_tpu.obs.slo import SLO, SloStatus, SloTracker
from raft_tpu.obs.spans import Span, active, span
from raft_tpu.obs.timeseries import (
    Anomaly,
    EwmaDetector,
    HistogramSeries,
    SeriesBank,
    TimeSeries,
    default_detectors,
)

__all__ = [
    "Anomaly",
    "DEFAULT_BUCKETS",
    "Counter",
    "EwmaDetector",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "HistogramSeries",
    "NULL_SCOPE",
    "Registry",
    "SLO",
    "SeriesBank",
    "SloStatus",
    "SloTracker",
    "Span",
    "TimeSeries",
    "active",
    "chrome_trace",
    "current_trace",
    "default_detectors",
    "disable",
    "enable",
    "inc",
    "is_enabled",
    "iter_trace_spans",
    "list_bundles",
    "load_bundle",
    "load_trace",
    "new_trace_id",
    "observe",
    "recorder",
    "registry",
    "set_gauge",
    "span",
    "timeseries",
    "trace_scope",
    "validate_trace",
    "write_metrics_jsonl",
    "write_trace",
]
