"""Query observability: lifecycle tracing, per-fingerprint profiles,
Prometheus exposition, slow-query logging — and the live serving plane
(in-flight query table, HBM ledger, flight recorder).

The serving stack (admission, result cache, degradation ladder, breaker,
estimator) makes multi-stage decisions per query; this subsystem makes
every stage visible (docs/observability.md):

- `spans`     — the `QueryTrace` span model, contextvar activation, the
                bounded `TraceStore` behind ``/v1/trace/{qid}``,
                `timed_jit_call` per-rung compile timing, and cross-query
                flow links (Chrome-trace flow events);
- `profiles`  — `ProfileStore`: rolling per-fingerprint compile/exec/bytes
                profiles behind ``SHOW PROFILES``, persisted by the
                checkpoint subsystem;
- `prometheus`— text exposition of the MetricsRegistry for
                ``/v1/metrics?format=prometheus``;
- `slowlog`   — threshold-gated span-tree dumps of latency outliers;
- `live`      — `QueryRegistry`: the in-flight query table behind
                ``SHOW QUERIES`` / ``GET /v1/queries`` and the target of
                ``CANCEL QUERY``;
- `ledger`    — `DeviceLedger`: live HBM accounting (reservations,
                measured footprints, cache, at-rest tables vs. budget)
                as ``serving.ledger.*`` gauges;
- `flight`    — the always-on bounded flight recorder of structured
                engine events (``GET /v1/debug/events``), with a
                registered event vocabulary (self-lint DSQL501);
- `xla`       — the ONE JAX monitoring listener: every XLA compile and
                persistent-cache load as ``xla:lower`` / ``xla:compile``
                spans and ``xla.*_ms`` histograms (registered at import).
"""
from . import flight
from . import live
from . import xla
from .ledger import DeviceLedger
from .live import LiveQuery, QueryRegistry
from .profiles import ProfileStore
from .prometheus import CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE
from .prometheus import render_prometheus
from .slowlog import maybe_log_slow
from .spans import (
    QueryTrace,
    Span,
    TraceStore,
    activate,
    compile_sink,
    current_trace,
    detail,
    fetch,
    load_span,
    load_trace,
    merge_chrome_traces,
    stage,
    timed_jit_call,
    trace_event,
)

__all__ = [
    "DeviceLedger",
    "LiveQuery",
    "ProfileStore",
    "PROMETHEUS_CONTENT_TYPE",
    "QueryRegistry",
    "QueryTrace",
    "Span",
    "TraceStore",
    "activate",
    "compile_sink",
    "current_trace",
    "detail",
    "fetch",
    "flight",
    "live",
    "load_span",
    "load_trace",
    "maybe_log_slow",
    "merge_chrome_traces",
    "render_prometheus",
    "stage",
    "timed_jit_call",
    "trace_event",
    "xla",
]
