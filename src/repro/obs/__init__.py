"""Observability: the always-on tracing + metrics + SLO layer.

Every layer of the system - mining wavefront slices, serving join
levels and the escalation ladder, streaming refresh/reconcile phases,
cluster routing rounds - reports through this package:

* ``metrics``  - ``MetricsRegistry``: typed counters / gauges /
                 histograms under dotted namespaces, with cheap
                 ``snapshot()`` / ``delta()`` / explicit-only
                 ``reset()``.  ``BucketHistogram`` adds fixed
                 log-scale-bucket latency percentiles (p50/p95/p99
                 quantile bounds, constant memory) - the always-on
                 store behind every ``*_seconds`` metric.  The old
                 ad-hoc ``stats`` dicts are ``StatsView`` facades over
                 a registry, so counters survive component rebuilds
                 and BENCH artifacts export a ``metrics`` block that
                 ``scripts/check_bench.py`` gates on.
* ``trace``    - the span tracer: ``trace.span("serving.join",
                 cat="dispatch", cells=n)`` regions bucketed into
                 host / dispatch / cache, per-query and
                 per-wavefront trace ids threaded through
                 ``ClusterRouter.route -> ClusterHost.call ->
                 PatternServer -> kernel dispatch`` by contextvar.
                 Two sinks: an in-memory buffer (full ``enable()``,
                 or the production mode ``enable_sampling(rate,
                 latency_threshold=...)`` keeping a deterministic
                 fraction of root trees plus every tail-latency /
                 ``mark()``-ed anomalous root) with Chrome-trace JSON
                 + JSONL export, and - whenever a JAX profiler session
                 is running - ``jax.profiler.TraceAnnotation``s on the
                 profiler's host plane, on the device trace's clock.
                 Neither sink blocks on the device; with no session
                 and the buffer disabled a span is a shared no-op.
                 Results stay bit-identical in every mode
                 (property-tested).
* ``flight``   - ``FlightRecorder``: a ring buffer of the last N kept
                 query span-trees + prefix-scoped metric deltas,
                 dumped to JSONL on demand, on anomaly, or by the
                 watchdog on an SLO breach.
* ``export``   - ``prometheus_text()`` exposition of any registry +
                 the strict ``validate_exposition()`` grammar check
                 CI gates on, and ``MetricsExporter`` for periodic
                 JSONL snapshot shipping (injectable clock).
* ``slo``      - declarative ``SloRule``s (quantile / rate / gauge /
                 counter bounds) shared by the in-process
                 ``SloWatchdog`` (registry deltas, breach counter,
                 flight-recorder dumps) and the
                 ``trace_report --slo`` CI gate.

``scripts/trace_report.py`` renders a phase-attribution table (self
time per bucket, per subsystem, top spans), a percentile block from
the bucket histograms, and doubles as the CI tier-6 trace-schema +
SLO gate.
"""
from . import trace  # noqa: F401
from .export import (  # noqa: F401
    MetricsExporter,
    prometheus_text,
    validate_exposition,
)
from .flight import FlightRecorder  # noqa: F401
from .metrics import (  # noqa: F401
    BucketHistogram,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    StatsView,
    global_registry,
)
from .slo import (  # noqa: F401
    Breach,
    SloRule,
    SloWatchdog,
    evaluate,
    load_rules,
)
