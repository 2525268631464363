"""repro.obs — structured tracing, metrics, and trace export for the
sweep/serving hot paths.

Quick start::

    from repro.obs import Tracer, build_sweep_report, write_chrome_trace

    with Tracer(jsonl_path="out/events.jsonl") as tr:
        front = pareto_front_streaming(w, space, shards=4, telemetry=tr)
        print(build_sweep_report(tr).render())
        write_chrome_trace("out/trace.json", tr)   # open in Perfetto

Every ``telemetry=`` knob defaults to ``None`` (the no-op
``NULL_TRACER``), so uninstrumented sweeps pay nothing.
"""

from repro.obs.tracer import (
    MAX_EVENTS,
    MAX_SAMPLES,
    RSS_INTERVAL_S,
    TOP_LEVEL_COUNTER,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_TRACER,
    NullTracer,
    TraceEvent,
    Tracer,
    as_tracer,
    rss_mb,
    timed_iter,
)
from repro.obs.export import chrome_trace, trace_lanes, write_chrome_trace
from repro.obs.report import (
    SweepReport,
    build_sweep_report,
    load_sweep_report,
    render_sweep_report,
    write_sweep_report,
)

__all__ = [
    "MAX_EVENTS",
    "MAX_SAMPLES",
    "RSS_INTERVAL_S",
    "TOP_LEVEL_COUNTER",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "TraceEvent",
    "Tracer",
    "as_tracer",
    "rss_mb",
    "timed_iter",
    "chrome_trace",
    "trace_lanes",
    "write_chrome_trace",
    "SweepReport",
    "build_sweep_report",
    "load_sweep_report",
    "render_sweep_report",
    "write_sweep_report",
]
