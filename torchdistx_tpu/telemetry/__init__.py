"""Unified telemetry: spans, counters, gauges, and trace export.

The observability layer for the whole stack — the tape recorder
(``_tape.py``), materialization (``materialize.py``), the compilation
cache (``utils/compilation_cache.py``), and the training loop
(``parallel/fit.py``) all report through this module, and the benchmark
(``benchmarks/run.py``) reads its counters and spans.  See
``docs/observability.md`` for the span/counter catalog and export formats.

Quick start::

    from torchdistx_tpu import telemetry

    telemetry.configure(collect=True)          # in-memory collector
    # ... materialize / train ...
    telemetry.snapshot()                       # {"counters", "gauges", "spans"}

    # or from the environment, with a JSON-lines trace file:
    #   TDX_TELEMETRY=/tmp/trace.jsonl python train.py

Instrumenting your own code::

    with telemetry.span("my.phase", size=n):
        ...
    telemetry.counter("my.events").add()
    telemetry.gauge("my.rate").set(v)
"""

from ._core import (  # noqa: F401
    Histogram,
    Span,
    add_listener,
    on_reset,
    configure,
    counter,
    counters,
    drain,
    emit_counters,
    enabled,
    event,
    events_enabled,
    flight_dump,
    flight_records,
    gauge,
    gauges,
    histogram,
    histograms,
    registry_view,
    remove,
    remove_listener,
    reset,
    snapshot,
    span,
    start_span,
    tracing,
)

__all__ = [
    "Histogram",
    "Span",
    "add_listener",
    "on_reset",
    "configure",
    "counter",
    "counters",
    "drain",
    "emit_counters",
    "enabled",
    "event",
    "events_enabled",
    "flight_dump",
    "flight_records",
    "gauge",
    "gauges",
    "histogram",
    "histograms",
    "registry_view",
    "remove",
    "remove_listener",
    "reset",
    "snapshot",
    "span",
    "start_span",
    "tracing",
]
