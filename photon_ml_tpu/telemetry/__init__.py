"""Unified telemetry: spans, metrics, and pluggable sinks.

Public surface::

    from photon_ml_tpu import telemetry

    with telemetry.Telemetry(output_dir=out, logger=logger) as tel:
        with tel.span("run", driver="glm"):
            tel.event("checkpoint.save", path=p)
            tel.counter("solver_iterations").inc(12)

Library code that cannot be handed a hub uses :func:`current` — a
disabled no-op by default, the driver-installed hub inside a driver run.
``python -m photon_ml_tpu.telemetry --selfcheck`` exercises every sink
and validates the outputs (see __main__.py).

The LIVE ops plane (docs/telemetry.md "Live ops plane") composes on
top: :class:`TimeSeriesSampler` streams registry snapshots into
``metrics_ts.jsonl``, :class:`MetricsExporter` serves Prometheus text
exposition at ``/metrics`` (mount both with :func:`mount_ops_plane`
behind a ``--metrics-port`` flag), and the :class:`FlightRecorder`
ring dumps the last-N events on crash / watchdog-fatal / injected
chaos fault (:func:`dump_flight_recorder`).
"""

from photon_ml_tpu.telemetry.core import (  # noqa: F401
    NULL,
    TRACE_HEADER,
    Counter,
    Gauge,
    Histogram,
    LayerSpan,
    MetricsRegistry,
    Span,
    Telemetry,
    TraceContext,
    compile_records,
    current,
    dump_flight_recorder,
    json_safe,
    layer_span,
    layer_spans,
    set_current,
)
from photon_ml_tpu.telemetry.exporter import (  # noqa: F401
    MetricsExporter,
    OpsPlane,
    host_identity,
    mount_ops_plane,
    parse_prometheus_text,
    prometheus_text,
)
from photon_ml_tpu.telemetry.recorder import FlightRecorder  # noqa: F401
from photon_ml_tpu.telemetry.sinks import (  # noqa: F401
    ChromeTraceSink,
    JsonlSink,
    LoggerSummarySink,
    Sink,
)
from photon_ml_tpu.telemetry.timeseries import (  # noqa: F401
    TimeSeriesSampler,
    read_series,
)
from photon_ml_tpu.telemetry.fleet import (  # noqa: F401
    FleetAggregator,
    SloPolicy,
)
