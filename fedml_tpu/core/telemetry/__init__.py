"""Unified telemetry: spans, counters, histograms, Perfetto export.

See docs/observability.md for the span taxonomy and naming conventions.
Typical use::

    from fedml_tpu.core import telemetry as tel

    with tel.span("fedavg.round", round=3):
        ...
    tel.counter("comm.host_to_device_bytes").add(nbytes)
    tel.histogram("server.aggregate_seconds").observe(dt)
    tel.export_chrome_trace("/tmp/round.json")   # open in ui.perfetto.dev
"""

from .core import (
    Counter,
    Histogram,
    Telemetry,
    counter,
    disabled_span_overhead_ns,
    export_chrome_trace,
    get_telemetry,
    histogram,
    profiler_sink_overhead_ns,
    record_span,
    reset,
    set_enabled,
    snapshot,
    span,
    summary,
    timed,
)
from . import devperf
from . import sketches
from .devperf import CompiledProgramRegistry, HbmSampler
from .flight_recorder import FlightRecorder
from .fleet import FleetTelemetry
from .health import ClientHealth, HealthReport, HealthTracker
from .sketches import (
    CardinalitySketch,
    FleetSketches,
    QuantileSketch,
    TelemetryCardinalityBudget,
    TopK,
)
from .slo import SLOEngine, SLOSpec
from .statusz import StatuszServer
from .tsdb import TimeSeriesStore
from .jax_hooks import (
    D2H_BYTES,
    H2D_BYTES,
    compile_count,
    record_transfer,
    track_compiles,
)
from .trace_context import (
    RESERVED_TELEMETRY_KEY,
    TraceContext,
    activated,
    current,
    extract,
    inject,
    new_trace_id,
    set_current,
)

__all__ = [
    "Telemetry",
    "CompiledProgramRegistry",
    "Counter",
    "HbmSampler",
    "Histogram",
    "devperf",
    "sketches",
    "CardinalitySketch",
    "FleetSketches",
    "QuantileSketch",
    "TelemetryCardinalityBudget",
    "TopK",
    "FleetTelemetry",
    "FlightRecorder",
    "ClientHealth",
    "HealthReport",
    "HealthTracker",
    "StatuszServer",
    "TimeSeriesStore",
    "SLOSpec",
    "SLOEngine",
    "get_telemetry",
    "span",
    "timed",
    "record_span",
    "counter",
    "histogram",
    "snapshot",
    "summary",
    "export_chrome_trace",
    "set_enabled",
    "reset",
    "disabled_span_overhead_ns",
    "profiler_sink_overhead_ns",
    "track_compiles",
    "compile_count",
    "record_transfer",
    "H2D_BYTES",
    "D2H_BYTES",
    "TraceContext",
    "RESERVED_TELEMETRY_KEY",
    "new_trace_id",
    "current",
    "set_current",
    "activated",
    "inject",
    "extract",
]
