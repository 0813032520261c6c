"""Telemetry subsystem: round-stats lane, trace spans, metrics, exporters.

Levels (``SimConfig.telemetry``):

  0  off — compiled round program identical to a telemetry-free build
  1  host — tracer spans + metrics registry / Prometheus snapshot
  2  full — additionally the in-program per-round stats lane and the
     per-round JSONL event log (``schema.LANE_FIELDS``)

The level is part of ``pipeline_key`` (program structure is static in
it); level 0 is bit-identical to not having telemetry at all, and the
lane at level 2 adds no collective — it is computed post-``psum`` and
fetched only at existing chunk boundaries.
"""
from .registry import Counter, CounterView, Gauge, Histogram, MetricsRegistry
from .schema import (COMPILE_COUNTERS, DISPATCH_KINDS, GUARD_COUNTERS,
                     LANE_FIELDS, LANE_WIDTH,
                     N_LANE_HOST, PIPELINE_COUNTERS, ROUND_EVENT_KEYS,
                     SPAN_NAMES)
from .session import TelemetrySession
from .trace import Tracer
from .export import JsonlWriter, dumps_event, write_prometheus

__all__ = [
    "Counter", "CounterView", "Gauge", "Histogram", "MetricsRegistry",
    "COMPILE_COUNTERS", "DISPATCH_KINDS", "GUARD_COUNTERS", "LANE_FIELDS",
    "LANE_WIDTH",
    "N_LANE_HOST", "PIPELINE_COUNTERS", "ROUND_EVENT_KEYS", "SPAN_NAMES",
    "TelemetrySession", "Tracer", "JsonlWriter", "dumps_event",
    "write_prometheus",
]
