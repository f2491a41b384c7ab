"""Telemetry plane for the online-RTRL runtime.

Three layers (see ROADMAP "telemetry plane"):

- `MetricPack` — in-jit metrics packing: all per-window scalars fused
  into the update chunk, one device->host readback, bit-identical chunk
  outputs (`repro.obs.metricpack`).
- `Registry` / `EventLog` — host-side counters, gauges, fixed-bucket
  histograms (interpolated p50/p95/p99), schema-versioned JSONL events,
  Prometheus text exposition (`repro.obs.registry`, `repro.obs.events`).
- `Tracer` — nested host spans (parent, self time, profiler clock) as
  `jax.profiler.TraceAnnotation`s, and the map from a compiled program's
  instructions to its named stages (`repro.obs.trace`).

`Telemetry` (`repro.obs.telemetry`) bundles the host-side layers behind
a facade with a no-op `null()` form, so the runtime instruments
unconditionally and the exporters cost nothing until `--metrics-dir`
turns them on.
"""
from repro.obs.cli import add_obs_args, finish_run, telemetry_from_args
from repro.obs.events import (KIND_FIELDS, SCHEMA_VERSION, EventLog,
                              SchemaError, read_events)
from repro.obs.metricpack import DEFAULT_FIELDS, MetricPack
from repro.obs.registry import (DEFAULT_LATENCY_BUCKETS_MS, Counter, Gauge,
                                Histogram, Registry)
from repro.obs.summary import format_summary, print_summary
from repro.obs.telemetry import Telemetry, git_sha
from repro.obs.trace import Tracer

__all__ = [
    "Counter", "DEFAULT_FIELDS", "DEFAULT_LATENCY_BUCKETS_MS", "EventLog",
    "Gauge", "Histogram", "KIND_FIELDS", "MetricPack", "Registry",
    "SCHEMA_VERSION", "SchemaError", "Telemetry", "Tracer", "add_obs_args",
    "finish_run", "format_summary", "git_sha", "print_summary",
    "read_events", "telemetry_from_args",
]
