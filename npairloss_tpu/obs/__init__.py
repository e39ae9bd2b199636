"""Run-telemetry subsystem (docs/OBSERVABILITY.md).

Three coordinated parts:

  * ``obs.sinks`` — structured metric sinks (JSONL / CSV / ring buffer /
    multiplex) behind the ``MetricLogger`` protocol;
  * ``obs.tracing`` — host-side hierarchical span tracing to
    Chrome-trace JSON (Perfetto), complementing device-side
    ``jax.named_scope`` / ``utils.profiling.trace``;
  * ``obs.health`` — optional jit-compatible training-health signals
    (grad/param/update norms, embedding magnitude, mined-pair hardness)
    gated by ``HealthConfig``;
  * ``obs.fleet`` — the multi-rank layer: rank-stamped telemetry with
    per-rank file streams, collective/comms attribution, offline
    straggler/skew aggregation (``prof --fleet``), and merged
    cross-rank Perfetto timelines;
  * ``obs.live`` — the ONLINE layer (§Live observatory): in-process
    metric registry fed by the telemetry streams, declarative SLOs
    with burn-rate alerting, Prometheus ``/metrics``, and per-answer
    freshness — imported explicitly (``npairloss_tpu.obs.live``), not
    re-exported here, so the no-live-obs path pays nothing;

tied together per run by ``obs.run.RunTelemetry`` (run dir with
``manifest.json`` + ``metrics.jsonl`` + ``trace.json``).

``obs.sinks`` and ``obs.tracing`` are stdlib-only modules; jax-free
processes (the offline gates) load them by file path to avoid this
package's jax-importing ``__init__``.
"""

from npairloss_tpu.obs.fleet.stamp import FleetStamp, fleet_stamp
from npairloss_tpu.obs.health import HealthConfig
from npairloss_tpu.obs.manifest import RunManifest
from npairloss_tpu.obs.run import RunTelemetry
from npairloss_tpu.obs.sinks import (
    FLEET_KEYS,
    REQUIRED_KEYS,
    CsvSink,
    JsonlSink,
    MetricLogger,
    MultiSink,
    RingBufferSink,
)
from npairloss_tpu.obs.tracing import SpanTracer, validate_chrome_trace

__all__ = [
    "HealthConfig",
    "RunManifest",
    "RunTelemetry",
    "FleetStamp",
    "fleet_stamp",
    "MetricLogger",
    "JsonlSink",
    "CsvSink",
    "RingBufferSink",
    "MultiSink",
    "SpanTracer",
    "validate_chrome_trace",
    "REQUIRED_KEYS",
    "FLEET_KEYS",
]
