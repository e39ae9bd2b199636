"""Live observatory — the ONLINE half of the observability stack.

Everything under ``obs/`` so far (sinks/traces, perf reports, fleet
reports) is post-hoc: artifacts on disk, analyzed after the fact.  This
package closes the loop while the process is still running
(docs/OBSERVABILITY.md §Live observatory):

  * :mod:`registry`  — lock-guarded in-process metric registry
    (counters / gauges / fixed-bound histograms) fed by a
    ``MetricLogger``-protocol sink adapter, so the EXISTING telemetry
    streams flow in with zero new call sites;
  * :mod:`slo`       — declarative SLO specs (metric, target, rolling
    window, burn-rate threshold) loaded from JSON/TOML, evaluated
    incrementally over the registry's sample windows;
  * :mod:`alerts`    — severities, hysteresis/dedup, a firing→resolved
    lifecycle persisted as the versioned ``npairloss-alerts-v1`` JSONL
    contract (``validate_alert_log`` IS the contract, like the perf and
    fleet report validators);
  * :mod:`watchdogs` — domain SLOs wired to signals the repo already
    computes (serve p99 / queue saturation, post-warmup compiles, train
    throughput vs the committed BENCH bar, non-finite-loss streaks,
    fleet straggler lag, snapshot/index staleness, embedding collapse);
  * :mod:`export`    — Prometheus text exposition (``/metrics``) and
    the localhost HTTP exporter the train side mounts;
  * :mod:`watch`     — the OFFLINE feed: tail a run directory's
    telemetry JSONL (per-rank files included) through the SAME
    evaluator — one engine, two feeds.

IMPORTANT: this whole package must stay importable WITHOUT jax (stdlib
only) — ``watch`` runs backend-free, and ``scripts/bench_check.py
--alerts`` file-path-loads the alert validator from a jax-free process
(the jax-free-parent contract).
"""

from npairloss_tpu.obs.live.alerts import (
    ALERTS_SCHEMA,
    Alert,
    AlertEngine,
    load_alert_log,
    unresolved_alerts,
    validate_alert_log,
)
from npairloss_tpu.obs.live.live import LiveObservatory
from npairloss_tpu.obs.live.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    RegistrySink,
)
from npairloss_tpu.obs.live.slo import (
    SLOSpec,
    SLOStatus,
    SLOEvaluator,
    load_slo_config,
)
from npairloss_tpu.obs.live.watchdogs import default_watchdogs
from npairloss_tpu.obs.live.export import prometheus_text, start_http_exporter
from npairloss_tpu.obs.live.watch import (
    reconcile_remediation,
    replay_records,
    watch_run_dir,
)

__all__ = [
    "ALERTS_SCHEMA",
    "Alert",
    "AlertEngine",
    "Counter",
    "Gauge",
    "Histogram",
    "LiveObservatory",
    "MetricRegistry",
    "RegistrySink",
    "SLOEvaluator",
    "SLOSpec",
    "SLOStatus",
    "default_watchdogs",
    "load_alert_log",
    "load_slo_config",
    "prometheus_text",
    "reconcile_remediation",
    "replay_records",
    "start_http_exporter",
    "unresolved_alerts",
    "validate_alert_log",
    "watch_run_dir",
]
