"""Perf observatory (docs/OBSERVABILITY.md §Perf observatory).

The static half of performance attribution: what a step COSTS (FLOPs,
bytes, collective bytes per ``named_scope`` region, from the compiled
HLO) and where the host's spans spend the wall clock — recoverable
from artifacts the host already has, without a device trace.  Device
time itself comes from a ``jax.profiler`` trace taken on the chip:

  * ``perf.costs`` — THE shared cost-analysis/MFU helper (every
    ``mfu`` number in the repo routes through here);
  * ``perf.hlo`` — per-``jax.named_scope``-region FLOPs / bytes /
    collective-bytes attribution parsed from compiled HLO text;
  * ``perf.roofline`` — chip peak specs + compute/memory/collective
    bound classification with arithmetic intensity;
  * ``perf.decompose`` — step-time and serve-latency decomposition
    from the obs.tracing span streams, wall-reconciled;
  * ``perf.report`` — the versioned ``prof`` report artifact
    (schema, validator, renderers).

All modules are stdlib-only; jax-free processes load the ones they
need by file path.
Entry points: ``python -m npairloss_tpu prof --step train|serve`` and
``scripts/bench_check.py``.
"""

from npairloss_tpu.obs.perf.costs import (
    PEAK_FLOPS,
    cost_analysis_dict,
    cost_bytes,
    cost_flops,
    mfu_from_timing,
    peak_flops,
)
from npairloss_tpu.obs.perf.decompose import (
    SERVE_CATEGORIES,
    STEP_CATEGORIES,
    decompose_step_time,
    serve_latency_decomposition,
)
from npairloss_tpu.obs.perf.hlo import (
    UNSCOPED,
    attribute_regions,
    region_of,
    stage_hlo_text,
)
from npairloss_tpu.obs.perf.report import (
    REPORT_SCHEMA,
    build_report,
    render_table,
    validate_report,
    write_report,
)
from npairloss_tpu.obs.perf.roofline import (
    BOUND_CLASSES,
    ChipSpec,
    chip_peaks,
    classify,
)

__all__ = [
    "PEAK_FLOPS",
    "cost_analysis_dict",
    "cost_bytes",
    "cost_flops",
    "mfu_from_timing",
    "peak_flops",
    "STEP_CATEGORIES",
    "SERVE_CATEGORIES",
    "decompose_step_time",
    "serve_latency_decomposition",
    "UNSCOPED",
    "attribute_regions",
    "region_of",
    "stage_hlo_text",
    "REPORT_SCHEMA",
    "build_report",
    "render_table",
    "validate_report",
    "write_report",
    "BOUND_CLASSES",
    "ChipSpec",
    "chip_peaks",
    "classify",
]
