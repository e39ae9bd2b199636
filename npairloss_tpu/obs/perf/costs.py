"""The ONE cost-analysis / MFU helper (docs/OBSERVABILITY.md §Perf).

The single home of XLA ``cost_analysis`` -> FLOPs -> MFU (``cli.py
cmd_time``, the ``prof`` report, ``Solver`` under ``perf_metrics``);
``utils.profiling`` re-exports the names, and every producer of an
``mfu`` number in the package goes through :func:`mfu_from_timing`.

Stdlib-only: the "stage" arguments are duck-typed
``jax.stages.Lowered``/``Compiled`` objects (anything with a
``cost_analysis()`` method), so jax-free processes can load this module
by file path like ``obs.sinks``.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

log = logging.getLogger("npairloss_tpu.perf")

# Peak dense bf16 FLOP/s per chip by device_kind substring (public
# specs); used only for MFU / roofline estimates.  Ordered: first match
# wins, so the more specific keys come first.
PEAK_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a device kind, or None if unknown."""
    kind = device_kind.lower()
    for key, peak in PEAK_FLOPS:
        if key in kind:
            return peak
    return None


def cost_analysis_dict(stage) -> Optional[Dict[str, float]]:
    """``stage.cost_analysis()`` normalized to one flat float dict.

    Accepts a ``jax.stages.Lowered`` (client-side analysis, no device
    compile — what the CLI ``time`` command uses, so the timed program
    is the only one the backend compiles) or a ``Compiled``.
    Non-numeric values are dropped; any failure (backends without
    analysis, empty modules) degrades to None, never raises.
    """
    try:
        cost = stage.cost_analysis()
        out = {}
        for k, v in dict(cost).items():
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                continue
        return out
    except Exception as e:  # noqa: BLE001 — analysis is best-effort
        log.debug("cost_analysis failed: %s", e)
        return None


def cost_flops(stage) -> Optional[float]:
    """XLA's analytic FLOPs for a lowered or compiled program, or None."""
    cost = cost_analysis_dict(stage)
    if cost is None:
        return None
    f = cost.get("flops", 0.0)
    return f if f > 0 else None


def cost_bytes(stage) -> Optional[float]:
    """XLA's analytic bytes-accessed estimate, or None."""
    cost = cost_analysis_dict(stage)
    if cost is None:
        return None
    b = cost.get("bytes accessed", 0.0)
    return b if b > 0 else None


def mfu_from_timing(
    stage=None,
    *,
    seconds: float,
    steps: int = 1,
    device_kind: str = "",
    flops: Optional[float] = None,
) -> Dict[str, Any]:
    """The one MFU computation: ``flops_per_step * steps / seconds``
    against the chip's peak.

    ``stage`` (lowered/compiled) supplies the per-step FLOPs unless
    ``flops`` is given explicitly; ``seconds`` is the wall time of
    ``steps`` steps.  Returns ``{"step_flops": float|None,
    "mfu": float|None}`` — keys are always present, values None when
    the estimate is unavailable (no cost analysis / unknown chip /
    non-positive timing), so call sites stay branch-free.
    """
    if flops is None and stage is not None:
        flops = cost_flops(stage)
    mfu = None
    peak = peak_flops(device_kind) if device_kind else None
    if flops and peak and seconds > 0 and steps > 0:
        mfu = (flops * steps / seconds) / peak
    return {"step_flops": flops, "mfu": mfu}
