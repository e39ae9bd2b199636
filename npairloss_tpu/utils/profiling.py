"""Tracing and profiling (SURVEY.md §5.1).

The reference has no profiling — only commented-out LOG(INFO) wall-clock
probes around its MPI calls and kernels (reference:
npair_multi_class_loss.cu:423, cu:464-468, cu:199).  Here the stages of
the loss graph carry ``jax.named_scope`` annotations (visible in
XProf/Perfetto and in HLO op names), ``trace`` captures a device profile
for TensorBoard/XProf, and ``StepTimer`` gives the wall-clock
steps/sec / embeddings/sec counters the reference never had.

This module is the DEVICE-side half of the observability story; the
HOST-side half (span tracing of data/dispatch/eval/snapshot/compile,
structured metric sinks, health signals) lives in ``npairloss_tpu.obs``
— see docs/OBSERVABILITY.md for when to reach for which.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Callable, Dict, Optional

import jax

# Stage annotation: ``with annotate("npair/sim"): ...`` names the ops
# traced inside it, so XProf timelines and HLO dumps show the pipeline
# stages (gather / sim / mine / select / loss) instead of a fused soup.
annotate = jax.named_scope


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_trace: bool = False):
    """Capture a device+host profile under ``logdir`` (XProf/TensorBoard
    format; optionally a Perfetto trace too).  Wrap a handful of
    training steps, not the whole run; only the process that holds the
    chip can trace it."""
    jax.profiler.start_trace(
        logdir, create_perfetto_trace=create_perfetto_trace
    )
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def time_scan(body, init_carry, *, steps: int = 10, warm: int = 2,
              repeats: int = 2, windows_out: list = None) -> float:
    """Time one computation as a ``lax.scan`` of ``steps`` iterations:
    host clock around a dispatch that ends in ``block_until_ready``.
    Returns milliseconds per iteration — the min over ``repeats`` timed
    windows; pass a list as ``windows_out`` to receive every window's
    ms/iter (artifact writers record these so the spread stays
    visible).

    ``body(carry, s) -> carry`` is the scan body; ``s`` is a float32
    that differs every iteration — fold it into the computation (e.g.
    perturb an input by ``s * 1e-6``) so iterations cannot be CSE'd,
    and accumulate something data-dependent into the carry so none can
    be elided.  The scan is jitted once and run ``warm`` times (compile
    + one-time backend setup) before the timed windows.
    """
    if steps < 1:
        raise ValueError(f"time_scan needs steps >= 1, got {steps}")
    if repeats < 1:
        raise ValueError(f"time_scan needs repeats >= 1, got {repeats}")
    import jax.numpy as jnp

    @jax.jit
    def many(c0):
        c, _ = jax.lax.scan(
            lambda c, s: (body(c, s), ()), c0,
            jnp.arange(steps, dtype=jnp.float32),
        )
        return c

    for _ in range(warm):
        jax.block_until_ready(many(init_carry))
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(init_carry))
        dt = time.perf_counter() - t0
        if windows_out is not None:
            windows_out.append(dt * 1e3 / steps)
        best = dt if best is None else min(best, dt)
    return best * 1e3 / steps


# Peak-FLOP table and cost analysis moved to their one home,
# obs.perf.costs (the perf observatory, docs/OBSERVABILITY.md); these
# re-exports keep the historical import path working.  The MFU
# computation itself is obs.perf.costs.mfu_from_timing — call that, do
# not re-derive flops/dt/peak by hand.
from npairloss_tpu.obs.perf.costs import (  # noqa: E402,F401  (re-export)
    PEAK_FLOPS,
    cost_flops,
    mfu_from_timing,
    peak_flops,
)


class StepTimer:
    """Sliding-window wall-clock throughput meter.

    ``tick(items)`` marks a step boundary and returns the current window
    stats; call with the per-step item count (e.g. batch size) to get
    items/sec (embeddings/sec for this framework's benchmarks).  The
    first tick only arms the timer.  Remember JAX dispatch is async —
    call ``jax.block_until_ready`` on a step output before the final
    tick, or wrap ticks around blocking points.

    ``emit`` (optional) receives each tick's stats dict — pass e.g.
    ``lambda s: telemetry.log("throughput", step, s)`` to route the
    counters through the obs metric pipeline instead of scraping logs.
    """

    def __init__(self, window: int = 50,
                 emit: Optional[Callable[[Dict[str, float]], None]] = None):
        self._durations: collections.deque = collections.deque(maxlen=window)
        self._items: collections.deque = collections.deque(maxlen=window)
        self._last: Optional[float] = None
        self._emit = emit

    def tick(self, items: int = 0) -> Dict[str, float]:
        now = time.perf_counter()
        armed = self._last is not None
        if armed:
            self._durations.append(now - self._last)
            self._items.append(items)
        self._last = now
        stats = self.stats()
        if self._emit is not None and armed:
            self._emit(stats)
        return stats

    def stats(self) -> Dict[str, float]:
        if not self._durations:
            return {"steps_per_sec": 0.0, "items_per_sec": 0.0,
                    "mean_step_ms": 0.0}
        total = sum(self._durations)
        return {
            "steps_per_sec": len(self._durations) / total,
            "items_per_sec": sum(self._items) / total,
            "mean_step_ms": 1000.0 * total / len(self._durations),
        }

    def reset(self):
        self._durations.clear()
        self._items.clear()
        self._last = None
