"""Sync-free stepping — the async execution pipeline (docs/PIPELINE.md).

The reference pays device<->host round-trips between every stage of
every step (npair_multi_class_loss.cu:222-337 runs mining on the host);
the transplant's synchronous loop still blocks on host work each
iteration: batches arrive as NumPy and transfer at dispatch, and any
per-step scalar read (telemetry, the divergence guard) stalls the
dispatch pipeline.  This package removes the steady-state host taxes:

  * :class:`DevicePrefetcher` — a staging thread that ``jax.device_put``s
    loader batches onto the mesh with the step's input sharding ahead of
    need, so the jitted step consumes already-resident, donated buffers;
  * :class:`DispatchController` — a semaphore on in-flight dispatched
    steps, so async dispatch cannot queue unboundedly (every queued
    step pins its donated inputs) when the device falls behind;
  * :class:`MetricWindow` — a device-side metric ring written inside the
    jitted step (plus an in-graph consecutive-non-finite loss counter),
    read back by the host only at display/eval/snapshot window
    boundaries;
  * :func:`enable_compile_cache` — the persistent XLA compilation cache
    at ``JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache/``, so no
    process recompiles a program another process already compiled;
  * :class:`HostSyncMonitor` — a counting ``device_put``/``device_get``
    shim that proves (or enforces) the no-mid-window-host-sync contract.

The Solver wires these together behind ``SolverConfig.pipeline``
(CLI ``--pipeline``), default OFF; the pipelined loop is parity-pinned
bit-identical to the synchronous one (tests/test_pipeline.py).
"""

from npairloss_tpu.pipeline.compile_cache import (
    CacheCounter,
    cache_entries,
    compile_cache_dir,
    enable_compile_cache,
)
from npairloss_tpu.pipeline.controller import DispatchController
from npairloss_tpu.pipeline.prefetcher import (
    DevicePrefetcher,
    PrefetchStageError,
)
from npairloss_tpu.pipeline.syncguard import (
    HostSyncMonitor,
    SyncGuardViolation,
    monitor_from_env,
)
from npairloss_tpu.pipeline.window import MetricWindow

__all__ = [
    "CacheCounter",
    "DevicePrefetcher",
    "DispatchController",
    "HostSyncMonitor",
    "MetricWindow",
    "PrefetchStageError",
    "SyncGuardViolation",
    "cache_entries",
    "compile_cache_dir",
    "enable_compile_cache",
    "monitor_from_env",
]
