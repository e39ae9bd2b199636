"""The chip the run is on: refuse to measure without one."""

from __future__ import annotations

import sys


def require_devices(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal:
        if len(devices) < chips:
            sys.exit(f"rehearsal needs {chips} devices, found {len(devices)} "
                     "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        return devices[:chips]
    if platform != "tpu":
        sys.exit(f"no accelerator: JAX reports platform {platform!r}; the "
                 "benchmark measures on a TPU only (--cpu-rehearsal for a toy run)")
    if len(devices) < chips:
        sys.exit(f"cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_report(devices) -> dict:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the peak on
    the fullest chip: the allocator's ``peak_bytes_in_use`` (live arrays)
    plus ``peak_bytes_reserved`` (the space the runtime sets aside for
    the loaded programs' temporaries, which on this TPU runtime is NOT
    inside ``peak_bytes_in_use``: a batch-480 GoogLeNet step reads 2.0 GB
    in use beside 9.1 GB reserved, its compiled temp size)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
