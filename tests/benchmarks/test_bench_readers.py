"""The readers on a made-up context: device metrics take their counts
from the traced window, host-clock metrics from the untraced one, and a
reader that finds nothing to read returns nothing (never 0)."""

import bench_path  # noqa: F401  (repo root on sys.path)

import types

import pytest

from benchmarks.harness import loader, peaks

PEAKS = peaks.peaks_for("TPU v5 lite")


def _ctx():
    cell = types.SimpleNamespace(chips=1, config={})
    return {
        "cell": cell, "peaks": PEAKS,
        "window": {"seconds": 20.0, "steps": 400},
        "serve": {"rows_per_batch": 1.2, "required_flops": 197e12, "p95_ms": 50.0},
        "traced": {"window": {"seconds": 4.0, "steps": 80}, "batches": 80,
                   "required_flops": 19.7e12, "search_flops": 1.0,
                   "search_bytes": 819e9 * 0.5},
        "trace": {"group_s": {"encode": 0.04, "probe_fused": 1.0, "rest": 0.0},
                  "busy_s_fullest": 0.5, "idle_share": 0.875},
    }


@pytest.mark.parametrize("reader,args,want", [
    ("scope_time", {"group": "encode", "per": "batch"}, 0.5),    # 40 ms over the slice's 80
    ("scope_time", {"group": "rest", "per": "step"}, None),      # nothing ran there
    ("scope_time", {"group": "absent", "per": "step"}, None),
    ("roofline", {"groups": ["probe_fused"]}, 50.0),             # 0.5 s of bytes over 1.0 s
    ("roofline", {"groups": ["rest"]}, None),
    ("step_mfu", {"kind": "serve_window"}, 5.0),                 # 197e12 over 20 s of peak
    ("step_mfu", {"kind": "serve_busy"}, 20.0),                  # the slice's work over 0.5 busy s
    ("idle_share", {}, 0.875),
    ("counter", {"key": "serve.p95_ms"}, 50.0),
    ("counter", {"key": "serve.absent"}, None),
])
def test_reader(reader, args, want):
    got = loader.reader(reader)(_ctx(), **args)
    assert got == (pytest.approx(want) if want is not None else None)


def test_device_readers_return_nothing_without_a_trace():
    ctx = dict(_ctx(), trace=None)
    for reader, args in (("scope_time", {"group": "encode", "per": "batch"}),
                         ("roofline", {"groups": ["probe_fused"]}),
                         ("step_mfu", {"kind": "serve_busy"}), ("idle_share", {})):
        assert loader.reader(reader)(ctx, **args) is None
