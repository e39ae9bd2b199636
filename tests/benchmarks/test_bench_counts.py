"""``counts.py`` and GoogLeNet's own count (beside its reference, asked
through its adapter) against hand-worked operations and bytes."""

import bench_path  # noqa: F401  (repo root on sys.path)

import json
import os

import pytest

from benchmarks.adapters import googlenet_v1 as adapter
from benchmarks.harness import counts, peaks
from benchmarks.reference import googlenet

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cfg(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")))


def test_googlenet_stem_and_total():
    # conv1: 112x112 outputs, 7x7x3 taps, 64 filters, 2 ops a multiply-add
    stem = 2 * 112 * 112 * 7 * 7 * 3 * 64
    assert stem == 236_027_904
    # conv2: 56x56, 1x1x64->64 then 3x3x64->192
    conv2 = 2 * 56 * 56 * (64 * 64 + 9 * 64 * 192)
    # inception 3a at 28x28 on 192 channels (Table 1: 64, 96/128, 16/32, 32)
    i3a = 2 * 28 * 28 * (192 * 64 + 192 * 96 + 9 * 96 * 128 + 192 * 16
                         + 25 * 16 * 32 + 192 * 32)
    total = googlenet.forward_flops(224, 3)
    assert total > stem + conv2 + i3a
    # the paper's ~1.5 billion multiply-adds
    assert total / 2 == pytest.approx(1.58e9, rel=0.02)
    assert total == 3_163_295_744
    assert adapter.forward_flops(_cfg("googlenet_v1")) == total


def test_train_is_three_forwards_plus_loss():
    cfg = _cfg("googlenet_v1")
    assert counts.train_flops_per_image(adapter, cfg, 480) == \
        3 * adapter.forward_flops(cfg) + 6 * 480 * 1024 == 9_492_836_352


def test_probe_and_scan_bytes():
    flops, nbytes = counts.probe_cost(batch=1, probes=32, cap=1000, dim=1024, clusters=0)
    assert nbytes == 32 * 1000 * 1024 * 4 and flops == 2 * 32 * 1000 * 1024
    flops, nbytes = counts.scan_cost(batch=32, rows=1_000_000, dim=1024)
    assert nbytes == 4_096_000_000 and flops == 2 * 32 * 1_000_000 * 1024


def test_roofline_says_which_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert counts.roofline_seconds(197e12, 1.0, p) == (pytest.approx(1.0), "compute")
    t, bound = counts.roofline_seconds(1.0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
