"""The trace reduction on a small recorded trace kept in the repo."""

import bench_path  # noqa: F401  (repo root on sys.path)

import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
GROUPS = {"npair": ["npair/"], "comm": ["comm/"], "optim": ["optim/"]}


@pytest.fixture(scope="module")
def trace():
    raw = json.load(open(os.path.join(HERE, "data", "small_trace.json")))
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_union_merges_nested_and_adjacent():
    assert tr.union([(0, 10), (5, 8), (10, 12), (20, 30)]) == [(0, 12), (20, 30)]


def test_region_drops_wrappers_and_primitive():
    assert tr.region_of("jit(s)/jit(main)/transpose(jvp(npair/sim))/dot_general") == "npair/sim"
    assert tr.region_of("jit(s)/jit(main)/jvp(Net)/conv1/Conv_0/conv") == "Net/conv1/Conv_0"
    assert tr.region_of("jit(s)/jit(main)/mul") == ""
    assert tr.region_of("") == ""


def test_leaf_events_drop_the_container(trace):
    names = [e[0] for e in tr.leaf_events(trace["devices"][0])]
    assert "while.3" not in names and "fusion.7" in names and "fusion.8" in names


def test_busy_idle_union(trace):
    red = tr.reduce(trace, GROUPS)
    # device 0: [100,600) + [700,900) + [950,1000) clipped = 750 us of 1000
    assert red["window_s"] == pytest.approx(1e-3)
    assert red["busy_s_fullest"] == pytest.approx(750e-6)
    assert red["idle_share"] == pytest.approx(0.25)
    # the mean over both devices is what the result line's busy_s carries
    assert red["busy_s"] == pytest.approx((750e-6 + 100e-6) / 2)


def test_time_by_scope(trace):
    g = tr.reduce(trace, GROUPS)["group_s"]
    assert g["npair"] == pytest.approx(250e-6)   # body 100 + sim 150, not the while
    assert g["comm"] == pytest.approx(100e-6)
    assert g["optim"] == pytest.approx(100e-6)
    assert g["rest"] == pytest.approx(250e-6)    # conv 200 + unnamed 50 (clipped)


def test_exposed_collective_time(trace):
    assert tr.reduce(trace, GROUPS)["collective_s"] == pytest.approx(100e-6)


def test_gap_attribution(trace):
    gaps = dict(tr.reduce(trace, GROUPS)["breakdown"]["idle_gaps"])
    assert gaps["bench/next_batch"] == pytest.approx(100e-6)   # [0,100)
    assert gaps["bench/wait_answer"] == pytest.approx(100e-6)  # [600,700)
    assert gaps["unannotated"] == pytest.approx(50e-6)         # [900,950)


def test_breakdown_names_and_sizes(trace):
    b = tr.reduce(trace, GROUPS)["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == ["conv1/Conv_0/fusion.1", pytest.approx(200e-6)]


def test_short_name_of_an_hlo_instruction():
    assert tr.short_name("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.7"
    assert tr.short_name("bench/window") == "bench/window"


def test_recorded_slice_of_a_training_step():
    """A slice of a real GoogLeNet step recorded on the v5e: the loss's
    ``npair/*`` regions are found between the trunk's forward and
    backward, every instant is counted once, and the groups add up."""
    raw = json.load(open(os.path.join(HERE, "data", "recorded_step_slice.json")))
    trace = {"devices": {0: [tuple(e) for e in raw["devices"]["0"]]},
             "host": [tuple(h) for h in raw["host"]]}
    red = tr.reduce(trace, GROUPS)
    regions = {tr.region_of(e[3]) for e in trace["devices"][0]}
    assert {"npair/sim", "npair/mine", "npair/select", "npair/loss"} <= regions
    g = red["group_s"]
    assert 50e-6 < g["npair"] < 200e-6 and g["rest"] > 10 * g["npair"]
    assert sum(g.values()) == pytest.approx(red["busy_s"], rel=0.02)
    assert 0.0 <= red["idle_share"] < 0.05
    assert red["breakdown"]["device_ops"][0][0].startswith("GoogLeNetEmbedding/")


def test_no_device_plane_reads_nothing():
    assert tr.reduce({"devices": {}, "host": []}, GROUPS) is None
