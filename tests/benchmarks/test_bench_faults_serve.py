"""Serving: the sound run is correct; an answer altered where it is
produced (rows shifted against their scores) is not, and neither is an
index probed less widely than the configuration states."""

import bench_path  # noqa: F401  (repo root on sys.path)

import argparse
import gc
import json
import time

import jax
import numpy as np
import pytest

from bench_drive import drive


@pytest.mark.parametrize("cell", ["googlenet_serve_flat_sat", "googlenet_serve_ivf_rate"])
def test_sound_run_is_correct(capsys, cell):
    line = drive(cell, capsys, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0


@pytest.mark.parametrize("cell", ["googlenet_serve_flat_sat", "googlenet_serve_ivf_rate"])
def test_altered_answer_is_not_correct(capsys, monkeypatch, cell):
    from npairloss_tpu.serve.engine import QueryEngine

    orig = QueryEngine.query

    def altered(self, *a, **kw):
        out = orig(self, *a, **kw)
        out["rows"] = (out["rows"] + 1) % self.index.size
        return out

    monkeypatch.setattr(QueryEngine, "query", altered)
    line = drive(cell, capsys, seconds=1.0)
    assert line["correct"] is False
    assert line["checks"]["score_gap"]["ok"] is False


def test_fewer_probes_than_stated_is_not_correct(capsys, monkeypatch):
    """The IVF cell states 32 probes (16 of 16 clusters at toy size); an
    engine that reads one cluster returns true rows with their true
    scores, in order, and misses the reference's best."""
    import dataclasses

    from npairloss_tpu.serve.engine import QueryEngine

    orig = QueryEngine.__init__

    def one_probe(self, index, cfg, *a, **kw):
        orig(self, index, dataclasses.replace(cfg, probes=1), *a, **kw)

    monkeypatch.setattr(QueryEngine, "__init__", one_probe)
    line = drive("googlenet_serve_ivf_rate", capsys, seconds=1.0)
    assert line["correct"] is False
    assert line["checks"]["score_gap"]["ok"] is True
    assert line["checks"]["recall_miss"]["ok"] is False


def _stalled_run(capsys, monkeypatch, max_queue, stall_s=2.0, seconds=3.0):
    """``googlenet_serve_ivf_rate`` at rehearsal size but at the mix's OWN
    rate, with the dispatcher stopped for ``stall_s`` on its first turn
    (the program's ``serve.queue_stall`` failpoint, its length patched):
    what a stop of the whole machine does to the queue."""
    from npairloss_tpu.resilience import failpoints

    from bench_drive import toy_cell
    from benchmarks.harness import loader, run_serve

    cell = toy_cell("googlenet_serve_ivf_rate")
    mix = loader.Cell("googlenet_serve_ivf_rate").traffic
    cell.traffic.update(rate_qps=mix["rate_qps"],
                        batcher=dict(mix["batcher"], max_queue=max_queue))
    assert stall_s * cell.traffic["rate_qps"] > 256  # more arrive than the cell's queue holds
    monkeypatch.setattr(failpoints, "SERVE_QUEUE_STALL_S", stall_s)
    args = argparse.Namespace(workload=cell.name, seed=2**31 + 9, seconds=seconds, trace=0,
                              cpu_rehearsal=True)
    capsys.readouterr()
    failpoints.arm("serve.queue_stall", times=1)
    try:
        run_serve.run(cell, jax.devices()[:1], args, time.perf_counter())
    finally:
        failpoints.reset()
        gc.unfreeze()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_stop_longer_than_the_queue_refuses_requests_and_is_not_correct(capsys, monkeypatch):
    """What ``poisson_0p8knee``'s 256 queue slots hold the served system
    to: 1.68 s of arrivals at 152 q/s.  A 2 s stop of the dispatcher (304
    arrivals), the program's own or the machine's, refuses requests and the
    run is not ``correct``, whatever the answers say.  A demonstration
    beside it, not the cell's setting: with 1,024 slots the same stop
    refuses nothing and shows only as a tail (``serve_p95_ms`` is what the
    per-layer ``rate_p95_ms`` reads), which no bound holds."""
    from benchmarks.harness import loader

    mix = loader.Cell("googlenet_serve_ivf_rate").traffic
    assert mix["batcher"]["max_queue"] < 2.0 * mix["rate_qps"]
    held = _stalled_run(capsys, monkeypatch, mix["batcher"]["max_queue"])
    assert held["numbers"]["refused"] > 0 and held["correct"] is False
    assert held["failed"] == held["numbers"]["refused"]
    quiet = drive("googlenet_serve_ivf_rate", capsys, seed=2**31 + 9, seconds=1.0)
    late = _stalled_run(capsys, monkeypatch, 1024)
    assert late["numbers"]["refused"] == 0.0 and late["failed"] == 0
    assert late["correct"] is True, late["checks"]
    assert late["attempted"] > 400
    # two thirds of the requests were due while the dispatcher slept its 2 s
    assert late["host_clock"]["serve_p95_ms"] > 1000.0
    assert quiet["host_clock"]["serve_p95_ms"] < 0.5 * late["host_clock"]["serve_p95_ms"]
