"""Serving: the sound run is correct; an answer altered where it is
produced (rows shifted against their scores) is not, and neither is an
index probed less widely than the configuration states."""

import bench_path  # noqa: F401  (repo root on sys.path)

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
