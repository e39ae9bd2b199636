"""The timed training path broken underneath: ``correct`` comes out false
for a step that returns its state unchanged and for half of the batch
left out; the sound run comes out true."""

import bench_path  # noqa: F401  (repo root on sys.path)

import pytest

from bench_drive import drive

CELL = "googlenet_train"


def test_sound_run_is_correct(capsys):
    line = drive(CELL, capsys)
    assert line["correct"] is True, line["checks"]


def test_state_returned_unchanged_is_not_correct(capsys, monkeypatch):
    from npairloss_tpu.train.solver import Solver

    orig = Solver._train_step_body

    def broken(self):
        step = orig(self)

        def train_step(state, inputs, labels):
            new, metrics = step(state, inputs, labels)
            opt = new["opt"]._replace(momentum_buf=state["opt"].momentum_buf)
            return {**state, "opt": opt}, metrics  # the count moves, nothing else

        return train_step

    monkeypatch.setattr(Solver, "_train_step_body", broken)
    line = drive(CELL, capsys)
    assert line["correct"] is False
    # every leaf's change is nought: the worst leaf reads 1, the median
    # leaf (measured against the median norm where its own is smaller) about 1
    assert line["numbers"]["delta_gap"] == pytest.approx(1.0)
    assert line["checks"]["delta_gap_median"]["value"] > 0.9


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from benchmarks.harness import train_window

    orig = train_window.Feed.__next__

    def half(self):
        x, lab = orig(self)
        return x[: len(x) // 2], lab[: len(lab) // 2]

    monkeypatch.setattr(train_window.Feed, "__next__", half)
    line = drive(CELL, capsys)
    assert line["correct"] is False
