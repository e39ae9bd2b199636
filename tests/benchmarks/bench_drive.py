"""Drives the rest of a run past the harness's look for a chip, at toy
size on the CPU, and returns the result line."""

import bench_path  # noqa: F401  (repo root on sys.path)

import argparse
import gc
import json
import time


def toy_cell(name):
    from benchmarks.harness import loader

    cell = loader.Cell(name)
    cell.config.update(cell.config.get("rehearsal", {}))
    cell.traffic.update(cell.traffic.get("rehearsal", {}))
    return cell


def drive(name, capsys, seed=2**31 + 5, seconds=0.5):
    import jax

    from benchmarks.harness import run_serve, run_train

    cell = toy_cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=0,
                              cpu_rehearsal=True)
    runner = run_train if cell.traffic["kind"] == "train" else run_serve
    capsys.readouterr()
    try:
        runner.run(cell, jax.devices()[:cell.chips], args, time.perf_counter())
    finally:
        gc.unfreeze()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
