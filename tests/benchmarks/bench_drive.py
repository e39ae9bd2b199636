"""Drives the rest of a run past the harness's look for a chip, at toy
size on the CPU, and returns the result line."""

import bench_path  # noqa: F401  (repo root on sys.path)

import argparse
import gc
import json
import os
import time

SCRATCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "scratch_family")


def grown_manifest():
    """(manifest, entries): the committed manifest with the scratch
    family's entries appended, as a later PR would append its own: a
    configuration, two cells, a per-layer metric, and its cells' names on
    the end-to-end lists."""
    from benchmarks.harness import loader

    man = loader.manifest()
    with open(os.path.join(SCRATCH, "manifest_entries.json")) as f:
        entries = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        man[key] += entries[key]
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + entries["end_to_end"].get(m["name"], [])
    return man, entries


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
