"""A ``--cpu-rehearsal`` run of each cell at toy size prints a well-formed
last line; without a chip the measuring path refuses."""

import bench_path  # noqa: F401  (repo root on sys.path)

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import loader

ROOT = loader.ROOT
CELLS = [w["name"] for w in loader.manifest()["workloads"]]


def _run(args, env_extra=None, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
                          capture_output=True, text=True, env=env, cwd=cwd, timeout=600)


@pytest.mark.parametrize("name,trace", [(c, t) for c in CELLS for t in (0, 1)])
def test_rehearsal_prints_a_well_formed_last_line(name, trace):
    p = _run(["--workload", name, "--seed", str(2**31 + 12345), "--seconds", "1",
              "--trace", str(trace), "--cpu-rehearsal"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0
    # a rehearsal never writes a CPU number under a device metric's name
    man = loader.manifest()
    counts_only = {m["name"] for m in man["per_layer"] if m["source"] == "program_counter"}
    assert set(line["metrics"]) <= counts_only
    for name_, c in line["checks"].items():
        assert set(c) == {"value", "limit", "ok"}
    # each number compared is also among the last lines of standard error
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def test_no_chip_no_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and not p.stdout.strip()
    assert "no accelerator" in p.stderr


def test_bare_checkout_refuses(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: exit non-zero,
    no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
                       capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip()
