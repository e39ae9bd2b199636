"""``BENCHMARK.json`` against the contract's letter, and the promise that
a cell, a traffic mix, a per-layer metric and a configuration of another
family are added as files."""

import bench_path  # noqa: F401  (repo root on sys.path)

import json
import os
import re
import shutil
import sys

import pytest

from benchmarks.harness import loader

ROOT = loader.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return loader.manifest()


def test_keys_and_sizes(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= man["run_seconds"] <= 51 and isinstance(man["run_seconds"], int)
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in man["paths"])
    assert 1 <= len(man["workloads"]) <= 24 and 1 <= len(man["configs"]) <= 24


def test_names_units_and_text(man):
    every = man["configs"] + man["workloads"] + man["end_to_end"] + man["per_layer"]
    names = [e["name"] for e in every]
    assert len(set(names)) == len(names)
    for e in every:
        assert NAME.match(e["name"]), e["name"]
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for e in man["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= e["bound"] <= 0.1 and e["source"] in ("host_clock", "device_trace")
    for e in man["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in man["paths"])


def test_four_chip_share(man):
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 4)


def test_widths_are_never_reduced(man):
    for c in man["configs"]:
        for key in c["reduced"]:
            assert not re.search(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head)", key), key


def test_every_cell_reports_setup_another_metric_and_a_layer(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in man["workloads"]:
        cell = loader.Cell(w["name"], man)
        mine = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        assert cell.per_layer(), w["name"]


def test_each_metric_moves_something_its_cells_report(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        target = e2e[m["moves"]]
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert "workloads" not in target or w in target["workloads"], (m["name"], w)


def test_each_metric_has_its_reader_file_and_nothing_of_the_manifest(man):
    for m in man["per_layer"]:
        spec = loader.metric_spec(m["name"])
        assert set(spec) == {"reader", "args"}, m["name"]
        assert callable(loader.reader(spec["reader"]))


def test_config_and_traffic_files_state_what_they_must(man):
    for c in man["configs"]:
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert f["assumed"] and f["precision"]["control"]
    for w in man["workloads"]:
        mix = json.load(open(os.path.join(loader.BENCH_DIR, "traffic", w["traffic"] + ".json")))
        assert mix["limits"] and mix["who"]


def test_rate_file_carries_its_sweep():
    mix = json.load(open(os.path.join(loader.BENCH_DIR, "traffic", "poisson_0p8knee.json")))
    assert mix["knee_qps"] and mix["sweep_date"]
    assert mix["rate_qps"] == pytest.approx(mix["knee_factor"] * mix["knee_qps"], rel=0.02)


def test_a_cell_a_mix_and_a_metric_are_added_as_files(tmp_path, monkeypatch):
    """Copy the benchmark, add a scratch cell (one traffic file, one
    manifest entry) and a scratch metric (one reader file, one manifest
    entry), and load them: no file that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()}
    man = loader.manifest()
    b = root / "benchmarks"
    mix = json.load(open(b / "traffic" / "closed_64.json"))
    mix["callers"] = 16
    (b / "traffic" / "scratch_closed_16.json").write_text(json.dumps(mix))
    (b / "metrics" / "scratch_rows.json").write_text(json.dumps(
        {"reader": "counter", "args": {"key": "serve.rows_per_batch"}}))
    man["workloads"].append({"name": "scratch_cell", "config": "googlenet_v1",
                             "traffic": "scratch_closed_16", "chips": 1, "why": "scratch"})
    man["per_layer"].append({"name": "scratch_rows", "unit": "rows", "better": "higher",
                             "source": "program_counter", "layer": "batcher",
                             "moves": "serve_answers_per_s", "workloads": ["scratch_cell"]})
    for m in man["end_to_end"]:
        if m["name"] == "serve_answers_per_s":
            m["workloads"] = m["workloads"] + ["scratch_cell"]
    monkeypatch.setattr(loader, "BENCH_DIR", str(b))
    cell = loader.Cell("scratch_cell", man)
    assert cell.traffic["callers"] == 16
    assert "scratch_rows" in {m["name"] for m in cell.per_layer()}
    assert "serve_answers_per_s" in {m["name"] for m in cell.end_to_end()}
    spec = loader.metric_spec("scratch_rows")
    assert loader.reader(spec["reader"])({"serve": {"rows_per_batch": 3.5}}, **spec["args"]) == 3.5
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_configuration_is_added_as_files(tmp_path, monkeypatch, capsys):
    """Copy the benchmark and add a scratch family that is no image trunk
    (the program's ``mlp`` on a vector input): one adapter, one reference,
    one configuration, a train mix, a serve mix and their manifest
    entries.  Both cells run the whole rehearsal path to a well-formed
    last line with ``correct`` true, and no file that was there changed."""
    import benchmarks.adapters
    import benchmarks.readers
    import benchmarks.reference
    from bench_drive import SCRATCH, drive, grown_manifest

    root = tmp_path / "checkout"
    b = root / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    added = {b / os.path.relpath(os.path.join(d, f), SCRATCH)
             for d, _, fs in os.walk(SCRATCH) for f in fs}
    assert not added & set(before)
    shutil.copytree(SCRATCH, b, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "manifest_entries.json"))
    man, entries = grown_manifest()
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    monkeypatch.setattr(loader, "ROOT", str(root))
    monkeypatch.setattr(loader, "BENCH_DIR", str(b))
    # the copy's packages: a PR's new modules would sit beside the old ones
    monkeypatch.setattr(benchmarks.adapters, "__path__", [str(b / "adapters")])
    monkeypatch.setattr(benchmarks.reference, "__path__", [str(b / "reference")])
    monkeypatch.setattr(benchmarks.readers, "__path__", [str(b / "readers")])
    try:
        for w in entries["workloads"]:
            line = drive(w["name"], capsys)
            assert list(line)[-1] == "checks" and line["attempted"] > 0
            assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
            assert line["correct"] is True and line["failed"] == 0, line["checks"]
        for m in entries["per_layer"]:  # its metric file and its reader, found by name
            assert m["name"] in {x["name"] for x in loader.Cell(m["workloads"][0]).per_layer()}
            spec = loader.metric_spec(m["name"])
            assert loader.reader(spec["reader"])({"serve": {"in_window": 7}}, **spec["args"]) == 7
            assert loader.reader(spec["reader"])({}, **spec["args"]) is None
    finally:
        for name in ("benchmarks.adapters.scratch_mlp", "benchmarks.reference.scratch_mlp",
                     "benchmarks.readers.scratch_in_window"):
            sys.modules.pop(name, None)
    assert {p: p.read_bytes() for p in before} == before


def test_an_unlisted_cell_is_refused():
    with pytest.raises(SystemExit):
        loader.Cell("no_such_cell")
