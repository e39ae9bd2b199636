"""obs: structured metric sinks, span tracing, run telemetry, and the
in-graph training-health signals (docs/OBSERVABILITY.md)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from npairloss_tpu.obs import (
    REQUIRED_KEYS,
    CsvSink,
    HealthConfig,
    JsonlSink,
    MultiSink,
    RingBufferSink,
    RunTelemetry,
    SpanTracer,
    validate_chrome_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- sinks ----------------------------------------------------------------


def test_jsonl_sink_roundtrip(tmp_path):
    path = tmp_path / "m.jsonl"
    sink = JsonlSink(str(path))
    sink.log({"run_id": "r1", "step": 1, "wall_time": 1.5,
              "phase": "train", "loss": 0.25})
    sink.log({"run_id": "r1", "step": 2, "wall_time": 2.5,
              "phase": "train", "loss": 0.125})
    sink.close()
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(rows) == 2
    assert rows[1]["loss"] == 0.125
    for row in rows:
        for key in REQUIRED_KEYS:
            assert key in row, key


def test_jsonl_sink_appends_across_instances(tmp_path):
    path = str(tmp_path / "m.jsonl")
    for i in range(2):
        s = JsonlSink(path)
        s.log({"i": i})
        s.close()
    assert len(open(path).read().splitlines()) == 2


def test_csv_sink_fixed_header(tmp_path):
    path = tmp_path / "m.csv"
    sink = CsvSink(str(path))
    sink.log({"step": 1, "loss": 0.5})
    # Extra keys are dropped (CSV cannot grow columns), missing -> "".
    sink.log({"step": 2, "loss": 0.25, "extra": 9})
    sink.log({"step": 3})
    sink.close()
    lines = path.read_text().splitlines()
    assert lines[0] == "step,loss"
    assert lines[2] == "2,0.25"
    assert lines[3] == "3,"


def test_ring_buffer_eviction():
    ring = RingBufferSink(capacity=4)
    for i in range(10):
        ring.log({"step": i})
    recs = ring.records()
    assert [r["step"] for r in recs] == [6, 7, 8, 9]
    assert ring.latest()["step"] == 9
    assert ring.total_logged == 10
    with pytest.raises(ValueError):
        RingBufferSink(capacity=0)


def test_multiplex_fan_out():
    a, b = RingBufferSink(8), RingBufferSink(8)
    multi = MultiSink([a, b])
    multi.log({"step": 1})
    assert a.latest() == {"step": 1}
    assert b.latest() == {"step": 1}

    class Boom:
        def log(self, rec):
            raise RuntimeError("boom")

        def flush(self):
            pass

        def close(self):
            pass

    # A failing child must not starve its siblings of the record.
    multi = MultiSink([Boom(), a])
    with pytest.raises(RuntimeError):
        multi.log({"step": 2})
    assert a.latest() == {"step": 2}


# -- tracing --------------------------------------------------------------


def test_tracer_chrome_trace_schema(tmp_path):
    tr = SpanTracer()
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            pass
    tr.instant("marker", note="x")
    path = tr.write(str(tmp_path / "trace.json"))
    obj = json.load(open(path))
    assert validate_chrome_trace(obj) is None
    events = obj["traceEvents"]
    names = [e["name"] for e in events]
    assert {"outer", "inner", "marker"} <= set(names)
    outer = next(e for e in events if e["name"] == "outer")
    inner = next(e for e in events if e["name"] == "inner")
    # "X" complete events; the inner span nests inside the outer by
    # timestamp containment (the Chrome/Perfetto stacking rule).
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"kind": "test"}


def test_tracer_event_cap_is_recorded():
    tr = SpanTracer(max_events=2)
    for i in range(5):
        tr.instant(f"e{i}")
    obj = tr.to_chrome_trace()
    assert len(obj["traceEvents"]) == 2
    assert obj["otherData"]["dropped_events"] == 3
    assert validate_chrome_trace(obj) is None


def test_validate_chrome_trace_rejects_bad_shapes():
    assert validate_chrome_trace([]) is not None
    assert validate_chrome_trace({"traceEvents": [{}]}) is not None
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0}]}
    ) is not None  # X without dur


# -- run telemetry --------------------------------------------------------


def test_run_telemetry_dir_contract(tmp_path):
    run_dir = tmp_path / "run"
    with RunTelemetry(str(run_dir)) as tel:
        tel.write_manifest(config={"model": "mlp"}, extra={"note": "t"})
        with tel.span("step/dispatch", batch=4):
            pass
        tel.log("train", 1, {"loss": 0.5})
        tel.log("eval", 1, {"loss": 0.4}, eval_batches=2)
    manifest = json.load(open(run_dir / "manifest.json"))
    assert manifest["run_id"] == tel.run_id
    assert manifest["config"] == {"model": "mlp"}
    assert manifest["package_version"]
    # conftest imports jax, so topology must be captured.
    assert manifest["topology"]["device_count"] >= 1
    rows = [json.loads(l)
            for l in open(run_dir / "metrics.jsonl").read().splitlines()]
    assert [r["phase"] for r in rows] == ["train", "eval"]
    for row in rows:
        for key in REQUIRED_KEYS:
            assert key in row, key
        assert row["run_id"] == tel.run_id
    assert rows[1]["eval_batches"] == 2
    trace = json.load(open(run_dir / "trace.json"))
    assert validate_chrome_trace(trace) is None
    assert tel.ring.latest()["phase"] == "eval"


def test_run_telemetry_envelope_wins_over_metric_collision(tmp_path):
    tel = RunTelemetry(str(tmp_path / "r"), metrics=False, trace=False)
    rec = tel.log("train", 7, {"step": 999, "loss": 1.0})
    assert rec["step"] == 7  # a metric named "step" must not corrupt rows
    tel.close()


# -- solver integration ---------------------------------------------------


def _tiny_solver(**kw):
    from npairloss_tpu import MiningMethod, NPairLossConfig
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    cfg = kw.pop("cfg", None) or SolverConfig(
        base_lr=0.1, lr_policy="fixed", momentum=0.9, weight_decay=0.0,
        display=0, test_interval=0, snapshot=0,
    )
    loss_cfg = NPairLossConfig(
        margin_diff=-0.05,
        an_mining_method=MiningMethod.HARD,
        ap_mining_method=MiningMethod.RAND,
    )
    return Solver(get_model("mlp", hidden=(32,), embedding_dim=16),
                  loss_cfg, cfg, input_shape=(8,), **kw)


def _batch(rng, n=16):
    from npairloss_tpu.data import synthetic_identity_batches

    return next(synthetic_identity_batches(n // 2, n // 2, 2, (8,),
                                           noise=0.5))


BASELINE_KEYS = sorted(
    ["loss", "lr", "retrieve_top1", "retrieve_top5", "retrieve_top10",
     "feature_asum"]
)

# The solver integration tests below each compile jitted steps (~1-2 s
# on CPU); they are consolidated — one no-health solver, one health
# solver — because the tier-1 run's 870 s budget has ~10 s of headroom
# over the rest of the suite (ROADMAP.md).


def test_solver_no_health_telemetry_and_keys(tmp_path, rng):
    """One no-health solver covers three pins: (a) the hot path exposes
    EXACTLY the pre-obs metric keys (the acceptance pin for 'identical
    HLO when disabled'), (b) train/evaluate emit enveloped rows through
    the sink, (c) compile/recompile capture shows in the span trace."""
    from npairloss_tpu.data import synthetic_identity_batches

    run_dir = tmp_path / "run"
    tel = RunTelemetry(str(run_dir))
    solver = _tiny_solver(telemetry=tel)
    batches = synthetic_identity_batches(8, 8, 2, (8,), noise=0.5)
    solver.train(batches, num_iters=2)

    x2, lab2 = _batch(rng, n=8)  # dynamic-batch path: new shape
    m = solver.step(x2, lab2)
    assert sorted(m.keys()) == BASELINE_KEYS

    ev = solver.evaluate(batches, 1)
    tel.close()

    rows = [json.loads(l)
            for l in open(run_dir / "metrics.jsonl").read().splitlines()]
    train_rows = [r for r in rows if r["phase"] == "train"]
    assert [r["step"] for r in train_rows] == [1, 2]
    for row in train_rows:
        for key in REQUIRED_KEYS + ("loss",):
            assert key in row, key
        assert sorted(set(row) - set(REQUIRED_KEYS)) == BASELINE_KEYS
    eval_rows = [r for r in rows if r["phase"] == "eval"]
    assert len(eval_rows) == 1 and eval_rows[0]["eval_batches"] == 1
    np.testing.assert_allclose(eval_rows[0]["loss"], ev["loss"], rtol=1e-6)

    trace = json.load(open(run_dir / "trace.json"))
    assert validate_chrome_trace(trace) is None
    names = [e["name"] for e in trace["traceEvents"]]
    # First dispatch per batch signature is the compile; repeat
    # signatures are plain dispatches; a signature after the first also
    # drops the step/recompile instant marker.
    assert names.count("step/compile") == 2
    assert names.count("step/dispatch") == 1
    assert names.count("step/recompile") == 1
    assert "data/next_batch" in names and "eval" in names


def test_solver_health_metrics_appear_when_enabled(rng):
    solver = _tiny_solver(health=HealthConfig())
    x, lab = _batch(rng)
    m = solver.step(x, lab)
    expected = {
        "grad_norm", "param_norm", "update_norm", "update_ratio",
        "emb_mag_mean", "emb_mag_max",
        "mined_pos_per_query", "mined_neg_per_query",
        "ap_threshold_mean", "an_threshold_mean",
    }
    assert expected <= set(m.keys())
    assert float(m["grad_norm"]) > 0
    assert 0 < float(m["update_ratio"]) < 1
    # update_ratio must be ||update||/||params|| of THIS step.
    ratio = float(m["update_norm"]) / (float(m["param_norm"]) + 1e-12)
    np.testing.assert_allclose(float(m["update_ratio"]), ratio, rtol=1e-4)
    # L2-normalized embeddings: magnitude pins to 1 (the reference's
    # feature-monitor invariant, cu:400-401 generalized).
    np.testing.assert_allclose(float(m["emb_mag_mean"]), 1.0, rtol=1e-5)
    np.testing.assert_allclose(float(m["emb_mag_max"]), 1.0, rtol=1e-5)
    assert float(m["mined_pos_per_query"]) >= 1.0
    # baseline retrieval metrics still present alongside
    assert "retrieve_top1" in m and "loss" in m

    # Edge regression (caught live): an all-same-label batch has no
    # negatives, so the AP mining threshold is a -inf/FLT_MAX sentinel
    # for every query — the hardness summary must skip sentinels and
    # stay FINITE (health rows feed assert_all_finite under
    # --debug-checks).
    x0 = np.random.default_rng(1).standard_normal((4, 8)).astype(np.float32)
    m0 = solver.step(x0, np.zeros(4, np.int32))
    vals = {k: float(v) for k, v in m0.items()}
    assert all(np.isfinite(v) for v in vals.values()), vals
    assert vals["loss"] == 0.0 and vals["mined_neg_per_query"] == 0.0


# -- CLI flags ------------------------------------------------------------
# marked slow: each spawns a full 2-iteration CLI training run (~1.5 s);
# the flag plumbing they cover is also exercised solver-level above, so
# the tier-1 budgeted run (-m 'not slow', ROADMAP.md) skips them and the
# unfiltered suite keeps them.


@pytest.mark.slow
def test_cli_telemetry_and_health_flags(tmp_path, monkeypatch):
    from npairloss_tpu.cli import main
    from npairloss_tpu.utils.debug import (
        debug_checks_enabled,
        enable_debug_checks,
    )

    monkeypatch.chdir(REPO)
    run_dir = tmp_path / "run"
    enable_debug_checks(False)
    try:
        rc = main([
            "train", "--solver", "examples/tiny_solver.prototxt",
            "--model", "mlp", "--max_iter", "2", "--synthetic",
            "--mesh", "1",
            "--telemetry-dir", str(run_dir), "--health-metrics",
            "--debug-checks",
        ])
    finally:
        was_enabled = debug_checks_enabled()
        enable_debug_checks(False)
    assert rc == 0
    assert was_enabled  # --debug-checks flipped the process-wide switch
    manifest = json.load(open(run_dir / "manifest.json"))
    assert manifest["config"]["health_metrics"] is True
    assert manifest["config"]["solver"]["max_iter"] == 2
    rows = [json.loads(l)
            for l in open(run_dir / "metrics.jsonl").read().splitlines()]
    train_rows = [r for r in rows if r["phase"] == "train"]
    assert len(train_rows) == 2
    assert "grad_norm" in train_rows[0]
    assert validate_chrome_trace(
        json.load(open(run_dir / "trace.json"))) is None


@pytest.mark.slow
def test_cli_trace_dir_only(tmp_path, monkeypatch):
    from npairloss_tpu.cli import main

    monkeypatch.chdir(REPO)
    trace_dir = tmp_path / "tr"
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "2", "--synthetic",
        "--mesh", "1",
        "--trace-dir", str(trace_dir),
    ])
    assert rc == 0
    assert validate_chrome_trace(
        json.load(open(trace_dir / "trace.json"))) is None
    # trace-only mode: no metric rows on disk
    assert not os.path.exists(trace_dir / "metrics.jsonl")


# -- tooling --------------------------------------------------------------


def test_check_no_print_clean_on_repo():
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_no_print.py")],
        capture_output=True,
    )
    assert rc.returncode == 0, rc.stderr.decode()


def test_check_no_print_flags_offender(tmp_path):
    bad = tmp_path / "lib.py"
    bad.write_text("def f():\n    print('leak')\n")
    exempt = tmp_path / "cli.py"
    exempt.write_text("print('fine')\n")
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_no_print.py"),
         str(tmp_path)],
        capture_output=True,
    )
    assert rc.returncode == 1
    err = rc.stderr.decode()
    assert "lib.py:2" in err and "cli.py" not in err


def test_sinks_load_without_package():
    """Jax-free processes load obs/sinks.py by file path — that module
    must import cleanly WITHOUT jax or the npairloss_tpu package."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('s', "
        f"{os.path.join(REPO, 'npairloss_tpu', 'obs', 'sinks.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "assert 'jax' not in sys.modules\n"
        "assert 'npairloss_tpu' not in sys.modules\n"
        "ring = mod.RingBufferSink(2)\n"
        "ring.log({'a': 1})\n"
        "assert ring.latest() == {'a': 1}\n"
    )
    rc = subprocess.run([sys.executable, "-c", code], capture_output=True)
    assert rc.returncode == 0, rc.stderr.decode()


# -- one tracer per process, one span function (obs.tracing) ---------------


@pytest.fixture
def no_tracer():
    """The test starts with no tracer installed and leaves the process
    as it found it."""
    from npairloss_tpu.obs import tracing

    prev = tracing.install(None)
    yield tracing
    tracing.install(prev)


def test_one_tracer_is_shared_by_telemetry_qtrace_and_span(tmp_path, no_tracer):
    from npairloss_tpu.obs.qtrace import QueryTracer

    tracing = no_tracer
    with tracing.span("nobody/listens"):  # neither tracer nor session: no-op
        pass
    tel = RunTelemetry(str(tmp_path / "r"), metrics=False)
    assert tracing.current() is tel.tracer
    qtracer = QueryTracer()
    assert qtracer.tracer is tel.tracer
    assert not hasattr(qtracer, "_t0")  # no clock of its own
    assert qtracer.wall_time_origin == tel.tracer.wall_time_origin
    with tracing.span("serve/dispatch", size=1):
        tracing.instant("serve/recompile", sig="s")
    with tel.span("step/dispatch"):
        pass
    qt = qtracer.begin("q")
    qtracer.admitted(qt)
    events = tel.tracer.events_since(0)[0]
    assert [e["name"] for e in events] == [
        "serve/recompile", "serve/dispatch", "step/dispatch"]
    # the query's own span reads on the same origin as the buffer's
    assert events[-1]["ts"] <= qt.events[0]["ts"] + qt.events[0]["dur"]
    assert set(qt.events[0]) == set(events[1])  # one event shape
    # a window in perf_counter seconds clips by the public origin
    import time

    assert tel.tracer.to_us(time.perf_counter()) >= events[-1]["ts"]
    assert tel.tracer.origin <= time.perf_counter()
    # a tracer assigned later is the installed one (the cap test's way)
    tel.tracer = SpanTracer(max_events=4)
    assert tracing.current() is tel.tracer
    tel.close()
    assert tracing.current() is None  # close takes its own tracer away
    # nothing installed: a QueryTracer brings and installs one; a seeded
    # clock stays private to its tracer
    made = QueryTracer()
    assert tracing.current() is made.tracer
    seeded = QueryTracer(clock=lambda: 5.0, wall=lambda: 9.0)
    assert seeded.tracer is not made.tracer
    assert tracing.current() is made.tracer
    assert seeded.wall_time_origin == 9.0 and seeded.tracer.origin == 5.0


def test_tagged_spans_carry_the_shared_identifier(no_tracer):
    import threading

    tracing = no_tracer
    tr = SpanTracer()
    tracing.install(tr)
    seen = []

    def other():  # tags are the calling thread's alone
        with tracing.span("other/thread"):
            seen.append(tracing.tags())

    with tracing.tagged(batch=7, replica="r0"):
        with tracing.span("serve/dispatch", size=2):
            with tracing.tagged(chunk=1):
                with tracing.span("serve/topk", rows=2):
                    pass
        tracing.instant("serve/recompile")
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    with tracing.span("serve/idle"):
        pass
    by = {e["name"]: e.get("args") for e in tr.events_since(0)[0]}
    assert by["serve/dispatch"] == {"batch": 7, "replica": "r0", "size": 2}
    assert by["serve/topk"] == {"batch": 7, "replica": "r0", "chunk": 1,
                                "rows": 2}
    assert by["serve/recompile"] == {"batch": 7, "replica": "r0"}
    assert by["other/thread"] is None and seen == [{}]
    assert by["serve/idle"] is None and tracing.tags() == {}


class _SlowToHost:
    """A device array's stand-in: the jitted call has returned, the
    result reaches the host only after ``__array__`` has waited."""

    def __init__(self, rows, wait_s):
        self.rows, self.wait_s = rows, wait_s

    def __array__(self, dtype=None, copy=None):
        import time

        time.sleep(self.wait_s)
        return self.rows


def _encoding_server(wait_s=0.0, qtrace=None):
    """A real engine over a tiny gallery whose encode is the identity
    on ``dim``-wide inputs (handed back as a slow-to-host array)."""
    from npairloss_tpu.serve import (
        BatcherConfig, EngineConfig, GalleryIndex, QueryEngine,
        RetrievalServer, ServerConfig)

    rng = np.random.default_rng(0)
    emb = rng.standard_normal((24, 8)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    idx = GalleryIndex.build(emb, np.arange(24, dtype=np.int32) // 4)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1, 4)))
    engine._encode_fn = lambda _state, x: _SlowToHost(np.asarray(x), wait_s)
    engine.warmup((8,))
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=4, max_delay_ms=1.0, max_queue=16),
        ServerConfig(metrics_window=0), input_shape=(8,), qtrace=qtrace)
    return emb, server


def test_dispatch_spans_share_a_batch_and_encode_ends_on_the_host(no_tracer):
    """Every span of one batch carries the batch's ``batch``, on
    whichever thread runs it.  ``serve/dispatch`` holds ``serve/encode``
    (with its ``wait`` child, ending only when the result is on the
    host: the old span closed at launch, ~0 ms here) and
    ``serve/launch``.  A lone request has nothing to overlap: the
    dispatcher finishes it itself (``serve/finish``, holding
    ``serve/topk`` with its ``wait`` and ``scan``, ``serve/gather`` and
    ``serve/assemble``, inside its dispatch) and replies.  Under a
    backlog the batch goes through ``serve/handoff`` to the completion
    thread, which runs the same ``serve/finish`` and then
    ``serve/reply``."""
    tracing = no_tracer
    tr = SpanTracer()
    tracing.install(tr)
    emb, server = _encoding_server(wait_s=0.05)
    start = tr.num_events  # past the warm-up's spans
    server.replicaset.start()
    try:
        (ans,) = server.handle_many([{"id": 0, "input": emb[5].tolist()}])
    finally:
        server.replicaset.close(drain=True)
    assert ans["neighbors"][0]["row"] == 5
    events = tr.events_since(start)[0]
    turn = [e for e in events if e.get("args", {}).get("batch") == 1]
    by = {e["name"]: e for e in turn}
    finish = ["serve/finish", "serve/topk", "serve/topk/wait",
              "serve/topk/scan", "serve/gather", "serve/assemble"]
    assert set(by) == {
        "serve/idle", "serve/batch", "serve/dispatch", "serve/encode",
        "serve/encode/wait", "serve/launch", "serve/reply", *finish}
    assert len(turn) == len(by)  # one of each, no hand-off
    assert len({e["tid"] for e in turn}) == 1  # all on the dispatcher
    assert all(e["args"]["replica"] == "r0" for e in turn)
    assert by["serve/dispatch"]["args"]["size"] == 1
    # a lone request: nothing was out when its launch began
    assert by["serve/batch"]["args"]["inflight"] == 0
    end = lambda e: e["ts"] + e["dur"]
    inside = lambda child, parent: (
        by[parent]["ts"] <= by[child]["ts"] and end(by[child]) <= end(by[parent]))
    assert inside("serve/encode/wait", "serve/encode")
    assert inside("serve/topk/wait", "serve/topk")
    assert inside("serve/topk/scan", "serve/topk")
    for child in ("serve/topk", "serve/gather", "serve/assemble"):
        assert inside(child, "serve/finish")
    for child in ("serve/encode", "serve/launch", "serve/finish"):
        assert inside(child, "serve/dispatch")
    scan = by["serve/topk/scan"]["args"]
    assert 1 <= scan["scan_blocks_merged"] <= scan["scan_blocks"]
    assert by["serve/encode/wait"]["dur"] >= 50e3  # the copy's wait, in us
    assert by["serve/encode"]["dur"] >= by["serve/encode/wait"]["dur"]
    # the thread's turn in order, nothing overlapping
    order = ["serve/idle", "serve/batch", "serve/dispatch", "serve/reply"]
    assert all(end(by[a]) <= by[b]["ts"] for a, b in zip(order, order[1:]))
    # the turn after it (the drain's) has another number
    assert {e["args"]["batch"] for e in events
            if e["name"] == "serve/idle"} == {1, 2}

    # a backlog: eight queued before the start, batches of four
    emb, server = _encoding_server(wait_s=0.05)
    start = tr.num_events
    futs = [server.submit({"id": i, "input": emb[i].tolist()})[0]
            for i in range(8)]
    server.replicaset.start()
    server.replicaset.close(drain=True)
    assert [f.result(timeout=0)["neighbors"][0]["row"] for f in futs] == \
        list(range(8))
    events = tr.events_since(start)[0]
    turn = [e for e in events if e.get("args", {}).get("batch") == 1]
    by = {e["name"]: e for e in turn}
    assert set(by) == {
        "serve/idle", "serve/batch", "serve/dispatch", "serve/encode",
        "serve/encode/wait", "serve/launch", "serve/handoff", "serve/reply",
        *finish}
    assert by["serve/dispatch"]["args"]["size"] == 4
    launch = ["serve/idle", "serve/batch", "serve/dispatch", "serve/encode",
              "serve/encode/wait", "serve/launch", "serve/handoff"]
    assert len({by[n]["tid"] for n in launch}) == 1
    assert len({by[n]["tid"] for n in finish + ["serve/reply"]}) == 1
    assert by["serve/dispatch"]["tid"] != by["serve/finish"]["tid"]
    for child in ("serve/encode", "serve/launch"):
        assert inside(child, "serve/dispatch")
    for child in ("serve/topk", "serve/gather", "serve/assemble"):
        assert inside(child, "serve/finish")
    # each thread's turn in order; the finish starts after its launch
    for order in (["serve/idle", "serve/batch", "serve/dispatch",
                   "serve/handoff"],
                  ["serve/launch", "serve/finish", "serve/reply"]):
        assert all(end(by[a]) <= by[b]["ts"] for a, b in zip(order, order[1:]))


def test_profiler_session_holds_the_program_spans_in_the_host_plane(
        tmp_path, no_tracer):
    """Any ``jax.profiler`` session — no tracer installed — finds the
    program's host spans beside the device operations, on its clock."""
    import jax

    sys.path.insert(0, REPO)
    from benchmarks.harness import trace_reduce, xplane

    from npairloss_tpu.data import synthetic_identity_batches
    from npairloss_tpu.train import SolverConfig

    emb, server = _encoding_server()
    solver = _tiny_solver(cfg=SolverConfig(
        base_lr=0.1, lr_policy="fixed", momentum=0.9, weight_decay=0.0,
        display=0, test_interval=0, snapshot=0, pipeline=True,
        pipeline_depth=1))
    batches = synthetic_identity_batches(8, 8, 2, (8,), noise=0.5)
    solver.train(batches, num_iters=2)  # compile outside the session
    server.replicaset.start()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        server.handle_many([{"id": 0, "input": emb[3].tolist()}])
        solver.train(batches, num_iters=5)
    finally:
        jax.profiler.stop_trace()
        server.replicaset.close(drain=True)
    assert no_tracer.current() is None
    found = {}
    for plane in xplane.read(trace_reduce.newest_xplane(str(tmp_path))):
        if not plane["name"].startswith("/host:"):
            continue
        for thread, line in enumerate(plane["lines"]):  # a line is a thread
            for name, _start, dur, stats in line["events"]:
                if name.startswith(("serve/", "step/", "data/", "pipeline/")):
                    found.setdefault(name, []).append((thread, dur, stats))
    for name in ("serve/dispatch", "serve/encode/wait", "step/device_wait",
                 "step/dispatch", "data/next_batch", "pipeline/stage"):
        assert name in found, sorted(found)
    (_thread, dur, stats), = found["serve/dispatch"]
    assert dur > 0 and int(stats["size"]) == 1 and int(stats["batch"]) == 1
    # the dispatch's children sit on the dispatcher's line, the staging
    # thread's span on its own
    assert found["serve/encode/wait"][0][0] == found["serve/dispatch"][0][0]
    assert found["pipeline/stage"][0][0] != found["step/device_wait"][0][0]
