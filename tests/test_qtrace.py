"""obs/qtrace: per-query stage tracing, exemplar sampling, the v1
artifact contract, and the composed-system timeline merge.

The load-bearing pins (docs/OBSERVABILITY.md §Query tracing):
  * one trace id per query, assigned at ingestion and propagated with
    the record across the admission/batcher/replica THREADS — every
    span in an exemplar tree carries that id, and the tree shows work
    from more than one thread;
  * span ordering and nesting obey the contract the validator checks
    (root covers everything; score/topk_merge nest inside dispatch);
  * the exemplar store is bounded — fastest evicted first, so the
    worst span tree is never lost — and retention is deterministic
    under a seeded clock;
  * the validator refuses doctored artifacts (≥6 distinct refusals
    pinned here) and the p99/exemplar cross-check refuses aggregation
    the exemplars can't explain;
  * qtrace OFF keeps every emitted stream byte-identical to a
    qtrace-free build, and the two latency populations (smoothed ring
    vs per-window list) admit exactly the same samples — dropped and
    errored queries enter NEITHER, and windows-off keeps the window
    list empty rather than growing an unbounded divergent copy;
  * the timeline merge gives exemplar trees their own per-replica
    lanes and renders alerts/remediation/chaos as instants.
"""

import json
import threading
import types

import numpy as np
import pytest

from npairloss_tpu.obs.qtrace import (
    MARKER_NAMES,
    QTraceConfig,
    QueryTracer,
    STAGES,
    qtrace_p99_consistency,
    validate_qtrace_report,
)
from npairloss_tpu.obs.qtrace.report import ROOT_SPAN
from npairloss_tpu.serve.batcher import BatcherConfig
from npairloss_tpu.serve.server import RetrievalServer, ServerConfig


class SeededClock:
    """Deterministic monotonic clock: time moves only when told to."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


def _tracer(clk, **cfg):
    return QueryTracer(QTraceConfig(**cfg), clock=clk,
                       wall=lambda: 1000.0 + clk.t)


def _run_query(tracer, clk, qid, admit_s=0.001, queue_s=0.002,
               assemble_s=0.003, dispatch_s=0.010, score_us=4000.0,
               merge_us=1000.0, replica="r0"):
    """Drive one query through every stage hook with seeded timing."""
    qt = tracer.begin(qid)
    clk.advance(admit_s)
    tracer.admitted(qt)
    clk.advance(queue_s)
    tracer.picked(qt)
    clk.advance(assemble_s)
    tracer.dispatch_begin([qt], replica=replica)
    clk.advance(dispatch_s)
    tracer.dispatch_end([qt], score_us=score_us, merge_us=merge_us)
    tracer.finish(qt)
    return qt


# -- seeded-clock determinism: spans, ordering, nesting ---------------------


def test_seeded_clock_stage_decomposition_exact():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=100.0)
    _run_query(tr, clk, "q1")
    rep = tr.report()
    assert validate_qtrace_report(rep) is None
    assert qtrace_p99_consistency(rep) is None
    assert rep["totals"] == {
        "queries": 1, "errors": 0, "dropped": 0, "violations": 0,
        "exemplars": 1, "evicted": 0, "reroutes": 0, "hotswap_flips": 0,
    }
    # 1+2+3+10 ms of seeded time; dispatch splits 10 into 5/4/1.
    b = rep["budget"]
    assert b["p99_ms"] == pytest.approx(16.0)
    assert b["dominant"] == "dispatch"
    assert b["worst_mean_ms"]["admit_wait"] == pytest.approx(1.0)
    assert b["worst_mean_ms"]["queue_wait"] == pytest.approx(2.0)
    assert b["worst_mean_ms"]["batch_assemble"] == pytest.approx(3.0)
    assert b["worst_mean_ms"]["dispatch"] == pytest.approx(5.0)
    assert b["worst_mean_ms"]["score"] == pytest.approx(4.0)
    assert b["worst_mean_ms"]["topk_merge"] == pytest.approx(1.0)


def test_exemplar_tree_ordering_and_nesting():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=100.0)
    _run_query(tr, clk, "q1")
    (ex,) = tr.report()["exemplars"]
    names = [e["name"] for e in ex["events"]]
    # Every stage span plus exactly one root, sorted by start ts.
    assert names.count(ROOT_SPAN) == 1
    for stage in STAGES:
        assert f"qtrace/{stage}" in names
    ts = [e["ts"] for e in ex["events"]]
    assert ts == sorted(ts)
    # Root covers the whole tree; score/topk_merge nest inside dispatch.
    root = next(e for e in ex["events"] if e["name"] == ROOT_SPAN)
    disp = next(e for e in ex["events"] if e["name"] == "qtrace/dispatch")
    for e in ex["events"]:
        assert e["ts"] >= root["ts"] - 2.0
        assert e["ts"] + e.get("dur", 0.0) <= \
            root["ts"] + root["dur"] + 2.0
    for name in ("qtrace/score", "qtrace/topk_merge"):
        e = next(ev for ev in ex["events"] if ev["name"] == name)
        assert e["ts"] >= disp["ts"] - 2.0
        assert e["ts"] + e["dur"] <= disp["ts"] + disp["dur"] + 2.0
    # Every span carries the exemplar's trace id and replica is stamped.
    assert all(e["args"]["trace_id"] == ex["trace_id"]
               for e in ex["events"])
    assert ex["replica"] == "r0"


# -- exemplar retention: ring bounds, determinism ---------------------------


def test_exemplar_ring_bounded_fastest_evicted_first():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=2, slo_ms=0.0)  # tail rule only
    for qid, disp_s in (("a", 0.010), ("b", 0.020), ("c", 0.030)):
        _run_query(tr, clk, qid, admit_s=0.0, queue_s=0.0,
                   assemble_s=0.0, dispatch_s=disp_s, score_us=0.0,
                   merge_us=0.0)
    # "a" (ring-empty retain) was evicted when "c" arrived; the two
    # slowest survive, so the worst span tree is never lost.
    rep = tr.report()
    assert validate_qtrace_report(rep) is None
    kept = sorted(ex["total_ms"] for ex in rep["exemplars"])
    assert kept == pytest.approx([20.0, 30.0])
    assert rep["totals"]["evicted"] == 1
    assert rep["totals"]["exemplars"] == 2
    # A below-tail query is NOT retained (never a flight recorder).
    _run_query(tr, clk, "d", admit_s=0.0, queue_s=0.0, assemble_s=0.0,
               dispatch_s=0.005, score_us=0.0, merge_us=0.0)
    rep = tr.report()
    assert sorted(ex["total_ms"] for ex in rep["exemplars"]) == \
        pytest.approx([20.0, 30.0])


def test_slo_violation_retained_even_when_store_prefers_it_not():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=1, slo_ms=1.0)
    _run_query(tr, clk, "slow", admit_s=0.0, queue_s=0.0,
               assemble_s=0.0, dispatch_s=0.050, score_us=0.0,
               merge_us=0.0)
    _run_query(tr, clk, "violating-but-faster", admit_s=0.0,
               queue_s=0.0, assemble_s=0.0, dispatch_s=0.010,
               score_us=0.0, merge_us=0.0)
    rep = tr.report()
    # Both violated; the store is full of a slower tree, so the second
    # counts as evicted rather than displacing the worst exemplar.
    assert rep["totals"]["violations"] == 2
    assert rep["totals"]["evicted"] == 1
    (ex,) = rep["exemplars"]
    assert ex["reason"] == "slo"
    assert ex["total_ms"] == pytest.approx(50.0)


def test_dropped_and_errored_enter_no_population():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=100.0)
    tr.drop(tr.begin("shed"))
    tr.drop(tr.begin("boom"), error=True)
    rep = tr.report()
    assert validate_qtrace_report(rep) is None
    assert rep["totals"]["queries"] == 2
    assert rep["totals"]["dropped"] == 1
    assert rep["totals"]["errors"] == 1
    # Neither the budget ring nor the exemplar store saw them.
    assert rep["budget"]["p99_ms"] == 0.0
    assert rep["budget"]["dominant"] == ""
    assert rep["exemplars"] == []
    assert tr.window_row() == {"qtrace_dominant": "",
                               "qtrace_dominant_ms": 0.0}


def test_window_row_drains_its_accumulator():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=100.0)
    _run_query(tr, clk, "q1", dispatch_s=0.030)
    row = tr.window_row()
    assert row["qtrace_dominant"] == "dispatch"
    assert row["qtrace_dominant_ms"] > 0
    # The accumulator is per-window: a second read starts empty, while
    # the smoothed budget ring still remembers the query.
    assert tr.window_row() == {"qtrace_dominant": "",
                               "qtrace_dominant_ms": 0.0}
    assert tr.budget()["p99_ms"] > 0


def test_marker_vocabulary_and_counts():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=100.0)
    tr.marker("hotswap_flip", generation=1)
    tr.marker("crash_reroute", dead="r0", target="r1", queries=3)
    with pytest.raises(ValueError):
        tr.marker("made_up_marker")
    rep = tr.report()
    assert rep["totals"]["hotswap_flips"] == 1
    assert rep["totals"]["reroutes"] == 1
    assert [m["name"] for m in rep["markers"]] == list(MARKER_NAMES)


# -- validator refusals -----------------------------------------------------


def _valid_report():
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=0.0)
    _run_query(tr, clk, "q1", dispatch_s=0.010)
    _run_query(tr, clk, "q2", dispatch_s=0.020)
    tr.marker("hotswap_flip", generation=1)
    rep = tr.report()
    assert validate_qtrace_report(rep) is None, "fixture must start valid"
    return json.loads(json.dumps(rep))


def _doctor_schema(rep):
    rep["schema"] = "npairloss-qtrace-v2"


def _doctor_missing_key(rep):
    del rep["budget"]


def _doctor_stage_vocab(rep):
    rep["stages"][3] = "disptach"


def _doctor_duplicate_trace_id(rep):
    src, dst = rep["exemplars"][0], rep["exemplars"][1]
    dst["trace_id"] = src["trace_id"]
    for ev in dst["events"]:
        ev["args"]["trace_id"] = src["trace_id"]


def _doctor_event_order(rep):
    rep["exemplars"][0]["events"].reverse()


def _doctor_nesting(rep):
    ex = rep["exemplars"][0]
    span = next(e for e in ex["events"]
                if e["name"] == "qtrace/queue_wait")
    span["dur"] = 1e9  # escapes the root span — broken nesting


def _doctor_totals_mismatch(rep):
    rep["totals"]["exemplars"] += 1


def _doctor_marker_name(rep):
    rep["markers"][0]["name"] = "surprise_party"


def _doctor_foreign_span(rep):
    ex = rep["exemplars"][0]
    ex["events"][0]["name"] = "qtrace/gpu_melt"


def _doctor_reason(rep):
    rep["exemplars"][0]["reason"] = "vibes"


@pytest.mark.parametrize(
    "doctor, expect",
    [
        (_doctor_schema, "foreign artifact"),
        (_doctor_missing_key, "missing key"),
        (_doctor_stage_vocab, "do not match the contract"),
        (_doctor_duplicate_trace_id, "duplicate trace_id"),
        (_doctor_event_order, "out of ts order"),
        (_doctor_nesting, "broken nesting"),
        (_doctor_totals_mismatch, "retained exemplars"),
        (_doctor_marker_name, "instant named one of"),
        (_doctor_foreign_span, "outside the qtrace vocabulary"),
        (_doctor_reason, "reason"),
    ],
    ids=["schema", "missing-key", "stage-vocab", "dup-trace-id",
         "event-order", "nesting", "totals-mismatch", "marker-name",
         "foreign-span", "reason"],
)
def test_validator_refuses_doctored_artifacts(doctor, expect):
    rep = _valid_report()
    doctor(rep)
    err = validate_qtrace_report(rep)
    assert err is not None and expect in err


def test_p99_consistency_cross_check():
    rep = _valid_report()
    assert qtrace_p99_consistency(rep) is None
    # Aggregation the exemplar set cannot explain: a logged p99 beyond
    # the worst retained tree by more than the ring tolerance.
    rep["budget"]["p99_ms"] = max(
        ex["total_ms"] for ex in rep["exemplars"]
    ) * (1.0 + rep["ring_tolerance"]) * 1.5
    err = qtrace_p99_consistency(rep)
    assert err is not None and "ring tolerance" in err


# -- server integration: propagation, byte-identity, populations ------------


class FakeEngine:
    """Engine-shaped stand-in: answers instantly, reports measured
    score/merge time through the per-call stage accumulator exactly
    like QueryEngine.query does — no device, no compiles."""

    def __init__(self, dim=4, k=2):
        self.index = types.SimpleNamespace(dim=dim)
        self.k = k
        self.compiles_total = 0
        self.compiles_after_warmup = 0

    def query(self, emb, normalize=True, stages=None):
        n = emb.shape[0]
        if stages is not None:
            stages["score_us"] = stages.get("score_us", 0.0) + 120.0
            stages["merge_us"] = stages.get("merge_us", 0.0) + 40.0
        rows = np.tile(np.arange(self.k), (n, 1)).astype(np.int64)
        return {"rows": rows, "ids": rows, "labels": rows,
                "scores": np.ones((n, self.k), np.float32)}

    def compile_stats(self):
        return {"compiles": 0}


class CapturingTelemetry:
    """Telemetry-shaped sink recording every emitted row verbatim."""

    metrics_enabled = True

    def __init__(self):
        self.rows = []

    def log(self, kind, step, row):
        self.rows.append((kind, json.dumps(row, sort_keys=True)))

    def flush(self):
        pass

    def span(self, name, **args):
        import contextlib

        return contextlib.nullcontext()


def _fake_server(qtrace=None, replicas=2, metrics_window=4,
                 telemetry=None):
    return RetrievalServer(
        [FakeEngine() for _ in range(replicas)],
        BatcherConfig(max_batch=4, max_delay_ms=2.0, max_queue=64),
        ServerConfig(metrics_window=metrics_window),
        telemetry=telemetry,
        qtrace=qtrace,
    )


def _records(prefix, n, dim=4):
    return [{"id": f"{prefix}{i}", "embedding": [0.1] * dim}
            for i in range(n)]


def test_trace_propagation_across_threads():
    tracer = QueryTracer(QTraceConfig(exemplars=64, slo_ms=0.0))
    srv = _fake_server(qtrace=tracer)
    srv.replicaset.start()
    errors = []

    def client(prefix):
        try:
            answers = srv.handle_many(_records(prefix, 6))
            assert all("error" not in a for a in answers)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    try:
        threads = [threading.Thread(target=client, args=(f"c{i}-",))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        srv.replicaset.close(drain=True)
    assert not errors
    rep = tracer.report()
    assert validate_qtrace_report(rep) is None
    assert rep["totals"]["queries"] == 24
    assert rep["totals"]["errors"] == 0
    assert rep["exemplars"], "tail rule must retain at least one tree"
    for ex in rep["exemplars"]:
        names = {e["name"] for e in ex["events"]}
        # The pipeline stages all made it into one tree, across the
        # client thread (admit/root) and the dispatcher (pick/dispatch)
        # — same trace id end to end, at least two distinct threads.
        for want in (ROOT_SPAN, "qtrace/admit_wait", "qtrace/queue_wait",
                     "qtrace/batch_assemble", "qtrace/dispatch"):
            assert want in names
        assert len({e["tid"] for e in ex["events"]}) >= 2
    # The summary carries the budget decomposition.
    s = srv.summary()
    assert s["qtrace"]["queries"] == 24
    assert s["qtrace"]["budget"]["dominant"] in STAGES


def test_qtrace_off_streams_byte_identical():
    tel = CapturingTelemetry()
    srv = _fake_server(qtrace=None, telemetry=tel)
    srv.replicaset.start()
    try:
        srv.handle_many(_records("q", 8))
    finally:
        srv.replicaset.close(drain=True)
    summary = srv.summary()
    rows = [row for kind, row in tel.rows if kind == "serve"]
    assert rows, "windows must have emitted"
    # The OFF posture: no qtrace key anywhere in any emitted byte.
    for row in rows:
        assert "qtrace" not in row
    assert "qtrace" not in json.dumps(summary)

    # Turning tracing ON adds ONLY the qtrace keys to the same stream.
    tel2 = CapturingTelemetry()
    tracer = QueryTracer(QTraceConfig(exemplars=8, slo_ms=0.0))
    srv2 = _fake_server(qtrace=tracer, telemetry=tel2)
    srv2.replicaset.start()
    try:
        srv2.handle_many(_records("q", 8))
    finally:
        srv2.replicaset.close(drain=True)
    on_rows = [json.loads(row) for kind, row in tel2.rows
               if kind == "serve"]
    assert any("qtrace_dominant" in r for r in on_rows)
    off_keys = {k for row in rows for k in json.loads(row)}
    on_keys = {k for r in on_rows for k in r}
    assert on_keys - off_keys <= {"qtrace_dominant",
                                  "qtrace_dominant_ms"}
    assert "qtrace" in srv2.summary()


def test_latency_populations_admit_identical_samples():
    # Satellite pin: the smoothed ring and the per-window list are two
    # views of ONE population.  With windows off the per-window list
    # must stay EMPTY (not an unbounded divergent copy of the ring),
    # and errored queries enter neither view.
    srv = _fake_server(qtrace=None, metrics_window=0)
    srv.replicaset.start()
    try:
        srv.handle_many(_records("ok", 5))
        answers = srv.handle_many([{"id": "bad"}])  # no embedding/input
        assert "error" in answers[0]
    finally:
        srv.replicaset.close(drain=True)
    assert srv.answered == 5 and srv.errors == 1
    assert len(srv._lat) == 5
    assert srv._window_lat == []

    # With windows ON both views admit exactly the answered samples.
    tracer = QueryTracer(QTraceConfig(exemplars=8, slo_ms=0.0))
    srv2 = _fake_server(qtrace=tracer, metrics_window=100)
    srv2.replicaset.start()
    try:
        srv2.handle_many(_records("ok", 5))
        srv2.handle_many([{"id": "bad"}])
    finally:
        srv2.replicaset.close(drain=True)
    assert len(srv2._lat) == 5
    assert len(srv2._window_lat) == 5  # window never filled: no emit
    rep = tracer.report()
    assert rep["totals"]["queries"] == 6
    assert rep["totals"]["errors"] == 1
    # The errored query is in no aggregation population.
    assert all(ex["qid"] != "bad" for ex in rep["exemplars"])


# -- the composed-system timeline -------------------------------------------


def test_merge_timeline_lanes_and_instants(tmp_path):
    from npairloss_tpu.obs.fleet.merge_traces import (
        OPS_PID,
        QTRACE_PID_BASE,
        SERVE_EVENTS_PID,
        merge_timeline,
    )

    run = tmp_path / "run"
    serve_tel = run / "serve_tel"
    serve_tel.mkdir(parents=True)

    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=0.0)
    _run_query(tr, clk, "q1", dispatch_s=0.020, replica="r0")
    _run_query(tr, clk, "q2", dispatch_s=0.030, replica="r1")
    tr.marker("hotswap_flip", generation=1)
    tr.write(str(serve_tel / "qtrace.json"))

    with open(serve_tel / "alerts.jsonl", "w") as f:
        f.write(json.dumps({"slo": "serve_p99", "state": "firing",
                            "ts": 1000.5, "severity": "page"}) + "\n")
        f.write(json.dumps({"slo": "serve_p99", "state": "resolved",
                            "ts": 1001.5, "severity": "page"}) + "\n")
    with open(serve_tel / "remediation.jsonl", "w") as f:
        f.write(json.dumps({"policy": "load_shed", "state": "succeeded",
                            "ts": 1001.0, "attempt": 1}) + "\n")
    with open(run / "gameday.json", "w") as f:
        json.dump({"faults": [{"name": "serve.latency", "target":
                               "serve", "kind": "failpoint",
                               "at_s": 5.0}]}, f)

    path, merged = merge_timeline(str(run))
    assert path is not None
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["otherData"]["sources"]["qtrace"] is True
    assert on_disk["otherData"]["sources"]["alerts"] == 2
    events = merged["traceEvents"]

    # One lane (pid) per replica, one row (tid) per exemplar tree.
    lane_names = {e["args"]["name"] for e in events
                  if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "serve queries r0" in lane_names
    assert "serve queries r1" in lane_names
    qtrace_spans = [e for e in events if e.get("ph") == "X"
                    and e["pid"] >= QTRACE_PID_BASE
                    and e["pid"] < SERVE_EVENTS_PID]
    assert {e["name"] for e in qtrace_spans} >= {ROOT_SPAN,
                                                 "qtrace/dispatch"}

    # Markers land on the serve-events lane; ops land as instants.
    assert any(e["pid"] == SERVE_EVENTS_PID
               and e["name"] == "hotswap_flip" for e in events)
    instants = {e["name"] for e in events
                if e.get("ph") == "i" and e["pid"] == OPS_PID}
    assert "alert:serve_p99 firing" in instants
    assert "alert:serve_p99 resolved" in instants
    assert "remediation:load_shed succeeded" in instants
    assert "chaos:serve.latency" in instants

    # Alignment: the alert fired 0.5 s after the tracer's origin, on
    # the merged timeline's shared clock (µs since base origin).
    fired = next(e for e in events
                 if e["name"] == "alert:serve_p99 firing")
    base = merged["otherData"]["wall_time_origin"]
    assert fired["ts"] == pytest.approx((1000.5 - base) * 1e6)


# -- measured stage positions, on the process's tracer ---------------------


def test_measured_positions_place_score_and_merge_where_they_ran():
    """``dispatch_end`` with measured intervals puts the spans where the
    work happened (cut to the dispatch span), not at its tail; durations
    alone still lay them back-to-back at the tail."""
    clk = SeededClock()
    tr = _tracer(clk, exemplars=4, slo_ms=100.0)
    qt = tr.begin("q1")
    tr.admitted(qt)
    tr.picked(qt)
    tr.dispatch_begin([qt], replica="r0", batch=7)
    assert qt.batch == 7
    clk.advance(0.010)  # dispatch is [0, 10 ms]
    tr.dispatch_end([qt], score_us=999.0, merge_us=999.0,
                    score_at=(2000.0, 5000.0), merge_at=(5500.0, 12000.0))
    tr.finish(qt)
    by = {e["name"]: e for e in qt.events}
    assert (by["qtrace/score"]["ts"], by["qtrace/score"]["dur"]) == \
        (2000.0, 3000.0)
    # the merge is cut at the dispatch span's end
    assert (by["qtrace/topk_merge"]["ts"], by["qtrace/topk_merge"]["dur"]) \
        == (5500.0, 4500.0)
    assert by["qtrace/dispatch"]["args"]["batch"] == 7
    assert by["qtrace/batch_assemble"]["args"]["batch"] == 7
    assert qt.stage_us == {"dispatch": 2500.0, "score": 3000.0,
                           "topk_merge": 4500.0}
    assert validate_qtrace_report(tr.report()) is None


def test_server_trees_carry_the_engine_measured_times():
    """Through a real engine: a rider's ``score`` and ``topk_merge``
    events lie inside its ``dispatch`` event at the times the engine's
    ``serve/topk`` and ``serve/gather`` .. ``serve/assemble`` spans
    read, and the tree names the ``batch`` those spans carry."""
    from npairloss_tpu.obs import tracing
    from npairloss_tpu.obs.tracing import SpanTracer
    from npairloss_tpu.serve import EngineConfig, GalleryIndex, QueryEngine

    rng = np.random.default_rng(3)
    emb = rng.standard_normal((32, 8)).astype(np.float32)
    idx = GalleryIndex.build(emb, np.arange(32, dtype=np.int32) // 4)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1, 4)))
    engine.warmup()
    shared = SpanTracer()
    prev = tracing.install(shared)
    try:
        tracer = QueryTracer(QTraceConfig(exemplars=8, slo_ms=0.0))
        assert tracer.tracer is shared
        srv = RetrievalServer(
            engine, BatcherConfig(max_batch=4, max_delay_ms=1.0),
            ServerConfig(metrics_window=0), qtrace=tracer)
        srv.replicaset.start()
        try:
            (ans,) = srv.handle_many(
                [{"id": "a", "embedding": emb[9].tolist()}])
        finally:
            srv.replicaset.close(drain=True)
    finally:
        tracing.install(prev)
    assert ans["neighbors"][0]["row"] == 9
    (ex,) = tracer.report()["exemplars"]
    tree = {e["name"]: e for e in ex["events"]}
    batch = tree["qtrace/dispatch"]["args"]["batch"]
    spans = {e["name"]: e for e in shared.events_since(0)[0]
             if e.get("args", {}).get("batch") == batch}
    end = lambda e: e["ts"] + e["dur"]
    disp = tree["qtrace/dispatch"]
    for name in ("qtrace/score", "qtrace/topk_merge"):
        assert disp["ts"] <= tree[name]["ts"]
        assert end(tree[name]) <= end(disp)
    # the engine reads its clock just outside each span: the tree's
    # stage holds the span, a clock read apart
    score, merge = tree["qtrace/score"], tree["qtrace/topk_merge"]
    assert score["ts"] <= spans["serve/topk"]["ts"]
    assert end(spans["serve/topk"]) <= end(score)
    assert merge["ts"] <= spans["serve/gather"]["ts"]
    assert end(spans["serve/assemble"]) <= end(merge)
    assert score["dur"] - spans["serve/topk"]["dur"] < 2000.0  # us
    # not at the tail: the reply and bookkeeping follow the merge
    assert end(tree["qtrace/score"]) <= tree["qtrace/topk_merge"]["ts"]
