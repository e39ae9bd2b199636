"""Perf observatory (npairloss_tpu/obs/perf/ + scripts/bench_check.py —
docs/OBSERVABILITY.md §Perf observatory).

Pins: the one shared cost/MFU helper (list-vs-dict cost_analysis,
missing keys), named-scope -> region aggregation on a toy 2-scope
jitted fn, roofline bound-class classification on synthetic fixtures,
span-stream step-time decomposition with the exact reconciliation
invariant, serve-span latency splits, the versioned report schema, and
that bench_check gates artifacts only (no trajectory mode is left).
All tier-1-fast: no device profiler, tiny jitted programs only.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- costs: the one shared helper ---------------------------------------------

class _Stage:
    def __init__(self, ret=None, raise_=False):
        self._ret, self._raise = ret, raise_

    def cost_analysis(self):
        if self._raise:
            raise RuntimeError("no analysis on this backend")
        return self._ret


def test_cost_helper_missing_keys_degrade():
    """Missing keys, non-numeric values and failing analyses are handled
    in ONE place and degrade to None, never raise."""
    from npairloss_tpu.obs.perf.costs import (
        cost_analysis_dict,
        cost_flops,
        mfu_from_timing,
    )

    assert cost_flops(_Stage({"flops": 10.0})) == 10.0
    assert cost_flops(_Stage({})) is None               # missing key
    assert cost_flops(_Stage({"flops": 0.0})) is None   # non-positive
    assert cost_flops(_Stage(raise_=True)) is None      # degrade, not raise
    assert cost_analysis_dict(
        _Stage({"flops": 1.0, "bad": "x"})) == {"flops": 1.0}

    est = mfu_from_timing(_Stage({"flops": 275e12}), seconds=1.0,
                          steps=1, device_kind="TPU v4")
    assert est["step_flops"] == 275e12
    assert est["mfu"] == pytest.approx(1.0)
    # Unknown chip / no analysis: keys present, values None.
    est = mfu_from_timing(_Stage(raise_=True), seconds=1.0,
                          device_kind="quantum abacus")
    assert est == {"step_flops": None, "mfu": None}


def test_exactly_one_mfu_helper_home():
    """utils.profiling re-exports the SAME objects — no second
    implementation survives anywhere."""
    from npairloss_tpu.obs.perf import costs
    from npairloss_tpu.utils import profiling

    assert profiling.cost_flops is costs.cost_flops
    assert profiling.peak_flops is costs.peak_flops
    assert profiling.PEAK_FLOPS is costs.PEAK_FLOPS
    assert profiling.mfu_from_timing is costs.mfu_from_timing


# -- hlo: region aggregation --------------------------------------------------

def test_region_of_paths():
    from npairloss_tpu.obs.perf.hlo import UNSCOPED, region_of

    assert region_of(
        "jit(step)/jit(main)/jvp(npair/sim)/dot_general") == "npair/sim"
    assert region_of(
        "jit(step)/jit(main)/transpose(jvp(MLPEmbedding))/head/dot_general"
    ) == "MLPEmbedding/head"
    # scan/while structural segments vanish; the scope survives.
    assert region_of(
        "jit(topk)/jit(main)/while/body/serve/score/dot") == "serve/score"
    assert region_of("jit(f)/jit(main)/add") == UNSCOPED
    assert region_of("x") == UNSCOPED
    assert region_of("") == UNSCOPED
    # depth truncation
    assert region_of(
        "jit(s)/jit(main)/jvp(A)/b/c/prim", depth=1) == "A"
    assert region_of(
        "jit(s)/jit(main)/jvp(A)/b/c/prim", depth=0) == "A/b/c"


def test_named_scope_region_aggregation_toy():
    """A 2-scope jitted fn attributes its gemm EXACTLY to its scope
    (2*M*N*K) with bytes and a nonzero elementwise share in the other,
    reconciling against XLA's own total."""
    import jax
    import jax.numpy as jnp

    from npairloss_tpu.obs.perf import (
        attribute_regions,
        cost_flops,
        stage_hlo_text,
    )

    n = 64

    def f(x):
        with jax.named_scope("regA"):
            y = x @ x
        with jax.named_scope("regB"):
            return jnp.sum(jnp.tanh(y))

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    regions = attribute_regions(stage_hlo_text(comp))
    regions.pop("_notes", None)
    assert "regA" in regions and "regB" in regions
    assert regions["regA"]["flops"] == 2.0 * n * n * n
    assert regions["regA"]["bytes"] > 0
    assert regions["regB"]["flops"] >= n * n  # tanh at least
    total = sum(r["flops"] for r in regions.values())
    xla = cost_flops(comp)
    assert xla is not None
    assert total == pytest.approx(xla, rel=0.2)


def test_instr_regex_matches_tpu_tiled_layouts():
    """TPU-optimized HLO stamps tiled layouts on result types
    (``f32[8,16]{1,0:T(8,128)}``, conv tiles like ``T(8,128)(2,1)``);
    the instruction regex must still match them.  CPU HLO carries no
    tiling, so only this pin catches the chip-only parse miss (which
    would silently empty the region table exactly on the platform the
    observatory targets)."""
    from npairloss_tpu.obs.perf.hlo import _INSTR_RE, _shapes_in

    m = _INSTR_RE.match(
        "  %fusion.1 = f32[8,16]{1,0:T(8,128)} fusion(%p0), kind=kLoop")
    assert m and m.group("opcode") == "fusion"
    assert _shapes_in(m.group("type")) == [("f32", (8, 16))]
    m = _INSTR_RE.match(
        "  ROOT %conv.2 = f32[4,14,14,32]{3,2,1,0:T(8,128)(2,1)} "
        "convolution(%a, %b), window={size=3x3}")
    assert m and m.group("opcode") == "convolution"
    assert _shapes_in(m.group("type")) == [("f32", (4, 14, 14, 32))]
    m = _INSTR_RE.match(
        "  %dot.3 = bf16[128,256]{1,0:T(8,128)(2,1)S(1)} dot(%x, %y)")
    assert m and m.group("opcode") == "dot"


def test_scan_body_multiplied_by_trip_count():
    """A lax.scan body's flops count once per trip (XLA's
    known_trip_count backend_config, else the condition-compare
    heuristic — found via the ``condition=`` attribute, not by call
    order: HLO prints condition before body).  Scan-based programs
    (ring/blockwise engines, the serve gallery stream) would otherwise
    undercount by the trip factor."""
    import jax
    import jax.numpy as jnp

    from npairloss_tpu.obs.perf import attribute_regions, stage_hlo_text

    n, trips = 8, 7

    def f(x):
        def body(c, _):
            with jax.named_scope("scanreg"):
                return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=trips)
        return jnp.sum(y)

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    regions = attribute_regions(stage_hlo_text(comp))
    notes = regions.pop("_notes", [])
    assert not any("trip_count_unknown" in str(x) for x in notes)
    assert regions["scanreg"]["flops"] == trips * 2.0 * n * n * n


# -- roofline -----------------------------------------------------------------

def test_roofline_classification_fixtures():
    from npairloss_tpu.obs.perf.roofline import (
        BOUND_CLASSES,
        chip_peaks,
        classify,
    )

    spec = chip_peaks("TPU v4")
    assert spec.known
    # High arithmetic intensity: way right of the ridge -> compute.
    c = classify(flops=spec.flops, bytes_accessed=1.0, spec=spec)
    assert c["bound"] == "compute"
    assert c["ai"] == pytest.approx(spec.flops)
    assert c["est_ms_at_roofline"] == pytest.approx(1e3)
    # One byte per flop: far left of the ridge -> memory.
    m = classify(flops=1e9, bytes_accessed=1e9, spec=spec)
    assert m["bound"] == "memory"
    # Interconnect-dominated -> collective.
    i = classify(flops=1.0, bytes_accessed=1.0,
                 collective_bytes=spec.ici_bytes_per_s, spec=spec)
    assert i["bound"] == "collective"
    assert i["est_ms_at_roofline"] == pytest.approx(1e3)
    # Nothing at all -> unknown.
    assert classify(0.0, 0.0, 0.0, spec)["bound"] == "unknown"
    assert all(x in BOUND_CLASSES
               for x in ("compute", "memory", "collective", "unknown"))
    # The CPU backend (and "no device") get the flagged reference spec.
    assert not chip_peaks("cpu").known
    assert not chip_peaks("").known
    assert chip_peaks("TPU v5 lite").hbm_bytes_per_s == 819e9


def test_unknown_accelerator_is_an_error_not_v4():
    """A device kind missing from the table must not borrow another
    chip's peaks — in the roofline or in the engine plan that reads it
    (parallel.plan picks dense vs ring from these numbers)."""
    from npairloss_tpu.obs.perf.roofline import chip_peaks
    from npairloss_tpu.parallel.plan import plan_engine

    with pytest.raises(ValueError, match="no peak spec"):
        chip_peaks("TPU v9 hyper")
    with pytest.raises(ValueError, match="no peak spec"):
        plan_engine(n_devices=4, n_hosts=1, shard_rows=30, emb_dim=1024,
                    device_kind="TPU v9 hyper")
    plan = plan_engine(n_devices=4, n_hosts=1, shard_rows=30,
                       emb_dim=1024, device_kind="TPU v5 lite")
    assert plan.to_dict()["peak_known"] is True


# -- decompose ----------------------------------------------------------------

def _ev(name, ts, dur, tid=1):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": tid}


def test_decompose_reconciles_and_nests():
    from npairloss_tpu.obs.perf.decompose import decompose_step_time

    events = [
        _ev("data/next_batch", 0, 1000),
        _ev("step/dispatch", 1000, 2000),
        _ev("step/device_wait", 3000, 4000),
        # eval contains eval/compile: self-time split, no double count.
        _ev("eval", 7000, 3000),
        _ev("eval/compile", 7500, 1000),
        # A staging-thread span must NOT be summed into the loop wall.
        _ev("pipeline/stage", 0, 9000, tid=2),
    ]
    dec = decompose_step_time(events, wall_ms=12.0)
    parts = dec["parts"]
    assert parts["data_wait"] == pytest.approx(1.0)
    assert parts["dispatch"] == pytest.approx(2.0)
    assert parts["device_compute"] == pytest.approx(4.0)
    assert parts["compile"] == pytest.approx(1.0)   # nested slice only
    assert parts["eval"] == pytest.approx(2.0)      # self time
    assert "h2d" not in parts                       # other thread
    # THE invariant: sum(parts) + unattributed == wall, exactly.
    assert sum(parts.values()) + dec["unattributed_ms"] == pytest.approx(
        dec["wall_ms"], abs=1e-6)
    assert dec["unattributed_ms"] == pytest.approx(2.0)


def test_decompose_unattributed_never_silently_absorbed():
    from npairloss_tpu.obs.perf.decompose import decompose_step_time

    dec = decompose_step_time([], wall_ms=5.0)
    assert dec["parts"] == {}
    assert dec["unattributed_ms"] == pytest.approx(5.0)


def test_decompose_serve_mode_admits_stage_categories():
    """A serve-step decomposition carries the serving stages as
    first-class parts (train mode still buries them in other_span — its
    category vocabulary is pinned); reconciliation holds either way."""
    from npairloss_tpu.obs.perf.decompose import decompose_step_time

    events = [_ev("serve/topk", 0, 2000), _ev("serve/encode", 3000, 1000)]
    dec = decompose_step_time(events, wall_ms=5.0, serve=True)
    assert dec["parts"]["topk"] == pytest.approx(2.0)
    assert dec["parts"]["encode"] == pytest.approx(1.0)
    assert sum(dec["parts"].values()) + dec["unattributed_ms"] == \
        pytest.approx(dec["wall_ms"], abs=1e-6)
    train = decompose_step_time(events, wall_ms=5.0)
    assert "topk" not in train["parts"]
    assert train["parts"]["other_span"] == pytest.approx(3.0)


def test_serve_span_decomposition_from_recorded_stream():
    from npairloss_tpu.obs.perf.decompose import (
        serve_latency_decomposition,
    )

    events = []
    # 100 topk spans of 1..100 ms, a few encode spans, on mixed tids.
    for i in range(100):
        events.append(_ev("serve/topk", i * 2000, (i + 1) * 1000,
                          tid=i % 3))
    for i in range(4):
        events.append(_ev("serve/encode", i * 500, 2000))
    events.append(_ev("serve/batch", 0, 3000))
    events.append(_ev("step/dispatch", 0, 1000))  # not a serve stage
    split = serve_latency_decomposition(events)
    assert set(split) == {"topk", "encode", "batch"}
    assert split["topk"]["count"] == 100
    assert split["topk"]["p50_ms"] == pytest.approx(50.0, abs=2.0)
    assert split["topk"]["p99_ms"] == pytest.approx(99.0, abs=2.0)
    assert split["encode"]["p50_ms"] == pytest.approx(2.0)
    # since_us cuts the window.
    late = serve_latency_decomposition(events, since_us=150_000)
    assert late["topk"]["count"] < 100


def test_serve_window_counts_boundary_straddling_spans():
    """The window cursor filters on span END: a long span in flight
    across the boundary belongs to the window it finished in — start-
    time filtering would drop exactly the longest (tail) spans and
    bias p99 low."""
    from npairloss_tpu.obs.perf.decompose import (
        serve_latency_decomposition,
    )

    straddler = _ev("serve/dispatch", 900, 5000)   # ends at 5900
    done_early = _ev("serve/dispatch", 0, 500)     # ends at 500
    split = serve_latency_decomposition(
        [straddler, done_early], since_us=1000)
    assert split["dispatch"]["count"] == 1
    assert split["dispatch"]["p99_ms"] == pytest.approx(5.0)


def test_tracer_events_since_incremental():
    """The serve windows' incremental read: each call returns only the
    spans FINISHED since the last cursor, O(window) not O(buffer), and
    surfaces the max_events drop count."""
    from npairloss_tpu.obs.tracing import SpanTracer

    tracer = SpanTracer(max_events=3)
    with tracer.span("serve/topk"):
        pass
    evs, idx, dropped = tracer.events_since(0)
    assert [e["name"] for e in evs] == ["serve/topk"] and dropped == 0
    with tracer.span("serve/encode"):
        with tracer.span("serve/dispatch"):
            pass
    evs, idx, dropped = tracer.events_since(idx)
    # Appends happen at span END — the nested span closed first.
    assert [e["name"] for e in evs] == ["serve/dispatch", "serve/encode"]
    with tracer.span("serve/topk"):  # over the cap: dropped, reported
        pass
    evs, idx, dropped = tracer.events_since(idx)
    assert evs == [] and dropped == 1


# -- report schema ------------------------------------------------------------

def test_report_schema_pinned_and_validator():
    import jax
    import jax.numpy as jnp

    from npairloss_tpu.obs.perf import (
        REPORT_SCHEMA,
        build_report,
        render_table,
        validate_report,
    )
    from npairloss_tpu.obs.perf.report import REGION_KEYS

    def f(x):
        with jax.named_scope("regA"):
            return jnp.sum(x @ x)

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    events = [_ev("step/dispatch", 0, 4000),
              _ev("step/device_wait", 4000, 5000)]
    report = build_report(
        step="train", device_kind="TPU v4", batch=32, stage=comp,
        span_events=events, wall_ms=10.0, ms_per_step=10.0, steps=1,
    )
    assert validate_report(report) is None
    assert report["schema"] == REPORT_SCHEMA
    names = {r["region"] for r in report["regions"]}
    assert "regA" in names
    for row in report["regions"]:
        for key in REGION_KEYS:
            assert key in row, key
    # Round-trips through JSON (the on-disk artifact).
    assert validate_report(json.loads(json.dumps(report))) is None
    assert "regA" in render_table(report)

    # Validator teeth: bad bound, missing key, broken reconciliation.
    bad = json.loads(json.dumps(report))
    bad["regions"][0]["bound"] = "quantum"
    assert "bound" in validate_report(bad)
    bad = json.loads(json.dumps(report))
    del bad["regions"][0]["ai"]
    assert "ai" in validate_report(bad)
    bad = json.loads(json.dumps(report))
    bad["decomposition"]["unattributed_ms"] += 5.0
    assert "reconcile" in validate_report(bad)
    assert validate_report({"schema": "nope"}) is not None


# -- solver perf rows ---------------------------------------------------------

def test_solver_perf_rows_opt_in(tmp_path):
    """perf_metrics=True emits one phase="perf" row per display window
    (ms_per_step + emb_per_sec, MFU only when the chip is known); the
    default emits NONE (the sync-vs-pipelined byte-parity contract
    covers perf rows only when both runs opt in)."""
    from conftest import make_identity_batch

    from npairloss_tpu import NPairLossConfig
    from npairloss_tpu.models import get_model
    from npairloss_tpu.obs import RunTelemetry
    from npairloss_tpu.train import Solver, SolverConfig

    def run(tag, perf):
        rng = np.random.default_rng(0)

        def batches():
            while True:
                (f,), (l,) = make_identity_batch(rng, 4, 2, 8)
                yield f, l

        solver = Solver(
            get_model("mlp", hidden=(8,), embedding_dim=4),
            NPairLossConfig(),
            SolverConfig(base_lr=0.01, lr_policy="fixed", display=2,
                         snapshot=0, test_interval=0),
            input_shape=(8,), perf_metrics=perf,
        )
        tel = RunTelemetry(str(tmp_path / tag), trace=False)
        solver.telemetry = tel
        try:
            solver.train(batches(), num_iters=4, log_fn=lambda s: None)
        finally:
            tel.close()
        rows = [json.loads(line)
                for line in open(tmp_path / tag / "metrics.jsonl")]
        return [r for r in rows if r["phase"] == "perf"]

    perf_rows = run("on", True)
    # display=2 over 4 steps -> boundaries at 2 and 4; the first arms
    # the window, the second emits.
    assert len(perf_rows) == 1
    row = perf_rows[0]
    assert row["step"] == 4
    assert row["ms_per_step"] > 0
    assert row["emb_per_sec"] > 0
    assert row["step_flops"] > 0
    assert "mfu" not in row  # CPU: unknown peak -> no made-up MFU
    assert run("off", False) == []


# -- serve window breakdown ---------------------------------------------------

def test_serve_summary_latency_split(tmp_path):
    """The drain summary (and window rows) carry the per-stage p50/p99
    split read from the serve/* spans."""
    from npairloss_tpu.obs import RunTelemetry
    from npairloss_tpu.serve import (
        EngineConfig,
        GalleryIndex,
        QueryEngine,
        RetrievalServer,
    )
    from npairloss_tpu.serve.batcher import BatcherConfig

    rng = np.random.default_rng(0)
    emb = rng.standard_normal((64, 16)).astype(np.float32)
    index = GalleryIndex.build(emb, np.arange(64).astype(np.int32) % 8)
    tel = RunTelemetry(str(tmp_path / "serve"), metrics=True)
    engine = QueryEngine(index, EngineConfig(top_k=3, buckets=(1, 4)),
                         telemetry=tel)
    engine.warmup()
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=4, max_delay_ms=10.0),
        telemetry=tel,
    )
    server.batcher.start()
    try:
        answers = server.handle_many([
            {"id": i, "embedding": emb[i].tolist()} for i in range(6)
        ])
    finally:
        server.batcher.close(drain=True)
    assert all("neighbors" in a for a in answers)
    s = server.summary()
    assert "topk_p50_ms" in s and "topk_p99_ms" in s
    assert s["topk_p50_ms"] > 0
    tel.close()


def test_server_latency_split_excludes_warmup_spans(tmp_path):
    """Pre-construction serve/* spans (warmup's XLA compiles — cmd_serve
    warms the engine BEFORE building the server) never enter the window
    rows or the drain summary: both cursors baseline at construction,
    so seconds-long compile spans can't masquerade as serving p99."""
    from npairloss_tpu.obs import RunTelemetry
    from npairloss_tpu.serve import (
        EngineConfig,
        GalleryIndex,
        QueryEngine,
        RetrievalServer,
    )
    from npairloss_tpu.serve.batcher import BatcherConfig

    rng = np.random.default_rng(0)
    emb = rng.standard_normal((16, 8)).astype(np.float32)
    index = GalleryIndex.build(emb, np.arange(16).astype(np.int32) % 4)
    tel = RunTelemetry(str(tmp_path / "serve"), metrics=True)
    with tel.tracer.span("serve/topk"):  # the "warmup compile" span
        pass
    engine = QueryEngine(index, EngineConfig(top_k=3, buckets=(1,)),
                         telemetry=tel)
    server = RetrievalServer(engine, BatcherConfig(max_batch=1),
                             telemetry=tel)
    s = server.summary()  # zero queries served -> zero split keys
    assert not any(k.startswith("topk_") for k in s)
    assert not server._window_latency_split()
    tel.close()


# -- bench_check: artifact gates only -----------------------------------------

def _load_bench_check():
    spec = importlib.util.spec_from_file_location(
        "_bench_check", os.path.join(REPO, "scripts", "bench_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize(
    "argv", [[], ["--history", "h.jsonl"], ["--tol", "0.1"]],
    ids=["no-mode", "history", "tol"])
def test_bench_check_gates_artifacts_only(argv, capsys):
    """No trajectory gate is left: with no mode the gate refuses to
    guess and names the artifact modes only; --history / --tol are not
    options (speed is the benchmark's and the driver's to compare)."""
    bc = _load_bench_check()
    with pytest.raises(SystemExit) as e:
        bc.main(argv)
    assert e.value.code == 2
    err = capsys.readouterr().err
    if not argv:
        for mode in ("--fleet-report", "--alerts", "--remediation",
                     "--quality", "--gameday", "--qtrace", "--wal",
                     "--tenants", "--static"):
            assert mode in err, mode
        assert "--history" not in err
    else:
        assert "unrecognized arguments" in err
