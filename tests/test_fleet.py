"""Fleet observatory (npairloss_tpu/obs/fleet/ — docs/OBSERVABILITY.md
§Fleet observatory): rank-stamped telemetry, the rank-aware path
scheme, straggler/skew aggregation, the fleet-report validator's teeth,
merged cross-rank timelines, and the collective/comms reconciliation.

The synthetic 4-rank fixtures hand-craft streams (skew, a missing rank,
a torn tail line, a clock offset, dropped spans) so the OFFLINE reader
contract is pinned independently of any live run; the live write path
is covered by the single-host-mesh solver test here and the real
2-process run in test_multiprocess.py (capability-gated).
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from npairloss_tpu.obs import (
    FLEET_KEYS,
    REQUIRED_KEYS,
    FleetStamp,
    RunTelemetry,
    SpanTracer,
    validate_chrome_trace,
)
from npairloss_tpu.obs.fleet import (
    build_fleet_report,
    merge_run_traces,
    validate_fleet_report,
)
from npairloss_tpu.obs.fleet import aggregate as agg
from npairloss_tpu.obs.fleet import comms as comms_mod
from npairloss_tpu.obs.fleet import stamp as stamp_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- stamp + path scheme ------------------------------------------------------


def test_stamp_keys_pin():
    # obs.sinks.FLEET_KEYS is the jax-free duplicate of STAMP_KEYS
    # (file-path loaders cannot import the package); this pin is what
    # lets them stay two literals.
    assert FLEET_KEYS == stamp_mod.STAMP_KEYS


def test_stamp_env_override_and_validation(monkeypatch):
    monkeypatch.setenv(stamp_mod.FLEET_PROCESS_ENV, "1/3")
    s = stamp_mod.fleet_stamp()
    assert (s.process_index, s.process_count) == (1, 3)
    monkeypatch.setenv(stamp_mod.FLEET_PROCESS_ENV, "junk")
    with pytest.raises(ValueError):
        stamp_mod.fleet_stamp()
    with pytest.raises(ValueError):
        FleetStamp(3, 3)  # rank out of range
    assert stamp_mod.resolve_fleet(None) is None
    assert stamp_mod.resolve_fleet(False) is None
    monkeypatch.delenv(stamp_mod.FLEET_PROCESS_ENV)
    # jax is imported under conftest: resolve_fleet(True) reads it.
    s = stamp_mod.resolve_fleet(True)
    assert s.process_count >= 1 and s.process_index == 0


def test_rank_path_scheme(tmp_path):
    assert stamp_mod.rank_metrics_name(3) == "telemetry.r3.jsonl"
    assert stamp_mod.rank_trace_name(0) == "trace.r0.json"
    assert stamp_mod.rank_of_file("telemetry.r12.jsonl") == 12
    assert stamp_mod.rank_of_file("metrics.jsonl") is None
    assert stamp_mod.rank_of_file("trace.json") is None
    for name in ("telemetry.r0.jsonl", "trace.r2.json", "manifest.r1.json",
                 "metrics.jsonl"):
        (tmp_path / name).write_text("{}\n")
    assert stamp_mod.discover_ranks(str(tmp_path)) == [0, 1, 2]


# -- RunTelemetry: fleet layout vs byte-identical parity ----------------------


def test_runtelemetry_fleet_layout_and_stamping(tmp_path):
    run = tmp_path / "run"
    for k in range(2):
        tel = RunTelemetry(str(run), fleet=FleetStamp(k, 2, (k,)))
        tel.write_manifest(config={"k": k})
        tel.log("train", 1, {"loss": 0.5})
        with tel.span("step/dispatch", batch=4, step=1):
            pass
        tel.close()
    names = sorted(os.listdir(run))
    assert names == [
        "manifest.r0.json", "manifest.r1.json",
        "telemetry.r0.jsonl", "telemetry.r1.jsonl",
        "trace.r0.json", "trace.r1.json",
    ]
    for k in range(2):
        rows = [json.loads(ln) for ln in
                (run / f"telemetry.r{k}.jsonl").read_text().splitlines()]
        for row in rows:
            for key in REQUIRED_KEYS + FLEET_KEYS:
                assert key in row, key
            assert row["process_index"] == k
            assert row["process_count"] == 2
            assert row["local_device_ids"] == [k]
        man = json.load(open(run / f"manifest.r{k}.json"))
        assert man["fleet"]["process_index"] == k
        trace = json.load(open(run / f"trace.r{k}.json"))
        assert trace["otherData"]["fleet"]["process_index"] == k


def test_runtelemetry_parity_without_fleet(tmp_path):
    """fleet=None keeps the pre-fleet contract bit-for-bit: legacy file
    names, rows carrying EXACTLY the envelope + metric keys (no rank
    stamps), no fleet block anywhere."""
    run = tmp_path / "run"
    tel = RunTelemetry(str(run))
    assert tel.fleet is None
    tel.write_manifest(config={})
    tel.log("train", 1, {"loss": 0.5})
    tel.close()
    assert sorted(os.listdir(run)) == [
        "manifest.json", "metrics.jsonl", "trace.json"]
    (row,) = [json.loads(ln) for ln in
              (run / "metrics.jsonl").read_text().splitlines()]
    assert sorted(row) == sorted(REQUIRED_KEYS + ("loss",))
    assert "fleet" not in json.load(open(run / "trace.json"))["otherData"]


# -- synthetic 4-rank fixture -------------------------------------------------

T0 = 1_700_000_000.0
STEP_S = 0.100
STRAGGLER = 2
LATE_S = 0.030
OFFSET_RANK = 3
OFFSET_S = 5.0  # rank 3's tracer origin is 5 s earlier (clock offset)


def _make_fleet_run(tmp_path, ranks=4, steps=6):
    """Hand-crafted fleet run dir: rank STRAGGLER dispatches LATE_S
    late every step; rank OFFSET_RANK's trace clock is OFFSET_S off
    (its ts values compensate, so ABSOLUTE times agree)."""
    run = tmp_path / "fleet"
    run.mkdir(exist_ok=True)
    for k in range(ranks):
        origin = T0 - (OFFSET_S if k == OFFSET_RANK else 0.0)
        late = LATE_S if k == STRAGGLER else 0.0
        events = []
        rows = []
        for s in range(1, steps + 1):
            abs_t = T0 + s * STEP_S + late
            events.append({
                "name": "step/dispatch", "ph": "X",
                "ts": (abs_t - origin) * 1e6, "dur": 500.0,
                "pid": 1000 + k, "tid": 1,
                "args": {"batch": 8, "step": s},
            })
            rows.append({
                "loss": 0.5 / s, "run_id": "fix", "step": s,
                "wall_time": abs_t + 0.001, "phase": "train",
                "process_index": k, "process_count": ranks,
                "local_device_ids": [k],
            })
        (run / f"telemetry.r{k}.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in rows))
        (run / f"trace.r{k}.json").write_text(json.dumps({
            "traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"wall_time_origin": origin},
        }))
        (run / f"manifest.r{k}.json").write_text(json.dumps({
            "run_id": "fix", "created": origin,
            "fleet": {"process_index": k, "process_count": ranks,
                      "local_device_ids": [k]},
        }))
    return run


def test_fleet_report_skew_and_straggler(tmp_path):
    run = _make_fleet_run(tmp_path)
    report = build_fleet_report(str(run))
    assert validate_fleet_report(report) is None, report
    assert report["process_count"] == 4
    assert report["ranks_present"] == [0, 1, 2, 3]
    # Dispatch-start spread = the straggler's lateness.
    skew = report["skew"]
    assert skew["source"] == "dispatch_spans"
    assert skew["dispatch_spread_ms_p50"] == pytest.approx(
        LATE_S * 1e3, rel=1e-6)
    # Slowest-rank identity with full persistence.
    assert skew["slowest"]["rank"] == STRAGGLER
    assert skew["slowest"]["share"] == 1.0
    assert skew["slowest"]["persistence"] == skew["steps_analyzed"]
    # Victims wait for the straggler; the straggler itself does not.
    by_rank = {r["rank"]: r for r in report["ranks"]}
    assert by_rank[STRAGGLER]["barrier_wait_share"] == 0.0
    assert by_rank[0]["barrier_wait_share"] > 0.0
    assert by_rank[0]["ms_per_step_p50"] == pytest.approx(
        STEP_S * 1e3, rel=1e-6)
    # Per-rank step counts agree -> no disagreement note.
    assert not any("disagree" in n for n in report["notes"])


def test_fleet_report_missing_rank_fails_validator(tmp_path):
    run = _make_fleet_run(tmp_path)
    for name in os.listdir(run):
        if ".r3." in name:
            os.unlink(run / name)
    report = build_fleet_report(str(run))
    # Manifests/rows still declare a 4-process fleet: the validator
    # must refuse a 3-rank report claiming to cover it.
    assert report["process_count"] == 4
    err = validate_fleet_report(report)
    assert err is not None and "missing" in err
    assert any("missing rank" in n for n in report["notes"])


def test_fleet_report_torn_tail_counted_not_fatal(tmp_path):
    run = _make_fleet_run(tmp_path)
    with open(run / "telemetry.r1.jsonl", "a") as f:
        f.write('{"loss": 0.1, "step": 7, "phase": "tr')  # killed mid-write
    report = build_fleet_report(str(run))
    assert validate_fleet_report(report) is None
    by_rank = {r["rank"]: r for r in report["ranks"]}
    assert by_rank[1]["torn_lines"] == 1
    assert by_rank[1]["flagged"]
    assert by_rank[0]["torn_lines"] == 0


def test_fleet_report_dropped_spans_flagged_not_averaged(tmp_path):
    run = _make_fleet_run(tmp_path)
    trace = json.load(open(run / "trace.r0.json"))
    trace["otherData"]["dropped_events"] = 7
    (run / "trace.r0.json").write_text(json.dumps(trace))
    report = build_fleet_report(str(run))
    assert validate_fleet_report(report) is None
    by_rank = {r["rank"]: r for r in report["ranks"]}
    assert by_rank[0]["spans_dropped"] == 7
    assert by_rank[0]["flagged"]
    assert any("dropped spans" in n for n in report["notes"])
    # Validator teeth: a dropped-spans rank that is NOT flagged must be
    # rejected — that is the 'flagged, not averaged' contract.
    for r in report["ranks"]:
        r["flagged"] = False
    err = validate_fleet_report(report)
    assert err is not None and "flagged" in err


def test_fleet_report_step_count_disagreement_noted(tmp_path):
    run = _make_fleet_run(tmp_path)
    lines = (run / "telemetry.r2.jsonl").read_text().splitlines()
    (run / "telemetry.r2.jsonl").write_text(
        "\n".join(lines[:-2]) + "\n")  # rank 2 lost its last 2 steps
    report = build_fleet_report(str(run))
    assert any("disagree" in n for n in report["notes"])


def test_validator_teeth(tmp_path):
    run = _make_fleet_run(tmp_path)
    good = build_fleet_report(str(run))
    assert validate_fleet_report(good) is None
    assert validate_fleet_report([]) is not None
    bad = dict(good, schema="nope")
    assert "schema" in validate_fleet_report(bad)
    bad = dict(good, ranks=[])
    assert validate_fleet_report(bad) is not None
    bad = dict(good, ranks=[{k: v for k, v in good["ranks"][0].items()
                             if k != "spans_dropped"}])
    assert "spans_dropped" in validate_fleet_report(bad)
    bad = dict(good, skew={})
    assert validate_fleet_report(bad) is not None
    bad = dict(good)
    bad.pop("comms")
    assert "comms" in validate_fleet_report(bad)


# -- merged timelines ---------------------------------------------------------


def test_merge_traces_lanes_and_clock_offsets(tmp_path):
    run = _make_fleet_run(tmp_path)
    path, merged = merge_run_traces(str(run))
    assert path == str(run / "fleet_trace.json")
    assert validate_chrome_trace(merged) is None
    lanes = {e["pid"] for e in merged["traceEvents"]}
    assert lanes == {0, 1, 2, 3}
    # One process_name metadata event per rank lane.
    names = {e["pid"]: e["args"]["name"]
             for e in merged["traceEvents"] if e["name"] == "process_name"}
    assert names == {k: f"rank {k}" for k in range(4)}
    # Clock alignment: rank 3's origin was OFFSET_S earlier; after the
    # offset re-base, its step-1 dispatch lands at the same merged ts
    # as rank 1's (both dispatch on time).
    meta = merged["otherData"]
    assert meta["clock_offsets_us"]["3"] == 0.0
    assert meta["clock_offsets_us"]["0"] == pytest.approx(OFFSET_S * 1e6)
    t_of = {
        (e["pid"], e["args"]["step"]): e["ts"]
        for e in merged["traceEvents"]
        if e.get("name") == "step/dispatch"
    }
    assert t_of[(3, 1)] == pytest.approx(t_of[(1, 1)], abs=1.0)
    assert t_of[(STRAGGLER, 1)] - t_of[(1, 1)] == pytest.approx(
        LATE_S * 1e6, rel=1e-6)


def test_merge_traces_missing_trace_noted(tmp_path):
    run = _make_fleet_run(tmp_path)
    os.unlink(run / "trace.r2.json")
    path, merged = merge_run_traces(str(run))
    assert path is not None
    assert {e["pid"] for e in merged["traceEvents"]} == {0, 1, 3}
    assert any("rank 2" in n for n in merged["otherData"]["notes"])


# -- comms reconciliation -----------------------------------------------------


def _per_opcode_fixture():
    return {
        "all-gather": {"bytes": 4096.0, "count": 2.0,
                       "regions": {"npair/gather/comm/all_gather": 4096.0}},
        "all-reduce": {"bytes": 1024.0, "count": 1.0,
                       "regions": {"MLPEmbedding/dense0": 1024.0}},
    }


def test_comm_rows_claimed_vs_unattributed():
    # No claim for the unscoped all-reduce -> its bytes are unattributed.
    out = comms_mod.comm_rows_from_hlo(_per_opcode_fixture())
    kinds = {k["kind"]: k for k in out["kinds"]}
    assert kinds["all_gather"]["claimed"]
    assert kinds["all_gather"]["scope_coverage"] == 1.0
    assert not kinds["allreduce"]["claimed"]
    assert out["unattributed_bytes"] == 1024.0
    # The solver's grad-sync claim covers it -> zero unattributed.
    out = comms_mod.comm_rows_from_hlo(
        _per_opcode_fixture(),
        extra_claims=comms_mod.grad_sync_claim_bytes(1024.0, 2))
    kinds = {k["kind"]: k for k in out["kinds"]}
    assert kinds["allreduce"]["claimed"]
    assert kinds["allreduce"]["scope_coverage"] == 0.0
    assert out["unattributed_bytes"] == 0.0


def test_effective_bandwidth_ici_vs_dcn():
    rows = comms_mod.comm_rows_from_hlo(
        _per_opcode_fixture(),
        extra_claims={"allreduce": 1024.0})
    ici = comms_mod.effective_bandwidth(rows, 10.0, "TPU v4", "ici")
    dcn = comms_mod.effective_bandwidth(rows, 10.0, "TPU v4", "dcn")
    assert ici["peak_bytes_per_s"] == 300e9
    assert dcn["peak_bytes_per_s"] == 25e9
    k = {r["kind"]: r for r in ici["kinds"]}["all_gather"]
    assert k["effective_bytes_per_s"] == pytest.approx(4096.0 / 0.010)
    u_ici = {r["kind"]: r for r in ici["kinds"]}["all_gather"][
        "link_utilization"]
    u_dcn = {r["kind"]: r for r in dcn["kinds"]}["all_gather"][
        "link_utilization"]
    assert u_dcn == pytest.approx(u_ici * 12.0, rel=1e-6)
    # No step time -> no bandwidth fabricated.
    none = comms_mod.effective_bandwidth(rows, None, "cpu", "ici")
    assert all(r["effective_bytes_per_s"] is None for r in none["kinds"])


def test_interconnect_peak_specs():
    from npairloss_tpu.obs.perf.roofline import chip_peaks, interconnect_peak

    spec = chip_peaks("TPU v4")
    assert interconnect_peak(spec, "ici") == 300e9
    assert interconnect_peak(spec, "dcn") == 25e9
    with pytest.raises(ValueError):
        interconnect_peak(spec, "pcie")
    # The CPU reference spec is flagged and has a DCN column too.
    fb = chip_peaks("cpu")
    assert not fb.known and fb.dcn_bytes_per_s > 0


_SYNTHETIC_HLO = """\
HloModule toy

%body (p: (s32[], f32[4,8], f32[4,8], f32[4,8], f32[4,8], f32[4,8], f32[4,8])) -> (s32[], f32[4,8], f32[4,8], f32[4,8], f32[4,8], f32[4,8], f32[4,8]) {
  %p = (s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) parameter(0)
  %gte = f32[4,8]{1,0} get-tuple-element((s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) %p), index=1
  %cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %gte), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(f)/comm/ppermute/ppermute"}
  ROOT %t = (s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) tuple(%p)
}

%cond (p: (s32[], f32[4,8], f32[4,8], f32[4,8], f32[4,8], f32[4,8], f32[4,8])) -> pred[] {
  %p = (s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) parameter(0)
  %iv = s32[] get-tuple-element((s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) %p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(s32[] %iv, s32[] %n), direction=LT
}

ENTRY %main (a: f32[4,8]) -> f32[8,8] {
  %a = f32[4,8]{1,0} parameter(0)
  %ag = f32[8,8]{1,0} all-gather(f32[4,8]{1,0} %a), dimensions={0}, metadata={op_name="jit(f)/npair/gather/comm/all_gather/all_gather"}
  %init = (s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) tuple(%a)
  %w = (s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) while((s32[], f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, f32[4,8]{1,0}, /*index=5*/f32[4,8]{1,0}, f32[4,8]{1,0}) %init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} add(f32[8,8]{1,0} %ag, f32[8,8]{1,0} %ag)
}
"""


def test_collective_bytes_by_opcode_trips_and_big_tuple_while():
    """Pins the large-carry ``while`` parse: XLA comments tuple element
    indices past 4 (``/*index=5*/``), which the old =-excluding type
    charset failed on — the whole ring scan body then went unwalked
    and every collective-permute byte silently vanished."""
    from npairloss_tpu.obs.perf.hlo import collective_bytes_by_opcode

    out = collective_bytes_by_opcode(_SYNTHETIC_HLO)
    assert out["all-gather"]["bytes"] == 8 * 8 * 4
    assert out["all-gather"]["count"] == 1
    assert "comm/all_gather" in next(iter(out["all-gather"]["regions"]))
    # collective-permute inside the 3-trip while body: x3.
    assert out["collective-permute"]["count"] == 3
    assert out["collective-permute"]["bytes"] == 3 * 4 * 8 * 4
    assert all("comm/ppermute" in r
               for r in out["collective-permute"]["regions"])


# -- solver integration: spans_dropped + the single-host fleet path ----------


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


def test_solver_spans_dropped_in_window_rows(tmp_path):
    """Satellite: the tracer-cap drop counter must surface in the
    solver's display-window rows (the serve window rows' contract,
    uniform for training) — and stay ABSENT when nothing dropped."""
    from npairloss_tpu.data import synthetic_identity_batches
    from npairloss_tpu.train import SolverConfig

    run = tmp_path / "run"
    tel = RunTelemetry(str(run))
    tel.tracer = SpanTracer(max_events=2)  # force the cap immediately
    solver = _tiny_solver(telemetry=tel, cfg=SolverConfig(
        base_lr=0.1, lr_policy="fixed", momentum=0.9, weight_decay=0.0,
        display=2, test_interval=0, snapshot=0,
    ))
    batches = synthetic_identity_batches(8, 8, 2, (8,), noise=0.5)
    solver.train(batches, num_iters=4)
    tel.close()
    rows = [json.loads(ln) for ln in
            (run / "metrics.jsonl").read_text().splitlines()]
    display = [r for r in rows if r["phase"] == "train"
               and r["step"] % 2 == 0]
    off = [r for r in rows if r["phase"] == "train" and r["step"] % 2]
    assert all(r.get("spans_dropped", 0) > 0 for r in display), display
    assert all("spans_dropped" not in r for r in off)


@pytest.mark.parametrize("engine", ["dense"])
def test_solver_single_host_fleet_path(tmp_path, engine):
    """The whole fleet path exercisable today on the single-host mesh
    (the ISSUE's core promise): forced fleet stamping on a 2-device
    mesh leaves rank-stamped rows, step-numbered dispatch spans,
    per-step comm marks, and fleet_comms.json — and `build_fleet_report`
    over the run dir reconciles every collective byte."""
    import jax

    from npairloss_tpu.data import synthetic_identity_batches
    from npairloss_tpu.parallel import data_parallel_mesh

    run = tmp_path / "run"
    tel = RunTelemetry(str(run), fleet=True)
    assert tel.fleet is not None and tel.fleet.process_count == 1
    mesh = data_parallel_mesh(jax.devices()[:2])
    solver = _tiny_solver(telemetry=tel, mesh=mesh, engine=engine)
    batches = synthetic_identity_batches(8, 8, 2, (8,), noise=0.5)
    solver.train(batches, num_iters=3)
    tel.close()

    rows = [json.loads(ln) for ln in
            (run / "telemetry.r0.jsonl").read_text().splitlines()]
    assert all(r["process_index"] == 0 for r in rows)
    assert os.path.exists(run / "fleet_comms.json")
    trace = json.load(open(run / "trace.r0.json"))
    dispatches = [e for e in trace["traceEvents"]
                  if e["name"].startswith(("step/dispatch", "step/compile"))
                  and e.get("ph") == "X"]
    assert sorted(e["args"]["step"] for e in dispatches) == [1, 2, 3]
    marks = [e for e in trace["traceEvents"]
             if e["name"].startswith("comm/") and e.get("ph") == "i"]
    assert marks and all("bytes" in e["args"] for e in marks)

    report = build_fleet_report(str(run))
    assert validate_fleet_report(report) is None, report
    comms = report["comms"]
    assert comms["available"]
    assert comms["unattributed_bytes"] == 0, comms
    kinds = {k["kind"]: k for k in comms["kinds"]}
    assert kinds["all_gather"]["scope_coverage"] == 1.0
    assert all(k["claimed"] for k in comms["kinds"])
    assert report["skew"]["source"] == "dispatch_spans"


def test_solver_fleet_comms_captured_on_late_telemetry_attach(tmp_path):
    """Review-round pin: attaching fleet telemetry AFTER the step
    already compiled (a warmed solver, the mp harness) must still
    capture the collective pricing at the next dispatch — the capture
    is gated on first-dispatch-under-fleet, not on a recompile that
    will never come."""
    import jax

    from npairloss_tpu.data import synthetic_identity_batches
    from npairloss_tpu.parallel import data_parallel_mesh

    mesh = data_parallel_mesh(jax.devices()[:2])
    solver = _tiny_solver(mesh=mesh)
    batches = synthetic_identity_batches(8, 8, 2, (8,), noise=0.5)
    x, lab = next(batches)
    solver.step(x, lab)  # compiles WITHOUT telemetry

    run = tmp_path / "run"
    tel = RunTelemetry(str(run), fleet=True)
    solver.telemetry = tel
    solver.train(batches, num_iters=3, log_fn=lambda s: None)
    tel.close()
    assert os.path.exists(run / "fleet_comms.json")
    report = build_fleet_report(str(run))
    assert report["comms"]["available"]
    assert report["comms"]["unattributed_bytes"] == 0


def test_solver_fleet_comms_repriced_on_recompile(tmp_path):
    """Review-round pin: a new batch signature is a NEW program with
    new collective payloads — the comm marks after the recompile must
    carry the new program's bytes, not the first signature's."""
    import jax

    from npairloss_tpu.parallel import data_parallel_mesh

    run = tmp_path / "run"
    tel = RunTelemetry(str(run), fleet=True)
    mesh = data_parallel_mesh(jax.devices()[:2])
    solver = _tiny_solver(telemetry=tel, mesh=mesh)
    rng = np.random.default_rng(0)

    def batch(n):
        f = rng.standard_normal((n, 8)).astype(np.float32)
        l = np.repeat(np.arange(n // 2), 2).astype(np.int32)
        return f, l

    solver.step(*batch(16))
    big = list(solver._comm_kinds)
    solver.step(*batch(8))  # dynamic-batch tail: recompiles
    small = list(solver._comm_kinds)
    tel.close()
    big_b = {k: b for k, b, _ in big}
    small_b = {k: b for k, b, _ in small}
    assert big_b.keys() == small_b.keys()
    assert all(small_b[k] < big_b[k] for k in big_b), (big_b, small_b)
    # And the emitted marks follow: the last comm marks carry the
    # small program's bytes.
    trace = tel.tracer.to_chrome_trace()
    marks = [e for e in trace["traceEvents"]
             if e["name"].startswith("comm/") and e.get("ph") == "i"]
    last_by_kind = {e["name"]: e["args"]["bytes"] for e in marks}
    for kind, b in small_b.items():
        assert last_by_kind[f"comm/{kind}"] == b


def test_merge_traces_drops_malformed_events(tmp_path):
    """One rank's damaged trace (an 'X' event without dur) must not
    invalidate the merged fleet timeline — malformed events are
    dropped at merge, per the never-fatal contract."""
    run = _make_fleet_run(tmp_path)
    trace = json.load(open(run / "trace.r1.json"))
    trace["traceEvents"].append({"name": "broken", "ph": "X",
                                 "ts": 1.0, "pid": 9, "tid": 1})
    trace["traceEvents"].append({"ph": "i", "ts": 2.0})  # no name
    (run / "trace.r1.json").write_text(json.dumps(trace))
    _, merged = merge_run_traces(str(run))
    assert validate_chrome_trace(merged) is None
    assert not any(e.get("name") == "broken"
                   for e in merged["traceEvents"])


def test_solver_without_fleet_keeps_trace_and_stream_shape(tmp_path):
    """Parity pin: a non-fleet solver run must emit NO comm marks, NO
    step args on dispatch spans, NO fleet_comms.json — the pre-fleet
    artifacts exactly."""
    from npairloss_tpu.data import synthetic_identity_batches

    run = tmp_path / "run"
    tel = RunTelemetry(str(run))
    solver = _tiny_solver(telemetry=tel)
    batches = synthetic_identity_batches(8, 8, 2, (8,), noise=0.5)
    solver.train(batches, num_iters=2)
    tel.close()
    assert not os.path.exists(run / "fleet_comms.json")
    trace = json.load(open(run / "trace.json"))
    assert not any(e["name"].startswith("comm/")
                   for e in trace["traceEvents"])
    for e in trace["traceEvents"]:
        if e["name"] in ("step/dispatch", "step/compile"):
            assert "step" not in (e.get("args") or {}), e


# -- bench_check --fleet-report gate ------------------------------------------


def _load_bench_check():
    spec = importlib.util.spec_from_file_location(
        "_bench_check_fleet", os.path.join(REPO, "scripts",
                                           "bench_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_check_fleet_report_gate(tmp_path):
    bc = _load_bench_check()
    run = _make_fleet_run(tmp_path)
    report = build_fleet_report(str(run))
    good = tmp_path / "good.json"
    good.write_text(json.dumps(report))
    assert bc.check_fleet_report(str(good)) == []
    assert bc.main(["--fleet-report", str(good)]) == 0

    # Per-rank step counts disagreeing must be refused.
    bad = json.loads(good.read_text())
    bad["ranks"][2]["steps"] -= 2
    p = tmp_path / "bad_steps.json"
    p.write_text(json.dumps(bad))
    vio = bc.check_fleet_report(str(p))
    assert vio and "disagree" in vio[0]
    assert bc.main(["--fleet-report", str(p)]) == 1

    # Unattributed collective bytes must be refused.
    bad = json.loads(good.read_text())
    bad["comms"] = {"available": True, "kinds": [
        {"kind": "all_to_all", "bytes_per_step": 9.0, "claimed": False,
         "effective_bytes_per_s": None, "link_utilization": None}],
        "unattributed_bytes": 9.0}
    p = tmp_path / "bad_comms.json"
    p.write_text(json.dumps(bad))
    vio = bc.check_fleet_report(str(p))
    assert vio and "unattributed" in vio[0]

    # Schema-invalid is refused via the ONE contract.
    bad = json.loads(good.read_text())
    bad["schema"] = "nope"
    p = tmp_path / "bad_schema.json"
    p.write_text(json.dumps(bad))
    vio = bc.check_fleet_report(str(p))
    assert vio and "schema" in vio[0]

    # All-zero step counts AGREE but measured nothing — refused.
    bad = json.loads(good.read_text())
    for r in bad["ranks"]:
        r["steps"] = 0
    p = tmp_path / "bad_zero.json"
    p.write_text(json.dumps(bad))
    vio = bc.check_fleet_report(str(p))
    assert vio and "0 steps" in vio[0]
