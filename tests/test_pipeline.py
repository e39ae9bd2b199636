"""Sync-free stepping tests (docs/PIPELINE.md): parity against the
synchronous loop (bit-identical params, byte-identical metric-key
streams), prefetcher drain/crash/resume, dispatch-depth bounding, the
no-mid-window-host-sync contract, and the compile-cache resolver."""

import json
import os
import threading

import numpy as np
import jax
import pytest

from npairloss_tpu import MiningMethod, NPairLossConfig
from npairloss_tpu.data import synthetic_identity_batches
from npairloss_tpu.models import get_model
from npairloss_tpu.parallel import data_parallel_mesh
from npairloss_tpu.pipeline import (
    DevicePrefetcher,
    DispatchController,
    HostSyncMonitor,
    MetricWindow,
    PrefetchStageError,
    compile_cache,
    compile_cache_dir,
    enable_compile_cache,
)
from npairloss_tpu.resilience import DivergenceConfig, failpoints
from npairloss_tpu.train import Solver, SolverConfig


def _make_solver(pipeline, mesh=None, **cfg_kw):
    kw = dict(
        base_lr=0.5, lr_policy="fixed", momentum=0.9, weight_decay=0.0,
        display=5, test_interval=0, snapshot=0, average_loss=10,
        pipeline=pipeline,
    )
    kw.update(cfg_kw)
    loss_cfg = NPairLossConfig(
        margin_diff=-0.05,
        an_mining_method=MiningMethod.HARD,
        ap_mining_method=MiningMethod.RAND,
    )
    model = get_model("mlp", hidden=(32,), embedding_dim=16)
    solver = Solver(model, loss_cfg, SolverConfig(**kw), mesh=mesh,
                    input_shape=(16,))
    batches = synthetic_identity_batches(8, 8, 2, (16,), noise=0.6)
    return solver, batches


def _params_equal(a, b):
    eq = jax.tree_util.tree_map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b,
    )
    return all(jax.tree_util.tree_leaves(eq))


# -- unit pieces -----------------------------------------------------------


class _FakeToken:
    def __init__(self, log, name):
        self.log, self.name = log, name

    def block_until_ready(self):
        self.log.append(self.name)


def test_dispatch_controller_bounds():
    log = []
    ctl = DispatchController(max_in_flight=2)
    for i in range(5):
        ctl.reserve()
        # The bound holds BEFORE each dispatch, and waits happen on the
        # OLDEST token, in order.
        assert ctl.in_flight <= 1
        ctl.admit(_FakeToken(log, i))
    assert log == [0, 1, 2]  # 5 dispatches, depth 2 -> blocked on 0,1,2
    ctl.drain()
    assert log == [0, 1, 2, 3, 4]
    assert ctl.blocked == 3
    with pytest.raises(ValueError):
        DispatchController(0)


def test_metric_window_roundtrip_and_streak():
    win = MetricWindow(["loss", "top1"], capacity=4)
    ring = win.init_ring()
    for loss, top1 in ((1.0, 0.5), (float("nan"), 0.25)):
        ring = win.update(
            ring, {"loss": np.float32(loss), "top1": np.float32(top1)}
        )
    host = jax.device_get(ring)
    rows = win.read(host)
    assert [list(r) for r in rows] == [["loss", "top1"]] * 2
    assert rows[0]["loss"] == np.float32(1.0)
    assert np.isnan(rows[1]["loss"])
    assert int(host["streak"]) == 1 and int(host["max_streak"]) == 1
    # Reset rewinds the buffer but carries the in-flight streak.
    ring = win.reset(ring)
    assert int(jax.device_get(ring["pos"])) == 0
    assert int(jax.device_get(ring["streak"])) == 1
    with pytest.raises(ValueError):
        MetricWindow(["top1"], 4)  # loss is mandatory


def test_prefetcher_stages_ahead_and_closes():
    placed = []

    def place(x, lab):
        placed.append(threading.get_ident())
        return jax.device_put((x, lab))

    def gen():
        for i in range(100):
            yield np.full((2, 4), i, np.float32), \
                np.arange(2, dtype=np.int32)

    with DevicePrefetcher(gen(), place, depth=2) as pf:
        for i in range(5):
            x, lab = pf.get()
            assert float(np.asarray(x)[0, 0]) == i
        assert pf.consumed == 5 and pf.staged >= 5
    # Staging ran off the consumer thread, and close() joined it.
    assert set(placed) != {threading.get_ident()}
    assert not pf._thread.is_alive()
    with pytest.raises(RuntimeError):
        pf.get()


def test_prefetcher_end_of_data_and_failure():
    place = lambda x, lab: (x, lab)  # noqa: E731
    pf = DevicePrefetcher(iter([(1, 2)]), place, depth=2)
    assert pf.get() == (1, 2)
    with pytest.raises(StopIteration):
        pf.get()
    pf.close()

    def gen():
        yield np.zeros(1), np.zeros(1)
        yield np.zeros(1), np.zeros(1)

    failpoints.reset()
    failpoints.arm("pipeline.stage", times=1)
    try:
        pf = DevicePrefetcher(gen(), place, depth=2)
        with pytest.raises(PrefetchStageError) as ei:
            pf.get()
        assert ei.value.batch_index == 0
        pf.close()
        assert not pf._thread.is_alive()
    finally:
        failpoints.reset()


# -- parity (the acceptance pin) ------------------------------------------


def _run_with_telemetry(solver, batches, num_iters, tmp_path, tag,
                        test_batches=None):
    from npairloss_tpu.obs import RunTelemetry

    logs = []
    tel = RunTelemetry(str(tmp_path / tag), trace=False)
    solver.telemetry = tel
    try:
        last = solver.train(batches, num_iters=num_iters,
                            test_batches=test_batches, log_fn=logs.append)
    finally:
        tel.close()
    rows = [json.loads(line) for line in
            open(tmp_path / tag / "metrics.jsonl")]
    return last, logs, rows


def test_pipelined_parity_single_device(tmp_path):
    """Sync vs pipelined: byte-identical metric-key streams (telemetry
    rows AND display lines) and bit-identical params, eval included."""
    outs = {}
    for tag, pipeline in (("sync", False), ("pipe", True)):
        solver, batches = _make_solver(
            pipeline, test_interval=6, test_iter=1,
            test_initialization=False,
        )
        outs[tag] = (solver,) + _run_with_telemetry(
            solver, batches, 12, tmp_path, tag,
            test_batches=synthetic_identity_batches(8, 8, 2, (16,),
                                                    noise=0.6, seed=1),
        )
    s_sync, last_s, logs_s, rows_s = outs["sync"]
    s_pipe, last_p, logs_p, rows_p = outs["pipe"]
    assert logs_s == logs_p  # display + TEST lines, values included
    assert last_s == last_p
    # Byte-identical metric-KEY streams: same rows, same key order.
    keys_s = [list(r) for r in rows_s]
    keys_p = [list(r) for r in rows_p]
    assert keys_s == keys_p
    # And the step/phase/value payloads match (envelope wall_time/run_id
    # legitimately differ).
    for rs, rp in zip(rows_s, rows_p):
        for k in rs:
            if k not in ("wall_time", "run_id"):
                assert rs[k] == rp[k], k
    assert _params_equal(s_sync.state["params"], s_pipe.state["params"])


def test_pipelined_parity_mesh_8dev(tmp_path):
    """The acceptance pin: >= 10 steps on the virtual 8-device CPU mesh,
    bit-identical params + identical metric-key streams."""
    outs = {}
    for tag, pipeline in (("sync", False), ("pipe", True)):
        mesh = data_parallel_mesh(jax.devices()[:8])
        solver, batches = _make_solver(pipeline, mesh=mesh, display=4)
        outs[tag] = (solver,) + _run_with_telemetry(
            solver, batches, 11, tmp_path, tag
        )
    s_sync, last_s, logs_s, rows_s = outs["sync"]
    s_pipe, last_p, logs_p, rows_p = outs["pipe"]
    assert logs_s == logs_p
    assert last_s == last_p
    assert [list(r) for r in rows_s] == [list(r) for r in rows_p]
    assert _params_equal(s_sync.state["params"], s_pipe.state["params"])


# -- the sync-free contract ------------------------------------------------


def test_pipelined_no_midwindow_host_syncs():
    solver, batches = _make_solver(True)
    mon = HostSyncMonitor(strict=True)  # a violation raises immediately
    solver.sync_monitor = mon
    solver.train(batches, num_iters=20, log_fn=lambda s: None)
    c = mon.counts()
    # Every batch put happened on the staging thread...
    assert c["put_guarded"] == 0 and c["put"] >= 20
    # ...and the step-loop thread read back exactly once per window
    # (display=5 -> boundaries at 5/10/15/20).
    assert c["get_guarded"] == 4
    assert mon.violations() == []


def test_pipeline_window_capacity_rules():
    solver, _ = _make_solver(True, display=100, snapshot=30)
    assert solver._pipeline_window_capacity(test_active=False) == 30
    solver.cfg.display = 0
    solver.cfg.snapshot = 0
    assert solver._pipeline_window_capacity(test_active=False) == 64
    solver.cfg.pipeline_window = 7
    assert solver._pipeline_window_capacity(test_active=False) == 7
    solver.cfg.display = 5
    assert solver._pipeline_window_capacity(test_active=False) == 5


def test_pipelined_exhaustion_flushes_window_tail(tmp_path):
    """A stream that exhausts mid-window must not drop the tail's
    telemetry: the pending rows are flushed on the way out, matching
    what the synchronous loop had already emitted step-by-step."""
    from npairloss_tpu.obs import RunTelemetry

    def seven():
        g = synthetic_identity_batches(8, 8, 2, (16,), noise=0.6)
        for _ in range(7):
            yield next(g)

    rows = {}
    for tag, pipeline in (("sync", False), ("pipe", True)):
        solver, _ = _make_solver(pipeline, display=0, pipeline_window=10)
        tel = RunTelemetry(str(tmp_path / tag), trace=False)
        solver.telemetry = tel
        try:
            with pytest.raises(StopIteration):
                solver.train(seven(), num_iters=50, log_fn=lambda s: None)
        finally:
            tel.close()
        rows[tag] = [json.loads(line) for line in
                     open(tmp_path / tag / "metrics.jsonl")]
    assert [r["step"] for r in rows["pipe"]] == [1, 2, 3, 4, 5, 6, 7]
    assert [list(r) for r in rows["sync"]] == [list(r) for r in
                                               rows["pipe"]]
    for rs, rp in zip(rows["sync"], rows["pipe"]):
        for k in rs:
            if k not in ("wall_time", "run_id"):
                assert rs[k] == rp[k], k


def test_pipelined_step_rebuild_relabels_compile():
    """A rebuilt pipelined step (cfg replaced, e.g. a rollback's
    lr_scale) is a NEW program: the shape-tracking must reset so the
    recompile is labeled step/compile and the expected-donation-warning
    filter is reinstalled — not a mislabeled step/dispatch leaking
    XLA's 'donated buffers were not usable' warning."""
    import warnings as _w

    solver, batches = _make_solver(True, display=0, pipeline_window=2)
    solver.train(batches, num_iters=2, log_fn=lambda s: None)
    assert solver._seen_step_shapes
    solver.cfg = solver.cfg  # the setter drops every jitted step
    assert solver._pipe_step_fn is None
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        solver.train(batches, num_iters=4, log_fn=lambda s: None)
    assert not [w for w in rec if "donated buffers" in str(w.message)]
    # The rebuild re-registered exactly the live signature.
    assert len(solver._seen_step_shapes) == 1


# -- resilience interop ----------------------------------------------------


@pytest.mark.slow  # snapshot commit + rollback restore: ~6s (tier-1 budget)
def test_pipelined_guard_rollback_windowed(tmp_path):
    """step.nan_loss mid-window: the guard trips at the boundary read,
    rolls back to a pre-streak snapshot, and training continues —
    identical recovery semantics, detection deferred to the window."""
    solver, batches = _make_solver(
        True, display=0, snapshot=4, pipeline_window=4,
        snapshot_prefix=str(tmp_path / "g_"),
    )
    solver.divergence = DivergenceConfig(patience=2, action="rollback",
                                         max_rollbacks=1)
    failpoints.reset()
    logs = []
    solver.train(batches, num_iters=6, log_fn=logs.append)
    failpoints.arm("step.nan_loss", times=2)
    try:
        solver.train(batches, num_iters=10, log_fn=logs.append)
    finally:
        failpoints.reset()
    rolled = [s for s in logs if "rolled back to iteration 4" in s]
    assert rolled, logs
    assert "2 consecutive non-finite losses at iteration 8" in rolled[0]
    assert solver.iteration == 10


def test_pipelined_guard_streak_resets_after_poisoned_window(monkeypatch):
    """A sub-patience poison streak at a window TAIL must be RESET by a
    later all-finite window: host-side poison is invisible to the
    device counter, so the replay must also run whenever the guard
    carries a streak — otherwise a lone NaN windows later completes a
    phantom streak and trips the guard where the sync loop would not."""
    calls = {"n": 0}
    real = failpoints.should_fire

    def fake(name):
        # Poison exact STEP numbers (one check per step), immune to the
        # prefetch-depth offset generator-side arming would have: 3-4
        # end window 1 with streak 2 (< patience 3); window 2 (5-8) is
        # all finite; the lone NaN at 9 must see streak 1, not 3.
        if name == "step.nan_loss":
            calls["n"] += 1
            return calls["n"] in (3, 4, 9)
        return real(name)

    monkeypatch.setattr(failpoints, "should_fire", fake)
    solver, batches = _make_solver(True, display=0, snapshot=0,
                                   pipeline_window=4)
    solver.divergence = DivergenceConfig(patience=3, action="halt")
    solver.train(batches, num_iters=12, log_fn=lambda s: None)
    assert solver.iteration == 12  # no phantom DivergenceError


@pytest.mark.slow  # 3 solvers + snapshot/restore: ~20s (tier-1 budget)
def test_pipelined_crash_resume_replays_batch_index(tmp_path):
    """A pipeline.stage crash mid-window surfaces, drains cleanly, and
    --resume auto + replaying the consumed batch stream yields params
    bit-identical to an uninterrupted synchronous run."""

    def indexed_batches(start=0):
        # Deterministic stream keyed by batch index so a resumed run can
        # replay from exactly the right position.
        gens = synthetic_identity_batches(8, 8, 2, (16,), noise=0.6)
        stream = [next(gens) for _ in range(32)]
        for i in range(start, len(stream)):
            yield stream[i]

    cfg = dict(display=0, snapshot=4, pipeline_window=4,
               snapshot_prefix=str(tmp_path / "c_"))

    # Reference: uninterrupted SYNC run to 8 steps (consumes batches
    # 0..7 — the parity anchor for the resumed pipelined run), with its
    # OWN snapshot prefix so its iter-8 snapshot cannot shadow the
    # crashed run's newest-valid candidate.
    ref, _ = _make_solver(False, **{**cfg,
                                    "snapshot_prefix": str(tmp_path / "r_")})
    ref.train(indexed_batches(), num_iters=8, log_fn=lambda s: None)

    # Pipelined run crashes mid-window-2: the 7th host batch arms the
    # pipeline.stage failpoint, so the staging thread dies while steps
    # 5-6 are in flight (window 2 never reaches its boundary).
    crashed, _ = _make_solver(True, **cfg)

    class _ArmAtBatch6:
        def __init__(self):
            self.it = indexed_batches()
            self.n = 0

        def __iter__(self):
            return self

        def __next__(self):
            if self.n == 6:
                failpoints.arm("pipeline.stage", times=1)
            self.n += 1
            return next(self.it)

    failpoints.reset()
    try:
        with pytest.raises(PrefetchStageError):
            crashed.train(_ArmAtBatch6(), num_iters=16,
                          log_fn=lambda s: None)
    finally:
        failpoints.reset()
    # Clean drain: no staging thread left alive behind the raise.
    assert not [t for t in threading.enumerate()
                if t.name == "npairloss-pipeline-stage" and t.is_alive()]
    # The snapshot cadence committed iteration 4 before the crash.
    resumed, _ = _make_solver(True, **cfg)
    restored = resumed.restore_auto()
    assert restored and resumed.iteration == 4
    # Replay from the correct batch index: iteration k consumed batch
    # k-1, so the resumed run continues with batch index 4.
    resumed.train(indexed_batches(start=resumed.iteration),
                  num_iters=8, log_fn=lambda s: None)
    assert _params_equal(ref.state["params"], resumed.state["params"])


def test_pipelined_preempt_flushes_partial_window(tmp_path):
    from npairloss_tpu.resilience import PreemptionSignal, TrainingPreempted

    solver, batches = _make_solver(
        True, display=0, snapshot=0, pipeline_window=10,
        snapshot_prefix=str(tmp_path / "p_"),
    )
    solver.preempt = PreemptionSignal()
    solver.preempt.request()
    with pytest.raises(TrainingPreempted) as ei:
        solver.train(batches, num_iters=50, log_fn=lambda s: None)
    # Preempt is polled per step: the boundary fired at step 1, flushed
    # the one-step window, and committed the emergency snapshot.
    assert ei.value.step == 1
    assert os.path.isdir(ei.value.snapshot_path)


# -- compile cache resolver --------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record (not apply) jax.config.update calls: the resolver's
    decisions are visible without touching process-global state."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_dir_env_var_is_the_whole_story(monkeypatch, tmp_path,
                                              config_updates):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own handling places the
    cache — no code path sets jax_compilation_cache_dir."""
    outside = str(tmp_path / "outside")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, outside)
    assert enable_compile_cache() == outside
    assert compile_cache_dir() == outside
    assert "jax_compilation_cache_dir" not in dict(config_updates)
    # The thresholds are still zeroed: every program is cached, so a
    # second run can prove it compiled nothing.
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates
    # The operations' metadata is part of the key: a cached executable
    # never shows a profile stale ``named_scope`` region names.
    assert ("jax_compilation_cache_include_metadata_in_key", True) \
        in config_updates


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, tmp_path, config_updates):
    """Unset: one fixed in-checkout path — never a temp name, a pid, a
    time or a run directory (the path is part of every entry's key)."""
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    monkeypatch.chdir(tmp_path)  # a run dir is not an input
    assert enable_compile_cache() == want
    assert compile_cache_dir() == want
    assert dict(config_updates)["jax_compilation_cache_dir"] == want
    # Exactly ONE place in the tree sets the knob.
    hits = []
    for root in ("npairloss_tpu", "scripts"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py") and '"jax_compilation_cache_dir"'
                     in open(os.path.join(dirpath, f)).read()]
    if '"jax_compilation_cache_dir"' in open(
            os.path.join(REPO, "chip_smoke.py")).read():
        hits.append("chip_smoke.py")
    assert [os.path.relpath(h, REPO) for h in hits] == [
        os.path.join("npairloss_tpu", "pipeline", "compile_cache.py")]


def test_second_process_compiles_nothing(tmp_path):
    """The acceptance round-trip, through the CLI: two fresh train
    processes share an outside cache dir; the second one reads every
    program (hits, no misses, no new entries) and nothing appears
    under the checkout because of it."""
    import subprocess
    import sys

    outside = tmp_path / "cc"
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="true")
    env[compile_cache.CACHE_DIR_ENV] = str(outside)
    before = compile_cache.cache_entries(compile_cache.DEFAULT_CACHE_DIR)
    stats = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-m", "npairloss_tpu", "--platform", "cpu",
             "train", "--solver", "examples/tiny_solver.prototxt",
             "--model", "mlp", "--synthetic", "--max_iter", "2"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [ln for ln in proc.stderr.splitlines()
                if ln.startswith("compile_cache ")][-1]
        stats.append(json.loads(line[len("compile_cache "):]))
    cold, warm = stats
    assert cold["dir"] == str(outside)
    assert cold["misses"] > 0 and cold["entries_after"] > 0
    assert warm["hits"] > 0 and warm["misses"] == 0
    assert warm["entries_after"] == cold["entries_after"]
    assert compile_cache.cache_entries(
        compile_cache.DEFAULT_CACHE_DIR) == before


def test_warmup_is_aot():
    """Solver.warmup compiles without dispatching: no training state
    consumed."""
    solver, _ = _make_solver(False)
    assert solver.warmup(4) > 0
    assert solver.iteration == 0
