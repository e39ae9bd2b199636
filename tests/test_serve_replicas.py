"""Replica tier + SLO-driven admission control (docs/SERVING.md).

Load-bearing pins:
  * replicas share the primary's compiled programs — warming the
    primary warms the tier, and no replica pays (or falsely counts) a
    duplicate XLA compile;
  * the ``serve.replica_crash`` failpoint kills one replica mid-burst
    and the tier REROUTES its in-flight and queued work to the
    survivors with ZERO client-visible errors (the resilience table's
    serving row; the gameday zero-drop gate), while the front end's
    ``queries == answered + errors + rejected`` invariant HOLDS;
  * admission control sheds load exactly while a watched SLO burns
    (the committed evaluator state — the same stream that drives
    alerts), counts every shed once in ``rejected``, keeps a probe
    trickle flowing so recovery stays observable, and readmits on
    clear.
"""

import threading
import time

import numpy as np
import pytest

from npairloss_tpu.obs.live import LiveObservatory
from npairloss_tpu.obs.live.registry import MetricRegistry
from npairloss_tpu.obs.live.slo import SLOSpec, SLOStatus
from npairloss_tpu.resilience import failpoints
from npairloss_tpu.serve import (
    AdmissionConfig,
    AdmissionController,
    BatcherConfig,
    EngineConfig,
    GalleryIndex,
    QueryEngine,
    QueueFullError,
    RetrievalServer,
    ServerConfig,
)
from npairloss_tpu.serve.replicas import ReplicaCrashError


def make_gallery(rng, ids=12, per_id=6, dim=16, noise=0.3):
    centers = rng.standard_normal((ids, dim))
    labels = np.repeat(np.arange(ids), per_id).astype(np.int32)
    emb = centers[labels] + noise * rng.standard_normal(
        (ids * per_id, dim)
    )
    return emb.astype(np.float32), labels


def _tier(rng, n_replicas=2, max_queue=64, admission=None,
          buckets=(1, 4)):
    emb, labels = make_gallery(rng)
    index = GalleryIndex.build(emb, labels)
    cfg = EngineConfig(top_k=3, buckets=buckets)
    primary = QueryEngine(index, cfg)
    engines = [primary] + [
        QueryEngine(index, cfg, share_compiled_with=primary)
        for _ in range(n_replicas - 1)
    ]
    primary.warmup()
    for e in engines[1:]:
        e.warmed = True
    server = RetrievalServer(
        engines,
        BatcherConfig(max_batch=buckets[-1], max_delay_ms=1.0,
                      max_queue=max_queue),
        ServerConfig(metrics_window=0),
        admission=admission,
    )
    return emb, server


# -- compile sharing ----------------------------------------------------------


def test_replicas_share_compiled_programs(rng):
    """After warming the primary ONLY, a shared replica's first real
    dispatch performs zero compiles (shared jit cache + shared
    signature set — it neither recompiles nor miscounts)."""
    emb, labels = make_gallery(rng)
    index = GalleryIndex.build(emb, labels)
    cfg = EngineConfig(top_k=3, buckets=(4,))
    primary = QueryEngine(index, cfg)
    replica = QueryEngine(index, cfg, share_compiled_with=primary)
    primary.warmup()
    replica.warmed = True
    assert replica._topk_fn is primary._topk_fn
    out = replica.query(emb[:4])
    assert out["rows"].shape == (4, 3)
    assert replica.compiles_total == 0
    assert replica.compiles_after_warmup == 0
    assert primary.compiles_after_warmup == 0


def test_share_compiled_with_validates_identity(rng):
    emb, labels = make_gallery(rng)
    index = GalleryIndex.build(emb, labels)
    other_index = GalleryIndex.build(emb, labels)
    cfg = EngineConfig(top_k=3, buckets=(4,))
    primary = QueryEngine(index, cfg)
    with pytest.raises(ValueError, match="same index"):
        QueryEngine(other_index, cfg, share_compiled_with=primary)
    with pytest.raises(ValueError, match="same index"):
        QueryEngine(index, EngineConfig(top_k=4, buckets=(4,)),
                    share_compiled_with=primary)


# -- routing ------------------------------------------------------------------


def test_routing_prefers_least_loaded_live_replica(rng):
    _, server = _tier(rng, n_replicas=3)
    reps = server.replicaset.replicas
    # fake queue depths without starting threads
    reps[0].batcher._q.put(("x", None, 0.0))
    reps[2].alive = False
    assert server.replicaset.pick() is reps[1]
    reps[1].batcher._q.put(("x", None, 0.0))
    reps[1].batcher._q.put(("x", None, 0.0))
    assert server.replicaset.pick() is reps[0]


def test_whole_tier_down_rejects_and_counts(rng):
    _, server = _tier(rng, n_replicas=2)
    for rep in server.replicaset.replicas:
        rep.alive = False
    with pytest.raises(QueueFullError, match="no live replicas"):
        server.submit({"id": 0, "embedding": [0.0] * 16})
    s = server.summary()
    assert s["rejected"] == 1 and s["queries"] == 1
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"]
    assert s["replicas_alive"] == 0


# -- crash containment --------------------------------------------------------


def test_replica_crash_reroutes_with_zero_client_errors(rng):
    """Kill one of two replicas mid-burst: the crashed replica's
    in-flight batch REROUTES to the survivor (zero client-visible
    errors — the gameday zero-drop contract), later traffic routes to
    the survivor, and the accounting invariant holds end to end."""
    emb, server = _tier(rng, n_replicas=2)
    server.replicaset.start()
    try:
        failpoints.arm("serve.replica_crash", times=1)
        answers = server.handle_many(
            [{"id": i, "embedding": emb[i].tolist()} for i in range(20)],
            timeout=30.0,
        )
        assert server.replicaset.alive_count == 1
        # the survivor keeps serving
        tail = server.handle_many(
            [{"id": 100 + i, "embedding": emb[i].tolist()}
             for i in range(8)],
            timeout=30.0,
        )
    finally:
        failpoints.reset()
        server.replicaset.close(drain=True)
    assert all("neighbors" in a for a in answers + tail), \
        "a replica crash with a survivor must stay client-invisible"
    s = server.summary()
    assert s["replicas"] == 2 and s["replicas_alive"] == 1
    assert s["queries"] == 28
    assert s["answered"] == 28 and s["errors"] == 0
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"], s


def test_replica_crash_delayed_arming_reroutes_late_batch(rng):
    """``delay`` arming (the name:count@delay grammar): the first
    dispatches pass unharmed, the crash lands mid-stream, and the
    rerouted batch still answers — zero errors end to end."""
    emb, server = _tier(rng, n_replicas=2)
    server.replicaset.start()
    try:
        failpoints.arm("serve.replica_crash", times=1, delay=2)
        answers = []
        for wave in range(4):
            answers += server.handle_many(
                [{"id": wave * 10 + i, "embedding": emb[i].tolist()}
                 for i in range(4)],
                timeout=30.0,
            )
    finally:
        failpoints.reset()
        server.replicaset.close(drain=True)
    assert server.replicaset.alive_count == 1
    assert all("neighbors" in a for a in answers), answers
    s = server.summary()
    assert s["errors"] == 0 and s["answered"] == 16
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"], s


def test_replica_crash_reroutes_through_both_phases(rng, tracer):
    """A backlog on one replica that crashes at its first dispatch: the
    batch launches on the survivor's engine and is finished on the
    crashed replica's own completion thread; every record is answered,
    and every batch launched has its finish under the same ``batch``
    and ``replica`` tags."""
    emb, server = _tier(rng, n_replicas=2)
    dead = server.replicaset.replicas[0]
    futs = [dead.batcher.submit({"id": i, "embedding": emb[i].tolist()})
            for i in range(8)]  # two batches of four, queued
    try:
        failpoints.arm("serve.replica_crash", times=1)
        server.replicaset.start()
        answers = [f.result(timeout=30.0) for f in futs]
    finally:
        failpoints.reset()
        server.replicaset.close(drain=True)
    assert not dead.alive and server.replicaset.alive_count == 1
    assert [a["neighbors"][0]["row"] for a in answers] == list(range(8))
    events = tracer.events_since(0)[0]
    key = lambda e: (e["args"]["replica"], e["args"]["batch"])
    launched = {key(e): e["tid"] for e in events
                if e["name"] == "serve/dispatch"}
    finished = {key(e): e["tid"] for e in events
                if e["name"] == "serve/finish"}
    assert launched.keys() == finished.keys() == {("r0", 1), ("r0", 2)}
    # the first went to the completion thread: a batch queued behind it
    assert launched[("r0", 1)] != finished[("r0", 1)]


def test_dead_replica_drains_queued_batches_to_survivor(rng):
    """Work already queued on a crashed replica reroutes to a live
    replica instead of failing — queued batches survive the crash."""
    emb, server = _tier(rng, n_replicas=2)
    rep = server.replicaset.replicas[0]
    fut = rep.batcher.submit({"id": 0, "embedding": emb[0].tolist()})
    rep.alive = False  # crashed between admission and dispatch
    server.replicaset.start()
    try:
        answer = fut.result(timeout=10.0)
        assert "neighbors" in answer, answer
    finally:
        server.replicaset.close(drain=True)


def test_dead_replica_fails_queued_batches_fast_when_tier_down(rng):
    """With NO live replica left, work queued on a crashed replica
    fails with the crash error instead of hanging the caller until
    timeout (the whole-tier-loss boundary of the reroute promise)."""
    emb, server = _tier(rng, n_replicas=1)
    rep = server.replicaset.replicas[0]
    rep.alive = False  # crashed between admission and dispatch
    server.replicaset.start()
    try:
        fut = rep.batcher.submit({"id": 0, "embedding": emb[0].tolist()})
        with pytest.raises(ReplicaCrashError):
            fut.result(timeout=10.0)
    finally:
        server.replicaset.close(drain=True)


# -- dropped-query accounting -------------------------------------------------


def test_queries_dropped_absent_by_default_at_zero(rng):
    """Default posture: ``queries_dropped`` stays absent-when-zero so
    existing drain streams keep byte parity."""
    emb, server = _tier(rng, n_replicas=1)
    server.replicaset.start()
    try:
        server.handle_many(
            [{"id": 0, "embedding": emb[0].tolist()}], timeout=30.0)
    finally:
        server.replicaset.close(drain=True)
    s = server.summary()
    assert "queries_dropped" not in s, s
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"], s


def test_queries_dropped_explicit_zero_under_gameday_posture(rng):
    """``ServerConfig(explicit_drops=True)`` (the gameday posture)
    writes ``queries_dropped: 0`` into the drain summary and /healthz —
    zero is EVIDENCE there, not a default."""
    emb, labels = make_gallery(rng)
    index = GalleryIndex.build(emb, labels)
    cfg = EngineConfig(top_k=3, buckets=(1, 4))
    primary = QueryEngine(index, cfg)
    primary.warmup()
    server = RetrievalServer(
        [primary],
        BatcherConfig(max_batch=4, max_delay_ms=1.0, max_queue=64),
        ServerConfig(metrics_window=0, explicit_drops=True),
    )
    server.replicaset.start()
    try:
        server.handle_many(
            [{"id": i, "embedding": emb[i].tolist()} for i in range(6)],
            timeout=30.0,
        )
    finally:
        server.replicaset.close(drain=True)
    s = server.summary()
    assert s["queries_dropped"] == 0, s
    assert server.healthz()["queries_dropped"] == 0


# -- admission control --------------------------------------------------------


def _status(name, burning):
    spec = SLOSpec(name=name, metric="m", op="<=", target=1.0)
    return SLOStatus(spec=spec, burning=burning, bad_fraction=1.0,
                     samples=4)


def test_admission_sheds_on_burn_probes_and_readmits():
    reg = MetricRegistry()
    ctl = AdmissionController(
        AdmissionConfig(slo_names=("p99",), probe_every=4),
        registry=reg)
    assert all(ctl.admit() for _ in range(10))  # healthy: admit all

    ctl.on_statuses([_status("p99", True), _status("other", True)])
    assert ctl.shedding
    decisions = [ctl.admit() for _ in range(8)]
    assert decisions == [False, False, False, True] * 2  # probe trickle
    assert ctl.sheds == 6 and ctl.probes_admitted == 2
    assert reg.get("serve_shedding").value == 1.0
    assert reg.get("serve_shed").value == 6

    ctl.on_statuses([_status("p99", False)])
    assert not ctl.shedding
    assert all(ctl.admit() for _ in range(10))
    assert reg.get("serve_shedding").value == 0.0


def test_admission_ignores_unwatched_slos():
    ctl = AdmissionController(AdmissionConfig(slo_names=("p99",)))
    ctl.on_statuses([_status("other", True)])
    assert not ctl.shedding and ctl.admit()


def test_admission_config_validates():
    with pytest.raises(ValueError, match="SLO name"):
        AdmissionConfig(slo_names=())
    with pytest.raises(ValueError, match="probe_every"):
        AdmissionConfig(probe_every=-1)


def test_server_sheds_into_rejected_invariant(rng):
    """A shed is a fast-reject: QueueFullError to the caller, one count
    in ``rejected`` (never errors), invariant intact, and the window/
    summary expose the shed tally."""
    ctl = AdmissionController(
        AdmissionConfig(slo_names=("p99",), probe_every=0))
    emb, server = _tier(rng, n_replicas=1, admission=ctl)
    server.replicaset.start()
    try:
        ok = server.handle_many(
            [{"id": 0, "embedding": emb[0].tolist()}], timeout=30.0)
        assert "neighbors" in ok[0]
        ctl.on_statuses([_status("p99", True)])
        shed = server.handle_many(
            [{"id": i, "embedding": emb[0].tolist()} for i in range(5)],
            timeout=30.0,
        )
        assert all("error" in a and "shed" in a["error"] for a in shed)
        ctl.on_statuses([_status("p99", False)])
        ok2 = server.handle_many(
            [{"id": 9, "embedding": emb[0].tolist()}], timeout=30.0)
        assert "neighbors" in ok2[0]
    finally:
        server.replicaset.close(drain=True)
    s = server.summary()
    assert s["shed"] == 5 and s["shedding"] is False
    assert s["rejected"] == 5 and s["errors"] == 0 and s["answered"] == 2
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"], s
    h = server.healthz()
    assert h["admission"]["shed"] == 5


def test_single_replica_summary_keeps_pre_tier_shape(rng):
    """No replicas/admission configured -> no new summary keys (the
    byte-parity posture: features off leave the stream untouched)."""
    _, server = _tier(rng, n_replicas=1)
    s = server.summary()
    for key in ("replicas", "replicas_alive", "shed", "shedding"):
        assert key not in s, key


# -- live-obs listener wiring -------------------------------------------------


def test_live_observatory_tick_feeds_listeners(tmp_path):
    """add_listener receives the COMMITTED statuses each tick — the
    admission controller's feed is the exact stream the alert engine
    reads, so shedding and the pager can never disagree."""
    spec = SLOSpec(name="p99", metric="serve_p99_ms", op="<=",
                   target=100.0, window_s=60.0, burn_threshold=0.5,
                   min_samples=1)
    live = LiveObservatory([spec], out_dir=None)
    ctl = AdmissionController(AdmissionConfig(slo_names=("p99",)))
    live.add_listener(ctl.on_statuses)
    t0 = time.time()
    live.registry.set("serve_p99_ms", 500.0, t0)
    live.tick(now=t0 + 1)
    assert ctl.shedding
    # recovery: fresh good samples age the burn out
    for i in range(8):
        live.registry.set("serve_p99_ms", 5.0, t0 + 61 + i)
    live.tick(now=t0 + 70)
    assert not ctl.shedding


def test_listener_failure_never_breaks_the_tick(tmp_path):
    spec = SLOSpec(name="p99", metric="serve_p99_ms", op="<=",
                   target=100.0, min_samples=1)
    live = LiveObservatory([spec], out_dir=None)
    seen = []
    live.add_listener(lambda statuses: 1 / 0)
    live.add_listener(lambda statuses: seen.append(len(statuses)))
    live.registry.set("serve_p99_ms", 5.0, time.time())
    live.tick()
    assert seen == [1]
