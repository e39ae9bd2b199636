"""Fused Pallas IVF probe kernel (ops/pallas_ivf.py) — ISSUE 19.

Load-bearing pins:
  * the fused gather+score+running-top-k kernel matches the lax.scan
    baseline to 1e-6 scores — across fp32/bf16/int8 (the int8 dequant
    happens INSIDE the kernel), ragged cluster tails, empty clusters,
    and ``probes > n_clusters`` — exercised in Pallas interpret mode so
    tier-1 proves the kernel without TPU hardware;
  * the probe-impl registry is the single vocabulary: the CLI flag
    choices pin to it (the staticcheck vocab pass holds the same pin),
    ``auto`` resolves per platform, and the fused/scan choice is part
    of the engine's compile signature;
  * the serving tier carries the choice end to end: /healthz stamps the
    RESOLVED impl (absent on flat tiers), ``swap_engines`` preserves it
    (hot-swap rebuilds from the old EngineConfig), a replica crash on a
    fused tier stays client-invisible, and the qtrace ``probe_fused``
    span validates under the unchanged npairloss-qtrace-v1 vocabulary.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from npairloss_tpu.ops.pallas_ivf import (
    CAP_ALIGN,
    PROBE_IMPLS,
    fused_probe_topk,
    probe_dispatch_count,
    resolve_probe_impl,
)
from npairloss_tpu.parallel.mesh import data_parallel_mesh
from npairloss_tpu.resilience import failpoints
from npairloss_tpu.serve import (
    BatcherConfig,
    EngineConfig,
    GalleryIndex,
    QueryEngine,
    RetrievalServer,
    ServerConfig,
)
from npairloss_tpu.serve.engine import _finalize_topk, _ivf_probe_topk
from npairloss_tpu.serve.ivf import (
    SCORINGS,
    IVFIndex,
    _quantize_int8,
    topk_recall,
)

ATOL = 1e-6  # the acceptance gate: fused == scan to 1e-6 scores


# -- registry / resolution ----------------------------------------------------


def test_probe_impl_registry_pins_cli_choices():
    """CLI flag vocabulary == the registry (the _PRECISION_CHOICES
    pattern; the staticcheck vocab pass holds the same pin), and the
    registry declares the 4 -> 2 dispatch-count drop."""
    from npairloss_tpu.cli import _PROBE_IMPL_CHOICES

    assert set(_PROBE_IMPL_CHOICES) == set(PROBE_IMPLS)
    assert PROBE_IMPLS["scan"]["dispatch_count"] == 4
    assert PROBE_IMPLS["fused"]["dispatch_count"] <= 2
    assert PROBE_IMPLS["fused"]["pallas"] is True


def test_resolve_probe_impl_per_platform():
    """Explicit choices pass through; ``auto`` picks the kernel only
    where Mosaic compiles it (interpret emulation is a parity harness,
    not a serving path)."""
    assert resolve_probe_impl("scan") == "scan"
    assert resolve_probe_impl("fused", platform="cpu") == "fused"
    assert resolve_probe_impl("auto", platform="tpu") == "fused"
    assert resolve_probe_impl("auto", platform="cpu") == "scan"
    assert resolve_probe_impl("auto", platform="gpu") == "scan"
    assert probe_dispatch_count("auto", platform="tpu") <= 2
    assert probe_dispatch_count("scan") == 4
    with pytest.raises(ValueError, match="probe_impl"):
        resolve_probe_impl("vectorized")


def test_engine_config_validates_probe_impl():
    with pytest.raises(ValueError, match="probe_impl"):
        EngineConfig(probe_impl="fast")
    assert EngineConfig(probe_impl="fused").probe_impl == "fused"


# -- kernel-level parity matrix ----------------------------------------------


def _layout(rng, kc, cap, d, empty=()):
    """Hand-built packed layout with ragged per-cluster fills and the
    given clusters forced EMPTY (cvalid False, all rows -1)."""
    packed = rng.standard_normal((kc, cap, d)).astype(np.float32)
    rows = np.arange(kc * cap, dtype=np.int32).reshape(kc, cap)
    for ci in range(kc):  # ragged tails
        fill = int(rng.integers(1, cap + 1))
        rows[ci, fill:] = -1
        packed[ci, fill:] = 0.0
    cvalid = np.ones(kc, bool)
    for ci in empty:
        rows[ci, :] = -1
        packed[ci] = 0.0
        cvalid[ci] = False
    cents = rng.standard_normal((kc, d)).astype(np.float32)
    return (jnp.asarray(packed), jnp.asarray(rows), jnp.asarray(cents),
            jnp.asarray(cvalid))


@pytest.mark.parametrize("scoring", SCORINGS)
@pytest.mark.parametrize(
    "kc,cap,d,probes,k,empty",
    [
        (7, 11, 24, 3, 5, (2,)),      # ragged + one empty cluster
        (7, 11, 24, 12, 10, (2, 5)),  # probes > n_clusters
        (4, 6, 130, 2, 40, ()),       # kl < k (probe set too small)
    ],
)
def test_fused_matches_scan_probe(rng, scoring, kc, cap, d, probes, k,
                                  empty):
    """The parity gate, kernel level: same probe set, 1e-6 scores, and
    identical finalized answers against the scan baseline — unaligned
    cap/D exercise the in-call tile re-pad."""
    packed, rows, cents, cvalid = _layout(rng, kc, cap, d, empty)
    q = jnp.asarray(rng.standard_normal((5, d)).astype(np.float32))
    scale = None
    if scoring == "bf16":
        packed = packed.astype(jnp.bfloat16)
    elif scoring == "int8":
        packed, scale = _quantize_int8(packed)
    kw = dict(k=k, probes=probes, scoring=scoring, g0=0)
    s0, r0 = _ivf_probe_topk(q, packed, rows, cents, cvalid, scale, **kw)
    s1, r1 = fused_probe_topk(q, packed, rows, cents, cvalid, scale, **kw)
    assert s1.shape == s0.shape and r1.shape == r0.shape
    # 1e-6 agreement RELATIVE to the score scale: these raw dots reach
    # O(10), so fp32 reduction-order noise scales with |score|.
    ref = np.asarray(s0)
    tol = ATOL * max(1.0, float(np.abs(ref[ref > -1e30]).max()))
    np.testing.assert_allclose(np.asarray(s1), ref, rtol=ATOL, atol=tol)
    f0s, f0r = _finalize_topk(s0, r0, k)
    f1s, f1r = _finalize_topk(s1, r1, k)
    np.testing.assert_allclose(np.asarray(f1s), np.asarray(f0s),
                               rtol=ATOL, atol=tol)
    # Identical answers wherever the scores are distinct; equal-score
    # rows must still be drawn from the same candidate multiset.
    same = np.asarray(f1r) == np.asarray(f0r)
    ties = np.isclose(np.asarray(f1s), np.asarray(f0s), atol=tol)
    assert np.all(same | ties)


# -- engine-level parity + recall --------------------------------------------


def _clustered(rng, n_clusters=12, per=30, dim=24):
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    emb = np.repeat(centers, per, axis=0) + 0.1 * rng.standard_normal(
        (n_clusters * per, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    lab = np.repeat(np.arange(n_clusters), per).astype(np.int32)
    return emb, lab


def test_engine_fused_recall_matches_scan(rng):
    """Engine level, all three scorings on ONE index: fused and scan
    answer with 1e-6-equal scores and IDENTICAL recall@{1,10} against
    the brute-force oracle — the ISSUE 19 acceptance gate."""
    emb, lab = _clustered(rng)
    q = emb[rng.choice(emb.shape[0], 16, replace=False)]
    flat = GalleryIndex.build(emb, lab, normalize=False)
    oracle = QueryEngine(flat, EngineConfig(top_k=10, buckets=(16,)))
    exact = oracle.query(q, normalize=False)["rows"]
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=8,
                             train_size=None)
    for scoring in SCORINGS:
        outs = {}
        for impl in ("scan", "fused"):
            eng = QueryEngine(ivf, EngineConfig(
                top_k=10, buckets=(16,), probes=4, scoring=scoring,
                probe_impl=impl))
            assert eng.probe_impl == impl
            outs[impl] = eng.query(q, normalize=False)
        np.testing.assert_allclose(
            outs["fused"]["scores"], outs["scan"]["scores"],
            rtol=ATOL, atol=ATOL, err_msg=scoring)
        for k in (1, 10):
            assert topk_recall(outs["fused"]["rows"], exact, k=k) == \
                topk_recall(outs["scan"]["rows"], exact, k=k), \
                (scoring, k)


def test_cap_is_tile_aligned_after_build_and_add(rng):
    """IVFIndex._place pads cap to the kernel's sublane alignment so
    the fused path's per-dispatch re-pad is a no-op at any geometry —
    and add()'s republish keeps the property."""
    emb, lab = _clustered(rng, n_clusters=6, per=21)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=5,
                             train_size=None)
    assert ivf.layout.cap % CAP_ALIGN == 0
    assert ivf.layout.packed.shape[1] == ivf.layout.cap
    ivf.add(emb[:3], lab[:3], normalize=False)
    assert ivf.layout.cap % CAP_ALIGN == 0


def test_probe_impl_is_part_of_the_compile_signature(rng):
    """scan and fused programs are DIFFERENT jit signatures: the
    compile accounting (and the strict guard) must see an impl flip as
    a counted compile, never a silent cache alias."""
    emb, lab = _clustered(rng, n_clusters=6, per=20)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=4,
                             train_size=None)
    sigs = set()
    for impl in ("scan", "fused"):
        eng = QueryEngine(ivf, EngineConfig(top_k=3, buckets=(4,),
                                            probe_impl=impl))
        _, sig = eng._topk_call(4, eng.index)
        sigs.add(sig)
    assert len(sigs) == 2


@pytest.mark.parametrize("scoring", ["fp32", "int8"])
def test_mesh_fused_matches_scan(rng, scoring):
    """Sharded fused probe (pallas_call inside shard_map, traced shard
    offset g0, REP_CHECK_OFF) answers exactly like the sharded scan."""
    mesh = data_parallel_mesh(jax.devices()[:4])
    emb, lab = _clustered(rng, n_clusters=10, per=32, dim=32)
    ivf = IVFIndex.build_ivf(emb, lab, mesh=mesh, normalize=False,
                             clusters=8, train_size=None)
    q = emb[rng.choice(emb.shape[0], 8, replace=False)]
    outs = {}
    for impl in ("scan", "fused"):
        eng = QueryEngine(ivf, EngineConfig(
            top_k=5, buckets=(8,), probes=4, scoring=scoring,
            probe_impl=impl))
        outs[impl] = eng.query(q, normalize=False)
    np.testing.assert_allclose(outs["fused"]["scores"],
                               outs["scan"]["scores"],
                               rtol=ATOL, atol=ATOL)


# -- serving tier: healthz / hot-swap / chaos --------------------------------


def _fused_tier(rng, n_replicas=2):
    emb, lab = _clustered(rng)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=6,
                             train_size=None)
    cfg = EngineConfig(top_k=3, buckets=(1, 4), probes=3,
                       probe_impl="fused")
    primary = QueryEngine(ivf, cfg)
    engines = [primary] + [
        QueryEngine(ivf, cfg, share_compiled_with=primary)
        for _ in range(n_replicas - 1)
    ]
    primary.warmup()
    for e in engines[1:]:
        e.warmed = True
    server = RetrievalServer(
        engines,
        BatcherConfig(max_batch=4, max_delay_ms=1.0, max_queue=64),
        ServerConfig(metrics_window=0),
    )
    return emb, lab, server


def test_healthz_stamps_resolved_probe_impl(rng):
    """/healthz carries the RESOLVED impl on an IVF tier and stays
    shape-identical (key absent) on a flat tier — the absent-when-off
    freshness-JSON contract."""
    emb, lab, server = _fused_tier(rng, n_replicas=1)
    assert server.healthz()["probe_impl"] == "fused"
    flat = GalleryIndex.build(emb, lab, normalize=False)
    eng = QueryEngine(flat, EngineConfig(top_k=3, buckets=(1, 4)))
    eng.warmup()
    flat_server = RetrievalServer(
        [eng], BatcherConfig(max_batch=4, max_delay_ms=1.0),
        ServerConfig(metrics_window=0))
    assert "probe_impl" not in flat_server.healthz()


def test_hot_swap_preserves_probe_impl(rng):
    """swap_engines with a tier rebuilt from the OLD EngineConfig (the
    SnapshotSwapper recipe) keeps serving the fused path: /healthz
    stamps 'fused' after the flip and the swapped tier still answers."""
    from npairloss_tpu.serve.server import Freshness

    emb, lab, server = _fused_tier(rng)
    server.replicaset.start()
    try:
        assert server.healthz()["probe_impl"] == "fused"
        old = server.engine
        new_index = IVFIndex.build_ivf(emb, lab, normalize=False,
                                       clusters=6, train_size=None)
        primary = QueryEngine(new_index, old.cfg)
        warm = primary.warmup()
        assert warm >= 0.0
        replica = QueryEngine(new_index, old.cfg,
                              share_compiled_with=primary)
        replica.warmed = True
        server.swap_engines([primary, replica],
                            Freshness.collect(index=new_index))
        assert server.engine.probe_impl == "fused"
        assert server.healthz()["probe_impl"] == "fused"
        a = server.handle({"id": 1, "embedding": emb[1].tolist()})
        assert "neighbors" in a
    finally:
        server.replicaset.close(drain=True)


def test_replica_crash_on_fused_tier_zero_client_errors(rng):
    """The gameday chaos leg on the fused path: kill one of two fused
    replicas mid-burst — the tier reroutes with zero client-visible
    errors, the accounting invariant holds, and /healthz still stamps
    the fused impl on the surviving tier."""
    emb, lab, server = _fused_tier(rng, n_replicas=2)
    server.replicaset.start()
    try:
        failpoints.arm("serve.replica_crash", times=1)
        answers = server.handle_many(
            [{"id": i, "embedding": emb[i].tolist()} for i in range(16)],
            timeout=60.0,
        )
        assert server.replicaset.alive_count == 1
    finally:
        failpoints.reset()
        server.replicaset.close(drain=True)
    assert all("neighbors" in a for a in answers)
    s = server.summary()
    assert s["errors"] == 0
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"]
    assert server.healthz()["probe_impl"] == "fused"


# -- qtrace: the probe_fused span --------------------------------------------


class _SeededClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def _traced_query(fused):
    from npairloss_tpu.obs.qtrace import QTraceConfig, QueryTracer

    clk = _SeededClock()
    tr = QueryTracer(QTraceConfig(exemplars=4, slo_ms=100.0),
                     clock=clk, wall=lambda: 1000.0 + clk.t)
    qt = tr.begin("q1")
    clk.advance(0.001)
    tr.admitted(qt)
    clk.advance(0.002)
    tr.picked(qt)
    clk.advance(0.003)
    tr.dispatch_begin([qt], replica="r0")
    clk.advance(0.010)
    tr.dispatch_end([qt], score_us=4000.0, merge_us=1000.0, fused=fused)
    tr.finish(qt)
    return tr.report()


@pytest.mark.parametrize("fused", [False, True])
def test_probe_fused_span_validates_and_nests(fused):
    """dispatch_end(fused=True) wraps the score/topk_merge clocks in
    ONE probe_fused span that validates under the v1 contract (stage
    vocabulary unchanged — scan artifacts carry no such span)."""
    from npairloss_tpu.obs.qtrace import STAGES, validate_qtrace_report
    from npairloss_tpu.obs.qtrace.report import PROBE_FUSED_SPAN

    rep = _traced_query(fused)
    assert validate_qtrace_report(rep) is None
    assert tuple(rep["stages"]) == STAGES  # vocabulary untouched
    (ex,) = rep["exemplars"]
    spans = {e["name"]: e for e in ex["events"]}
    if not fused:
        assert PROBE_FUSED_SPAN not in spans
        return
    pf = spans[PROBE_FUSED_SPAN]
    score = spans["qtrace/score"]
    merge = spans["qtrace/topk_merge"]
    disp = spans["qtrace/dispatch"]
    # probe_fused covers exactly score+merge and nests inside dispatch.
    assert pf["dur"] == pytest.approx(score["dur"] + merge["dur"])
    assert pf["ts"] == pytest.approx(score["ts"])
    assert pf["ts"] >= disp["ts"] - 2.0
    assert pf["ts"] + pf["dur"] <= disp["ts"] + disp["dur"] + 2.0
    # stage_us decomposition is impl-agnostic: score/topk_merge budgets
    # survive unchanged.
    assert rep["budget"]["worst_mean_ms"]["score"] == pytest.approx(4.0)
    assert rep["budget"]["worst_mean_ms"]["topk_merge"] == \
        pytest.approx(1.0)
