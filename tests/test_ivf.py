"""IVF approximate index: recall parity vs the brute-force oracle,
shared-kmeans identity, packing/degenerate cases, add-republish, and
persistence (docs/SERVING.md §Approximate index).

The load-bearing contract: with ``probes >= n_clusters`` every cluster
is scored, so the IVF answer SET must equal the flat exact scan's at
fp32 scoring — on one device and on the 8-device mesh.  Partial probes
and reduced scoring dtypes trade recall for latency; those floors are
pinned here.
"""

import numpy as np
import pytest

import jax

from npairloss_tpu.parallel.mesh import data_parallel_mesh
from npairloss_tpu.serve import EngineConfig, GalleryIndex, QueryEngine
from npairloss_tpu.serve.ivf import IVFIndex, topk_recall


def _mesh(width):
    if width == 1:
        return None
    return data_parallel_mesh(jax.devices()[:width])


def _clustered_data(rng, n_clusters=16, per=40, dim=24, spread=0.12):
    """Well-separated gaussian blobs: the geometry IVF exists for."""
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    emb = np.repeat(centers, per, axis=0) + spread * rng.standard_normal(
        (n_clusters * per, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    lab = np.repeat(np.arange(n_clusters), per).astype(np.int32)
    return emb, lab


def _queries(rng, emb, n=24, noise=0.05):
    q = emb[rng.choice(emb.shape[0], n, replace=False)]
    q = q + noise * rng.standard_normal(q.shape).astype(np.float32)
    return q / np.linalg.norm(q, axis=1, keepdims=True)


# -- one implementation of k-means ------------------------------------------


def test_kmeans_is_the_shared_implementation():
    """eval_retrieval's NMI k-means and the IVF builder's k-means must
    be the SAME objects (ops.kmeans) — the identity pin that keeps the
    offline clustering metric and the serving index from drifting."""
    from npairloss_tpu.ops import eval_retrieval, kmeans
    from npairloss_tpu.serve import ivf

    assert eval_retrieval.kmeans_assign is kmeans.kmeans_assign
    assert ivf.kmeans_fit is kmeans.kmeans_fit
    assert ivf.assign_to_centroids is kmeans.assign_to_centroids


def test_kmeans_fit_agrees_with_kmeans_assign(rng):
    """Unsampled kmeans_fit + streamed assignment == the one-shot
    jitted kmeans_assign (same seeding, same Lloyd steps)."""
    from npairloss_tpu.ops.kmeans import (
        assign_to_centroids,
        kmeans_assign,
        kmeans_fit,
    )

    emb, _ = _clustered_data(rng, n_clusters=8, per=25, dim=16)
    a_ref = np.asarray(kmeans_assign(emb, 8, iters=10, seed=3))
    cents = kmeans_fit(emb, 8, iters=10, seed=3, train_size=None)
    a_fit = assign_to_centroids(emb, cents, block=64)
    np.testing.assert_array_equal(a_ref, a_fit)


def test_kmeans_fit_sampled_still_covers_clusters(rng):
    """A subsampled fit must still place usable centroids: assignments
    land every point in SOME cluster and the blob structure survives
    (every true blob maps to a dominant fitted cluster)."""
    from npairloss_tpu.ops.kmeans import assign_to_centroids, kmeans_fit

    emb, lab = _clustered_data(rng, n_clusters=6, per=50, dim=16)
    cents = kmeans_fit(emb, 6, iters=10, seed=0, train_size=120)
    assign = assign_to_centroids(emb, cents, block=100)
    assert assign.shape == (300,)
    assert assign.min() >= 0 and assign.max() < 6
    for c in range(6):
        vals, counts = np.unique(assign[lab == c], return_counts=True)
        assert counts.max() / 50 >= 0.9  # blob stays together


# -- recall parity vs the flat oracle ----------------------------------------


@pytest.mark.parametrize("mesh_width", [1, 8])
def test_full_probe_matches_flat_exactly(rng, mesh_width):
    """probes >= n_clusters scores every gallery row: the IVF answer
    SET must equal the brute-force oracle's at fp32 — recall exactly
    1.0 on every mesh width."""
    mesh = _mesh(mesh_width)
    emb, lab = _clustered_data(rng)
    q = _queries(rng, emb)
    flat = GalleryIndex.build(emb, lab, mesh=mesh, normalize=False)
    oracle = QueryEngine(flat, EngineConfig(top_k=10, buckets=(24,)))
    ivf = IVFIndex.build_ivf(emb, lab, mesh=mesh, normalize=False,
                             clusters=13, train_size=None)
    eng = QueryEngine(ivf, EngineConfig(top_k=10, buckets=(24,),
                                        probes=13))
    r = topk_recall(eng.query(q)["rows"], oracle.query(q)["rows"])
    assert r == 1.0


@pytest.mark.parametrize("scoring,floor", [("bf16", 0.9), ("int8", 0.85)])
def test_reduced_scoring_recall_floor(rng, scoring, floor):
    """bf16/int8 cluster-scan scoring at FULL probe: the only error
    source is the matmul dtype, and recall vs the fp32 oracle must
    stay above the floor."""
    emb, lab = _clustered_data(rng)
    q = _queries(rng, emb)
    flat = GalleryIndex.build(emb, lab, normalize=False)
    oracle = QueryEngine(flat, EngineConfig(top_k=10, buckets=(24,)))
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=13,
                             train_size=None)
    eng = QueryEngine(ivf, EngineConfig(top_k=10, buckets=(24,),
                                        probes=13, scoring=scoring))
    r = topk_recall(eng.query(q)["rows"], oracle.query(q)["rows"])
    assert r >= floor, f"{scoring} recall {r}"


@pytest.mark.parametrize("mesh_width", [1, 8])
@pytest.mark.parametrize("probes", [1, 4])
def test_partial_probe_recall_on_clustered_data(rng, mesh_width, probes):
    """On separated blobs a query's true neighbors share its blob, so
    even probes=1 must find most of them; recall grows with probes and
    the mesh path agrees with single-device."""
    emb, lab = _clustered_data(rng)
    q = _queries(rng, emb)
    flat = GalleryIndex.build(emb, lab, normalize=False)
    oracle_rows = QueryEngine(
        flat, EngineConfig(top_k=10, buckets=(24,))).query(q)["rows"]
    mesh = _mesh(mesh_width)
    ivf = IVFIndex.build_ivf(emb, lab, mesh=mesh, normalize=False,
                             clusters=16, train_size=None)
    eng = QueryEngine(ivf, EngineConfig(top_k=10, buckets=(24,),
                                        probes=probes))
    r = topk_recall(eng.query(q)["rows"], oracle_rows)
    assert r >= 0.75, f"probes={probes} recall {r}"


def test_mesh_and_single_device_probe_same_clusters(rng):
    """The mesh merge is a layout detail, not a semantic one: the same
    probe set scored across 8 shards must return the same answer SET
    as one device (scores bit-compare too at fp32)."""
    emb, lab = _clustered_data(rng)
    q = _queries(rng, emb)
    outs = []
    for width in (1, 8):
        ivf = IVFIndex.build_ivf(emb, lab, mesh=_mesh(width),
                                 normalize=False, clusters=12,
                                 train_size=None)
        eng = QueryEngine(ivf, EngineConfig(top_k=8, buckets=(24,),
                                            probes=5))
        outs.append(eng.query(q))
    np.testing.assert_allclose(outs[0]["scores"], outs[1]["scores"],
                               atol=1e-6)
    assert topk_recall(outs[0]["rows"], outs[1]["rows"]) == 1.0


# -- degenerate cases ---------------------------------------------------------


def test_fewer_clusters_than_probes(rng):
    """probes clamps to the cluster count — a 3-cluster index probed
    with 8 is just a full scan, exact vs the oracle."""
    emb, lab = _clustered_data(rng, n_clusters=4, per=30)
    q = _queries(rng, emb, n=8)
    flat = GalleryIndex.build(emb, lab, normalize=False)
    oracle = QueryEngine(flat, EngineConfig(top_k=5, buckets=(8,)))
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=3,
                             train_size=None)
    eng = QueryEngine(ivf, EngineConfig(top_k=5, buckets=(8,), probes=8))
    assert topk_recall(eng.query(q)["rows"],
                       oracle.query(q)["rows"]) == 1.0


@pytest.mark.parametrize("mesh_width", [1, 8])
def test_empty_clusters_never_pollute_answers(rng, mesh_width):
    """More centroids than distinct points forces duplicate centroids
    and EMPTY clusters (plus mesh padding clusters on width 8); no
    -1 pad row may ever reach an answer, and the answer must still be
    the exact top-k."""
    base = rng.standard_normal((6, 16)).astype(np.float32)
    emb = np.repeat(base, 4, axis=0)  # 24 rows, only 6 distinct points
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    lab = np.repeat(np.arange(6), 4).astype(np.int32)
    q = emb[:5]
    mesh = _mesh(mesh_width)
    ivf = IVFIndex.build_ivf(emb, lab, mesh=mesh, normalize=False,
                             clusters=10, train_size=None)
    sizes = np.bincount(ivf.assign_host, minlength=10)
    assert (sizes == 0).any(), "fixture must actually produce empties"
    eng = QueryEngine(ivf, EngineConfig(top_k=4, buckets=(8,),
                                        probes=10))
    out = eng.query(q)
    assert (out["rows"] >= 0).all() and (out["rows"] < 24).all()
    flat = GalleryIndex.build(emb, lab, normalize=False)
    oracle = QueryEngine(flat, EngineConfig(top_k=4, buckets=(8,)))
    assert topk_recall(out["rows"], oracle.query(q)["rows"]) == 1.0


def test_probe_set_smaller_than_top_k_pads_safely(rng):
    """A probe set that cannot yield top_k candidates (one probed
    1-row cluster per query) pads with -inf scores and VALID row 0 —
    the host label/id mapping must never index a sentinel."""
    emb = np.eye(8, 16, dtype=np.float32)  # orthogonal: 1 row/cluster
    lab = np.arange(8, dtype=np.int32)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=8,
                             train_size=None)
    eng = QueryEngine(ivf, EngineConfig(top_k=4, buckets=(4,), probes=1))
    out = eng.query(emb[:3])
    assert out["rows"].shape == (3, 4)
    assert (out["rows"] >= 0).all()
    # the real candidate leads; the padded tail carries -inf scores
    assert (out["scores"][:, 0] > 0.99).all()
    assert (out["scores"][:, 1:] < -1e30).all()


def test_int8_requires_ivf(rng):
    emb, lab = _clustered_data(rng, n_clusters=4, per=10)
    flat = GalleryIndex.build(emb, lab, normalize=False)
    with pytest.raises(ValueError, match="int8"):
        QueryEngine(flat, EngineConfig(top_k=2, buckets=(4,),
                                       scoring="int8"))


def test_engine_config_validates_scoring_and_probes():
    with pytest.raises(ValueError, match="scoring"):
        EngineConfig(scoring="fp16")
    with pytest.raises(ValueError, match="probes"):
        EngineConfig(probes=0)


# -- add() / atomic republish -------------------------------------------------


@pytest.mark.parametrize("mesh_width", [1, 8])
def test_add_reassigns_into_existing_clusters(rng, mesh_width):
    """add() assigns new rows to their nearest EXISTING centroid and
    republishes atomically: the layout object is REPLACED (not
    mutated), the cluster count is unchanged, and a full-probe query
    afterwards is exact over the union gallery."""
    emb, lab = _clustered_data(rng, n_clusters=8, per=25)
    mesh = _mesh(mesh_width)
    ivf = IVFIndex.build_ivf(emb, lab, mesh=mesh, normalize=False,
                             clusters=8, train_size=None)
    eng = QueryEngine(ivf, EngineConfig(top_k=6, buckets=(8,), probes=8))
    old_layout = ivf.layout
    q = _queries(rng, emb, n=8)
    eng.query(q)  # warm the pre-add shapes

    extra, extra_lab = _clustered_data(rng, n_clusters=8, per=5)
    ivf.add(extra, extra_lab, normalize=False)
    assert ivf.layout is not old_layout, "republish must swap, not mutate"
    assert ivf.layout.n_clusters == old_layout.n_clusters
    assert ivf.size == 240
    assert ivf.assign_host.shape == (240,)
    # new rows went to their nearest centroid
    from npairloss_tpu.ops.kmeans import assign_to_centroids

    np.testing.assert_array_equal(
        ivf.assign_host[200:],
        assign_to_centroids(
            extra / np.linalg.norm(extra, axis=1, keepdims=True),
            ivf.centroids_host))

    all_emb = np.concatenate([emb, extra])
    all_emb /= np.linalg.norm(all_emb, axis=1, keepdims=True)
    all_lab = np.concatenate([lab, extra_lab])
    flat = GalleryIndex.build(all_emb, all_lab, normalize=False)
    oracle = QueryEngine(flat, EngineConfig(top_k=6, buckets=(8,)))
    assert topk_recall(eng.query(q)["rows"],
                       oracle.query(q)["rows"]) == 1.0


def test_add_invalidates_scored_cache(rng):
    """The bf16/int8 slabs derive from the layout; a republish must
    rebuild them (a stale quantized slab would silently drop the new
    rows from every int8 answer)."""
    emb, lab = _clustered_data(rng, n_clusters=4, per=10)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=4,
                             train_size=None)
    slab8, scale8 = ivf.scored_arrays("int8")
    assert ivf.scored_arrays("int8")[0] is slab8  # cached
    ivf.add(emb[:4] + 0.01, lab[:4])
    slab8b, _ = ivf.scored_arrays("int8")
    assert slab8b is not slab8


# -- persistence --------------------------------------------------------------


def test_ivf_save_load_roundtrip(rng, tmp_path):
    """Commit + restore under kind ivf-index: same centroids/assign,
    same answers; load_index dispatches on the manifest kind."""
    from npairloss_tpu.serve.index import load_index, read_manifest

    emb, lab = _clustered_data(rng, n_clusters=6, per=20)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=6,
                             train_size=None)
    path = str(tmp_path / "g.ivf.gidx")
    ivf.save(path)
    m = read_manifest(path)
    assert m["kind"] == "ivf-index" and m["n_clusters"] == 6

    restored = load_index(path)
    assert isinstance(restored, IVFIndex)
    np.testing.assert_array_equal(restored.assign_host, ivf.assign_host)
    np.testing.assert_allclose(restored.centroids_host,
                               ivf.centroids_host)
    q = _queries(rng, emb, n=8)
    cfg = EngineConfig(top_k=5, buckets=(8,), probes=3)
    a = QueryEngine(ivf, cfg).query(q)
    b = QueryEngine(restored, cfg).query(q)
    np.testing.assert_array_equal(a["rows"], b["rows"])
    np.testing.assert_allclose(a["scores"], b["scores"], atol=1e-6)


def test_flat_loader_refuses_ivf_commit(rng, tmp_path):
    """GalleryIndex.load on an ivf-index commit fails validation loudly
    (kind mismatch) instead of serving half an index."""
    from npairloss_tpu.resilience.snapshot import SnapshotValidationError

    emb, lab = _clustered_data(rng, n_clusters=4, per=10)
    ivf = IVFIndex.build_ivf(emb, lab, normalize=False, clusters=4,
                             train_size=None)
    path = str(tmp_path / "g.ivf.gidx")
    ivf.save(path)
    with pytest.raises(SnapshotValidationError, match="kind"):
        GalleryIndex.load(path)


def test_load_newest_serves_mixed_kinds(rng, tmp_path):
    """A serving prefix can mix flat and ivf commits; load_newest picks
    the newest valid one whatever its kind."""
    from npairloss_tpu.serve.index import load_newest

    emb, lab = _clustered_data(rng, n_clusters=4, per=10)
    GalleryIndex.build(emb, lab, normalize=False).save(
        str(tmp_path / "g.0001.gidx"))
    IVFIndex.build_ivf(emb, lab, normalize=False, clusters=4,
                       train_size=None).save(
        str(tmp_path / "g.0002.gidx"))
    path, idx = load_newest(str(tmp_path / "g"))
    assert path.endswith("g.0002.gidx")
    assert isinstance(idx, IVFIndex)


# -- recall harness sanity ----------------------------------------------------


def test_topk_recall_counts_set_overlap():
    a = np.array([[1, 2, 3], [4, 5, 6]])
    b = np.array([[3, 2, 9], [4, 5, 6]])
    assert topk_recall(a, b) == pytest.approx((2 + 3) / 6)
    assert topk_recall(a, b, k=1) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        topk_recall(a, b[:1])
