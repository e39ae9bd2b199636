"""IVFIndex — the clustered (inverted-file) approximate gallery index.

The flat :class:`~npairloss_tpu.serve.index.GalleryIndex` scan is
O(N·D) per query — exact, and untenable at the 10^8-row galleries the
ROADMAP north-star implies.  This module is the serving-side answer
(ROADMAP item 2; the TPU-v4 embedding-hardware thesis in PAPERS.md —
retrieval at scale is the workload the hardware exists for): k-means
centroids over the gallery (the SHARED ``ops.kmeans`` implementation —
farthest-point seeding + Lloyd's, identical math to the offline NMI
protocol), a cluster-packed layout, and a probe-top-C query path that
scores only the probed clusters:

  * **Build**: centroids from :func:`ops.kmeans.kmeans_fit` (trained on
    a bounded sample at gallery scale), full assignment streamed via
    :func:`ops.kmeans.assign_to_centroids`, then rows PACKED per
    cluster into a dense ``(KC, cap, D)`` slab (``cap`` = largest
    cluster; short clusters pad with row id -1) plus a parallel
    ``(KC, cap)`` table of ORIGINAL gallery row ids — answers keep the
    flat index's global row numbering, so labels/ids mapping and the
    recall-parity harness need no translation.
  * **Probe** (the engine's jitted path, serve/engine.py): one
    ``(B, KC)`` centroid matmul, ``top_k`` -> C probed clusters per
    query, then a ``lax.scan`` over probes gathering one ``(B, cap, D)``
    cluster slab per step and merging a running top-k — per-query work
    drops from O(N·D) to O((KC + C·cap)·D).
  * **Mesh**: clusters shard over the mesh axis (centroids replicate —
    they are KC·D, tiny); every shard computes the same global probe
    set, gathers only the probed clusters it owns (others mask to
    -inf), and the per-shard top-k candidates merge exactly like the
    flat engine's shard merge.
  * **Scoring dtype**: the cluster-scan matmul can run fp32 (HIGHEST —
    the flat oracle's precision), bf16 (single-pass MXU gemms; speed
    on the current chip: not measured), or int8 with a per-cluster scale
    (max-abs symmetric quantization) — gated by the recall-parity
    harness (tests/test_ivf.py) against the brute-force oracle.
  * **add()**: new rows assign to their nearest EXISTING centroid (no
    re-clustering) and the whole packed layout republishes atomically —
    one reference swap of the :class:`IVFLayout` tuple, so an in-flight
    query reads either the old layout or the new one, never a mix.

Persistence rides the ``GalleryIndex`` commit path (atomic rename +
CRC manifest) under kind ``ivf-index`` with two extra arrays
(``centroids``, ``assign``); load rebuilds the packed layout
deterministically from the assignment instead of re-running k-means.
``--index-kind flat`` remains the recall oracle (docs/SERVING.md
§Approximate index).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import time
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from npairloss_tpu.ops.kmeans import assign_to_centroids, kmeans_fit
from npairloss_tpu.serve.index import _KIND_REGISTRY, GalleryIndex

log = logging.getLogger("npairloss_tpu.serve")

IVF_KIND = "ivf-index"

SCORINGS = ("fp32", "bf16", "int8")


class IVFLayout(NamedTuple):
    """One immutable published generation of the device-resident index.

    ``packed``/``rows`` shard over the cluster axis; ``centroids``/
    ``cluster_valid`` replicate.  ``add()`` builds a whole new layout
    and swaps the index's reference — the atomic-republish contract.
    """

    packed: jax.Array        # (KC, cap, D) float32, cluster-sharded
    rows: jax.Array          # (KC, cap) int32 global row ids, -1 = pad
    centroids: jax.Array     # (KC, D) float32, replicated
    cluster_valid: jax.Array  # (KC,) bool, replicated (non-empty, real)
    n_clusters: int          # true (unpadded) cluster count
    cap: int                 # rows per packed cluster slab


@jax.jit
def _to_bf16(packed: jax.Array) -> jax.Array:
    return packed.astype(jnp.bfloat16)


@jax.jit
def _quantize_int8(packed: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-cluster max-abs quantization: (KC, cap, D) f32 ->
    ((KC, cap, D) int8, (KC,) f32 scale).  Sharding follows the input
    (elementwise + per-cluster reductions never cross the cluster
    axis), so the quantized slab lands exactly where the fp32 one
    lives."""
    scale = jnp.max(jnp.abs(packed), axis=(1, 2)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(packed / scale[:, None, None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


@dataclasses.dataclass
class IVFIndex(GalleryIndex):
    """Clustered gallery index; see the module docstring.

    Build via :meth:`build_ivf` / :meth:`from_gallery` / :meth:`load`,
    never the raw constructor.  The flat device arrays the parent
    places (``emb``/``labels``/``valid``) stay ``None`` — the packed
    layout IS the device residency; host master copies are inherited.
    """

    KIND = IVF_KIND
    ARRAY_NAMES = ("emb", "labels", "ids", "centroids", "assign")

    centroids_host: Optional[np.ndarray] = None  # (kc, D) float32
    assign_host: Optional[np.ndarray] = None     # (N,) int32
    layout: Optional[IVFLayout] = None
    # scoring-dtype variants, keyed by scoring name and TAGGED with the
    # layout generation they derive from ("bf16" -> (layout, slab), ...)
    # — staleness is self-detecting (the tag is compared by identity
    # against the caller's captured layout), so a republish racing a
    # dispatch can never poison another generation's cache.
    _scored: Optional[Dict[str, tuple]] = None
    # The offline recall birth certificate (docs/OBSERVABILITY.md
    # §Quality observatory): :func:`measure_parity`'s recall@K-per-
    # scoring-mode numbers, stamped into the commit manifest at build
    # time so the LIVE shadow-recall gauge has a committed baseline.
    # Preserved through load/re-commit (an ``add()`` re-commit keeps
    # the measurement it was born with — the manifest records when).
    parity: Optional[dict] = None

    # -- construction -----------------------------------------------------

    @classmethod
    def build_ivf(
        cls,
        embeddings: np.ndarray,
        labels: np.ndarray,
        ids: Optional[np.ndarray] = None,
        mesh: Optional[Mesh] = None,
        axis: str = "dp",
        normalize: bool = True,
        clusters: int = 0,
        iters: int = 10,
        seed: int = 0,
        train_size: Optional[int] = 131072,
    ) -> "IVFIndex":
        """Cluster + pack extracted embeddings into a served IVF index.

        ``clusters=0`` picks ~sqrt(N) (the classical IVF balance point:
        centroid-scan and cluster-scan cost equalize).  ``train_size``
        bounds the k-means training set; the full gallery only pays the
        streamed assignment pass.
        """
        emb = np.asarray(embeddings, np.float32)
        lab = np.asarray(labels, np.int32).reshape(-1)
        if emb.ndim != 2 or emb.shape[0] != lab.shape[0]:
            raise ValueError(
                f"embeddings {emb.shape} / labels {lab.shape} mismatch"
            )
        if emb.shape[0] == 0:
            raise ValueError("cannot build an empty gallery")
        from npairloss_tpu.serve.index import l2_normalize_rows

        if normalize:
            emb = l2_normalize_rows(emb)
        if ids is None:
            ids = np.arange(emb.shape[0], dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64).reshape(-1)
            if ids.shape[0] != emb.shape[0]:
                raise ValueError(
                    f"ids {ids.shape} / embeddings {emb.shape} mismatch"
                )
        n = emb.shape[0]
        kc = int(clusters) or max(1, int(round(math.sqrt(n))))
        centroids = kmeans_fit(emb, kc, iters=iters, seed=seed,
                               train_size=train_size)
        assign = assign_to_centroids(emb, centroids)
        idx = cls(
            emb=None, labels=None, valid=None,  # type: ignore
            ids=ids, size=n, mesh=mesh, axis=axis, created=time.time(),
            _host_emb=emb, _host_labels=lab,
            centroids_host=centroids, assign_host=assign,
        )
        idx._place()
        log.info(
            "ivf index built: %d rows -> %d clusters (cap %d, dim %d)",
            n, idx.layout.n_clusters, idx.layout.cap, idx.dim)
        return idx

    @classmethod
    def from_gallery(cls, gallery: GalleryIndex, **build_kw) -> "IVFIndex":
        """Cluster an already-built/loaded flat gallery (shares its host
        arrays — rows are already unit-norm).  The ingest watermark
        rides along: the IVF rebuild contains exactly the rows the flat
        gallery did, so it covers the same WAL prefix — dropping it
        would force a full replay against the converted index."""
        out = cls.build_ivf(
            gallery._host_emb, gallery._host_labels, ids=gallery.ids,
            mesh=gallery.mesh, axis=gallery.axis, normalize=False,
            **build_kw)
        out.ingest_watermark = gallery.ingest_watermark
        return out

    # -- packing / placement ----------------------------------------------

    def _place(self) -> None:
        """Pack rows per cluster and publish a fresh :class:`IVFLayout`.

        The swap at the end is the atomic-republish point: everything
        is assembled off to the side first, then ONE reference
        assignment makes it live — a concurrently-dispatching engine
        (which reads ``self.layout`` exactly once per dispatch) sees
        the old generation or the new one, never halves of both.
        """
        emb = self._host_emb
        assign = self.assign_host
        n, d = emb.shape
        kc = int(self.centroids_host.shape[0])
        g = self.mesh.size if self.mesh is not None else 1
        kc_pad = kc + (-kc) % g
        sizes = np.bincount(assign, minlength=kc)
        # Cap rounds up to the fused probe kernel's sublane alignment
        # (lcm of the fp32/bf16/int8 min tiles), so the Pallas path's
        # per-dispatch tile re-pad is a width-zero no-op — the 1M-row
        # slab is never copied on the hot path.  The extra rows carry
        # the same -1 sentinel as ragged tails and mask identically in
        # both probe impls.
        from npairloss_tpu.ops.pallas_ivf import CAP_ALIGN

        cap = max(int(sizes.max()), 1)
        cap += (-cap) % CAP_ALIGN
        order = np.argsort(assign, kind="stable")
        offsets = np.zeros(kc + 1, np.int64)
        offsets[1:] = np.cumsum(sizes)
        packed = np.zeros((kc_pad, cap, d), np.float32)
        rows = np.full((kc_pad, cap), -1, np.int32)
        sa = assign[order]
        pos = np.arange(n) - offsets[sa]
        packed[sa, pos] = emb[order]
        rows[sa, pos] = order.astype(np.int32)
        cents = np.zeros((kc_pad, d), np.float32)
        cents[:kc] = self.centroids_host
        cvalid = np.zeros(kc_pad, bool)
        cvalid[:kc] = sizes > 0
        if self.mesh is not None:
            # Same declarative table as the flat gallery
            # (parallel.partition.gallery_rules): packed slabs shard
            # over the mesh axis on their cluster dim, centroid tables
            # replicate — one placement source of truth across serve.
            from npairloss_tpu.parallel.partition import (
                gallery_rules,
                match_partition_shardings,
                place_tree,
            )

            tree = {"packed": packed, "rows": rows,
                    "centroids": cents, "cluster_valid": cvalid}
            placed = place_tree(
                tree,
                match_partition_shardings(
                    gallery_rules(self.axis), tree, self.mesh),
            )
            layout = IVFLayout(
                packed=placed["packed"],
                rows=placed["rows"],
                centroids=placed["centroids"],
                cluster_valid=placed["cluster_valid"],
                n_clusters=kc, cap=cap,
            )
        else:
            layout = IVFLayout(
                packed=jax.device_put(jnp.asarray(packed)),
                rows=jax.device_put(jnp.asarray(rows)),
                centroids=jax.device_put(jnp.asarray(cents)),
                cluster_valid=jax.device_put(jnp.asarray(cvalid)),
                n_clusters=kc, cap=cap,
            )
        self.size = n
        if self._scored is None:
            self._scored = {}
        self.layout = layout  # the atomic republish

    def scored_arrays(self, scoring: str,
                      layout: Optional[IVFLayout] = None) -> tuple:
        """(slab, scale-or-None) for the requested scoring dtype against
        ``layout`` (default: the current one) — derived once per layout
        generation and cached.  A dispatch that captured its layout
        MUST pass it in, so every array it scores comes from ONE
        generation even when ``add()`` republishes mid-flight; a stale
        cache entry (tagged with a different generation) is recomputed,
        never served.  ``fp32`` returns the packed slab itself;
        ``bf16`` a half-width cast (the cluster-scan gather moves half
        the bytes); ``int8`` the symmetric per-cluster quantization."""
        if scoring not in SCORINGS:
            raise ValueError(
                f"scoring must be one of {SCORINGS}, got {scoring!r}")
        if layout is None:
            layout = self.layout
        if scoring == "fp32":
            return layout.packed, None
        cached = self._scored.get(scoring)
        if cached is not None and cached[0] is layout:
            return cached[1]
        if scoring == "bf16":
            out = (_to_bf16(layout.packed), None)
        else:
            out = _quantize_int8(layout.packed)
        self._scored[scoring] = (layout, out)
        return out

    # -- incremental add ---------------------------------------------------

    def add(
        self,
        embeddings: np.ndarray,
        labels: np.ndarray,
        ids: Optional[np.ndarray] = None,
        normalize: bool = True,
    ) -> int:
        """Append rows, assigning each to its nearest EXISTING centroid
        (refresh cadence keeps the trained cluster geometry; a drifted
        corpus warrants a rebuild), then republish the packed layout
        atomically.  A grown ``cap`` is a new program signature for the
        engine — one counted recompile, same as the flat path's padded-
        size growth."""
        emb, lab, ids = self._validate_added_rows(
            embeddings, labels, ids, normalize)
        new_assign = assign_to_centroids(emb, self.centroids_host)
        self._host_emb = np.concatenate([self._host_emb, emb])
        self._host_labels = np.concatenate([self._host_labels, lab])
        self.ids = np.concatenate([self.ids, ids])
        self.assign_host = np.concatenate([self.assign_host, new_assign])
        self._place()
        self.created = time.time()
        return self.size

    # -- persistence -------------------------------------------------------

    def _tree(self):
        return {
            "emb": self._host_emb,
            "labels": self._host_labels,
            "ids": self.ids,
            "centroids": self.centroids_host,
            "assign": self.assign_host,
        }

    def _manifest_extra(self) -> dict:
        # Merge the base extras (the ingest watermark) — an IVF commit
        # that dropped the watermark would force a full-WAL replay on
        # every cold restart and block segment GC forever.
        return {
            **super()._manifest_extra(),
            "n_clusters": int(self.centroids_host.shape[0]),
            **({"parity": self.parity} if self.parity else {}),
        }

    @classmethod
    def _from_tree(cls, tree, manifest, mesh, axis) -> "IVFIndex":
        idx = super()._from_tree(tree, manifest, mesh, axis)
        parity = manifest.get("parity")
        if isinstance(parity, dict):
            idx.parity = parity
        idx.centroids_host = np.asarray(tree["centroids"], np.float32)
        idx.assign_host = np.asarray(tree["assign"], np.int32)
        if idx.assign_host.shape[0] != idx.size:
            from npairloss_tpu.resilience.snapshot import (
                SnapshotValidationError,
            )

            raise SnapshotValidationError(
                f"ivf assignment length {idx.assign_host.shape[0]} != "
                f"gallery size {idx.size}")
        return idx

    # -- shape views -------------------------------------------------------

    @property
    def dim(self) -> int:
        return int(self._host_emb.shape[1])

    @property
    def padded_size(self) -> int:
        # The flat arrays are never placed; the meaningful extent is
        # the true row count (compile signatures key on the layout).
        return int(self.size)

    @property
    def n_clusters(self) -> int:
        return int(self.layout.n_clusters)


_KIND_REGISTRY[IVF_KIND] = IVFIndex


# -- recall-parity harness ----------------------------------------------------


def topk_recall(
    approx_rows: np.ndarray,
    exact_rows: np.ndarray,
    k: Optional[int] = None,
) -> float:
    """Recall@K of an approximate answer set against the exact oracle:
    mean over queries of |approx top-K ∩ exact top-K| / K.  ``rows``
    are (B, >=K) global gallery row ids (the engines' ``"rows"``
    output); this is the gate the bf16/int8 scoring modes and every
    probe count must clear (tests/test_ivf.py; the benchmark's cell 3
    holds the served answers to a ``recall_miss`` limit of its own)."""
    a = np.asarray(approx_rows)
    e = np.asarray(exact_rows)
    if a.shape[0] != e.shape[0]:
        raise ValueError(
            f"query counts differ: {a.shape[0]} vs {e.shape[0]}")
    if a.shape[0] == 0:
        return 1.0
    k = int(k) if k is not None else int(e.shape[1])
    hits = 0
    for i in range(a.shape[0]):
        hits += len(set(a[i, :k].tolist()) & set(e[i, :k].tolist()))
    return hits / float(a.shape[0] * k)


def measure_parity(
    index: IVFIndex,
    probes: int = 8,
    ks: Tuple[int, ...] = (1, 5, 10),
    sample: int = 256,
    scorings: Tuple[str, ...] = SCORINGS,
    seed: int = 0,
) -> dict:
    """The build-time recall birth certificate: recall@K of the probe
    path vs the flat brute-force oracle, per scoring mode, on a bounded
    sample of gallery rows re-used as queries.  Stamped into the IVF
    commit manifest (``manifest["parity"]``) so the LIVE shadow-recall
    gauge (obs.quality.shadow) has a committed baseline to be compared
    against in /healthz and the quality report — the operating-target
    discipline applied to answer quality.

    Single-device and unwarmed engines throughout: one measurement at
    build time, never a serving-path compile."""
    from npairloss_tpu.serve.engine import EngineConfig, QueryEngine
    from npairloss_tpu.serve.index import GalleryIndex

    n = index.size
    ks = tuple(k for k in ks if k <= n)
    if not ks:
        raise ValueError(f"gallery of {n} rows supports none of ks")
    kmax = max(ks)
    m = min(int(sample), n)
    rng = np.random.default_rng(seed)
    rows = rng.choice(n, size=m, replace=False)
    queries = index._host_emb[rows]
    bucket = min(64, m)
    flat = GalleryIndex.build(
        index._host_emb, index._host_labels, ids=index.ids,
        normalize=False)
    oracle = QueryEngine(
        flat, EngineConfig(top_k=kmax, buckets=(bucket,), scoring="fp32"))
    exact = oracle.query(queries, normalize=False)["rows"]
    probes = max(1, min(int(probes), index.n_clusters))
    recall: Dict[str, Dict[str, float]] = {}
    for scoring in scorings:
        engine = QueryEngine(
            index, EngineConfig(top_k=kmax, buckets=(bucket,),
                                probes=probes, scoring=scoring))
        approx = engine.query(queries, normalize=False)["rows"]
        recall[scoring] = {
            f"at_{k}": round(topk_recall(approx, exact, k), 4) for k in ks
        }
    return {
        "probes": probes,
        "sample": m,
        "ks": list(ks),
        "recall": recall,
        "measured_at": time.time(),
    }
