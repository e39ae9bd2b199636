"""QueryEngine — the jitted online query path: encode -> topk answers.

One dispatch per micro-batch: (optionally) encode raw inputs through the
restored model trunk, ``ops.normalize`` the query rows, then a
block-streamed similarity matmul against the mesh-resident gallery with
``lax.top_k`` merged across gallery blocks and mesh shards.  The
math is the deployment protocol of ``ops/eval_retrieval.py`` — fp32
HIGHEST-precision cosine on the MXU — so served answers are exactly
consistent with the offline ``gallery_recall_at_k`` numbers (parity is
pinned by tests/test_serve.py).

Streaming + merge layout (docs/SERVING.md):

  * within a shard, gallery rows stream in fixed blocks through a
    ``lax.scan`` carrying the running (B, k) best scores/rows — the
    B x N similarity matrix is never materialized (the
    ``ops/eval_retrieval.py`` trick, applied to the gallery axis);
  * a turn of the scan scores ``_SCAN_GROUP`` blocks; their ``top_k``
    and its merge into the carry run only when they can change the
    answer: when one of their scores is strictly greater than that
    query's carried k-th best (one ``lax.cond`` a turn).  With rows in
    random order a lone query merges about ``k * ln(turns)`` of them;
    the scan counts the merges and the ``serve/topk/scan`` span carries
    the count;
  * across shards, each mesh shard returns its local top-k with GLOBAL
    row numbers (shard offset via ``axis_index``); the (G, B, k)
    candidates reshape to (B, G*k) in ascending-shard order and one
    final ``top_k`` merges them.

Both merges preserve ``lax.top_k``'s lowest-index-wins tie-break:
candidates always concatenate in ascending global-row order, so the
streamed/sharded answer is bit-identical to a dense single-device
``top_k`` over the whole gallery.  A skipped turn keeps that: every
row of a later turn has a higher row number than every carried one,
so a score equal to the carried k-th would have lost the tie.

Steady-state serving never compiles: :meth:`warmup` compiles and primes
every padding bucket with one dummy dispatch each (populating the
persistent compile cache when one is enabled — see
:meth:`QueryEngine.warmup` for why AOT ``lower().compile()`` would pay
each compile twice).  Every later compile is COUNTED
(``compiles_after_warmup``) via
the jit cache size, and ``NPAIRLOSS_SERVE_COMPILE_GUARD=strict`` turns
a post-warmup compile into an error — the serving twin of the pipeline
sync guard.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from npairloss_tpu.ops.normalize import l2_normalize
from npairloss_tpu.ops.pallas_ivf import (
    PROBE_IMPLS,
    fused_probe_topk,
    resolve_probe_impl,
)
from npairloss_tpu.obs import tracing
from npairloss_tpu.resilience import failpoints
from npairloss_tpu.serve.index import GalleryIndex, l2_normalize_rows
from npairloss_tpu.serve.ivf import SCORINGS, IVFIndex

log = logging.getLogger("npairloss_tpu.serve")

COMPILE_GUARD_ENV = "NPAIRLOSS_SERVE_COMPILE_GUARD"

_NEG_FILL = float(-np.finfo(np.float32).max)


class ServeCompileError(RuntimeError):
    """A post-warmup XLA compile happened under the strict guard."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """``buckets`` are the fixed query padding sizes (ascending); every
    micro-batch pads to the smallest bucket that fits, so steady state
    dispatches only ``len(buckets)`` distinct programs.  ``top_k`` is
    the answer length; ``gallery_block`` the gallery rows of one streamed
    block inside a shard (a scan step walks ``_SCAN_GROUP`` of them:
    bounds the similarity working set).

    ``probes`` is the IVF probe width (clusters scored per query —
    clamped to the cluster count; ignored by a flat index).
    ``scoring`` picks the similarity-matmul dtype: ``fp32`` is the
    oracle's HIGHEST-precision path; ``bf16`` casts both sides of the
    scoring gemm to the MXU's native width;
    ``int8`` additionally quantizes the stored slab with a per-cluster
    scale (IVF only — flat storage has no cluster to scale by).  Both
    reduced modes are gated by the recall-parity harness
    (docs/SERVING.md §Approximate index).

    ``probe_impl`` picks the IVF probe-path implementation from the
    :data:`npairloss_tpu.ops.pallas_ivf.PROBE_IMPLS` registry:
    ``scan`` is the lax.scan gather+score baseline, ``fused`` the
    single-pass Pallas kernel, ``auto`` the per-platform pick (fused
    on TPU, scan elsewhere) — resolved once at engine build and
    stamped into /healthz.  Ignored by a flat
    index.

    ``length_buckets`` (ascending; empty = fixed-shape float inputs, the
    default) makes this a TOKEN engine: an input is a 1-D int32 row of
    token ids, and a dispatch pads its rows to a ``buckets`` entry and
    every row to the smallest length bucket that holds the longest, so
    steady state dispatches at most ``len(buckets) x
    len(length_buckets)`` encode programs.  ``token_budget`` (0 = none)
    is the most ``rows x padded length`` one dispatch may hold: warm-up
    compiles the pairs within it, and the batcher holds back a co-rider
    that would pass it (docs/SERVING.md §Inputs)."""

    top_k: int = 10
    buckets: Tuple[int, ...] = (1, 8, 32)
    gallery_block: int = 4096
    probes: int = 8
    scoring: str = "fp32"
    probe_impl: str = "scan"
    length_buckets: Tuple[int, ...] = ()
    token_budget: int = 0

    def __post_init__(self):
        if not self.buckets or list(self.buckets) != sorted(
                set(int(b) for b in self.buckets)):
            raise ValueError(
                f"buckets must be ascending and unique, got {self.buckets}"
            )
        if list(self.length_buckets) != sorted(
                set(int(b) for b in self.length_buckets)) or any(
                b < 1 for b in self.length_buckets):
            raise ValueError(
                "length_buckets must be ascending, unique and positive, "
                f"got {self.length_buckets}"
            )
        if self.token_budget and not self.length_buckets:
            # A keyword this kind of engine does not take: the error a
            # dataclass gives an unknown field (TypeError), which is what
            # a float-input tier's configuration said of it before token
            # engines existed (tests/benchmarks/test_bench_seam.py).
            raise TypeError(
                f"token_budget={self.token_budget} is a token engine's "
                "field: it needs length_buckets"
            )
        if self.token_budget < 0:
            raise ValueError(
                f"token_budget must be >= 0, got {self.token_budget}"
            )
        if self.token_budget and \
                self.buckets[0] * self.length_buckets[-1] > self.token_budget:
            raise ValueError(
                f"token_budget={self.token_budget} cannot hold "
                f"{self.buckets[0]} row(s) of the longest bucket "
                f"{self.length_buckets[-1]}"
            )
        if self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if self.probes < 1:
            raise ValueError(f"probes must be >= 1, got {self.probes}")
        if self.scoring not in SCORINGS:
            raise ValueError(
                f"scoring must be one of {SCORINGS}, got {self.scoring!r}"
            )
        if self.probe_impl not in PROBE_IMPLS:
            raise ValueError(
                f"probe_impl must be one of {sorted(PROBE_IMPLS)}, "
                f"got {self.probe_impl!r}"
            )


def _scored_matmul(q, g, scoring: str):
    """The similarity gemm in the configured dtype, fp32-accumulated:
    ``fp32`` is the oracle's HIGHEST path; ``bf16`` casts both sides
    (MXU-native width; the recall-parity harness gates the answer
    drift).  ``g`` may arrive int8 (the IVF quantized slab) — the cast
    happens AFTER the gather, so the bandwidth win is real; the caller
    applies the per-cluster scale to the product."""
    if scoring == "fp32":
        return jnp.dot(
            q, g.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    return jnp.dot(
        q.astype(jnp.bfloat16), g.astype(jnp.bfloat16).T,
        preferred_element_type=jnp.float32,
    )


# Gallery blocks a turn of the flat scan scores, tests and, if it must,
# merges as one.  Measured on a v5e at 2M x 1024 (PERF.md, PR 30): a
# turn's test and branch cost 3-4 us and one ``top_k`` over two blocks
# less than two over one, so two beat one in every case measured; four
# merge too often once a bucket holds several distinct queries.
_SCAN_GROUP = 2


def _stream_topk(q, emb, labels_unused, valid, k: int, block: int,
                 scoring: str = "fp32"):
    """Running top-k of ``q @ emb.T`` over gallery blocks.

    Returns (scores, rows, scan): scores and rows of shape (B, k) with
    rows GLOBAL over ``emb`` (0-based), and ``scan`` = int32 [turns
    walked, turns merged], a turn being ``_SCAN_GROUP`` blocks.  Invalid
    (padding) rows never win; the final clamped turn masks rows a
    previous one already scored, so each gallery row is a candidate
    exactly once.

    A turn's rows are merged only when they can change the answer: when
    one of their scores is STRICTLY greater than that query's carried
    k-th best.  Turns go by in ascending row order, so every candidate
    of a turn has a higher row number than every carried one, and a
    score equal to the carried k-th loses ``top_k``'s lowest-index-wins
    tie anyway: the carry a skipped turn leaves is the carry its merge
    would have left, bit for bit.
    """
    n = emb.shape[0]
    b = int(min(block, n))
    group = max(1, min(_SCAN_GROUP, n // b))
    w = group * b
    turns = -(-n // w)
    kw = min(k, w)
    bq = q.shape[0]

    def one_turn(carry, j):
        best_s, best_r, merged = carry
        start = jnp.minimum(j * w, n - w)
        v = jax.lax.dynamic_slice_in_dim(valid, start, w, axis=0)
        # named_scope: the scoring gemm vs the top-k merge show up as
        # separate regions in `prof --step serve` (obs.perf) — the
        # split that decides whether bf16/int8 scoring pays.
        with jax.named_scope("serve/score"):
            # One gemm a block, of the shape ``gallery_block`` gives it,
            # so a score does not depend on the group.  The barrier keeps
            # the test's reduction out of the gemm's own fusion, where it
            # cost ~10 us a turn (PERF.md, PR 30).
            sims = jnp.concatenate(jax.lax.optimization_barrier([
                _scored_matmul(
                    q, jax.lax.dynamic_slice_in_dim(emb, start + i * b, b, axis=0),
                    scoring)
                for i in range(group)]), axis=1)
        # Mask padding rows AND the final turn's clamped overlap (rows
        # below the unclamped start were scored by an earlier turn — a
        # duplicate candidate would corrupt the top-k answer).
        ok = v & (jnp.arange(w, dtype=jnp.int32) >= j * w - start)

        def merge(best_s, best_r):
            new_s, new_i = jax.lax.top_k(sims, kw)
            # Best-first concat keeps candidates in ascending global row
            # order within equal scores, so top_k's lowest-index-first
            # tie-break reproduces the dense answer exactly.
            cand_s = jnp.concatenate([best_s, new_s], axis=1)
            cand_r = jnp.concatenate([best_r, start + new_i], axis=1)
            best_s, sel = jax.lax.top_k(cand_s, k)
            return best_s, jnp.take_along_axis(cand_r, sel, axis=1)

        with jax.named_scope("serve/merge"):
            sims = jnp.where(ok[None, :], sims, jnp.float32(_NEG_FILL))
            # top_k returns scores in descending order: the last column
            # is the carried k-th best (_NEG_FILL until k rows are in).
            wins = jnp.any(sims > best_s[:, -1:])
            best_s, best_r = jax.lax.cond(
                wins, merge, lambda s, r: (s, r), best_s, best_r)
        return (best_s, best_r, merged + wins.astype(jnp.int32)), None

    init = (
        jnp.full((bq, k), jnp.float32(_NEG_FILL)),
        jnp.zeros((bq, k), jnp.int32),
        jnp.int32(0),
    )
    (best_s, best_r, merged), _ = jax.lax.scan(
        one_turn, init, jnp.arange(turns, dtype=jnp.int32)
    )
    return best_s, best_r, jnp.stack([jnp.int32(turns), merged])


def _ivf_probe_topk(q, packed, rows, centroids, cvalid, scale,
                    k: int, probes: int, scoring: str, g0):
    """Probe-top-C clustered top-k over one shard's packed slab.

    ``q`` (B, D) replicated; ``packed`` (KC_local, cap, D) this shard's
    cluster slabs (fp32/bf16, or int8 with ``scale`` (KC_local,));
    ``rows`` (KC_local, cap) GLOBAL gallery row ids (-1 pad);
    ``centroids``/``cvalid`` the full replicated (KC, D)/(KC,) tables;
    ``g0`` this shard's first global cluster id.  Returns (B, kl)
    scores + global rows, kl = min(k, probes*cap) — all shards compute
    the SAME global probe set from the replicated centroids, each
    gathers only the probed clusters it owns (the rest mask to -inf),
    and the cross-shard merge is exactly the flat engine's.

    Every static extent (cap, probe width, kl) derives from the TRACED
    shapes, so an ``add()`` that grows ``cap`` forces the retrace that
    recomputes them — the flat path's add contract, kept.
    """
    kc_full = centroids.shape[0]
    kc_local = packed.shape[0]
    cap = packed.shape[1]
    c = min(probes, kc_full)
    kl = min(k, c * cap)
    bq = q.shape[0]

    with jax.named_scope("serve/probe"):
        # Centroid scan: one small (B, KC) gemm picks the probe set.
        # Padded/empty clusters mask out so a probe slot is never
        # wasted on a slab of -1 rows while a real cluster waits.
        cs = jnp.dot(
            q, centroids.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        cs = jnp.where(cvalid[None, :], cs, jnp.float32(_NEG_FILL))
        _, probe = jax.lax.top_k(cs, c)  # (B, c) global cluster ids

    def one_probe(carry, j):
        best_s, best_r = carry
        cid = probe[:, j]
        owned = (cid >= g0) & (cid < g0 + kc_local)
        lid = jnp.where(owned, cid - g0, 0)
        g = packed[lid]   # (B, cap, D) gather — the scan's working set
        r = rows[lid]     # (B, cap) global row ids
        with jax.named_scope("serve/score"):
            if scoring == "fp32":
                sims = jnp.einsum(
                    "bcd,bd->bc", g, q,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )
            else:
                sims = jnp.einsum(
                    "bcd,bd->bc",
                    g.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                if scale is not None:
                    sims = sims * scale[lid][:, None]
        ok = (r >= 0) & owned[:, None]
        with jax.named_scope("serve/merge"):
            sims = jnp.where(ok, sims, jnp.float32(_NEG_FILL))
            kb = min(kl, cap)
            blk_s, blk_i = jax.lax.top_k(sims, kb)
            blk_r = jnp.take_along_axis(r, blk_i, axis=1)
            cand_s = jnp.concatenate([best_s, blk_s], axis=1)
            cand_r = jnp.concatenate([best_r, blk_r], axis=1)
            new_s, sel = jax.lax.top_k(cand_s, kl)
            new_r = jnp.take_along_axis(cand_r, sel, axis=1)
        return (new_s, new_r), None

    init = (
        jnp.full((bq, kl), jnp.float32(_NEG_FILL)),
        jnp.zeros((bq, kl), jnp.int32),
    )
    (best_s, best_r), _ = jax.lax.scan(
        one_probe, init, jnp.arange(c, dtype=jnp.int32)
    )
    return best_s, best_r


def _finalize_topk(s, r, k: int):
    """Clamp an IVF candidate list to the answer shape (B, k): pad with
    -inf columns when the probe set cannot yield k candidates, and pin
    every unfilled slot's row to 0 (a VALID gallery row — the host-side
    label/id mapping must never index with a mask sentinel)."""
    kl = s.shape[1]
    if kl < k:
        pad = k - kl
        s = jnp.concatenate(
            [s, jnp.full((s.shape[0], pad), jnp.float32(_NEG_FILL))], 1)
        r = jnp.concatenate(
            [r, jnp.zeros((r.shape[0], pad), jnp.int32)], 1)
    else:
        s, sel = jax.lax.top_k(s, k)
        r = jnp.take_along_axis(r, sel, axis=1)
    r = jnp.where(s > jnp.float32(_NEG_FILL) * 0.5, r, 0)
    return s, r


@dataclasses.dataclass(frozen=True)
class _Chunk:
    """One bucket chunk of a launched top-k: the jitted call's device
    outputs, the true rows among the bucket's, and the index's host
    label / id arrays as they were read at launch."""

    out: tuple
    n: int
    bucket: int
    labels: np.ndarray
    ids: np.ndarray
    t_score: float  # perf_counter at launch


class PendingSearch:
    """A top-k on the device.  :meth:`collect` waits for it and maps the
    winning rows to labels and ids through the arrays read AT LAUNCH: a
    republish (``add()``, a swapped index) landing between the two
    phases never maps one gallery's rows through another's labels.  It
    touches no engine state, so it may run on another thread than the
    launch while the engine launches the next batch."""

    def __init__(self, chunks: Sequence[_Chunk], top_k: int,
                 stages: Optional[Dict[str, Any]] = None):
        self._chunks = list(chunks)
        self._k = top_k
        self._stages = stages

    def collect(self) -> Dict[str, np.ndarray]:
        """``{"scores", "rows", "labels", "ids"}``, each (B, top_k)."""
        if not self._chunks:
            k = self._k
            return {
                "scores": np.zeros((0, k), np.float32),
                "rows": np.zeros((0, k), np.int32),
                "labels": np.zeros((0, k), np.int32),
                "ids": np.zeros((0, k), np.int64),
            }
        outs = [self._collect(c) for c in self._chunks]
        return {key: np.concatenate([o[key] for o in outs])
                for key in outs[0]}

    def _collect(self, c: _Chunk) -> Dict[str, np.ndarray]:
        n = c.n
        with tracing.span("serve/topk", rows=n, bucket=c.bucket):
            scores, rows, *scan = c.out
            with tracing.span("serve/topk/wait"):
                scores = np.asarray(scores)[:n]
                rows = np.asarray(rows)[:n]
                scan = [np.asarray(x) for x in scan]
            if scan:
                # The flat scan's own count of turns walked and merged
                # (an IVF probe returns none): known only now, so a span
                # of its own carries it into the tracer and the
                # profiler's host plane.
                turns, merged = (int(x) for x in scan[0])
                with tracing.span("serve/topk/scan", scan_blocks=turns,
                                  scan_blocks_merged=merged):
                    pass
        t_score1 = time.perf_counter()
        with tracing.span("serve/gather", rows=n):
            out = {
                "scores": scores,
                "rows": rows,
                "labels": c.labels[rows],
                "ids": c.ids[rows],
            }
        stages = self._stages
        if stages is not None:
            # Device scoring vs host gather (the qtrace score /
            # topk_merge split), widened across bucket chunks; the
            # score runs from the launch to the end of the wait.
            stages["score_at"] = (
                stages.get("score_at", (c.t_score,))[0], t_score1)
            stages["gather_at"] = (
                stages.get("gather_at", (t_score1,))[0],
                time.perf_counter())
        return out


class QueryEngine:
    """Answers ``(B, D)`` query embeddings with the gallery's top-k.

    ``model``/``state`` (a Flax module + the ``restore_for_inference``
    tree) enable :meth:`encode` for raw-input queries; embedding-only
    serving needs neither.  A search runs in two phases: :meth:`launch`
    (normalize, host-to-device, the jitted top-k's launch: span
    ``serve/launch``) returns a :class:`PendingSearch` whose
    ``collect()`` waits for the device (``serve/topk`` ⊃ ``/wait``) and
    gathers labels and ids (``serve/gather``); :meth:`query` is the two
    in one call.  ``serve/encode`` holds its ``/wait`` child around the
    blocking device-to-host copy; spans go through
    ``obs.tracing.span``; ``telemetry`` is accepted for the callers
    that pass it and reads nothing.  Thread-safety: launches and
    encodes are serialized by the MicroBatcher (one dispatcher thread);
    a pending search's ``collect`` may run on its completion thread at
    the same time, and reads no engine state.  The engine keeps no
    per-call mutable state beyond the compile and token counters, which
    only the launching thread writes.
    """

    def __init__(
        self,
        index: GalleryIndex,
        cfg: EngineConfig = EngineConfig(),
        model=None,
        state: Optional[Dict[str, Any]] = None,
        telemetry=None,
        share_compiled_with: Optional["QueryEngine"] = None,
        share_programs_with: Optional["QueryEngine"] = None,
    ):
        if cfg.top_k > index.size:
            raise ValueError(
                f"top_k={cfg.top_k} exceeds gallery size {index.size}"
            )
        self.index = index
        self.cfg = cfg
        self.model = model
        self.state = state
        self.telemetry = telemetry
        self.warmed = False
        self.compiles_total = 0
        self.compiles_after_warmup = 0
        # Token engines only (``length_buckets`` set): true tokens the
        # encode path was handed, and the tokens it ran padded to
        # (rows bucket x length bucket), warm-up's dummies included.
        self.tokens_encoded = 0
        self.tokens_padded = 0
        self._guard = os.environ.get(COMPILE_GUARD_ENV, "").strip().lower()
        self._ivf = isinstance(index, IVFIndex)
        # Resolved once here ("auto" -> the platform pick) so every
        # consumer — the jitted program choice, /healthz, the qtrace
        # fused flag — reports the impl that actually runs.
        # None for flat engines: the probe path does not exist there,
        # and /healthz keeps its pre-IVF shape (absent-when-off).
        self.probe_impl = (
            resolve_probe_impl(cfg.probe_impl) if self._ivf else None)
        if cfg.scoring == "int8" and not self._ivf:
            raise ValueError(
                "scoring='int8' needs an IVF index (the per-cluster "
                "scale has no flat-gallery equivalent); use bf16 or "
                "--index-kind ivf"
            )
        if share_compiled_with is not None and \
                share_programs_with is not None:
            raise ValueError(
                "share_compiled_with and share_programs_with are "
                "mutually exclusive"
            )
        if share_programs_with is not None:
            # Cross-index program sharing (multi-tenant serving,
            # docs/SERVING.md §Multi-tenant): the jitted topk/encode
            # closures capture ONLY the config (k, block, probes,
            # scoring, probe impl) and the mesh/axis — index arrays and
            # model state are traced ARGUMENTS — so engines over
            # DIFFERENT galleries can reuse one set of callables.  Two
            # tenants at one (bucket, padded_size, D) geometry then hit
            # the same executable: tenant count never multiplies
            # compiles (the shared ``_seen_sigs`` set plus the cache-
            # size accounting prove it per dispatch).  Everything the
            # closures DO capture must match, loudly:
            other = share_programs_with
            if other.cfg != cfg:
                raise ValueError(
                    "share_programs_with requires an identical "
                    f"EngineConfig (got {cfg} vs {other.cfg})"
                )
            if other._ivf != self._ivf:
                raise ValueError(
                    "share_programs_with requires the same index kind "
                    "(flat vs IVF programs differ)"
                )
            if other.index.mesh is not index.mesh or \
                    other.index.axis != index.axis:
                raise ValueError(
                    "share_programs_with requires the same mesh object "
                    "and axis (the sharded program captures them)"
                )
            if other.model is not model:
                raise ValueError(
                    "share_programs_with requires the same model object "
                    "(the encode program captures it; state is an "
                    "argument)"
                )
            self._seen_sigs = other._seen_sigs
            self._topk_fn = other._topk_fn
            self._encode_fn = other._encode_fn
            return
        if share_compiled_with is not None:
            # Replica-tier compile sharing (docs/SERVING.md): replicas
            # of ONE index+config reuse the primary's jitted callables
            # AND its signature set, so warming the primary warms the
            # whole tier and no replica ever pays (or falsely counts)
            # a duplicate XLA compile.
            other = share_compiled_with
            if other.index is not index or other.cfg != cfg:
                raise ValueError(
                    "share_compiled_with requires the same index object "
                    "and an identical EngineConfig"
                )
            self._seen_sigs = other._seen_sigs
            self._topk_fn = other._topk_fn
            self._encode_fn = other._encode_fn
        else:
            self._seen_sigs: set = set()
            self._build_fns()

    # -- jitted programs ---------------------------------------------------

    def _build_fns(self) -> None:
        if self._ivf:
            self._build_ivf_fns()
        else:
            self._build_flat_fns()
        self._build_encode_fn()

    def _build_flat_fns(self) -> None:
        k = self.cfg.top_k
        block = self.cfg.gallery_block
        scoring = self.cfg.scoring
        index = self.index

        def topk_single(q, emb, labels, valid):
            return _stream_topk(q, emb, labels, valid, k, block, scoring)

        if index.mesh is not None:
            mesh, axis = index.mesh, index.axis

            def per_shard(q, emb, labels, valid):
                # Shard extent comes from the TRACED local shard, not a
                # value captured at engine build: GalleryIndex.add() can
                # grow padded_size, and the retrace the new shapes force
                # must compute offsets for the NEW layout.
                shard_n = emb.shape[0]
                kl = min(k, shard_n)
                s, r, scan = _stream_topk(q, emb, labels, valid, kl, block,
                                          scoring)
                offset = jax.lax.axis_index(axis) * shard_n
                return s[None], (r + offset)[None], scan[None]

            sharded = jax.shard_map(
                per_shard,
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(axis)),
                out_specs=(P(axis), P(axis), P(axis)),
                # Every output is P(axis); the per-shard scan starts
                # from a replicated (-inf, 0) carry that turns varying
                # on the first block, which the varying-axes checker
                # rejects as a carry type change, and it has no rule
                # for the fused probe's pallas_call either.
                check_vma=False,
            )

            def topk(q, emb, labels, valid):
                # (G, B, kl) per-shard candidates -> (B, G*kl) in
                # ascending-shard (== ascending global row) order, then
                # one merging top_k.  The shards' block counts add up.
                s, r, scan = sharded(q, emb, labels, valid)
                g, _, kl = s.shape
                s = jnp.transpose(s, (1, 0, 2)).reshape(q.shape[0], g * kl)
                r = jnp.transpose(r, (1, 0, 2)).reshape(q.shape[0], g * kl)
                best_s, sel = jax.lax.top_k(s, k)
                best_r = jnp.take_along_axis(r, sel, axis=1)
                return best_s, best_r, scan.sum(axis=0)

            self._topk_fn = jax.jit(topk)
        else:
            self._topk_fn = jax.jit(topk_single)

    def _build_ivf_fns(self) -> None:
        """The probe-top-C clustered path (serve/ivf.py): centroid scan
        -> gather probed clusters -> scored top-k merge across clusters
        and mesh shards.  Same dispatch protocol as the flat path —
        (B, k) scores + GLOBAL gallery rows — so the server, warmup,
        and compile accounting are unchanged."""
        k = self.cfg.top_k
        probes = self.cfg.probes
        scoring = self.cfg.scoring
        index = self.index
        with_scale = scoring == "int8"
        # Both impls share the exact operand/return protocol, so the
        # registry choice is one function pointer — everything
        # downstream (finalize, shard merge, compile accounting) is
        # impl-agnostic.
        probe_fn = (fused_probe_topk if self.probe_impl == "fused"
                    else _ivf_probe_topk)

        def single(q, packed, rows, cents, cvalid, scale=None):
            s, r = probe_fn(
                q, packed, rows, cents, cvalid, scale,
                k=k, probes=probes, scoring=scoring, g0=0)
            return _finalize_topk(s, r, k)

        if index.mesh is not None:
            mesh, axis = index.mesh, index.axis
            g = mesh.size

            def per_shard(q, packed, rows, cents, cvalid, scale=None):
                kc_local = packed.shape[0]
                g0 = jax.lax.axis_index(axis) * kc_local
                s, r = probe_fn(
                    q, packed, rows, cents, cvalid, scale,
                    k=k, probes=probes, scoring=scoring, g0=g0)
                return s[None], r[None]

            specs = [P(), P(axis), P(axis), P(), P()]
            if with_scale:
                specs.append(P(axis))
            sharded = jax.shard_map(
                per_shard, mesh=mesh,
                in_specs=tuple(specs),
                out_specs=(P(axis), P(axis)),
                check_vma=False,
            )

            def topk(q, packed, rows, cents, cvalid, scale=None):
                args = (q, packed, rows, cents, cvalid)
                if with_scale:
                    args += (scale,)
                s, r = sharded(*args)
                _, _, kl = s.shape
                s = jnp.transpose(s, (1, 0, 2)).reshape(q.shape[0], g * kl)
                r = jnp.transpose(r, (1, 0, 2)).reshape(q.shape[0], g * kl)
                return _finalize_topk(s, r, k)

            self._topk_fn = jax.jit(topk)
        else:
            self._topk_fn = jax.jit(single)

    def _build_encode_fn(self) -> None:
        if self.model is not None:
            model = self.model

            def encode(state, x):
                variables = {"params": state["params"]}
                if state.get("batch_stats"):
                    variables["batch_stats"] = state["batch_stats"]
                with jax.named_scope("serve/encode"):
                    emb = model.apply(variables, x, train=False)
                with jax.named_scope("serve/normalize"):
                    return l2_normalize(emb)

            def encode_tokens(state, ids, lengths):
                with jax.named_scope("serve/encode"):
                    emb = model.apply({"params": state["params"]}, ids,
                                      lengths, train=False)
                with jax.named_scope("serve/normalize"):
                    return l2_normalize(emb)

            self._encode_fn = jax.jit(
                encode_tokens if self.cfg.length_buckets else encode)
        else:
            self._encode_fn = None

    def _cache_size(self) -> Optional[int]:
        sizes = []
        for fn in (self._topk_fn, self._encode_fn):
            if fn is None:
                continue
            get = getattr(fn, "_cache_size", None)
            if get is None:
                return None
            sizes.append(get())
        return sum(sizes) if sizes else 0

    def _count_compiles(self, sig, n_before: Optional[int]) -> None:
        """Signature-set + executable-cache-size compile accounting; the
        cache size also catches sharding/aval-keyed recompiles the
        signature heuristic cannot predict (the PR-4 lesson)."""
        fresh = sig not in self._seen_sigs
        self._seen_sigs.add(sig)
        grew = (n_before is not None
                and (self._cache_size() or 0) > n_before)
        # serve.compile_storm (docs/RESILIENCE.md): count a PHANTOM
        # post-warmup compile — no real XLA work, but every consumer of
        # the accounting (watchdog, strict guard, window rows) sees one,
        # so the re-warm remediation is deterministically drivable.
        # Short-circuit order matters: an unwarmed (re-warming) engine
        # must not consume armed fires.
        storm = self.warmed and failpoints.should_fire(
            "serve.compile_storm")
        if not (fresh or grew or storm):
            return
        self.compiles_total += 1
        if not self.warmed:
            return
        self.compiles_after_warmup += 1
        tracing.instant("serve/recompile", sig=str(sig))
        log.warning("serve: post-warmup XLA compile (sig=%s)", sig)
        if self._guard == "strict":
            raise ServeCompileError(
                f"post-warmup compile in the serving hot path (sig={sig}); "
                "warm every bucket before taking traffic "
                "(docs/SERVING.md)"
            )

    # -- query path --------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest configured bucket >= n (callers chunk above max)."""
        for b in self.cfg.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"batch of {n} exceeds the largest bucket "
            f"{self.cfg.buckets[-1]} (the batcher must chunk)"
        )

    def length_bucket_for(self, n: int) -> int:
        """Smallest configured length bucket >= n tokens."""
        for b in self.cfg.length_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"row of {n} tokens exceeds the largest length bucket "
            f"{self.cfg.length_buckets[-1]}"
        )

    def padded_tokens(self, lengths: Sequence[int]) -> int:
        """Tokens one dispatch of rows of these lengths runs padded to:
        its rows bucket x its length bucket (what ``token_budget``
        bounds)."""
        return (self.bucket_for(len(lengths))
                * self.length_bucket_for(max(lengths)))

    def _encode_tokens(self, rows: Sequence[np.ndarray]) -> np.ndarray:
        """The token engine's encode: int32 rows of differing length,
        right-padded to (rows bucket, length bucket) with their true
        lengths beside them (a padding row is one token long: the pool
        divides by the length)."""
        n = len(rows)
        bucket = self.bucket_for(n)
        lens = [len(r) for r in rows]
        width = self.length_bucket_for(max(lens))
        tokens = sum(lens)
        n_before = self._cache_size()
        with tracing.span("serve/encode", rows=n, bucket=bucket,
                          tokens=tokens, padded_tokens=bucket * width,
                          length_bucket=width):
            ids = np.zeros((bucket, width), np.int32)
            lengths = np.ones((bucket,), np.int32)
            for i, r in enumerate(rows):
                ids[i, :lens[i]] = r
            lengths[:n] = lens
            emb = self._encode_fn(self.state, jnp.asarray(ids),
                                  jnp.asarray(lengths))
            with tracing.span("serve/encode/wait"):
                emb = np.asarray(emb)
        self.tokens_encoded += tokens
        self.tokens_padded += bucket * width
        self._count_compiles(("encode", (bucket, width)), n_before)
        return emb[:n]

    def encode(self, inputs) -> np.ndarray:
        """Raw inputs -> unit-norm query embeddings via the restored
        trunk (eval mode), padded per bucket like :meth:`query`.  A token
        engine (``cfg.length_buckets``) takes a list of 1-D int32 rows
        and pads their length to a bucket as well."""
        if self._encode_fn is None:
            raise RuntimeError(
                "engine built without model/state: embedding queries only"
            )
        if self.cfg.length_buckets:
            return self._encode_tokens(inputs)
        x = np.asarray(inputs, np.float32)
        n = x.shape[0]
        bucket = self.bucket_for(n)
        n_before = self._cache_size()
        # The span is the whole encode as the dispatcher pays it: pad,
        # host->device, launch, the wait for the device and the
        # device->host copy (the jitted call alone returns at launch).
        with tracing.span("serve/encode", rows=n, bucket=bucket):
            if bucket > n:
                x = np.concatenate(
                    [x, np.zeros((bucket - n, *x.shape[1:]), np.float32)]
                )
            emb = self._encode_fn(self.state, jnp.asarray(x))
            with tracing.span("serve/encode/wait"):
                emb = np.asarray(emb)
        self._count_compiles(("encode", tuple(x.shape)), n_before)
        return emb[:n]

    def query(
        self, embeddings: np.ndarray, normalize: bool = True,
        stages: Optional[Dict[str, float]] = None,
        launched: Optional["PendingSearch"] = None,
    ) -> Dict[str, np.ndarray]:
        """Top-k for ``(B, D)`` query embeddings: :meth:`launch`, then
        its ``collect()``.  Returns ``{"scores", "rows", "labels",
        "ids"}``, each (B, top_k).  ``launched`` is what an earlier
        :meth:`launch` of these embeddings returned: only its collect
        runs here (a two-phase caller launches on one thread and
        collects on another)."""
        if launched is None:
            launched = self.launch(embeddings, normalize, stages)
        return launched.collect()

    def launch(
        self, embeddings: np.ndarray, normalize: bool = True,
        stages: Optional[Dict[str, float]] = None,
    ) -> PendingSearch:
        """Launch the top-k for ``(B, D)`` query embeddings; returns
        the :class:`PendingSearch` that collects it.

        Pads B to the smallest bucket (chunking batches above the
        largest), copies to the device and launches the jitted
        streamed/sharded top-k; the collect maps winning gallery rows
        to labels/ids host-side.

        ``stages`` (optional) is a per-call accumulator the qtrace
        layer passes in: WHEN the top-k (launch to the end of the
        device-to-host copy) and the host label/id gather ran lands in
        ``score_at`` / ``gather_at``, each ``(start, end)`` in
        ``perf_counter`` seconds, first bucket chunk's start to last
        chunk's end.  Per-call (not an engine
        attribute) on purpose — a crash reroute dispatches two batches
        on one engine concurrently, and racing attributes would charge
        one batch's score time to the other's trace.
        """
        q = np.asarray(embeddings, np.float32)
        if q.ndim != 2 or q.shape[1] != self.index.dim:
            raise ValueError(
                f"queries {q.shape} do not match gallery dim "
                f"{self.index.dim}"
            )
        with tracing.span("serve/launch", rows=q.shape[0]):
            if normalize and q.shape[0]:
                q = l2_normalize_rows(q)
            max_b = self.cfg.buckets[-1]
            chunks = [self._query_bucketed(q[i:i + max_b])
                      for i in range(0, q.shape[0], max_b)]
        return PendingSearch(chunks, self.cfg.top_k, stages)

    def _topk_call(self, bucket: int, idx):
        """(dispatch args, compile signature) for ``idx``'s current
        state — read ONCE per dispatch, so an IVF republish (add())
        lands between dispatches, never inside one."""
        if self._ivf:
            layout = idx.layout
            slab, scale = idx.scored_arrays(self.cfg.scoring,
                                            layout=layout)
            args = (slab, layout.rows, layout.centroids,
                    layout.cluster_valid)
            if scale is not None:
                args += (scale,)
            sig = ("ivf", bucket, tuple(layout.packed.shape),
                   self.cfg.scoring, self.probe_impl)
            return args, sig
        return ((idx.emb, idx.labels, idx.valid),
                ("topk", bucket, idx.padded_size, idx.dim))

    def _query_bucketed(self, q: np.ndarray) -> _Chunk:
        """Pad one chunk to its bucket, copy it to the device and launch
        the top-k on the index as it is read here, once."""
        n = q.shape[0]
        bucket = self.bucket_for(n)
        if bucket > n:
            q = np.concatenate(
                [q, np.zeros((bucket - n, q.shape[1]), np.float32)]
            )
        idx = self.index
        # serve.recall_drop (docs/RESILIENCE.md): deterministically
        # mis-probe the IVF top-C selection for this dispatch — the
        # centroid scan runs against the NEGATED query, so the probe
        # set is the worst clusters and recall collapses while shapes,
        # sharding, and compile signatures stay identical (zero
        # recompiles, the strict guard never trips).  Gated on
        # ``warmed`` so warmup/re-warm dispatches never consume armed
        # fires, and on the IVF path so a flat tier (the recall
        # oracle) leaves the arming untouched.
        if self._ivf and self.warmed and \
                failpoints.should_fire("serve.recall_drop"):
            q = -q
        args, sig = self._topk_call(bucket, idx)
        labels, ids = idx._host_labels, idx.ids
        n_before = self._cache_size()
        t_score = time.perf_counter()
        out = self._topk_fn(jnp.asarray(q), *args)
        # A compile happens inside the call, before it returns: counted
        # here, on the launching thread, never beside another launch.
        self._count_compiles(sig, n_before)
        return _Chunk(tuple(out), n, bucket, labels, ids, t_score)

    # -- warmup ------------------------------------------------------------

    def warmup(self, input_shape: Optional[Sequence[int]] = None) -> float:
        """Compile and prime every padding bucket with one dummy
        dispatch each — after this returns, steady-state serving
        performs ZERO XLA compiles (the counters prove it).  The
        dispatch-time compile consults AND populates the persistent
        compile cache when one is enabled, so replica restarts
        deserialize instead of recompiling.  (An AOT
        ``lower().compile()`` first would pay every compile twice: jit's
        dispatch cache ignores AOT executables, so the priming dispatch
        recompiles from scratch.)  Returns the wall seconds spent.

        ``input_shape`` is one float input's shape.  A token engine
        warms from its own ``length_buckets`` instead -- every (rows
        bucket, length bucket) pair within ``token_budget`` -- and
        reads nothing of the argument."""
        import time as _time

        idx = self.index
        t0 = _time.perf_counter()
        for bucket in self.cfg.buckets:
            with tracing.span("serve/warmup", bucket=bucket, kind="topk"):
                self.query(np.zeros((bucket, idx.dim), np.float32),
                           normalize=False)
            if self._encode_fn is not None and self.cfg.length_buckets:
                budget = self.cfg.token_budget
                for width in self.cfg.length_buckets:
                    if budget and bucket * width > budget:
                        break
                    with tracing.span("serve/warmup", bucket=bucket,
                                      kind="encode", length_bucket=width):
                        self.encode([np.zeros((width,), np.int32)] * bucket)
            elif self._encode_fn is not None:
                if input_shape is None:
                    raise ValueError(
                        "warmup needs input_shape to warm the encode path"
                    )
                with tracing.span("serve/warmup", bucket=bucket,
                                  kind="encode"):
                    self.encode(np.zeros((bucket, *tuple(input_shape)),
                                         np.float32))
        self.warmed = True
        dt = _time.perf_counter() - t0
        log.info("serve warmup: %d bucket(s) compiled in %.2fs",
                 len(self.cfg.buckets), dt)
        return dt

    def rewarm(self, input_shape: Optional[Sequence[int]] = None) -> float:
        """Re-prime every padding bucket and RESET the post-warmup
        compile counter — the compile-storm remediation action
        (docs/RESILIENCE.md §Remediation).  The re-warm dispatches run
        with ``warmed`` cleared, so any compile they trigger counts as
        warmup (never trips the strict guard), and
        ``compiles_after_warmup`` restarts at zero so the post-warmup-
        compile watchdog can observe recovery.  Returns wall seconds.

        A re-warm that RAISES resets nothing: the engine keeps serving
        (``warmed`` restored so accounting stays armed) and the storm
        evidence in ``compiles_after_warmup`` survives — the alert that
        triggered the failed remediation must keep its basis."""
        self.warmed = False
        try:
            dt = self.warmup(input_shape)  # sets warmed=True on success
        except BaseException:
            self.warmed = True
            raise
        self.compiles_after_warmup = 0
        return dt

    def compile_stats(self) -> Dict[str, Any]:
        return {
            "warmed": self.warmed,
            "compiles_total": self.compiles_total,
            "compiles_after_warmup": self.compiles_after_warmup,
            "executable_cache_size": self._cache_size(),
            # Token engines only (absent-when-off: a float-input tier's
            # summary keeps its shape).
            **({"tokens_encoded": self.tokens_encoded,
                "tokens_padded": self.tokens_padded}
               if self.cfg.length_buckets else {}),
        }
