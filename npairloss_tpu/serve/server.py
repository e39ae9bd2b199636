"""RetrievalServer — snapshot-to-answers front ends over the engine.

Two front ends share one serving core (admit -> micro-batch -> jitted
top-k -> answer):

  * **stdin/JSONL** (:meth:`RetrievalServer.run_jsonl`): one request
    object per line in, one answer object per line out, in request
    order.  The loop reads ahead (bounded by the batcher's admission
    queue) so consecutive requests coalesce into micro-batches.
  * **localhost HTTP** (:meth:`RetrievalServer.run_http`): ``POST
    /query`` with a JSON request (or JSONL body of several), ``GET
    /healthz`` for liveness/stats.  Each request thread submits and
    waits, so concurrent clients batch naturally.

Request: ``{"id": ..., "embedding": [...]}`` (a query embedding) or
``{"id": ..., "input": [...]}`` (raw input, needs a restored model).
Answer: ``{"id", "neighbors": [{"rank", "row", "gallery_id", "label",
"score"}, ...]}``; a rejected/failed query answers ``{"id", "error"}``
instead of being silently dropped.  An ingest record ``{"id",
"ingest": {"ids", "labels", "embeddings"}}`` takes the durable path
instead (docs/RESILIENCE.md §Durability): write-ahead log append +
group-commit fsync barrier BEFORE the ``{"id", "ingested", "seq"}``
ack, so a SIGKILL after the ack can never lose the vectors.

Shutdown is the training preemption contract (docs/RESILIENCE.md)
applied to serving: SIGTERM/SIGINT set the ``resilience.preempt`` flag,
the front end stops ADMITTING, every in-flight query drains to an
answer, telemetry flushes, and the process exits
:data:`~npairloss_tpu.resilience.preempt.EXIT_PREEMPTED` (75) so a
supervisor knows the stop was graceful.  A final ``serve_drain``
summary record (queries, answers, p50/p99, compile counters) is the
last line the JSONL front end writes.
"""

from __future__ import annotations

import base64
import collections
import contextlib
import dataclasses
import functools
import json
import logging
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from npairloss_tpu.obs import tracing
from npairloss_tpu.resilience import failpoints
from npairloss_tpu.resilience.preempt import EXIT_PREEMPTED, PreemptionSignal
from npairloss_tpu.serve.batcher import BatcherConfig, QueueFullError
from npairloss_tpu.serve.engine import QueryEngine

log = logging.getLogger("npairloss_tpu.serve")


class UnknownTenantError(ValueError):
    """A record named a tenant the registry does not know.  Raised
    from ``submit`` BEFORE the query is counted: an unregistered id is
    a malformed request (the bad-JSON accounting — errors, never
    queries/rejected), not admitted-then-shed traffic."""


def encode_ingest_body(ingest: Dict[str, Any]) -> Dict[str, Any]:
    """A client ingest block -> the ``npairloss-wal-v1`` ``kind: "add"``
    record body (docs/RESILIENCE.md §Durability).  ``ids`` are REQUIRED:
    the WAL is the replay source of truth, and auto-assigned ids would
    come out different on every replay — breaking the exactly-once
    duplicate check.  The embedding matrix rides as base64 float32 so
    the record (and the jax-free WAL validator reading it) stays
    numpy-free."""
    if not isinstance(ingest, dict):
        raise ValueError("ingest must be an object")
    emb = np.asarray(ingest.get("embeddings"), np.float32)
    if emb.ndim != 2 or emb.shape[0] == 0 or emb.shape[1] == 0:
        raise ValueError(
            f"ingest embeddings must be a non-empty 2-D matrix, got "
            f"shape {emb.shape}")
    labels = ingest.get("labels")
    ids = ingest.get("ids")
    if not isinstance(labels, list) or len(labels) != emb.shape[0]:
        raise ValueError("ingest labels must list one label per row")
    if not isinstance(ids, list) or len(ids) != emb.shape[0]:
        raise ValueError(
            "ingest ids must list one id per row (replay determinism "
            "forbids auto-assignment)")
    return {
        "kind": "add",
        "ids": [int(i) for i in ids],
        "labels": [int(x) for x in labels],
        "dim": int(emb.shape[1]),
        "emb": base64.b64encode(emb.tobytes()).decode("ascii"),
    }


def decode_ingest_payload(payload: Dict[str, Any]
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The inverse of :func:`encode_ingest_body`: a replayed WAL record
    body -> ``(embeddings, labels, ids)`` ready for ``index.add``."""
    ids = np.asarray(payload["ids"], np.int64)
    raw = base64.b64decode(payload["emb"])
    emb = np.frombuffer(raw, np.float32)
    dim = int(payload["dim"])
    if dim < 1 or emb.size != ids.shape[0] * dim:
        raise ValueError(
            f"ingest record seq {payload.get('seq')}: embedding bytes "
            f"({emb.size} float32) do not match {ids.shape[0]} row(s) "
            f"of dim {dim}")
    return (emb.reshape(ids.shape[0], dim).copy(),
            np.asarray(payload["labels"], np.int32), ids)


@dataclasses.dataclass(frozen=True)
class Freshness:
    """What the serving tier is answering FROM, and how old it is
    (ROADMAP item 4 first slice; docs/OBSERVABILITY.md §Live
    observatory).  ``snapshot_*`` identify the restored model behind
    the encode path (None for embedding-only serving);
    ``index_created`` is the gallery's commit/assembly wall time
    (``GalleryIndex.created``).  ``ages()`` turns both into seconds —
    stamped on every answer, on ``/healthz``, and on the drain
    summary, live-obs on or off."""

    index_path: Optional[str] = None
    index_created: Optional[float] = None
    snapshot_path: Optional[str] = None
    snapshot_step: Optional[int] = None
    snapshot_created: Optional[float] = None

    @classmethod
    def collect(cls, index=None, index_path: Optional[str] = None,
                snapshot_path: Optional[str] = None) -> "Freshness":
        """From the served objects: the index's ``created`` attribute
        plus the snapshot's commit manifest (``train.snapshot_info`` —
        no array loads)."""
        snap_step = snap_created = None
        if snapshot_path is not None:
            from npairloss_tpu.train import snapshot_info

            info = snapshot_info(snapshot_path)
            snapshot_path = info["path"]
            snap_step, snap_created = info["step"], info["created"]
        return cls(
            index_path=index_path,
            index_created=getattr(index, "created", None),
            snapshot_path=snapshot_path,
            snapshot_step=snap_step,
            snapshot_created=snap_created,
        )

    def ages(self, now: Optional[float] = None) -> Dict[str, float]:
        """``model_age_s``/``index_age_s`` — keys absent when the
        corresponding identity is unknown (embedding-only serving has
        no model age; a manifest-less index has no commit time), so a
        consumer never mistakes "unknown" for "fresh"."""
        now = time.time() if now is None else now
        out: Dict[str, float] = {}
        if self.index_created is not None:
            out["index_age_s"] = round(max(now - self.index_created, 0.0), 3)
        if self.snapshot_created is not None:
            out["model_age_s"] = round(
                max(now - self.snapshot_created, 0.0), 3)
        return out

    def identity(self) -> Dict[str, Any]:
        """The non-age half (for /healthz + the drain summary): which
        snapshot/index, omitting unknown fields."""
        out: Dict[str, Any] = {}
        if self.index_path is not None:
            out["index_path"] = self.index_path
        if self.snapshot_path is not None:
            out["snapshot_path"] = self.snapshot_path
        if self.snapshot_step is not None:
            out["snapshot_step"] = self.snapshot_step
        return out


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """``metrics_window``: queries per emitted latency/throughput row
    (0 = none); ``latency_window``: ring capacity for the percentile
    estimate; ``poll_s``: front-end wakeup period for noticing a drain
    request while idle; ``explicit_drops``: carry ``queries_dropped``
    in the summary/healthz even at zero (a gameday verdict's zero-drop
    gate must read a MEASURED 0, not an absent key — the
    ``compiles_after_warmup`` explicit-key posture; default off keeps
    clean streams byte-identical to pre-PR)."""

    metrics_window: int = 100
    latency_window: int = 1024
    poll_s: float = 0.1
    explicit_drops: bool = False


def _token_cfg(engine):
    """The engine's config when it is a TOKEN engine (``length_buckets``
    set), else None; an engine stand-in without a ``cfg`` is a float
    engine."""
    cfg = getattr(engine, "cfg", None)
    return cfg if getattr(cfg, "length_buckets", ()) else None


def _float_input(value) -> np.ndarray:
    return np.asarray(value, np.float32)


def _token_input(value, longest: int, vocab: Optional[int]) -> np.ndarray:
    """One token record's ``input``: a 1-D integer sequence of 1 to
    ``longest`` ids inside the vocabulary, as int32; anything else is
    that request's error."""
    row = np.asarray(value)
    if row.ndim != 1 or (row.size and row.dtype.kind not in "iu"):
        raise ValueError(
            "token input must be a 1-D sequence of integer ids, got "
            f"shape {row.shape} dtype {row.dtype}"
        )
    if not 1 <= row.shape[0] <= longest:
        raise ValueError(
            f"token input of {row.shape[0]} ids; this engine takes 1 to "
            f"{longest}"
        )
    if row.min() < 0 or (vocab is not None and row.max() >= vocab):
        raise ValueError(
            f"token id outside the vocabulary [0, {vocab}): "
            f"{int(row.min())}..{int(row.max())}"
        )
    return row.astype(np.int32, copy=False)


@dataclasses.dataclass
class _LaunchedBatch:
    """A batch between its two phases: its records, the answers known
    at launch (per-record errors), the query rows with their item
    positions and stacked (None when no record reached the search), the
    engine's launched top-k (None from an engine stand-in without
    ``launch``: it searches at the finish), and what the finish needs
    to stamp and trace the answers."""

    items: List[Dict[str, Any]]
    answers: List[Optional[Dict[str, Any]]]
    emb_rows: List[tuple]
    rows: Optional[np.ndarray]
    search: Any
    stages: Optional[Dict[str, Any]]
    qts: List[Any]
    tstamp: Dict[str, Any]
    entry: Any
    engine: Any


class RetrievalServer:
    """N replica engines + per-replica batchers + the request/answer
    protocol (one engine is the degenerate, pre-replica-tier shape)."""

    def __init__(
        self,
        engine,
        batcher_cfg: BatcherConfig = BatcherConfig(),
        cfg: ServerConfig = ServerConfig(),
        telemetry=None,
        preempt: Optional[PreemptionSignal] = None,
        freshness: Optional[Freshness] = None,
        live=None,
        admission=None,
        input_shape=None,
        qtrace=None,
    ):
        from npairloss_tpu.serve.replicas import ReplicaSet

        # ``engine`` may be one QueryEngine or a sequence of replica
        # engines (docs/SERVING.md §Approximate index): each replica
        # gets its own batcher/admission queue; routing is least-loaded
        # live replica.  ``self.engine`` stays the primary — compile
        # stats and index identity are tier-wide (replicas share the
        # primary's compiled programs).
        engines = (list(engine) if isinstance(engine, (list, tuple))
                   else [engine])
        self.engines: List[QueryEngine] = engines  # guarded-by: _lock
        self.engine = engines[0]  # guarded-by: _lock
        self.cfg = cfg
        self.telemetry = telemetry
        self.preempt = preempt
        # Freshness identity (stamped on every answer + /healthz + the
        # drain summary — live-obs on or off) and the optional
        # LiveObservatory (obs.live): /metrics exposition + SLO status
        # on /healthz.  Both default None: the pre-PR server shape.
        self.freshness = freshness  # guarded-by: _lock
        self.live = live
        # SLO-burn-driven admission control (serve/admission.py): when
        # set, submits consult it BEFORE routing — a shed is a
        # fast-reject counted in the ``rejected`` invariant.
        self.admission = admission
        # Per-query stage tracing (obs.qtrace): trace ids assigned at
        # ingestion ride each record through admission, the router, the
        # batcher, and the engine; None (the default) keeps every
        # emitted stream byte-identical to a qtrace-free build (the
        # shadow=None posture, pinned by tests/test_qtrace.py).
        self.qtrace = qtrace
        # Raw-input shape for encode-path re-warms (None = embedding-
        # only serving) and the optional RemediationEngine whose
        # last-action-per-policy the summary/healthz surface
        # (docs/RESILIENCE.md §Remediation).
        self.input_shape = (tuple(input_shape)
                            if input_shape is not None else None)
        self.remediation = None
        # Optional ShadowScorer (obs.quality.shadow): the dispatch
        # OFFERS every answered query; the scorer samples, queues, and
        # re-scores off the hot path.  None (the default) keeps the
        # serving path and every emitted stream byte-identical to a
        # shadow-free build (pinned by tests/test_quality.py).
        self.shadow = None
        # Hot-swap state (serve/hotswap.py): count of engine-tier
        # republishes, and whether a re-warm has made the window rows'
        # compiles_after_warmup key EXPLICIT (present even at zero) so
        # the post-warmup-compile watchdog can observe recovery — clean
        # never-remediated runs keep the absent-when-zero contract.
        self.swaps = 0  # guarded-by: _lock
        self._explicit_compile_key = False
        # Durable-ingest state (docs/RESILIENCE.md §Durability): all
        # None/zero until ``attach_wal`` arms the path, so a WAL-less
        # server keeps its pre-PR behavior and summary shape.  The
        # ingest lock serializes record application, checkpointing, and
        # the hot-swap flip — ``_lock`` is only ever taken INSIDE it
        # (never the reverse), so the two can nest without deadlock.
        self.wal = None
        self._ingest_lock = threading.Lock()
        self._ingest_apply: Optional[Callable[[Dict[str, Any]], None]] = None
        self._checkpoint_fn: Optional[Callable[[int], Optional[str]]] = None
        self._checkpoint_every = 0
        self.ingest_batches = 0  # guarded-by: _lock
        self.ingest_vectors = 0  # guarded-by: _lock
        self.ingest_errors = 0  # guarded-by: _lock
        self._ingest_watermark = 0  # guarded-by: _ingest_lock
        self._ckpt_watermark = 0  # guarded-by: _ingest_lock
        self._ingest_since_ckpt = 0  # guarded-by: _ingest_lock
        # Multi-tenant map (serve/tenants.py): empty until
        # ``enable_tenants`` installs it, so a single-tenant server
        # keeps every pre-PR behavior and stream byte-identical.  When
        # armed, each query/ingest record must carry a registered
        # "tenant" id; counters, freshness, quota, admission, shadow,
        # and ingest split per entry while the replica tier, front
        # ends, and compiled programs stay shared.
        self.tenants: Dict[str, Any] = {}
        self._replica_idx: Dict[str, int] = {}
        self.replicaset = ReplicaSet(
            engines, batcher_cfg, self._replica_dispatch,
            on_batch=self._record_batch,
            on_pick=self._qtrace_pick if qtrace is not None else None,
            # A token engine bounds a dispatch in padded TOKENS (rows
            # bucket x length bucket), not in requests alone.
            fits=(self._token_coriders
                  if _token_cfg(self.engine) is not None else None),
        )
        self._lat = collections.deque(maxlen=max(cfg.latency_window, 1))
        # THIS window's latencies, cleared at each emission: window rows
        # report the window they describe (a live p99 watchdog must see
        # recovery when behavior recovers — a 1024-deep running ring
        # would keep an old incident's tail in every later row);
        # the drain/healthz percentiles still read the smoothed ring.
        self._window_lat: list = []
        # Request threads, the dispatcher, and the hot-swap path all
        # touch the counters and the published engine tier: mutations
        # hold the lock (enforced by `staticcheck`, docs/STATICCHECK.md;
        # the swap attrs engine/engines/freshness/swaps are annotated
        # where cmd_serve first publishes them, in ``swap_engines``).
        self._lock = threading.Lock()
        self.queries = 0  # guarded-by: _lock
        self.answered = 0  # guarded-by: _lock
        self.errors = 0  # guarded-by: _lock
        # Errors refused BEFORE admission (bad JSON, unknown tenant):
        # counted in ``errors`` but never in ``queries``, so the drop
        # residual must exclude them or a refusal reads as a negative
        # drop count.
        self.errors_refused = 0  # guarded-by: _lock
        self._window_t0 = time.perf_counter()
        self._window_n = 0
        self._last_batch: Dict[str, Any] = {}
        # Tracer event-index cursor for the per-window latency
        # decomposition (obs.perf.decompose): each emitted window reads
        # only the spans appended since the previous one (appends
        # happen at span END, so a span in flight across the boundary
        # lands in the next window instead of vanishing), and the read
        # is O(window), never a full-buffer rescan under the tracer
        # lock.  The cursor's read-advance is guarded by its own lock:
        # window emissions run on whichever request thread crossed the
        # window threshold (deliberately outside self._lock), and two
        # concurrent emissions reading the same stale cursor would
        # double-count one window's spans into both splits.  Both
        # cursors baseline at CONSTRUCTION time: cmd_serve warms the
        # engine first, and warmup's serve/topk spans are XLA compiles
        # — seconds-long outliers that would otherwise own the first
        # window's and the drain summary's p99.
        tracer = self._tracer()
        baseline = tracer.num_events if tracer is not None else 0
        self._events_start_idx = baseline
        self._window_events_idx = baseline
        self._window_events_lock = threading.Lock()

    @property
    def batcher(self):
        """The primary replica's batcher (the pre-replica-tier attribute;
        aggregate counters live on ``self.replicaset``)."""
        return self.replicaset.replicas[0].batcher

    def _replica_dispatch(self, replica):
        """Per-replica dispatch wrapper: crash containment around the
        shared answer logic.  The ``serve.replica_crash`` failpoint
        (docs/RESILIENCE.md) kills THIS replica: its in-flight batch —
        and every batch still queued on it — REROUTES to a surviving
        replica (zero client-visible errors), and the router stops
        selecting it.  Only a whole-tier loss fails the work.  The
        callable launches the batch and returns its finish, which the
        batcher's completion thread calls."""

        def dispatch(items: List[Dict[str, Any]]) -> Callable[[], List]:
            if not replica.alive:
                return self._reroute(replica, items)
            if failpoints.should_fire("serve.replica_crash"):
                replica.alive = False
                log.error("replica %s crashed (injected); %d live "
                          "replica(s) remain — rerouting its work",
                          replica.name, self.replicaset.alive_count)
                return self._reroute(replica, items)
            return self._launch(items, engine=replica.engine,
                                replica=replica.name)

        return dispatch

    def _reroute(self, dead, items: List[Dict[str, Any]]
                 ) -> Callable[[], List[Dict[str, Any]]]:
        """Launch a dead replica's batch on a surviving replica's
        engine — the ``serve.replica_crash`` containment promise: a
        replica loss stays invisible to clients while ANY replica
        survives.  Runs on the dead replica's own dispatcher thread
        (replicas share one compiled-program set, so the reroute costs
        no extra compile and never waits on another queue); a
        whole-tier loss raises, failing the batch to error answers.
        Deliberately NOT ``replicaset.pick()``: pick counts a
        whole-tier miss in ``rejected``, and these queries are about to
        be counted in ``errors`` — one query must land in exactly one
        term of the drain invariant."""
        from npairloss_tpu.serve.replicas import ReplicaCrashError

        live = [r for r in self.replicaset.replicas if r.alive]
        if not live:
            raise ReplicaCrashError(
                f"replica {dead.name} is down and no live replica "
                "remains")
        target = min(live, key=lambda r: r.batcher.queue_depth)
        log.warning("rerouting %d quer%s from dead replica %s to %s",
                    len(items), "y" if len(items) == 1 else "ies",
                    dead.name, target.name)
        if self.qtrace is not None:
            # The reroute instant explains the detour in any exemplar
            # that rode it (and the gameday attribution check reads the
            # marker count as the replica-crash evidence).
            self.qtrace.marker("crash_reroute", dead=dead.name,
                               target=target.name, queries=len(items))
        return self._launch(items, engine=target.engine,
                            replica=target.name)

    # -- telemetry ---------------------------------------------------------

    def _record_batch(self, stats: Dict[str, Any]) -> None:
        self._last_batch = stats

    # -- qtrace glue (no-ops unless a QueryTracer is attached) -------------

    def _qtrace_begin(self, rec):
        """Assign a trace id at ingestion; the context rides the record
        itself so the batcher/replica threads need no side channel."""
        if self.qtrace is None or not isinstance(rec, dict):
            return None
        qt = self.qtrace.begin(rec.get("id"))
        rec["_qt"] = qt
        return qt

    def _qtrace_pick(self, item) -> None:
        """Batcher ``on_pick`` hook: the dispatcher pulled this record
        off its replica's admission queue — ``queue_wait`` ends."""
        qt = item.get("_qt") if isinstance(item, dict) else None
        if qt is not None:
            self.qtrace.picked(qt)

    def _qtrace_drop(self, qt, error: bool = False) -> None:
        """A query that will never be answered: counted by the tracer,
        excluded from both aggregation populations (the same population
        the latency rings keep — see ``_record_latency``)."""
        if qt is not None and self.qtrace is not None:
            self.qtrace.drop(qt, error=error)

    def _record_latency(self, seconds: float, qt=None,
                        entry=None) -> None:
        if qt is not None and self.qtrace is not None:
            # Finish the trace BEFORE the window-threshold check so the
            # query that closes a window lands in that window's stage
            # decomposition, mirroring its latency sample below.
            self.qtrace.finish(qt)
        qps, lat_snap = 0.0, None
        with self._lock:
            self._lat.append(seconds * 1e3)
            if self.cfg.metrics_window:
                # One population, two views: a sample enters the
                # smoothed ring AND the window list here or nowhere
                # (dropped/errored queries enter neither) — with
                # windows off the per-window list must stay empty, not
                # accumulate a divergent unbounded copy of the ring
                # (pinned by tests/test_qtrace.py).
                self._window_lat.append(seconds * 1e3)
            if entry is not None:
                # The tenant's own rings: same sample, same population
                # rule — its p99 SLO burns on ITS tail, not the tier's.
                entry.answered += 1
                entry.lat.append(seconds * 1e3)
                if self.cfg.metrics_window:
                    entry.window_lat.append(seconds * 1e3)
            self.answered += 1
            self._window_n += 1
            if (self.cfg.metrics_window
                    and self._window_n >= self.cfg.metrics_window):
                now = time.perf_counter()
                qps = self._window_n / max(now - self._window_t0, 1e-9)
                lat_snap = self._window_lat
                self._window_lat = []
                self._window_t0 = now
                self._window_n = 0
        if lat_snap is not None:
            self._emit_window(qps, lat_snap)

    def _account(self, answer: Dict[str, Any], t0: float,
                 qt=None) -> Dict[str, Any]:
        """Per-answer bookkeeping: an ``{"id", "error"}`` answer (a
        malformed record the dispatch answered individually) counts as
        an error, everything else as an answered query with latency —
        attributed to the answer's tenant in tenant mode (the dispatch
        stamped the id, so no side channel is needed)."""
        entry = (self.tenants.get(answer.get("tenant"))
                 if self.tenants and isinstance(answer, dict) else None)
        if "error" in answer:
            with self._lock:
                self.errors += 1
                if entry is not None:
                    entry.errors += 1
            self._qtrace_drop(qt, error=True)
        else:
            self._record_latency(time.perf_counter() - t0, qt,
                                 entry=entry)
        return answer

    def _percentiles(
        self, lat: Optional[List[float]] = None
    ) -> Dict[str, float]:
        if lat is None:
            lat = list(self._lat)
        if not lat:
            return {"p50_ms": 0.0, "p99_ms": 0.0}
        return {
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
        }

    def _tracer(self):
        tel = self.telemetry
        return getattr(tel, "tracer", None) if tel is not None else None

    @staticmethod
    def _latency_split(events) -> Dict[str, float]:
        """Per-stage p50/p99 (encode/batch/dispatch/topk/admit) from a
        list of serve/* span events — flattened to
        ``<stage>_p50_ms``/``<stage>_p99_ms`` row keys (the Gemma-
        serving-style latency decomposition, obs.perf.decompose)."""
        from npairloss_tpu.obs.perf.decompose import (
            serve_latency_decomposition,
        )

        split = serve_latency_decomposition(events)
        return {
            f"{stage}_{q}": v
            for stage, row in split.items()
            for q, v in row.items() if q != "count"
        }

    def _window_latency_split(self) -> Dict[str, float]:
        """The current window's split: spans appended (= finished)
        since the last window read, via the tracer's incremental
        cursor.  ``spans_dropped`` surfaces the tracer's max_events cap
        in the row stream itself — a capped tracer means the split has
        silently gone partial, and that must be visible where the
        p50/p99 numbers are read."""
        tracer = self._tracer()
        if tracer is None:
            return {}
        with self._window_events_lock:
            events, self._window_events_idx, dropped = tracer.events_since(
                self._window_events_idx)
        out = self._latency_split(events)
        if dropped:
            out["spans_dropped"] = dropped
        return out

    def _emit_window(self, qps: float, lat: List[float]) -> None:
        """One latency/throughput/queue-depth row per window — the
        serving counterpart of the train loop's display cadence.  The
        counters were snapshot under the lock; the percentile math and
        telemetry/log I/O here run OUTSIDE it so concurrent answer
        accounting never stalls on a window emission."""
        row = {
            "qps": round(qps, 1),
            **{k: round(v, 3) for k, v in self._percentiles(lat).items()},
            "queue_depth": self.replicaset.queue_depth,
            "batches": self.replicaset.batches,
            "rejected": self._rejected_total(),
            **self._window_latency_split(),
            # THIS window's p99 budget decomposition: the dominant
            # stage among its worst queries (absent with qtrace off —
            # the spans_dropped byte-identity contract).
            **(self.qtrace.window_row()
               if self.qtrace is not None else {}),
            **{f"batch_{k}": round(v, 3) if isinstance(v, float) else v
               for k, v in self._last_batch.items()},
        }
        if len(self.engines) > 1:
            # Replica-tier keys only exist on a replicated tier, so a
            # single-replica row stream stays byte-identical to pre-PR
            # (the spans_dropped contract).
            row["replicas_alive"] = self.replicaset.alive_count
        if self.admission is not None and self.admission.sheds:
            row["shed"] = self.admission.sheds
        compiles = self._compiles_after_warmup()
        if compiles or self._explicit_compile_key:
            # The strict guard's counting twin, in-row (the
            # spans_dropped contract: present only when > 0, so clean
            # streams stay byte-identical to pre-PR) — the live-obs
            # post-warmup-compile watchdog reads exactly this key.
            # After a re-warm remediation the key turns EXPLICIT
            # (present at zero): absent-when-zero would starve the
            # watchdog of the good samples resolution requires
            # (silence holds a burning SLO, by design).
            row["compiles_after_warmup"] = compiles
        # A token tier's running totals, beside the compile count
        # (absent on a float-input tier: its rows keep their shape).
        row.update(self._token_totals())
        if self.telemetry is not None and self.telemetry.metrics_enabled:
            try:
                self.telemetry.log("serve", self.answered, row)
            except Exception as e:  # noqa: BLE001 — telemetry is not the run
                log.error("serve metrics emission failed: %s", e)
        log.info("serve window: %s", row)
        if self.tenants:
            self._emit_tenant_windows()

    def _emit_tenant_windows(self) -> None:
        """One tenant-stamped row per tenant that answered this window.
        The ``tenant`` key makes the RegistrySink land every metric on
        labeled series (``serve_p99_ms{tenant="a"}``) — the sample
        streams the per-tenant SLOs burn on — so a noisy tenant's tail
        cannot hide inside the aggregate window row, and a quiet
        tenant emits nothing (no stale gauges)."""
        snaps: List[tuple] = []
        with self._lock:
            for tid in sorted(self.tenants):
                entry = self.tenants[tid]
                lat = entry.take_window()
                if lat:
                    snaps.append((tid, entry, lat))
        for tid, entry, lat in snaps:
            trow = {
                "tenant": tid,
                "queries": len(lat),
                **{k: round(v, 3)
                   for k, v in self._percentiles(lat).items()},
            }
            if entry.quota is not None and entry.quota.sheds:
                trow["quota_sheds"] = entry.quota.sheds
            if entry.admission is not None and entry.admission.sheds:
                trow["shed"] = entry.admission.sheds
            if entry.rejected:
                trow["rejected"] = entry.rejected
            if self.telemetry is not None \
                    and self.telemetry.metrics_enabled:
                try:
                    self.telemetry.log("serve", self.answered, trow)
                except Exception as e:  # noqa: BLE001 — telemetry is not the run
                    log.error("tenant %r metrics emission failed: %s",
                              tid, e)
            log.info("serve tenant window: %s", trow)

    # -- serving core ------------------------------------------------------

    def _dispatch(self, items: List[Dict[str, Any]],
                  engine: Optional[QueryEngine] = None,
                  replica: Optional[str] = None
                  ) -> List[Dict[str, Any]]:
        """A batch's two phases in one call: launch, then finish."""
        return self._launch(items, engine=engine, replica=replica)()

    def _launch(self, items: List[Dict[str, Any]],
                engine: Optional[QueryEngine] = None,
                replica: Optional[str] = None
                ) -> Callable[[], List[Dict[str, Any]]]:
        """Batcher dispatch: launch the batch, return its finish (a
        call with no arguments -> one answer per item, in order).
        Single-tenant: straight through to the core.  Tenant mode: a
        micro-batch may coalesce queries for SEVERAL galleries (the
        batchers are shared — that is the one-tier contract), so the
        batch splits by tenant id and each group launches on its
        tenant's engine for THIS replica; the finish answers the groups
        in turn and reassembles the answers in item order."""
        if not self.tenants:
            return functools.partial(
                self._finish_core,
                self._launch_core(items, engine=engine, replica=replica))
        ridx = self._replica_idx.get(replica, 0)
        groups: Dict[Any, List[int]] = {}
        for i, rec in enumerate(items):
            tid = rec.get("tenant") if isinstance(rec, dict) else None
            groups.setdefault(tid, []).append(i)
        answers: List[Optional[Dict[str, Any]]] = [None] * len(items)
        launched = []
        for tid, idxs in groups.items():
            entry = self.tenants.get(tid)
            if entry is None:
                # Defensive: submit() already refuses unknown tenants;
                # a record that lost its id between admit and dispatch
                # still answers instead of crashing its co-riders.
                for i in idxs:
                    answers[i] = {"id": items[i].get("id"),
                                  "tenant": tid,
                                  "error": f"unknown tenant {tid!r}"}
                continue
            eng = entry.engines[ridx if ridx < len(entry.engines)
                                else 0]
            launched.append((idxs, self._launch_core(
                [items[i] for i in idxs], engine=eng, replica=replica,
                entry=entry)))

        def finish() -> List[Dict[str, Any]]:
            for idxs, pending in launched:
                for i, ans in zip(idxs, self._finish_core(pending)):
                    answers[i] = ans
            return answers

        return finish

    def _token_coriders(self, items) -> int:
        """The batcher's ``fits`` on a token tier: how many of ``items``,
        counted from the first, ride in ONE dispatch.  A co-rider must
        cost nothing: the dispatch's padded tokens, counted as it would
        run (rows bucket x length bucket), stay within the primary
        engine's ``token_budget`` AND within the sum of what its rows
        would run padded alone.  Then riding together can only save the
        per-dispatch search and host share, never add device work.
        Prefixes are judged whole: with rows buckets (1, 4) two rows of
        one length bucket fail and four pass, so the answer is the
        LONGEST prefix that holds, not the first that fails.  A record
        with no usable token row counts one token: it fails alone at
        parse, whatever it rides with."""
        engine = self.engine
        cfg = engine.cfg
        longest = cfg.length_buckets[-1]

        def length(rec):
            try:
                n = len(rec["input"])
            except (KeyError, TypeError):
                return 1
            return n if 1 <= n <= longest else 1

        lens = [length(r) for r in items[:cfg.buckets[-1]]]
        best, alone = 1, 0
        for k, n in enumerate(lens, 1):
            alone += engine.padded_tokens([n])
            together = engine.padded_tokens(lens[:k])
            if together <= alone and (
                    not cfg.token_budget or together <= cfg.token_budget):
                best = k
        return best

    def _launch_core(self, items: List[Dict[str, Any]],
                     engine: Optional[QueryEngine] = None,
                     replica: Optional[str] = None,
                     entry=None) -> "_LaunchedBatch":
        """Coalesced query records -> a launched top-k; its answers come
        from :meth:`_finish_core`.  A malformed
        record (missing field, wrong embedding shape, ragged input)
        answers ``{"id", "error"}`` WITHOUT failing its co-riders — one
        hostile client must not degrade unrelated traffic sharing the
        micro-batch.  Raw-'input' records encode as ONE stacked
        dispatch (that is the batcher's whole point), then merge with
        the embedding records for one top-k launch.  ``entry`` scopes
        freshness stamps, the shadow offer, and the answers' ``tenant``
        key to one tenant (None = the single-tenant tier)."""
        from npairloss_tpu.serve.engine import ServeCompileError

        if engine is None:
            engine = self.engine
        qts = ([qt for it in items
                if isinstance(it, dict)
                and (qt := it.get("_qt")) is not None]
               if self.qtrace is not None else [])
        # Answers carry their tenant id in tenant mode — the routing
        # evidence bench_check's tenant gate audits (and the key
        # _account uses to attribute errors without a side channel).
        tstamp = ({"tenant": entry.tenant_id}
                  if entry is not None else {})
        if qts:
            # ``batch_assemble`` ends here; everything from this point
            # to the answers — parse, encode, failpoint stalls, the
            # engine call — is the ``dispatch`` stage (score/topk_merge
            # are split back out of it at the finish).
            self.qtrace.dispatch_begin(
                qts, replica=replica, batch=tracing.tags().get("batch"))
        stages: Optional[Dict[str, Any]] = {} if qts else None
        if failpoints.should_fire("serve.latency"):
            # Deterministic latency fault (docs/RESILIENCE.md): every
            # query in this batch pays the stall — the p99 spike the
            # live-obs alert lifecycle is tested against.  Sited here
            # (not in the engine) so warmup's dispatches stay fast.
            time.sleep(failpoints.SERVE_LATENCY_FAULT_S)
        dim = engine.index.dim
        tcfg = _token_cfg(engine)
        if tcfg is None:
            parse_input, stack = _float_input, np.stack
        else:
            parse_input, stack = functools.partial(
                _token_input, longest=tcfg.length_buckets[-1],
                vocab=getattr(engine.model, "vocab_size", None)), list
        answers: List[Optional[Dict[str, Any]]] = [None] * len(items)
        emb_rows: List[tuple] = []  # (item position, (D,) query row)
        enc_rows: List[tuple] = []  # (item position, raw input array)
        for i, rec in enumerate(items):
            try:
                if "embedding" in rec:
                    e = np.asarray(rec["embedding"], np.float32)
                    if e.shape != (dim,):
                        raise ValueError(
                            f"embedding shape {e.shape} does not match "
                            f"gallery dim ({dim},)"
                        )
                    emb_rows.append((i, e))
                elif "input" in rec:
                    enc_rows.append((i, parse_input(rec["input"])))
                else:
                    raise ValueError(
                        "query record needs an 'embedding' or 'input' field"
                    )
            except Exception as e:  # noqa: BLE001 — answer THIS record
                answers[i] = {"id": rec.get("id"), **tstamp,
                              "error": str(e)}
        if enc_rows:
            try:
                enc = engine.encode(stack([x for _, x in enc_rows]))
                emb_rows.extend(
                    (i, row) for (i, _), row in zip(enc_rows, enc)
                )
            except ServeCompileError:
                raise  # strict-guard trip is a server fault, fail loudly
            except Exception as e:  # noqa: BLE001 — ragged stack, no model
                for i, _ in enc_rows:
                    answers[i] = {"id": items[i].get("id"), **tstamp,
                                  "error": str(e)}
        rows = search = None
        if emb_rows:
            rows = np.stack([x for _, x in emb_rows])
            # Only thread the stage-clock dict through when tracing is
            # live, and launch only on an engine that has the split:
            # engine stand-ins (tests, external adapters) need grow
            # neither to serve an untraced tier.
            launch = getattr(engine, "launch", None)
            if launch is not None:
                search = (launch(rows) if stages is None
                          else launch(rows, stages=stages))
        return _LaunchedBatch(items, answers, emb_rows, rows, search,
                              stages, qts, tstamp, entry, engine)

    def _finish_core(self, b: "_LaunchedBatch") -> List[Dict[str, Any]]:
        """A launched batch -> per-query answers: the top-k's collect,
        the answers' assembly, the shadow offer and the qtrace
        ``dispatch`` stage's end."""
        items, answers, emb_rows, stages, qts, tstamp, entry = (
            b.items, b.answers, b.emb_rows, b.stages, b.qts, b.tstamp,
            b.entry)
        t_asm = (0.0, 0.0)
        if b.rows is not None:
            # The answer comes out of the engine's ``query``: it
            # collects what the launch started.
            if b.search is not None:
                out = b.engine.query(b.rows, launched=b.search)
            elif stages is None:
                out = b.engine.query(b.rows)
            else:
                out = b.engine.query(b.rows, stages=stages)
            # Host-side answer assembly is merge work: it joins the
            # device top-K with labels/ids/freshness into the wire
            # shape, so it lands in ``topk_merge``, not dispatch self.
            t_asm0 = time.perf_counter()
            with tracing.span("serve/assemble", rows=len(emb_rows)):
                fresh = (entry.freshness if entry is not None
                         else self.freshness)
                ages = fresh.ages() if fresh is not None else {}
                for j, (i, _) in enumerate(emb_rows):
                    answers[i] = {
                        "id": items[i].get("id"),
                        **tstamp,
                        # Per-answer freshness stamp (ROADMAP item 4):
                        # how old the model/index behind THIS answer is
                        # — the TENANT'S freshness in tenant mode.
                        **ages,
                        "neighbors": [
                            {
                                "rank": r,
                                "row": int(out["rows"][j, r]),
                                "gallery_id": int(out["ids"][j, r]),
                                "label": int(out["labels"][j, r]),
                                "score": round(
                                    float(out["scores"][j, r]), 6),
                            }
                            for r in range(out["scores"].shape[1])
                        ],
                    }
            t_asm = (t_asm0, time.perf_counter())
            shadow = (entry.shadow if entry is not None
                      else self.shadow)
            if shadow is not None:
                # Shadow offer AFTER the answers are built: a hash +
                # bounded put per sampled query, never a wait — the
                # scorer re-scores on its own thread (obs.quality).
                # Tenant mode offers to the TENANT'S scorer, whose
                # oracle is that tenant's gallery.
                try:
                    for j, (i, row) in enumerate(emb_rows):
                        # The raw query row — the oracle re-normalizes
                        # exactly like the serving engine did.
                        shadow.offer(items[i].get("id"), row,
                                     out["rows"][j],
                                     out["scores"][j])
                except Exception as e:  # noqa: BLE001 — shadow must not fail answers
                    log.error("shadow offer failed: %s", e)
        if qts:
            # The engine's MEASURED top-k and gather intervals
            # (perf_counter seconds; a stand-in may report the
            # durations ``score_us``/``merge_us`` alone); the assembly
            # above joins the gather.
            to_us = self.qtrace.tracer.to_us
            score_at, gather_at = stages.get("score_at"), \
                stages.get("gather_at")
            self.qtrace.dispatch_end(
                qts,
                score_us=stages.get("score_us", 0.0),
                merge_us=(stages.get("merge_us", 0.0)
                          + (t_asm[1] - t_asm[0]) * 1e6),
                score_at=score_at and (to_us(score_at[0]),
                                       to_us(score_at[1])),
                merge_at=gather_at and (to_us(gather_at[0]),
                                        to_us(t_asm[1])),
                # Fused probe path: the score/merge clocks came out of
                # ONE Pallas dispatch, so the trace wraps them in a
                # probe_fused span (the stage vocabulary is unchanged).
                fused=getattr(b.engine, "probe_impl", None) == "fused")
        return answers

    # -- durable ingest (docs/RESILIENCE.md §Durability) --------------------

    def attach_wal(self, wal, apply_fn: Callable[[Dict[str, Any]], None],
                   *, checkpoint_fn: Optional[Callable[[int],
                                                       Optional[str]]] = None,
                   checkpoint_every: int = 0, watermark: int = 0,
                   checkpoint_watermark: int = 0) -> None:
        """Arm the durable-ingest path: ``wal`` takes every record
        BEFORE the ack, ``apply_fn(payload)`` applies a durable record
        to the ingest gallery, and ``checkpoint_fn(watermark)``
        publishes a snapshot covering everything up to ``watermark``
        (returning its path, or None when there was nothing new) —
        after which the server GCs the WAL segments that snapshot
        covers.  ``watermark`` seeds the applied high-water mark (the
        cold-restart replay already happened by the time this is
        called); ``checkpoint_watermark`` seeds the last PUBLISHED
        watermark (the base artifact's)."""
        self.wal = wal
        self._ingest_apply = apply_fn
        self._checkpoint_fn = checkpoint_fn
        self._checkpoint_every = int(checkpoint_every)
        self._ingest_watermark = int(watermark)  # unguarded-ok: attach_wal runs at startup, before run_jsonl/serve threads exist
        self._ckpt_watermark = int(checkpoint_watermark)  # unguarded-ok: startup-only, no concurrent ingest yet

    @property
    def ingest_watermark(self) -> int:
        """The last WAL sequence number applied to the ingest gallery
        (== the last acknowledged ingest; acks happen-after apply)."""
        with self._ingest_lock:
            return self._ingest_watermark

    def _handle_ingest(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        """One ingest record, start to ack: encode -> WAL append ->
        group-commit durability barrier -> apply -> ack.  The ack NEVER
        precedes the fsync covering the record — that ordering is the
        whole durability contract, and the SIGKILL drill's oracle
        assumes it.  Ingest records never enter the query pipeline, so
        the drain invariant's population (queries == answered + errors
        + rejected) is untouched."""
        rid = rec.get("id")
        if self.tenants:
            # Tenant mode: the record routes to its tenant's own WAL +
            # watermark (one durability domain per tenant — a noisy
            # neighbor's ingest burst cannot delay another tenant's
            # checkpoint).
            try:
                entry = self._tenant_entry(rec)
            except UnknownTenantError as e:
                with self._lock:
                    self.ingest_errors += 1
                return {"id": rid, "error": str(e)}
            return self._tenant_ingest(entry, rec)
        if self.wal is None or self._ingest_apply is None:
            with self._lock:
                self.ingest_errors += 1
            return {"id": rid,
                    "error": "ingest requires a WAL (serve --wal-dir)"}
        try:
            body = encode_ingest_body(rec.get("ingest"))
        except (ValueError, TypeError) as e:
            with self._lock:
                self.ingest_errors += 1
            return {"id": rid, "error": f"bad ingest record: {e}"}
        try:
            seq = self.wal.append(body)
            self.wal.wait_durable(seq)
        except Exception as e:  # noqa: BLE001 — the client must hear "not durable"
            with self._lock:
                self.ingest_errors += 1
            log.error("ingest %r failed before durability: %s", rid, e)
            return {"id": rid, "error": f"ingest not durable: {e}"}
        body["seq"] = seq
        with self._ingest_lock:
            self._ingest_apply(body)
            self._ingest_watermark = seq
            self._ingest_since_ckpt += 1
        n = len(body["ids"])
        with self._lock:
            self.ingest_batches += 1
            self.ingest_vectors += n
        return {"id": rid, "ingested": n, "seq": seq}

    def _tenant_ingest(self, entry, rec: Dict[str, Any]
                       ) -> Dict[str, Any]:
        """One tenant's ingest record through ITS durability domain
        (serve/tenants.py TenantIngest): same encode -> WAL -> fsync
        barrier -> apply -> ack ordering as the single-tenant path,
        against the tenant's own WAL and watermark.  Aggregate ingest
        counters still tick, so Σ per-tenant == tier totals."""
        rid = rec.get("id")
        tid = entry.tenant_id
        ing = entry.ingest
        if ing is None:
            with self._lock:
                self.ingest_errors += 1
            return {"id": rid, "tenant": tid,
                    "error": f"tenant {tid!r} ingest requires a WAL "
                             "(serve --wal-dir)"}
        try:
            body = encode_ingest_body(rec.get("ingest"))
        except (ValueError, TypeError) as e:
            ing.note_error()
            with self._lock:
                self.ingest_errors += 1
            return {"id": rid, "tenant": tid,
                    "error": f"bad ingest record: {e}"}
        try:
            seq = ing.commit(body)
        except Exception as e:  # noqa: BLE001 — the client must hear "not durable"
            ing.note_error()
            with self._lock:
                self.ingest_errors += 1
            log.error("tenant %r ingest %r failed before durability: "
                      "%s", tid, rid, e)
            return {"id": rid, "tenant": tid,
                    "error": f"ingest not durable: {e}"}
        n = len(body["ids"])
        with self._lock:
            self.ingest_batches += 1
            self.ingest_vectors += n
        ing.maybe_checkpoint()
        return {"id": rid, "tenant": tid, "ingested": n, "seq": seq}

    def _maybe_checkpoint(self) -> None:
        if (self._checkpoint_fn is None or self._checkpoint_every <= 0):
            return
        with self._ingest_lock:
            due = self._ingest_since_ckpt >= self._checkpoint_every
        if due:
            self.checkpoint_now()

    def checkpoint_now(self) -> Optional[str]:
        """Publish an index snapshot at the current applied watermark,
        then GC the WAL segments it covers — the one place snapshot
        publication and WAL GC read the same sequence number.  Returns
        the published path (None when nothing new was applied or no
        checkpoint sink is attached)."""
        if self._checkpoint_fn is None or self.wal is None:
            return None
        with self._ingest_lock:
            wm = self._ingest_watermark
            if wm <= self._ckpt_watermark:
                return None
            try:
                path = self._checkpoint_fn(wm)
            except Exception as e:  # noqa: BLE001 — a failed publish is not data loss
                log.error("ingest checkpoint at watermark %d failed: %s "
                          "— WAL retains the records", wm, e)
                return None
            self._ckpt_watermark = wm
            self._ingest_since_ckpt = 0
        if path is not None:
            try:
                self.wal.gc(wm)
            except Exception as e:  # noqa: BLE001 — GC is space, not safety
                log.error("wal GC at watermark %d failed: %s", wm, e)
        return path

    def ingest_stats(self) -> Dict[str, Any]:
        """The /healthz + drain ``ingest`` block (present only when a
        WAL is attached — the freshness-JSON contract): counters, the
        two watermarks, and the WAL's own durability stats (including
        the torn-tail counts recovery promised to surface)."""
        with self._ingest_lock:
            wm, ckpt = self._ingest_watermark, self._ckpt_watermark
        with self._lock:
            out: Dict[str, Any] = {
                "batches": self.ingest_batches,
                "vectors": self.ingest_vectors,
                "errors": self.ingest_errors,
            }
        out["watermark"] = wm
        out["checkpoint_watermark"] = ckpt
        try:
            out["wal"] = self.wal.stats() if self.wal is not None else {}
        except Exception as e:  # noqa: BLE001 — stats must not fail health
            out["wal"] = {"error": str(e)}
        return out

    # -- multi-tenant map (serve/tenants.py) --------------------------------

    def enable_tenants(self, entries: Dict[str, Any]) -> None:
        """Install the tenant-keyed serving map — startup-only, like
        ``attach_wal``: one ``TenantEntry`` per tenant id, each holding
        exactly one engine per replica (replica r serves tenant t from
        ``entry.engines[r]``, so the tier's batchers/queues stay
        shared while every tenant answers from its own gallery)."""
        if self.tenants:
            raise ValueError("tenant map already installed")
        entries = dict(entries)
        if not entries:
            raise ValueError("enable_tenants needs >= 1 tenant entry")
        for tid, entry in entries.items():
            if len(entry.engines) != len(self.engines):
                raise ValueError(
                    f"tenant {tid!r} has {len(entry.engines)} "
                    f"engine(s); the replica tier has "
                    f"{len(self.engines)}")
        self.tenants = entries  # unguarded-ok: enable_tenants runs at startup, before serving threads exist
        self._replica_idx = {
            rep.name: i
            for i, rep in enumerate(self.replicaset.replicas)}

    def _tenant_entry(self, record) -> Any:
        """The entry a record routes to (tenant mode only); raises
        :class:`UnknownTenantError` for a missing/unregistered id so
        the caller accounts it as a malformed request."""
        tid = record.get("tenant") if isinstance(record, dict) else None
        entry = self.tenants.get(tid)
        if entry is None:
            raise UnknownTenantError(
                f"unknown tenant {tid!r} (registered: "
                f"{sorted(self.tenants)})")
        return entry

    def swap_tenant_engines(self, tenant_id: str, engines,
                            freshness: Optional[Freshness] = None
                            ) -> None:
        """Atomically republish ONE tenant's engine set — the
        ``swap_engines`` commit point scoped to an entry.  Every other
        tenant's pointers are untouched; in-flight batches finish on
        the engines they started with (the dispatcher resolves
        ``entry.engines`` per batch), so no tenant drops a query.
        The flip holds the tenant's ingest lock (when it has one) so a
        durable-ingest apply never races the republish — the
        single-tenant lock order, per entry."""
        entry = self.tenants.get(tenant_id)
        if entry is None:
            raise UnknownTenantError(
                f"unknown tenant {tenant_id!r} (registered: "
                f"{sorted(self.tenants)})")
        engines = list(engines)
        if len(engines) != len(entry.engines):
            raise ValueError(
                f"tenant {tenant_id!r} swap must preserve the replica "
                f"count: got {len(engines)}, entry has "
                f"{len(entry.engines)}")
        ingest_lock = (entry.ingest.lock if entry.ingest is not None
                       else contextlib.nullcontext())
        with ingest_lock:
            with self._lock:
                entry.engines = engines
                if freshness is not None:
                    entry.freshness = freshness
                entry.swaps += 1
                self.swaps += 1
                generation = self.swaps
        if self.qtrace is not None:
            self.qtrace.marker("hotswap_flip", generation=generation,
                               tenant=tenant_id)
        log.warning(
            "hot-swap %d: tenant %r republished (%s)", generation,
            tenant_id,
            freshness.identity() if freshness else "same identity")

    def _all_engines(self) -> List[QueryEngine]:
        """Every distinct engine behind the tier: the replica anchors
        plus each tenant's sets, deduped by identity (tenant 0's
        engines ARE ``self.engines``) — the population compile
        counters sum over."""
        seen: Dict[int, QueryEngine] = {id(e): e for e in self.engines}
        for entry in self.tenants.values():
            for e in entry.engines:
                seen.setdefault(id(e), e)
        return list(seen.values())

    # -- remediation actuators (docs/RESILIENCE.md §Remediation) -----------

    def swap_engines(self, engines, freshness: Optional[Freshness] = None,
                     prepare: Optional[Callable[[], None]] = None
                     ) -> None:
        """Atomically publish a fresh engine tier — the hot-swap commit
        point (ROADMAP item 3's actuation half).  The caller must have
        built AND WARMED the new primary off the serving path
        (serve/hotswap.py does); here each replica's engine pointer
        flips, so its NEXT batch dispatches on the new engine while any
        in-flight batch finishes on the engine it started with — zero
        dropped queries, zero serving-path compiles.  Freshness flips
        with the tier, so per-answer model/index ages drop at the same
        instant the answers start coming from the new snapshot."""
        engines = list(engines)
        if len(engines) != len(self.engines):
            raise ValueError(
                f"swap must preserve the replica count: got "
                f"{len(engines)}, tier has {len(self.engines)}")
        # The flip runs under the ingest lock so a durable-ingest apply
        # or checkpoint never races the republish (``prepare`` is the
        # hot-swap's chance to reconcile ingest state against the
        # incoming tier's watermark at the same serialization point);
        # WAL-less servers pay one uncontended acquire.
        with self._ingest_lock:
            if prepare is not None:
                prepare()
            with self._lock:
                self.engines = engines
                self.engine = engines[0]
                if freshness is not None:
                    self.freshness = freshness
                self.swaps += 1
            for rep, eng in zip(self.replicaset.replicas, engines):
                rep.engine = eng
        if self.qtrace is not None:
            # The generation-flip instant: answers after this marker
            # come from the new snapshot — a tail spike next to it is
            # swap cost, not load (docs/OBSERVABILITY.md runbook).
            self.qtrace.marker("hotswap_flip", generation=self.swaps)
        log.warning("hot-swap %d: serving tier republished (%s)",
                    self.swaps,
                    freshness.identity() if freshness else "same identity")

    def rewarm(self) -> Dict[str, Any]:
        """Re-warm every padding bucket and reset the tier's
        post-warmup compile counters — the compile-storm remediation
        action.  From here on the window rows carry an EXPLICIT
        ``compiles_after_warmup`` (including 0) so the watchdog sees
        recovery."""
        dt = self.engine.rewarm(self.input_shape)
        for e in self.engines[1:]:
            # Replicas share the primary's programs + signature set;
            # only their counters need the reset.
            e.compiles_after_warmup = 0
        for entry in self.tenants.values():
            # Each tenant's primary re-dispatches its own buckets (a
            # shared signature set makes repeats free); replicas again
            # only reset counters.  rewarm never clears shared
            # signatures, so the loop cannot thrash the cache.
            if entry.engines[0] is not self.engine:
                dt += entry.engines[0].rewarm(self.input_shape)
            for e in entry.engines:
                if e is not entry.engines[0] and e is not self.engine:
                    e.compiles_after_warmup = 0
        self._explicit_compile_key = True
        return {"warmup_s": round(dt, 3)}

    def _rejected_total(self) -> int:
        """Every rejection source, once each: batcher backpressure +
        whole-tier-down + admission sheds — the ``rejected`` term of
        the drain invariant."""
        total = self.replicaset.rejected
        if self.admission is not None:
            total += self.admission.sheds
        for entry in self.tenants.values():
            # Per-tenant fast-rejects (quota + tenant admission) never
            # reach the replicaset or the global controller, so adding
            # them double-counts nothing; backpressure and global sheds
            # were counted above and only ATTRIBUTED to entry.rejected.
            if entry.quota is not None:
                total += entry.quota.sheds
            if entry.admission is not None:
                total += entry.admission.sheds
        return total

    def _compiles_after_warmup(self) -> int:
        # Replicas (and same-geometry tenants) share one signature set,
        # so summing never double-counts a compile; single-engine this
        # is the old value.
        return sum(e.compiles_after_warmup for e in self._all_engines())

    def _token_totals(self) -> Dict[str, int]:
        """Tier-wide ``tokens_encoded`` (true tokens handed to encode)
        and ``tokens_padded`` (rows bucket x length bucket they ran
        padded to); empty unless the primary is a token engine."""
        if _token_cfg(self.engine) is None:
            return {}
        engines = self._all_engines()
        return {"tokens_encoded": sum(getattr(e, "tokens_encoded", 0)
                                      for e in engines),
                "tokens_padded": sum(getattr(e, "tokens_padded", 0)
                                     for e in engines)}

    def submit(self, record: Dict[str, Any]):
        """Admit one query record; returns (future, t_submit).  Raises
        :class:`QueueFullError` on backpressure — from a full replica
        queue, a fully-down tier, or the admission controller shedding
        under SLO burn (all counted in ``rejected``)."""
        qt = (record.get("_qt")
              if self.qtrace is not None and isinstance(record, dict)
              else None)
        # Tenant resolution happens BEFORE any counting: an unknown
        # tenant is a malformed request (UnknownTenantError -> errors,
        # like bad JSON), never an admitted-then-shed query.
        entry = self._tenant_entry(record) if self.tenants else None
        if entry is not None and qt is not None:
            qt.tenant = entry.tenant_id
        with tracing.span("serve/admit"):
            with self._lock:  # HTTP front end submits from many threads
                self.queries += 1
                if entry is not None:
                    entry.queries += 1
            if entry is not None and entry.quota is not None and \
                    not entry.quota.admit():
                # Quota shed: THIS tenant's token bucket ran dry — a
                # per-tenant fast-reject (its neighbors' queues and
                # counters never see the query).
                with self._lock:
                    entry.rejected += 1
                raise QueueFullError(
                    f"quota exceeded for tenant "
                    f"{entry.tenant_id!r}; retry after backoff")
            if entry is not None and entry.admission is not None and \
                    not entry.admission.admit(trace=qt):
                with self._lock:
                    entry.rejected += 1
                raise QueueFullError(
                    f"load shed: tenant {entry.tenant_id!r} SLO "
                    "burning (admission control); retry after backoff")
            if self.admission is not None and \
                    not self.admission.admit(trace=qt):
                if entry is not None:
                    # Tier-wide shed, attributed to the tenant whose
                    # query it refused (sum of per-tenant rejected must
                    # reproduce the aggregate).
                    with self._lock:
                        entry.rejected += 1
                raise QueueFullError(
                    "load shed: SLO burning (admission control); retry "
                    "after backoff")
            if qt is not None:
                # ``admit_wait`` closes BEFORE the enqueue: the record
                # becomes visible to the dispatcher the instant it
                # lands in the queue, and the queue put is the only
                # ordering edge between this thread and ``picked``.
                self.qtrace.admitted(qt)
            try:
                fut = self.replicaset.submit(record)
            except QueueFullError:
                if entry is not None:
                    # Backpressure lands on the submitting tenant too:
                    # counted where replicaset.rejected counts it.
                    with self._lock:
                        entry.rejected += 1
                raise
            return fut, time.perf_counter()

    def handle_many(
        self,
        records: List[Dict[str, Any]],
        timeout: Optional[float] = 60.0,
    ) -> List[Dict[str, Any]]:
        """Blocking multi-query path: admit EVERY record before waiting
        on any, so co-riders from one request coalesce into shared
        micro-batches instead of each paying its own deadline wait."""
        staged: List[Any] = []
        for rec in records:
            qt = self._qtrace_begin(rec)
            try:
                staged.append((rec, *self.submit(rec), qt))
            except UnknownTenantError as e:
                # Malformed request (never admitted): errors, not
                # queries/rejected — the bad-JSON accounting.
                with self._lock:
                    self.errors += 1
                    self.errors_refused += 1
                self._qtrace_drop(qt, error=True)
                staged.append((rec, None, str(e), None))
            except QueueFullError as e:
                # counted in batcher.rejected — NOT also in errors, or
                # the drain invariant queries == answered + errors +
                # rejected double-counts every rejection
                self._qtrace_drop(qt)
                staged.append((rec, None, str(e), None))
        answers = []
        for rec, fut, t0_or_err, qt in staged:
            if fut is None:
                answers.append({"id": rec.get("id"),
                                "error": t0_or_err})
                continue
            try:
                answer = fut.result(timeout=timeout)
            except Exception as e:  # noqa: BLE001 — answer the error
                with self._lock:
                    self.errors += 1
                self._qtrace_drop(qt, error=True)
                answers.append({"id": rec.get("id"), "error": str(e)})
                continue
            answers.append(self._account(answer, t0_or_err, qt))
        return answers

    def handle(self, record: Dict[str, Any],
               timeout: Optional[float] = 60.0) -> Dict[str, Any]:
        """Blocking one-query path (the HTTP front end's per-thread
        call): admit, wait, account latency."""
        return self.handle_many([record], timeout=timeout)[0]

    def _queries_dropped(self) -> int:
        """The drain invariant's residual: admitted queries no term of
        ``answered + errors + rejected`` accounts for.  At drain (all
        batchers closed, every future resolved) a nonzero residual is a
        real drop — a query the tier swallowed; read mid-flight it also
        counts queries still in their batch, which is why the key is
        absent-when-zero unless ``explicit_drops`` asks for the
        measured 0.  Refused-before-admission errors (bad JSON,
        unknown tenant) sit in ``errors`` but never entered
        ``queries``, so they are excluded — a refusal is not a
        negative drop."""
        return (self.queries - self.answered
                - (self.errors - self.errors_refused)
                - self._rejected_total())

    def summary(self) -> Dict[str, Any]:
        dropped = self._queries_dropped()
        return {
            "event": "serve_drain",
            "queries": self.queries,
            "answered": self.answered,
            "errors": self.errors,
            "rejected": self._rejected_total(),
            # Zero-drop evidence (docs/RESILIENCE.md §Gameday): present
            # whenever nonzero, and present AT zero when explicit_drops
            # is on — the gameday zero-drop gate refuses an absent key.
            **({"queries_dropped": dropped}
               if (dropped or self.cfg.explicit_drops) else {}),
            "batches": self.replicaset.batches,
            # Replica/admission state only when the feature is on (the
            # single-replica summary keeps its pre-PR shape).
            **({"replicas": len(self.engines),
                "replicas_alive": self.replicaset.alive_count}
               if len(self.engines) > 1 else {}),
            **({"shed": self.admission.sheds,
                "shedding": (self.admission.shedding
                             or self.admission.forced)}
               if self.admission is not None else {}),
            # Freshness identity + ages (live-obs on or off): what this
            # run was answering from, and how stale it had become.
            **(self.freshness.identity()
               if self.freshness is not None else {}),
            **(self.freshness.ages()
               if self.freshness is not None else {}),
            # Hot-swap count (absent when the tier never swapped) and
            # the last remediation per policy (key absent = policy
            # never fired; block absent = no engine attached — the
            # freshness-JSON contract, docs/RESILIENCE.md §Remediation).
            **({"hot_swaps": self.swaps} if self.swaps else {}),
            **({"remediation": self.remediation.last_by_policy()}
               if self.remediation is not None else {}),
            # Durable-ingest evidence (block absent = no WAL attached —
            # the freshness-JSON contract): counters, watermarks, and
            # the WAL's torn-tail counts, on /healthz and the drain
            # summary alike (docs/RESILIENCE.md §Durability).
            **({"ingest": self.ingest_stats()}
               if self.wal is not None else {}),
            # The online recall estimate (obs.quality): block absent =
            # shadowing off — the freshness-JSON contract again, so a
            # --shadow-rate 0 run keeps its pre-PR summary shape.
            **({"quality": self.shadow.stats()}
               if self.shadow is not None else {}),
            # The per-stage p99 budget decomposition (obs.qtrace):
            # block absent = tracing off — the freshness-JSON contract
            # once more, so an untraced run keeps its pre-PR shape.
            **({"qtrace": self.qtrace.summary_block()}
               if self.qtrace is not None else {}),
            # Per-tenant evidence (serve/tenants.py): one block per
            # tenant — counters, freshness, quota, shed, ingest,
            # quality — absent entirely in single-tenant mode (the
            # freshness-JSON contract), so Σ per-tenant counters can be
            # audited against the aggregates above (bench_check
            # --tenants does).
            **({"tenants": {tid: self.tenants[tid].stats_block()
                            for tid in sorted(self.tenants)}}
               if self.tenants else {}),
            # Errors no tenant row can own (unknown-tenant refusals,
            # bad JSON — never admitted, so never attributed): the
            # explicit remainder that makes the tenant error audit
            # exact — Σ per-tenant errors + this == aggregate errors.
            **({"errors_unattributed":
                self.errors - sum(e.errors
                                  for e in self.tenants.values())}
               if self.tenants else {}),
            **{k: round(v, 3) for k, v in self._percentiles().items()},
            # Whole-run latency split: where an answer's time went,
            # stage by stage (one read at drain, not per window; from
            # the construction-time baseline so warmup compiles never
            # masquerade as serving tail latency).
            **(self._latency_split(
                self._tracer().events_since(self._events_start_idx)[0])
               if self._tracer() is not None else {}),
            # Compile counters are tier-wide sums (replicas — and
            # same-geometry tenants — share one signature set, so sums
            # never double-count and both keys stay mutually consistent
            # — whichever engine took a count must not make
            # after_warmup exceed total).
            **{**self.engine.compile_stats(),
               "compiles_total": sum(e.compiles_total
                                     for e in self._all_engines()),
               "compiles_after_warmup": self._compiles_after_warmup(),
               **self._token_totals()},
        }

    def healthz(self) -> Dict[str, Any]:
        """The /healthz payload: liveness + the whole-run summary
        (which now carries the freshness identity/ages), enriched with
        per-SLO status and active alerts when a LiveObservatory is
        attached — the JSON shape tests/test_live.py pins."""
        out = {
            "ok": True,
            "draining": self._preempted(),
            **self.summary(),
            # The RESOLVED IVF probe impl (scan/fused — never "auto")
            # behind this tier's answers; absent on a flat tier, where
            # the probe path does not exist (absent-when-off, the
            # freshness-JSON contract).  Survives hot-swap because
            # swap_engines rebuilds from the old EngineConfig.
            **({"probe_impl": pi}
               if (pi := getattr(self.engine, "probe_impl", None))
               is not None else {}),
        }
        if self.admission is not None:
            out["admission"] = self.admission.stats()
        if self.live is not None:
            out.update(self.live.health())
        return out

    def _drain(self) -> Dict[str, Any]:
        """Finish in-flight batches, flush telemetry, return the
        summary record.  Idempotent enough for every exit path."""
        self.replicaset.close(drain=True)
        if self.wal is not None:
            # Final ingest checkpoint: everything acked this run lands
            # in a published snapshot before the process exits, so a
            # clean shutdown leaves nothing for cold-restart replay.
            try:
                self.checkpoint_now()
            except Exception as e:  # noqa: BLE001 — drain must finish
                log.error("drain-time ingest checkpoint failed: %s", e)
        for tid in sorted(self.tenants):
            # Same clean-shutdown promise per tenant's durability
            # domain; one tenant's failed publish must not stop the
            # others' (its WAL keeps the records either way).
            ing = self.tenants[tid].ingest
            if ing is None:
                continue
            try:
                ing.checkpoint_now()
            except Exception as e:  # noqa: BLE001 — drain must finish
                log.error("drain-time tenant %r checkpoint failed: %s",
                          tid, e)
        s = self.summary()
        if self.qtrace is not None and self.qtrace.out_path:
            try:
                self.qtrace.write()
            except Exception as e:  # noqa: BLE001 — the artifact is not the run
                log.error("qtrace artifact write failed: %s", e)
        if self.telemetry is not None:
            with contextlib.suppress(Exception):
                if self.telemetry.metrics_enabled:
                    self.telemetry.log("serve", self.answered, s)
                self.telemetry.flush()
        log.info("serve drain: %s", s)
        return s

    def _preempted(self) -> bool:
        return self.preempt is not None and self.preempt.requested

    # -- stdin/JSONL front end --------------------------------------------

    def run_jsonl(self, in_stream, out_stream) -> int:
        """Serve line-delimited JSON until EOF or preemption; answers go
        out in request order.  Returns the process exit code (0 on EOF,
        EXIT_PREEMPTED after a graceful drain)."""
        self.replicaset.start()
        pending: collections.deque = collections.deque()
        emit_lock = threading.Lock()

        def emit(obj) -> None:
            with emit_lock:
                out_stream.write(json.dumps(obj) + "\n")
                out_stream.flush()

        def flush_ready(block: bool) -> None:
            while pending:
                rec_id, fut, t0, qt = pending[0]
                if not block and not fut.done():
                    return
                try:
                    answer = self._account(fut.result(timeout=120.0),
                                           t0, qt)
                except Exception as e:  # noqa: BLE001
                    with self._lock:
                        self.errors += 1
                    self._qtrace_drop(qt, error=True)
                    answer = {"id": rec_id, "error": str(e)}
                pending.popleft()
                emit(answer)

        # A dedicated reader thread blocks in readline and feeds a
        # queue, so the loop notices a SIGTERM within poll_s even while
        # idle.  (An fd-level select + buffered readline cannot do this
        # safely: readline reads ahead into the stream buffer, and lines
        # stranded there never make the fd readable again — the tail of
        # a burst would sit unanswered until EOF.)
        lines_q: queue.Queue = queue.Queue()
        _eof = object()

        def _read() -> None:
            try:
                for line in iter(in_stream.readline, ""):
                    lines_q.put(line)
            except Exception as e:  # noqa: BLE001 — surface as EOF
                log.warning("jsonl reader: %s", e)
            finally:
                lines_q.put(_eof)

        threading.Thread(target=_read, daemon=True,
                         name="serve-jsonl-reader").start()
        preempted = False
        try:
            eof = False
            while not eof:
                if self._preempted():
                    preempted = True
                    break
                try:
                    line = lines_q.get(timeout=self.cfg.poll_s)
                except queue.Empty:
                    flush_ready(block=False)
                    continue
                if line is _eof:
                    eof = True
                    break
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError as e:
                    with self._lock:
                        self.errors += 1
                        self.errors_refused += 1
                    emit({"id": None, "error": f"bad request JSON: {e}"})
                    continue
                if isinstance(rec, dict) and "ingest" in rec:
                    # Durable-ingest path: WAL + fsync barrier BEFORE
                    # the ack, never through the query pipeline (the
                    # drain invariant's population stays query-only).
                    emit(self._handle_ingest(rec))
                    self._maybe_checkpoint()
                    continue
                qt = self._qtrace_begin(rec)
                try:
                    fut, t0 = self.submit(rec)
                    pending.append((rec.get("id"), fut, t0, qt))
                except UnknownTenantError as e:
                    # Malformed request (never admitted): errors, not
                    # queries/rejected — the bad-JSON accounting.
                    with self._lock:
                        self.errors += 1
                        self.errors_refused += 1
                    self._qtrace_drop(qt, error=True)
                    emit({"id": rec.get("id"), "error": str(e)})
                except QueueFullError as e:
                    # counted in batcher.rejected, not errors (drain
                    # invariant: queries == answered + errors + rejected)
                    self._qtrace_drop(qt)
                    emit({"id": rec.get("id"), "error": str(e)})
                flush_ready(block=False)
        finally:
            # Graceful drain on EVERY exit: stop admitting, answer every
            # in-flight query, flush telemetry — zero drops.
            self.replicaset.close(drain=True)
            flush_ready(block=True)
            emit(self._drain())
        # A SIGTERM that lands while the reader is blocked can surface
        # as EOF first (the supervisor closes stdin as it signals);
        # any observed preemption request still means "preempted".
        return EXIT_PREEMPTED if (preempted or self._preempted()) else 0

    # -- localhost HTTP front end -----------------------------------------

    def run_http(self, port: int, host: str = "127.0.0.1") -> int:
        """Serve HTTP until preemption (the only exit path besides an
        error); each request thread batches through the shared core."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server_ref = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route through logging
                log.debug("http: " + fmt, *args)

            def _send(self, code: int, obj) -> None:
                body = (json.dumps(obj) + "\n").encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _send_text(self, code: int, text: str, ctype: str) -> None:
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self._send(200, server_ref.healthz())
                elif self.path == "/metrics":
                    if server_ref.live is None:
                        self._send(404, {
                            "error": "live observatory not enabled "
                                     "(serve --live-obs)"})
                        return
                    from npairloss_tpu.obs.live import prometheus_text

                    self._send_text(
                        200, prometheus_text(server_ref.live.registry),
                        "text/plain; version=0.0.4")
                else:
                    self._send(404, {"error": "unknown path"})

            def do_POST(self):
                if self.path != "/query":
                    self._send(404, {"error": "unknown path"})
                    return
                if server_ref._preempted():
                    self._send(503, {"error": "draining"})
                    return
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length).decode("utf-8", "replace")
                try:
                    lines = [ln for ln in raw.splitlines() if ln.strip()]
                    recs = [json.loads(ln) for ln in lines]
                except ValueError as e:
                    self._send(400, {"error": f"bad request JSON: {e}"})
                    return
                if not recs:
                    self._send(400, {"error": "empty request"})
                    return
                answers = server_ref.handle_many(recs)
                self._send(200, answers[0] if len(answers) == 1 else answers)

        self.replicaset.start()
        httpd = ThreadingHTTPServer((host, port), Handler)
        httpd.timeout = self.cfg.poll_s
        log.info("serving on http://%s:%d (POST /query, GET /healthz)",
                 host, httpd.server_address[1])
        try:
            while not self._preempted():
                httpd.handle_request()
        finally:
            with contextlib.suppress(Exception):
                httpd.server_close()
            self._drain()
        return EXIT_PREEMPTED
