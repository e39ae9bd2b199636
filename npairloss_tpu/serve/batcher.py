"""MicroBatcher — deadline-bounded query coalescing with backpressure.

Serving traffic arrives one query at a time; the accelerator wants
fixed-shape micro-batches.  The batcher sits between them: callers
``submit()`` individual queries and get a ``Future``; a single
dispatcher thread takes the oldest queued query and everything queued
behind it, up to the largest padding bucket, waits for more only while
the queue is empty and that query's latency deadline has not passed,
then hands the batch to ``dispatch_fn`` and distributes the per-query
results.

A batch runs in two phases: the dispatcher thread forms it and
LAUNCHES it (``dispatch_fn``: parse, encode, the top-k's launch); its
FINISH (the top-k's wait, the answers) and the reply follow.  Under a
backlog -- a whole batch queued behind the one just launched, or
another batch still out -- the dispatcher hands the launched batch to
the replica's completion thread and goes straight back to the queue:
batch n+1 forms and encodes while batch n's search runs on the
device, and the cycle is the larger of the two phases, not their sum.
The dispatcher waits at the hand-off while the completion thread still
holds the previous batch: at most one batch lies between them, which
bounds what is in flight and keeps answers in batch order.  Otherwise
nothing would overlap the finish, and the dispatcher runs it itself,
inside its dispatch, and replies: a lone request or a partial batch
at an offered rate pays no hand-off.  No setting: each tier gets the
overlap from the backlog it has.

Admission is a BOUNDED queue, modeled on the training pipeline's
``DispatchController`` (pipeline/controller.py): when the engine falls
behind, ``submit`` raises :class:`QueueFullError` immediately —
reject-with-backpressure, never unbounded growth.  The caller (the
server front end) turns that into a rejected-request answer the client
can retry against another replica.

The deadline bounds the WAIT for co-riders, never the taking of what
is queued.  A turn first takes every query that is already there,
without waiting, until the batch is full or the owner's ``fits`` hook
stops it; taking them costs the head no time.  Only when the queue is
empty and the batch is not full does it look at the clock: the deadline
is measured from the head's SUBMIT time, so queue wait counts against
it and a head that has outlived it dispatches at once with whoever is
there; otherwise it waits for co-riders until the deadline and no
longer.  So a query never waits more than ``max_delay_ms`` for
co-riders (dispatch+compute time is on top — bound it by warming the
engine, docs/SERVING.md), and a backlog goes out in full batches
whatever ``max_delay_ms`` is.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import logging
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from npairloss_tpu.obs import tracing
from npairloss_tpu.resilience import failpoints

log = logging.getLogger("npairloss_tpu.serve")

_STOP = object()


class QueueFullError(RuntimeError):
    """Admission queue at capacity — backpressure, client should retry."""


@dataclasses.dataclass(frozen=True)
class BatcherConfig:
    """``max_batch`` is the largest co-ridership (the engine's largest
    padding bucket); ``max_delay_ms`` the added-latency budget a query
    may spend waiting for co-riders; ``max_queue`` the admission bound
    beyond which submits are rejected."""

    max_batch: int = 32
    max_delay_ms: float = 5.0
    max_queue: int = 256

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")


class MicroBatcher:
    """``start()`` -> ``submit(item) -> Future`` -> ``close(drain=...)``.

    ``dispatch_fn(items)`` receives the coalesced list and returns one
    result per item, in order, or a call with no arguments that returns
    them (the batch's second phase, run on the completion thread); an
    exception in either fails every future in that batch and no other
    (the server answers each with an error record).
    ``on_batch`` (optional) receives a stats dict per dispatched batch;
    ``on_pick`` (optional) receives each item the instant the dispatcher
    pulls it off the queue into the forming batch — the queue-wait/
    assemble boundary per-query tracing needs (obs.qtrace), a no-op
    when unset.  ``name`` is the replica this batcher feeds.
    ``fits(items)`` (optional) lets the owner bound a dispatch in
    something other than requests (a token engine's padded tokens): it
    is shown the batch so far with the candidates behind it, in order,
    and returns how many of them, counted from the first, ride in one
    dispatch.  It judges them TOGETHER (two rows may not fit a bucket
    that four fill).  The rest is held back, in order, to head the next
    turns; the head of a turn always goes.

    Both threads' time is spanned whole (obs.tracing).  Dispatcher:
    ``serve/idle`` (waiting for a head: nothing was queued) ->
    ``serve/batch`` (forming the batch: ``size``, of which ``drained``
    co-riders were taken without waiting, ``held`` refused by ``fits``,
    ``waited_ms`` under the deadline; ``inflight`` 1 if the previous
    batch was not yet answered as this one's launch began) ->
    ``serve/dispatch`` (the launch) -> ``serve/handoff`` (the wait for
    the completion thread).  Completion thread: ``serve/finish`` ->
    ``serve/reply`` (the futures' done-callbacks run inline here).  A
    batch the dispatcher finishes itself has its ``serve/finish``
    inside its ``serve/dispatch`` and its ``serve/reply`` after it, on
    the dispatcher thread.  Every span of one batch, the engine's
    included, carries the batch's sequence number ``batch`` (and
    ``replica``).
    """

    def __init__(
        self,
        dispatch_fn: Callable[[List[Any]], Sequence[Any]],
        cfg: BatcherConfig = BatcherConfig(),
        on_batch: Optional[Callable[[Dict[str, Any]], None]] = None,
        on_pick: Optional[Callable[[Any], None]] = None,
        name: Optional[str] = None,
        fits: Optional[Callable[[List[Any]], int]] = None,
    ):
        self.cfg = cfg
        self._dispatch_fn = dispatch_fn
        self._on_batch = on_batch
        self._on_pick = on_pick
        self._fits = fits
        # What ``fits`` refused: off the queue, not yet in a batch, in
        # submission order and fewer than ``max_batch``; only the
        # dispatcher thread touches it (and ``_stopping``: the
        # sentinel has been taken off the queue, nothing is behind it).
        self._held: collections.deque = collections.deque()
        self._stopping = False
        self._tags = {"replica": name} if name else {}
        self._q: queue.Queue = queue.Queue(maxsize=cfg.max_queue)
        self._thread: Optional[threading.Thread] = None
        self._finisher: Optional[threading.Thread] = None
        # The hand-off slot: the launched batch the completion thread
        # has not yet answered (None when it holds none, ``_STOP`` once
        # the dispatcher is done).  The dispatcher fills it only empty.
        self._slot: Any = None
        self._slot_cv = threading.Condition()
        self._closed = threading.Event()
        # Serializes the closed-check + enqueue in submit() against
        # close() setting the flag: without it a racing submit can land
        # its item BEHIND the _STOP sentinel, where the dispatcher never
        # sees it and the future hangs until the caller's timeout.
        self._admit_lock = threading.Lock()
        self.batches = 0
        self.dispatched = 0
        self.rejected = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "MicroBatcher":
        if self._thread is None:
            self._slot = None
            self._finisher = threading.Thread(
                target=self._finish_loop, name="serve-finisher",
                daemon=True)
            self._finisher.start()
            self._thread = threading.Thread(
                target=self._loop, name="serve-batcher", daemon=True
            )
            self._thread.start()
        return self

    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop accepting and shut the dispatcher down.

        ``drain=True`` (the SIGTERM contract): every already-admitted
        query is dispatched and answered before the threads exit — zero
        dropped in-flight queries.  ``drain=False`` fails the queued
        futures with :class:`QueueFullError` instead; a batch already
        launched is answered either way.
        """
        with self._admit_lock:
            # Under the lock no submit is between its closed-check and
            # its enqueue, so every admitted item is already in the
            # queue and the sentinel below is guaranteed to land last.
            self._closed.set()
        if self._thread is None:
            return
        if not drain:
            # Fail whatever is still queued; the sentinel below stops
            # the loop before it can pick more work up.
            pending = []
            with contextlib.suppress(queue.Empty):
                while True:
                    pending.append(self._q.get_nowait())
            for item in pending:
                if item is not _STOP:
                    item[1].set_exception(
                        QueueFullError("batcher closed without drain")
                    )
        # The sentinel lands BEHIND any admitted work, so a draining
        # close processes the whole queue first; the dispatcher hands
        # it on behind its last batch, and the completion thread stops
        # once that batch is answered.
        self._q.put(_STOP)
        deadline = time.perf_counter() + timeout
        self._thread.join(timeout=timeout)
        self._finisher.join(
            timeout=max(deadline - time.perf_counter(), 0.0))
        if self._thread.is_alive() or self._finisher.is_alive():
            log.error("batcher close: dispatcher did not drain in %.1fs",
                      timeout)
        else:
            # Closed for good (submit refuses from here on): drop the
            # owner's callbacks.  They are its bound methods, a cycle
            # owner -> batcher -> owner; without them a closed server
            # and its engines' device memory go by reference counting
            # alone, also where the host has frozen the collector
            # (gc.freeze) and the cycle would never be walked.
            self._dispatch_fn = self._on_batch = self._on_pick = None
            self._fits = None
        self._thread = self._finisher = None

    # -- admission ---------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        """Admitted and not yet in a batch: queued or held back."""
        return self._q.qsize() + len(self._held)

    def submit(self, item: Any) -> concurrent.futures.Future:
        """Admit one query; returns its Future.  Raises
        :class:`QueueFullError` when the admission queue is at capacity
        or the batcher is closing."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        with self._admit_lock:
            if self._closed.is_set():
                raise QueueFullError("batcher is closed")
            try:
                self._q.put_nowait((item, fut, time.perf_counter()))
            except queue.Full:
                self.rejected += 1
                raise QueueFullError(
                    f"admission queue full ({self.cfg.max_queue}); retry"
                ) from None
        return fut

    # -- dispatcher --------------------------------------------------------

    def _loop(self) -> None:
        seq = 0
        try:
            while True:
                seq += 1
                with tracing.tagged(batch=seq, **self._tags):
                    if self._turn(seq):
                        return
        finally:
            # Behind the last batch: the completion thread stops there.
            self._hand_off(_STOP)

    def _turn(self, seq: int) -> bool:
        """One head, its co-riders, their launch; True = stop."""
        with tracing.span("serve/idle"):
            head = self._held.popleft() if self._held else self._q.get()
        if head is _STOP:
            return True
        if failpoints.should_fire("serve.queue_stall"):
            # Deterministic dispatcher stall (docs/RESILIENCE.md):
            # admissions pile up behind the held queue, driving the
            # queue-saturation watchdog and, past max_queue, the
            # QueueFullError backpressure path — without touching
            # the dispatch math.
            time.sleep(failpoints.SERVE_QUEUE_STALL_S)
        if self._on_pick is not None:
            # After the stall, before coalescing: a stalled
            # dispatcher is queue wait, not assemble time.
            self._on_pick(head[0])
        batch = [head]
        deadline = head[2] + max(self.cfg.max_delay_ms, 0.0) / 1e3
        drained, waited = 0, 0.0
        # The one co-rider the last wait brought; it is judged with
        # what queued up behind it.
        late: list = []
        with tracing.span("serve/batch") as sp:
            while True:
                # What is already there, first: taking it costs the
                # head no wait, whatever the clock says.
                fresh = late + self._take_queued(
                    self.cfg.max_batch - len(batch) - len(late))
                drained += max(self._admit(batch, fresh) - len(late), 0)
                if self._stopping or self._held \
                        or len(batch) >= self.cfg.max_batch:
                    break
                # The queue is empty and the batch is not full: only
                # now the deadline, which bounds the wait for co-riders.
                t = time.perf_counter()
                if t >= deadline:
                    break
                try:
                    late = [self._q.get(timeout=deadline - t)]
                except queue.Empty:
                    late = []
                waited += time.perf_counter() - t
                if not late:
                    break
                if late[0] is _STOP:
                    self._stopping = True
                    break
            with self._slot_cv:
                inflight = int(self._slot is not None)
            sp.note(size=len(batch), drained=drained,
                    held=len(self._held), waited_ms=waited * 1e3,
                    inflight=inflight)
        self._launch(batch, drained, seq)
        # The sentinel was the queue's last entry: what is held back
        # is all that is left, and it heads the next turns.
        return self._stopping and not self._held

    def _take_queued(self, room: int) -> list:
        """Up to ``room`` requests that are already here, without
        waiting: what was held back first, then the queue."""
        taken: list = []
        while len(taken) < room:
            if self._held:
                taken.append(self._held.popleft())
                continue
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                self._stopping = True
                break
            taken.append(item)
        return taken

    def _admit(self, batch: list, fresh: list) -> int:
        """``fresh`` joins ``batch`` as far as ``fits`` lets it (all of
        it without the hook); the rest is held back in order.  Returns
        how many joined."""
        n = len(fresh)
        if fresh and self._fits is not None:
            n = self._fits([b[0] for b in batch + fresh]) - len(batch)
            n = min(max(n, 0), len(fresh))
        if self._on_pick is not None:
            for item in fresh[:n]:
                self._on_pick(item[0])
        batch.extend(fresh[:n])
        self._held.extendleft(reversed(fresh[n:]))
        return n

    def _launch(self, batch, drained: int, seq: int) -> None:
        """The batch's first phase.  With another batch out, or a whole
        batch queued behind this one (a backlog), the batch goes to the
        completion thread and this thread goes back to the queue;
        otherwise nothing would overlap it, and this thread finishes it
        too, inside its dispatch, and replies."""
        items = [b[0] for b in batch]
        t0 = time.perf_counter()
        try:
            with tracing.span("serve/dispatch", size=len(items)):
                job = _Launched(batch, self._dispatch_fn(items), drained,
                                t0, seq, self.queue_depth)
                overlap = self._overlaps()
                if not overlap:
                    results = self._finish(job)
        except Exception as e:  # noqa: BLE001 — fail the batch, not the loop
            self._fail(batch, e)
            return
        if overlap:
            with tracing.span("serve/handoff"):
                self._hand_off(job)
        else:
            self._reply(job, results)

    def _overlaps(self) -> bool:
        """Whether the batch just launched goes to the completion
        thread: another batch is out (answers keep their order), or a
        whole batch waits behind it, whose forming and encoding then
        runs while this one's search is on the device."""
        with self._slot_cv:
            out = self._slot is not None
        return out or self.queue_depth >= self.cfg.max_batch

    def _hand_off(self, job) -> None:
        """Put ``job`` in the slot once the completion thread has
        answered the batch before it."""
        with self._slot_cv:
            while self._slot is not None:
                self._slot_cv.wait()
            self._slot = job
            self._slot_cv.notify_all()

    def _finish_loop(self) -> None:
        """The completion thread: each launched batch in turn, finished
        and answered; the slot empties only then."""
        while True:
            with self._slot_cv:
                while self._slot is None:
                    self._slot_cv.wait()
                job = self._slot
            if job is _STOP:
                return
            try:
                with tracing.tagged(batch=job.seq, **self._tags):
                    try:
                        results = self._finish(job)
                    except Exception as e:  # noqa: BLE001 — fail the batch, not the loop
                        self._fail(job.batch, e)
                    else:
                        self._reply(job, results)
            finally:
                with self._slot_cv:
                    self._slot = None
                    self._slot_cv.notify_all()

    @staticmethod
    def _finish(job: "_Launched") -> Sequence[Any]:
        """The batch's second phase: one result per item."""
        with tracing.span("serve/finish", size=len(job.batch)):
            results = (job.pending() if callable(job.pending)
                       else job.pending)
        if len(results) != len(job.batch):
            raise RuntimeError(
                f"dispatch_fn returned {len(results)} results for "
                f"{len(job.batch)} items"
            )
        return results

    def _reply(self, job: "_Launched", results: Sequence[Any]) -> None:
        batch = job.batch
        now = time.perf_counter()
        # Done-callbacks (a closed-loop caller's next submit) run
        # inline on the thread that answers: the completion thread's
        # under a backlog, off the dispatcher's path.
        with tracing.span("serve/reply", size=len(batch)):
            for (_, fut, _), res in zip(batch, results):
                fut.set_result(res)
        self.batches += 1
        self.dispatched += len(batch)
        if self._on_batch is not None:
            self._on_batch({
                "size": len(batch),
                "dispatch_ms": (now - job.t0) * 1e3,
                "oldest_wait_ms": (job.t0 - batch[0][2]) * 1e3,
                "queue_depth": job.depth,
                "drained": job.drained,
            })

    @staticmethod
    def _fail(batch, e: Exception) -> None:
        for _, fut, _ in batch:
            if not fut.done():
                fut.set_exception(e)
        log.error("batch dispatch failed (%d queries): %s", len(batch), e)


@dataclasses.dataclass(frozen=True)
class _Launched:
    """A batch between its phases: (item, future, submit time) triples,
    what ``dispatch_fn`` returned, and how and when it was launched."""

    batch: list
    pending: Any
    drained: int
    t0: float
    seq: int
    depth: int
