"""Device-resident batch prefetch: host batches -> staged device buffers.

The data loader (``data/loader.py``) already overlaps sample+decode with
training on a host thread, but its batches are NumPy — the transfer to
the device happens implicitly at dispatch time, on the training thread,
every step.  :class:`DevicePrefetcher` adds the missing half: a staging
thread that pulls host batches and ``jax.device_put``s them with the
step's input sharding *ahead of need* (depth-k double buffering,
default 2), so the train loop's ``get()`` returns batches that are
already resident and safe to donate into the jitted step.

Failure contract (mirrors the loader's): an exception in the staging
thread — including the ``pipeline.stage`` failpoint — is queued and
re-raised from ``get()`` as :class:`PrefetchStageError` carrying the
batch index; the thread exits and ``close()`` joins it, so SIGTERM /
exception paths drain cleanly (no dangling put against a dying
backend).  ``staged``/``consumed`` count batches through the stage so a
resume can reason about exactly which batch index the pipeline died on.
"""

from __future__ import annotations

import logging
import queue
import threading
from typing import Callable, Iterator

from npairloss_tpu.obs import tracing
from npairloss_tpu.resilience import failpoints

log = logging.getLogger("npairloss_tpu.pipeline")


class PrefetchStageError(RuntimeError):
    """The staging thread died; carries the batch index it died on."""

    def __init__(self, batch_index: int, cause: BaseException):
        super().__init__(
            f"pipeline staging failed at batch {batch_index}: "
            f"{type(cause).__name__}: {cause}"
        )
        self.batch_index = batch_index


class _StageFailure:
    __slots__ = ("exc", "batch_index")

    def __init__(self, exc: BaseException, batch_index: int):
        self.exc = exc
        self.batch_index = batch_index


class _EndOfData:
    __slots__ = ()


class DevicePrefetcher:
    """Iterator of device-resident batches, staged ``depth`` ahead.

    Args:
      batches: host iterator yielding (inputs, labels) NumPy batches.
        Only the staging thread touches it (generators are fine).
      place: host batch -> device batch; typically ``Solver._stage_batch``
        (explicit ``jax.device_put`` with the step's input sharding).
      depth: staged batches held ready (>=1).  Device memory cost is
        ``depth`` extra batches — the price of never waiting on a
        transfer.

    Each staging put is recorded as a ``pipeline/stage`` span
    (``obs.tracing.span``) on the staging thread's timeline.
    """

    def __init__(
        self,
        batches: Iterator,
        place: Callable,
        depth: int = 2,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._it = batches
        self._place = place
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.staged = 0  # written by the staging thread only
        self.consumed = 0
        self._thread = threading.Thread(
            target=self._run, name="npairloss-pipeline-stage", daemon=True
        )
        self._thread.start()

    # -- staging thread ----------------------------------------------------

    def _run(self):
        def put(item) -> bool:
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.5)
                    return True
                except queue.Full:
                    continue
            return False

        while not self._stop.is_set():
            try:
                try:
                    host = next(self._it)
                except StopIteration:
                    put(_EndOfData())
                    return
                failpoints.fire("pipeline.stage")
                with tracing.span("pipeline/stage", batch_index=self.staged):
                    dev = self._place(*host)
                self.staged += 1
            except BaseException as exc:  # surfaced in get(), never silent
                put(_StageFailure(exc, self.staged))
                return
            if not put(dev):
                return

    # -- consumer side -----------------------------------------------------

    def get(self):
        """Next device-resident batch; blocks only if staging is behind.

        Raises :class:`PrefetchStageError` when the staging thread died
        (the thread has already exited — ``close()`` just joins), and
        ``StopIteration`` when the host iterator ended.
        """
        if self._stop.is_set():
            raise RuntimeError("prefetcher is closed")
        item = self._queue.get()
        if isinstance(item, _EndOfData):
            self._stop.set()
            raise StopIteration
        if isinstance(item, _StageFailure):
            self._stop.set()
            raise PrefetchStageError(item.batch_index, item.exc) from item.exc
        self.consumed += 1
        return item

    def __iter__(self):
        return self

    def __next__(self):
        return self.get()

    def close(self):
        """Stop staging and join the thread (drains the queue so a put
        blocked on a full queue can observe the stop event)."""
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():  # pragma: no cover - diagnostic only
            log.warning("pipeline staging thread did not join within 5s")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self._stop.set()
        except AttributeError:
            pass
