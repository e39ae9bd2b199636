"""The MultibatchData pipeline: sample -> decode (host threads) -> augment
(device, jitted) -> prefetch queue.

The reference's data layer runs decode + augmentation on a CPU prefetch
thread per rank (SURVEY.md §3.5).  Here the host only decodes and
resizes; every augmentation op (warp, crop, mirror, mean) runs on the
accelerator as one jitted graph (``data.transforms``), and a background
thread keeps a bounded queue of ready batches so the training step never
waits on input.
"""

from __future__ import annotations

import logging
import queue
import threading
import weakref
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from npairloss_tpu.config.schema import DataLayerConfig, TransformerConfig
from npairloss_tpu.data.dataset import ArrayDataset, ListFileDataset
from npairloss_tpu.data.sampler import IdentityBalancedSampler
from npairloss_tpu.data.transforms import augment
from npairloss_tpu.resilience import failpoints

log = logging.getLogger("npairloss_tpu.data")


class PrefetchWorkerError(RuntimeError):
    """The prefetch worker died more times than the respawn budget
    allows; carries the failing batch index and respawn count so a
    pod-scale log names *where* the pipeline died, not just that it
    did."""


class _WorkerFailure:
    """Queue marker for a worker death: the exception plus the batch
    index it died on (consumed by ``__next__``, which respawns or
    raises with context)."""

    __slots__ = ("exc", "batch_index")

    def __init__(self, exc: BaseException, batch_index: int):
        self.exc = exc
        self.batch_index = batch_index


def _identity_counts(cfg: DataLayerConfig) -> Tuple[int, int]:
    ids = cfg.identity_num_per_batch
    imgs = cfg.img_num_per_identity
    if not ids or not imgs:
        # Fall back to pairs (the minimum the mining contract allows).
        imgs = imgs or 2
        ids = ids or max(1, (cfg.batch_size or 2) // imgs)
    return ids, imgs


class MultibatchLoader:
    """Iterator of (images[float32 NHWC], labels[int32]) batches."""

    def __init__(
        self,
        dataset,
        cfg: DataLayerConfig,
        transformer: Optional[TransformerConfig] = None,
        train: bool = True,
        seed: int = 0,
        prefetch: int = 2,
        max_worker_restarts: int = 3,
    ):
        self.dataset = dataset
        self.cfg = cfg
        self.transformer = transformer
        self.train = train
        ids, imgs = _identity_counts(cfg)
        self.sampler = IdentityBalancedSampler(
            dataset.labels,
            ids,
            imgs,
            rand_identity=cfg.rand_identity,
            shuffle=cfg.shuffle,
            seed=seed,
        )
        self._key = jax.random.PRNGKey(seed)
        self._queue: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
        self._stop = threading.Event()
        # Bounded fault tolerance (docs/RESILIENCE.md): a worker death
        # respawns the thread up to ``max_worker_restarts`` CONSECUTIVE
        # times before surfacing a PrefetchWorkerError with the batch
        # context; a successfully delivered batch resets the budget, so
        # sparse transient errors over a multi-day run never accumulate
        # into an abort while a deterministic failure still dies after
        # max_worker_restarts + 1 attempts.
        self.max_worker_restarts = max_worker_restarts
        self._respawns = 0
        self._batch_seq = 0  # written by the (single) worker thread only
        self._spawn_worker()

    def _spawn_worker(self):
        # The worker holds only a weakref to the loader, so an abandoned
        # loader (no close()) is still garbage-collectable; __del__ then
        # stops the thread.
        self._thread = threading.Thread(
            target=_prefetch_worker,
            args=(weakref.ref(self), self._queue, self._stop),
            daemon=True,
        )
        self._thread.start()

    # -- host side: sample + decode (see _prefetch_worker) -----------------

    def _produce_one(self):
        failpoints.fire("data.worker")
        idx = next(self.sampler)
        images = self.dataset.load_batch(idx).astype(np.float32)
        labels = self.dataset.labels[idx].astype(np.int32)
        self._batch_seq += 1
        return images, labels

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        return self

    def __next__(self):
        while True:
            if self._stop.is_set():
                raise StopIteration("loader is closed")
            item = self._queue.get()
            if isinstance(item, _WorkerFailure):
                if self._respawns < self.max_worker_restarts:
                    self._respawns += 1
                    log.warning(
                        "data prefetch worker died at batch %d (%s: %s); "
                        "respawning (%d/%d)",
                        item.batch_index, type(item.exc).__name__,
                        item.exc, self._respawns, self.max_worker_restarts,
                    )
                    self._spawn_worker()
                    continue
                self._stop.set()
                raise PrefetchWorkerError(
                    f"data prefetch worker failed at batch "
                    f"{item.batch_index} after {self._respawns} "
                    f"respawns: {type(item.exc).__name__}: {item.exc}"
                ) from item.exc
            images, labels = item
            self._respawns = 0  # healthy batch: the budget is per-streak
            return _maybe_augment(self, images), labels

    def close(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        # The worker only weakrefs the loader, so this runs even without
        # close(); stop the thread rather than leak it.
        try:
            self._stop.set()
        except AttributeError:
            pass


def _prefetch_worker(loader_ref, q: queue.Queue, stop: threading.Event):
    """Module-level worker holding only a weakref to the loader (plus its
    queue/stop-event, which don't reference back), so an abandoned loader
    is garbage-collectable even while the worker blocks on a full queue."""

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=1.0)
                return True
            except queue.Full:
                continue
        return False

    while not stop.is_set():
        loader = loader_ref()
        if loader is None:
            return
        try:
            item = loader._produce_one()
            fatal = False
        except BaseException as exc:  # surface in __next__, not silently
            # Wrapped with the batch index so the consumer can respawn
            # (bounded) or raise with context instead of a bare error.
            item, fatal = _WorkerFailure(exc, loader._batch_seq), True
        del loader  # no strong ref while blocking on the queue
        if not put(item) or fatal:
            return


class NativeMultibatchLoader:
    """MultibatchLoader on the C++ runtime (``data.native``): sampling,
    decode, resize and batch assembly run in native worker threads off
    the GIL; augmentation stays on-device as one jitted graph."""

    def __init__(
        self,
        cfg: DataLayerConfig,
        transformer: Optional[TransformerConfig] = None,
        train: bool = True,
        seed: int = 0,
        prefetch: int = 2,
        threads: int = 4,
    ):
        from npairloss_tpu.data import native

        self.cfg = cfg
        self.transformer = transformer
        self.train = train
        self._key = jax.random.PRNGKey(seed)
        self.dataset = native.NativeListFileDataset(
            cfg.root_folder, cfg.source, cfg.new_height, cfg.new_width
        )
        ids, imgs = _identity_counts(cfg)
        self._prefetcher = native.NativePrefetcher(
            self.dataset, ids, imgs,
            rand_identity=cfg.rand_identity, shuffle=cfg.shuffle,
            seed=seed, threads=threads, prefetch=prefetch,
        )

    def __iter__(self):
        return self

    def __next__(self):
        images, labels = next(self._prefetcher)
        return _maybe_augment(self, images.astype(np.float32)), labels

    def close(self):
        self._prefetcher.close()
        self.dataset.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _maybe_augment(loader, images):
    """On-device augmentation shared by both loaders: applied only when
    the transform config is non-default or a DataTransformer is set, with
    the loader's own PRNG key chain."""
    if (
        loader.cfg.transform == type(loader.cfg.transform)()
        and loader.transformer is None
    ):
        return images
    loader._key, sub = jax.random.split(loader._key)
    return augment(
        images, sub,
        tp=loader.cfg.transform, transformer=loader.transformer,
        train=loader.train,
    )


def multibatch_loader(
    cfg: DataLayerConfig,
    transformer: Optional[TransformerConfig] = None,
    train: Optional[bool] = None,
    seed: int = 0,
    prefetch: int = 2,
    native: str = "auto",
):
    """Build the full pipeline from a parsed MultibatchData layer config.

    ``native``: "auto" uses the C++ runtime when it is buildable AND the
    config can use it (fixed resize dims — the loader's batch contract);
    "never" forces the Python pipeline; "require" raises when the native
    runtime is unavailable.  Decode-format support differs: native reads
    JPEG (when built against libjpeg — the CUB/SOP case) plus
    PPM/PGM/BMP/NPY-u8; the Python path reads anything PIL does — a
    native worker hitting an unsupported format surfaces the error on
    the next batch, so "auto" keeps Python for such datasets (routing
    samples the first ~4k list entries, see _list_file_all_suffixed).
    """
    if train is None:
        train = cfg.phase == "TRAIN"
    if native not in ("auto", "never", "require"):
        raise ValueError(f"native must be auto/never/require, got {native!r}")
    if native != "never" and cfg.new_height and cfg.new_width:
        from npairloss_tpu.data import native as nd

        available = nd.native_available()  # cached; check before file I/O
        # JPEG routes native only when the build linked libjpeg.
        supported = nd.native_suffixes() if available else ()
        if native == "require":
            # Asked for by name: fail with the loader's own reason,
            # never fall back to the Python pipeline.
            nd.require_native()
            return NativeMultibatchLoader(
                cfg, transformer, train=train, seed=seed,
                prefetch=prefetch,
            )
        try:
            if available and _list_file_all_suffixed(cfg.source,
                                                     supported):
                return NativeMultibatchLoader(
                    cfg, transformer, train=train, seed=seed,
                    prefetch=prefetch,
                )
        except OSError:
            pass  # unreadable list file: let the Python path report it
    elif native == "require":
        raise RuntimeError(
            "native loader requires new_height/new_width (fixed batch shape)"
        )
    dataset = ListFileDataset(
        cfg.root_folder, cfg.source, cfg.new_height, cfg.new_width
    )
    return MultibatchLoader(
        dataset, cfg, transformer, train=train, seed=seed, prefetch=prefetch
    )


def shard_batches(
    batches: Iterator[Tuple[np.ndarray, np.ndarray]],
    rank: int,
    count: int,
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Per-process disjoint shards of a deterministic pod-global batch
    stream — the multi-controller data model (docs/DISTRIBUTED.md).

    Every controller builds the SAME loader (same list file, same
    seed), so each one computes the identical global batch schedule;
    this wrapper hands process ``rank`` rows
    ``[rank*n : (rank+1)*n]`` of every batch (``n = rows // count``).
    The shards are disjoint by construction, their concatenation in
    rank order IS the global batch (``process_local_batch`` reassembles
    exactly it on the mesh), and the global batch — hence the training
    trajectory — is independent of how many controllers split it: the
    single-process run on the unsliced stream is the bit-identical
    parity oracle.  Mirrors the reference's per-rank MultibatchData
    with a shared schedule (``mpirun -np G``, cu:17-43).

    Loud on a batch whose rows don't divide by ``count`` — a silently
    dropped remainder would change the pool every step.
    """
    if not (0 <= int(rank) < int(count)):
        raise ValueError(f"rank {rank} outside [0, {count})")
    rank, count = int(rank), int(count)

    def gen():
        for inputs, labels in batches:
            rows = len(labels)
            if rows % count:
                raise ValueError(
                    f"global batch of {rows} rows does not divide over "
                    f"{count} processes; fix identity_num_per_batch x "
                    "img_num_per_identity to a multiple of the process "
                    "count")
            n = rows // count
            sl = slice(rank * n, (rank + 1) * n)
            yield np.asarray(inputs)[sl], np.asarray(labels)[sl]

    return gen()


def _list_file_all_suffixed(source: str, suffixes, sample: int = 4096) -> bool:
    """True when the list file's entries all carry a native-decodable
    suffix.  Bounded: only the first ``sample`` entries are examined (an
    O(dataset) pre-scan per loader is not acceptable for million-image
    lists); datasets are overwhelmingly suffix-homogeneous, and a
    mixed-format tail misrouted to the native runtime fails loudly at
    decode time rather than silently.
    """
    seen = 0
    with open(source, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            path = line.rsplit(None, 1)[0].lower()
            if not path.endswith(suffixes):
                return False
            seen += 1
            if seen >= sample:
                break
    return True
