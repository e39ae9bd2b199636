"""serve/: index commit/restore, engine parity, batcher, drain contract.

The load-bearing pins (docs/SERVING.md):
  * served top-K answers are EXACTLY consistent with the offline
    protocol (``ops.eval_retrieval.gallery_recall_at_k``) on identical
    embeddings — streamed blocks and mesh shards included;
  * the index commit is atomic and a torn index is skipped, never
    served (the resilience.snapshot contract applied to galleries);
  * the micro-batcher honors deadline/bucket/backpressure bounds;
  * a drain (the SIGTERM path) answers every admitted query — zero
    drops — and steady-state serving performs zero XLA compiles after
    warmup (counted via the engine's compile accounting, not eyeballed).
"""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from npairloss_tpu.resilience.snapshot import SnapshotValidationError
from npairloss_tpu.serve import (
    BatcherConfig,
    EngineConfig,
    GalleryIndex,
    MicroBatcher,
    QueryEngine,
    QueueFullError,
    RetrievalServer,
    ServerConfig,
)
from npairloss_tpu.serve.index import load_newest


def make_gallery(rng, ids=12, per_id=6, dim=16, noise=0.3):
    centers = rng.standard_normal((ids, dim))
    labels = np.repeat(np.arange(ids), per_id).astype(np.int32)
    emb = centers[labels] + noise * rng.standard_normal(
        (ids * per_id, dim)
    )
    return emb.astype(np.float32), labels


# -- index ------------------------------------------------------------------


def test_index_build_persist_restore_roundtrip(rng, tmp_path):
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    path = str(tmp_path / "g-0001.gidx")
    idx.save(path)
    idx2 = GalleryIndex.load(path)
    np.testing.assert_array_equal(idx2._host_labels, idx._host_labels)
    np.testing.assert_array_equal(idx2.ids, idx.ids)
    # build() normalized once; the round-tripped rows are bit-identical
    np.testing.assert_array_equal(idx2._host_emb, idx._host_emb)
    assert idx2.size == idx.size and idx2.dim == idx.dim


def test_index_torn_commit_is_skipped(rng, tmp_path):
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    good = str(tmp_path / "g-0001.gidx")
    bad = str(tmp_path / "g-0002.gidx")
    idx.save(good)
    idx.save(bad)
    # Bit-rot the newer index's embedding bytes: load must refuse it...
    with open(os.path.join(bad, "emb.npy"), "r+b") as f:
        f.seek(256)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(SnapshotValidationError):
        GalleryIndex.load(bad)
    # ...and the newest-first scan must fall back to the older valid one.
    found = load_newest(str(tmp_path / "g-"))
    assert found is not None and found[0] == good
    # A tmp dir (crash mid-commit) is invisible to the scan entirely.
    os.rename(bad, str(tmp_path / "g-0003.gidx.tmp-123-ab"))
    found = load_newest(str(tmp_path / "g-"))
    assert found is not None and found[0] == good


def test_index_add_appends_and_pads(rng):
    from npairloss_tpu.parallel import data_parallel_mesh

    emb, lab = make_gallery(rng, ids=5, per_id=3)
    mesh = data_parallel_mesh()
    idx = GalleryIndex.build(emb, lab, mesh=mesh)
    assert idx.padded_size % mesh.size == 0
    n0 = idx.size
    add_emb = rng.standard_normal((7, emb.shape[1])).astype(np.float32)
    idx.add(add_emb, np.arange(7).astype(np.int32))
    assert idx.size == n0 + 7
    assert idx.padded_size % mesh.size == 0
    assert idx.ids.shape[0] == idx.size
    # validity mask exactly covers the true rows
    assert int(np.asarray(idx.valid).sum()) == idx.size


# -- engine parity ----------------------------------------------------------


def _served_recall(engine, emb, labels, ks):
    """Recall@K from served answers under the offline protocol: query
    each gallery row, drop the self row, membership-in-top-K."""
    out = engine.query(emb)
    n = emb.shape[0]
    recalls = {}
    for k in ks:
        hits = 0
        for i in range(n):
            rows = [r for r in out["rows"][i] if r != i][:k]
            hits += bool(np.any(labels[np.asarray(rows)] == labels[i]))
        recalls[k] = hits / n
    return recalls


@pytest.mark.parametrize("use_mesh", [False, True])
def test_served_topk_matches_gallery_recall(rng, use_mesh):
    """The acceptance pin: served answers reproduce
    ``gallery_recall_at_k`` EXACTLY on the same embeddings — through
    streamed gallery blocks and (parametrized) the sharded merge."""
    from npairloss_tpu.ops.eval_retrieval import evaluate_embeddings

    emb, lab = make_gallery(rng, ids=10, per_id=5, dim=16, noise=0.8)
    ks = (1, 2, 4, 8)
    mesh = None
    if use_mesh:
        from npairloss_tpu.parallel import data_parallel_mesh

        mesh = data_parallel_mesh()
    idx = GalleryIndex.build(emb, lab, mesh=mesh)
    engine = QueryEngine(
        idx,
        EngineConfig(top_k=max(ks) + 1, buckets=(8, 64),
                     gallery_block=13),
    )
    want = evaluate_embeddings(emb, lab, ks=ks)
    got = _served_recall(engine, emb, lab, ks)
    n = emb.shape[0]
    for k in ks:
        # Exact consistency = identical HIT COUNTS (the offline number
        # is an fp32 mean of 0/1s; the count is its exact content).
        assert round(got[k] * n) == round(want[f"recall_at_{k}"] * n), k
        assert got[k] == pytest.approx(want[f"recall_at_{k}"], abs=1e-6)


def test_streamed_blocks_and_shards_are_bit_identical(rng):
    """Gallery-block size and mesh sharding are implementation details:
    every combination returns the same rows AND bit-identical scores."""
    from npairloss_tpu.parallel import data_parallel_mesh

    emb, lab = make_gallery(rng, ids=8, per_id=5, dim=8, noise=1.0)
    ref = None
    mesh = data_parallel_mesh()
    for m, block in ((None, 64), (None, 7), (None, 13), (mesh, 7)):
        idx = GalleryIndex.build(emb, lab, mesh=m)
        engine = QueryEngine(
            idx, EngineConfig(top_k=5, buckets=(16, 64),
                              gallery_block=block)
        )
        out = engine.query(emb[:11])
        if ref is None:
            ref = out
        else:
            np.testing.assert_array_equal(out["rows"], ref["rows"])
            np.testing.assert_array_equal(out["scores"], ref["scores"])


def test_query_validates_and_chunks(rng):
    emb, lab = make_gallery(rng, ids=4, per_id=4, dim=8)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(2, 4)))
    with pytest.raises(ValueError, match="dim"):
        engine.query(np.zeros((2, 5), np.float32))
    # 11 queries chunk through max-bucket 4 dispatches (4+4+3->pad 4)
    out = engine.query(emb[:11])
    assert out["rows"].shape == (11, 3)
    with pytest.raises(ValueError, match="exceeds gallery size"):
        QueryEngine(idx, EngineConfig(top_k=100))


# -- batcher ----------------------------------------------------------------


def test_batcher_deadline_flushes_partial_batch():
    batches = []
    b = MicroBatcher(
        lambda items: [i * 10 for i in items],
        BatcherConfig(max_batch=8, max_delay_ms=30.0, max_queue=16),
    ).start()
    try:
        t0 = time.perf_counter()
        fut = b.submit(3)
        assert fut.result(timeout=5.0) == 30  # alone, under deadline
        waited = time.perf_counter() - t0
        assert waited < 2.0  # deadline (30ms) + dispatch, not the 5s cap
    finally:
        b.close()


def test_batcher_coalesces_to_bucket_and_pads(rng):
    """Queries submitted together ride one dispatch, padded to the
    smallest engine bucket that fits (the padded shape is what the
    jitted program sees — pinned via the engine's signature set)."""
    emb, lab = make_gallery(rng, ids=4, per_id=4, dim=8)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=2, buckets=(1, 4, 8)))
    engine.warmup()
    stats = []
    server = RetrievalServer(
        engine,
        BatcherConfig(max_batch=8, max_delay_ms=50.0, max_queue=32),
        ServerConfig(metrics_window=0),
    )
    server.batcher._on_batch = stats.append
    server.batcher.start()
    try:
        futs = [server.batcher.submit({"embedding": emb[i].tolist()})
                for i in range(3)]
        answers = [f.result(timeout=10.0) for f in futs]
    finally:
        server.batcher.close()
    assert [a["neighbors"][0]["row"] for a in answers] == [0, 1, 2]
    # 3 queries coalesced into one batch...
    assert server.batcher.batches == 1 and stats[0]["size"] == 3
    # ...dispatched at the padded bucket-4 signature (warmup saw it).
    assert ("topk", 4, idx.padded_size, idx.dim) in engine._seen_sigs
    assert engine.compiles_after_warmup == 0


def test_batcher_backpressure_rejects_not_queues():
    release = threading.Event()

    def slow_dispatch(items):
        release.wait(timeout=10.0)
        return items

    b = MicroBatcher(
        slow_dispatch,
        BatcherConfig(max_batch=1, max_delay_ms=0.0, max_queue=2),
    ).start()
    try:
        futs = [b.submit(i) for i in range(2)]  # fills dispatcher + queue
        time.sleep(0.2)  # let the dispatcher pick work up
        with pytest.raises(QueueFullError):
            for i in range(8):  # queue bound, not unbounded growth
                futs.append(b.submit(100 + i))
        assert b.rejected >= 1
        release.set()
        for f in futs:
            f.result(timeout=10.0)  # everything admitted still answers
    finally:
        release.set()
        b.close()


def _gated_batcher(cfg, **kw):
    """A batcher whose dispatches wait for ``release``: a backlog forms
    behind the first one.  -> (batcher, entered, release, batches)."""
    entered, release, batches = threading.Event(), threading.Event(), []

    def dispatch(items):
        batches.append(list(items))
        entered.set()
        release.wait(timeout=10.0)
        return items

    b = MicroBatcher(dispatch, cfg, **kw).start()
    return b, entered, release, batches


def _batch_spans(tr):
    return [e["args"] for e in tr.events_since(0)[0]
            if e["name"] == "serve/batch"]


@pytest.mark.parametrize("delay_ms", [0.0, 5.0, 250.0])
def test_batcher_backlog_goes_out_in_full_batches(delay_ms):
    """What is queued is taken before the clock is read: behind a slow
    dispatch every head has outlived a 0 or 5 ms deadline, and the
    backlog still goes out ``max_batch`` at a time, in submission order
    (it went out in singles when the deadline was looked at first)."""
    b, entered, release, batches = _gated_batcher(
        BatcherConfig(max_batch=4, max_delay_ms=delay_ms, max_queue=32))
    try:
        first = b.submit(-1)
        assert entered.wait(timeout=5.0)
        futs = [b.submit(i) for i in range(10)]
        assert b.queue_depth == 10
        time.sleep(0.02)
        release.set()
        assert first.result(timeout=10.0) == -1
        assert [f.result(timeout=10.0) for f in futs] == list(range(10))
    finally:
        release.set()
        b.close()
    assert batches == [[-1], [0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]
    assert b.dispatched == 11 and b.batches == 4


def test_batcher_closed_loop_runs_full_batches():
    """Eight callers, each sending its next request from its answer's
    done-callback (inline, on the completion thread): every turn finds
    at least ``max_batch`` queued, so but for the first batch and the
    tail every dispatch is full, with ``max_delay_ms`` long passed."""
    sizes, done, total = [], threading.Event(), 80
    lock, sent, answered = threading.Lock(), [0], [0]

    def dispatch(items):
        sizes.append(len(items))
        time.sleep(0.002)  # a dispatch outlasts the 1 ms deadline
        return items

    b = MicroBatcher(dispatch, BatcherConfig(max_batch=4, max_delay_ms=1.0,
                                             max_queue=16)).start()

    def launch():
        with lock:
            if sent[0] >= total:
                return
            sent[0] += 1
            i = sent[0]
        b.submit(i).add_done_callback(finished)

    def finished(_fut):
        launch()
        with lock:
            answered[0] += 1
            if answered[0] == total:
                done.set()

    try:
        for _ in range(8):
            launch()
        assert done.wait(timeout=20.0)
    finally:
        b.close()
    assert sum(sizes) == total == b.dispatched
    assert all(n == 4 for n in sizes[1:-3]), sizes


def test_batcher_deadline_bounds_the_wait_for_coriders_only(tracer):
    """A head on an empty queue waits for co-riders until ``max_delay_ms``
    after its SUBMIT and no longer; one that has outlived its deadline
    in the queue is dispatched at once."""
    stats = []
    b, entered, release, batches = _gated_batcher(
        BatcherConfig(max_batch=8, max_delay_ms=40.0, max_queue=16),
        on_batch=stats.append)
    try:
        a = b.submit(0)
        assert entered.wait(timeout=5.0)
        c = b.submit(1)
        time.sleep(0.08)  # c outlives its 40 ms in the queue
        release.set()
        assert (a.result(timeout=10.0), c.result(timeout=10.0)) == (0, 1)
    finally:
        release.set()
        b.close()
    first, second = _batch_spans(tracer)[:2]
    assert batches == [[0], [1]]
    # dispatched max_delay_ms after its submit (and whatever the
    # machine was late by), having waited since it was picked
    assert 0.0 < first["waited_ms"] < 1000.0
    assert 35.0 <= stats[0]["oldest_wait_ms"] < 1000.0
    assert second["waited_ms"] == 0.0 and second["size"] == 1


def test_batcher_counts_how_a_batch_formed(tracer):
    """``serve/batch`` carries ``size``, ``drained`` (co-riders taken
    without waiting), ``held`` (refused by ``fits``) and ``waited_ms``;
    ``on_batch`` gains ``drained``."""
    stats = []
    b, entered, release, batches = _gated_batcher(
        BatcherConfig(max_batch=4, max_delay_ms=0.0, max_queue=16),
        on_batch=stats.append, fits=lambda items: min(len(items), 3))
    try:
        futs = [b.submit(0)]
        assert entered.wait(timeout=5.0)
        futs += [b.submit(i) for i in range(1, 6)]
        release.set()
        assert [f.result(timeout=10.0) for f in futs] == list(range(6))
    finally:
        release.set()
        b.close()
    assert batches == [[0], [1, 2, 3], [4, 5]]
    spans = _batch_spans(tracer)
    assert [(s["size"], s["drained"], s["held"]) for s in spans] == [
        (1, 0, 0), (3, 2, 1), (2, 1, 0)]
    assert all(s["waited_ms"] == 0.0 and s["batch"] == i + 1
               for i, s in enumerate(spans))
    assert [(s["size"], s["drained"]) for s in stats] == [
        (1, 0), (3, 2), (2, 1)]
    # the depth a batch leaves behind counts what was held back
    assert [s["queue_depth"] for s in stats] == [5, 2, 0]


# -- a second batch in flight -----------------------------------------------


def _wait_for(pred, timeout=10.0):
    """Until ``pred()`` holds (it is made true by another thread's
    event, not by the clock); False after ``timeout``."""
    deadline = time.perf_counter() + timeout
    while not pred():
        if time.perf_counter() > deadline:
            return False
        time.sleep(0.002)
    return True


class _HeldSearch:
    """A float engine stand-in with the launch / collect split whose
    collect waits for ``release``: a batch stays unanswered on the
    completion thread while the dispatcher goes on."""

    def __init__(self, dim=4, k=2):
        self.index = type("Idx", (), {"dim": dim})()
        self.k = k
        self.launched = []
        self.collecting = threading.Event()
        self.release = threading.Event()

    def launch(self, q, stages=None):
        self.launched.append(q.shape[0])
        n, k = q.shape[0], self.k

        class Pending:
            def collect(inner):
                self.collecting.set()
                assert self.release.wait(timeout=10.0)
                rows = np.tile(np.arange(k), (n, 1))
                return {"rows": rows, "ids": rows, "labels": rows,
                        "scores": np.ones((n, k), np.float32)}

        return Pending()

    def query(self, q, normalize=True, stages=None, launched=None):
        return (launched or self.launch(q)).collect()


def test_second_batch_launches_while_the_first_is_unanswered(tracer):
    """Under a backlog (a whole batch queued behind the one launched),
    batch 1 goes to the completion thread, whose top-k is held; batch
    2's dispatch runs to its hand-off while batch 1 is unanswered, and
    its ``serve/batch`` says so (``inflight`` 1; batch 1 found nothing
    out: 0)."""
    engine = _HeldSearch()
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=1, max_delay_ms=0.0, max_queue=16),
        ServerConfig(metrics_window=0))
    rec = lambda i: {"id": i, "embedding": [0.5] * 4}
    # queued before the dispatcher starts: batch 2 waits behind batch 1
    first, _ = server.submit(rec(0))
    second, _ = server.submit(rec(1))
    server.replicaset.start()
    try:
        assert engine.collecting.wait(timeout=5.0)
        # batch 2 launched and went as far as the hand-off ...
        assert _wait_for(lambda: any(
            e["name"] == "serve/handoff" and e["args"]["batch"] == 1
            for e in tracer.events_since(0)[0]) and len(engine.launched) == 2)
        assert _wait_for(lambda: any(
            e["name"] == "serve/dispatch" and e["args"]["batch"] == 2
            for e in tracer.events_since(0)[0]))
        # ... and neither batch is answered: the first is held
        assert not first.done() and not second.done()
        engine.release.set()
        assert first.result(timeout=10.0)["id"] == 0
        assert second.result(timeout=10.0)["id"] == 1
    finally:
        engine.release.set()
        server.replicaset.close(drain=True)
    events = tracer.events_since(0)[0]
    batch = {e["args"]["batch"]: e["args"] for e in events
             if e["name"] == "serve/batch"}
    assert batch[1]["inflight"] == 0 and batch[2]["inflight"] == 1
    by = {(e["name"], e.get("args", {}).get("batch")): e for e in events}
    end = lambda e: e["ts"] + e["dur"]
    # the dispatcher waited at batch 2's hand-off until batch 1 was answered
    assert by[("serve/reply", 1)]["ts"] <= end(by[("serve/handoff", 2)])
    assert by[("serve/dispatch", 2)]["tid"] != by[("serve/finish", 1)]["tid"]
    assert by[("serve/finish", 1)]["tid"] == by[("serve/finish", 2)]["tid"]


def test_a_republish_between_launch_and_collect_keeps_the_launch_index(rng):
    """A pending search maps its rows through the labels and ids of the
    index it was launched on: a republish that lands before the collect
    (here the engine's index swapped for one with other labels and ids)
    answers the NEXT search, never this one."""
    emb, lab = make_gallery(rng, ids=4, per_id=4, dim=8)
    first = GalleryIndex.build(emb, lab)
    other = GalleryIndex.build(emb, lab + 100,
                               ids=np.arange(len(lab), dtype=np.int64) + 5000)
    engine = QueryEngine(first, EngineConfig(top_k=3, buckets=(4,)))
    want = engine.query(emb[:3])
    pending = engine.launch(emb[:3])
    engine.index = other
    got = pending.collect()
    for key in ("rows", "scores", "labels", "ids"):
        np.testing.assert_array_equal(got[key], want[key])
    after = engine.query(emb[:3])
    np.testing.assert_array_equal(after["labels"], want["labels"] + 100)
    np.testing.assert_array_equal(after["ids"], want["ids"] + 5000)
    # growing the launch's own index in between changes nothing either
    engine.index = first
    pending = engine.launch(emb[:3])
    first.add(emb[:2], np.array([7, 7], np.int32))
    got = pending.collect()
    for key in ("rows", "scores", "labels", "ids"):
        np.testing.assert_array_equal(got[key], want[key])


def _two_phase_batcher(finish_gate=None, fail_on=None):
    """A batcher, not started, whose ``dispatch_fn`` launches nothing
    and returns the batch's finish: it waits for ``finish_gate`` (if
    given) and raises for a batch holding ``fail_on``.  -> (batcher,
    entered, finished)."""
    entered, finished = threading.Event(), []

    def dispatch(items):
        def finish():
            entered.set()
            if finish_gate is not None:
                assert finish_gate.wait(timeout=10.0)
            if fail_on in items:
                raise RuntimeError(f"finish failed on {fail_on}")
            finished.append(list(items))
            return [x * 10 for x in items]
        return finish

    b = MicroBatcher(dispatch, BatcherConfig(max_batch=1, max_delay_ms=0.0,
                                             max_queue=16))
    return b, entered, finished


def test_draining_close_answers_the_batch_in_finish_and_the_one_behind_it():
    """``close(drain=True)`` with one batch held in its finish on the
    completion thread, the next launched and waiting at the hand-off
    and a third queued: every future is answered, in batch order,
    before the threads exit."""
    gate = threading.Event()
    b, entered, finished = _two_phase_batcher(finish_gate=gate)
    futs = [b.submit(i) for i in (1, 2, 3)]  # a backlog before the start
    b.start()
    assert entered.wait(timeout=5.0)
    assert _wait_for(lambda: b.queue_depth == 1)  # batch 2 launched
    closer = threading.Thread(target=b.close, kwargs={"drain": True})
    closer.start()
    assert not any(f.done() for f in futs)
    gate.set()
    closer.join(timeout=10.0)
    assert not closer.is_alive()
    assert [f.result(timeout=0) for f in futs] == [10, 20, 30]
    assert finished == [[1], [2], [3]]
    assert b.batches == 3 and b.dispatched == 3


def test_an_exception_in_finish_fails_that_batch_only():
    """A finish that raises fails its own batch's futures; the batch
    behind it is answered."""
    b, _entered, _finished = _two_phase_batcher(fail_on=1)
    bad, good = b.submit(1), b.submit(2)  # batch 1 goes to the completion thread
    b.start()
    try:
        with pytest.raises(RuntimeError, match="finish failed on 1"):
            bad.result(timeout=10.0)
        assert good.result(timeout=10.0) == 20
    finally:
        b.close()
    assert b.batches == 1 and b.dispatched == 1


def test_two_phases_under_contention_lose_no_answer_or_count():
    """Sixteen callers (more than cores), a short switch interval, and
    batches that sometimes hand off and sometimes finish inline: every
    future gets its own answer, and ``batches`` / ``dispatched`` count
    every batch launched and every item, whichever thread replied."""
    import sys

    launched = []
    lock = threading.Lock()

    def dispatch(items):
        with lock:
            launched.append(len(items))
        return lambda: [x * 2 for x in items]

    b = MicroBatcher(dispatch, BatcherConfig(max_batch=4, max_delay_ms=0.0,
                                             max_queue=4096)).start()
    got, errors = {}, []

    def caller(c):
        try:
            for i in range(100):
                x = c * 1000 + i
                got[x] = b.submit(x).result(timeout=30.0)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
        b.close()
    assert not errors
    assert len(got) == 1600 and all(v == 2 * x for x, v in got.items())
    assert b.dispatched == sum(launched) == 1600
    assert b.batches == len(launched)


@pytest.mark.parametrize("fits,want", [
    (None, [[0], [1, 2, 3, 4], [5, 6]]),
    (lambda items: 1, [[0], [1], [2], [3], [4], [5], [6]]),
    (lambda items: min(len(items), 2), [[0], [1, 2], [3, 4], [5, 6]]),
], ids=["no-hook", "singles", "pairs"])
def test_batcher_stop_met_mid_drain_answers_everything(fits, want):
    """A draining ``close`` whose sentinel is met while a turn takes
    what is queued: that batch, and everything the hook held back,
    is still dispatched and answered before the thread exits."""
    b, entered, release, batches = _gated_batcher(
        BatcherConfig(max_batch=4, max_delay_ms=1000.0, max_queue=16),
        fits=fits)
    futs = [b.submit(0)]
    assert entered.wait(timeout=5.0)
    futs += [b.submit(i) for i in range(1, 7)]
    closer = threading.Thread(target=b.close)
    closer.start()
    deadline = time.perf_counter() + 5.0
    while b._q.qsize() < 7 and time.perf_counter() < deadline:
        time.sleep(0.005)  # the sentinel is queued behind the six
    t0 = time.perf_counter()
    release.set()
    closer.join(timeout=10.0)
    assert not closer.is_alive()
    # the last batch is not full: it did not sit out its second
    assert time.perf_counter() - t0 < 0.5
    assert [f.result(timeout=1.0) for f in futs] == list(range(7))
    assert batches == want
    with pytest.raises(QueueFullError):
        b.submit(99)


# -- server: drain + zero-recompile steady state ----------------------------


def _jsonl_server(rng, metrics_window=0, telemetry=None):
    from npairloss_tpu.resilience import PreemptionSignal

    emb, lab = make_gallery(rng, ids=6, per_id=4, dim=8)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1, 4, 8)),
                         telemetry=telemetry)
    engine.warmup()
    preempt = PreemptionSignal()  # driven via .request(), no handlers
    server = RetrievalServer(
        engine,
        BatcherConfig(max_batch=8, max_delay_ms=5.0, max_queue=64),
        ServerConfig(metrics_window=metrics_window),
        telemetry=telemetry, preempt=preempt,
    )
    return emb, server, preempt


def test_jsonl_roundtrip_order_and_summary(rng):
    emb, server, _ = _jsonl_server(rng)
    lines = "".join(
        json.dumps({"id": i, "embedding": emb[i].tolist()}) + "\n"
        for i in range(17)
    )
    out = io.StringIO()
    rc = server.run_jsonl(io.StringIO(lines), out)
    assert rc == 0
    recs = [json.loads(ln) for ln in out.getvalue().splitlines()]
    assert [r["id"] for r in recs[:-1]] == list(range(17))  # in order
    for r in recs[:-1]:
        assert r["neighbors"][0]["row"] == r["id"]  # self is top-1
    summary = recs[-1]
    assert summary["event"] == "serve_drain"
    assert summary["answered"] == 17 and summary["errors"] == 0
    assert summary["compiles_after_warmup"] == 0


def test_sigterm_drain_answers_every_admitted_query(rng):
    """The preemption contract: requesting a drain mid-stream stops
    ADMISSION but answers every already-admitted query (zero drops),
    emits the summary, and returns EXIT_PREEMPTED."""
    from npairloss_tpu.resilience import EXIT_PREEMPTED

    emb, server, preempt = _jsonl_server(rng)
    r_fd, w_fd = os.pipe()
    in_stream = os.fdopen(r_fd, "r")
    writer = os.fdopen(w_fd, "w")
    out = io.StringIO()
    result = {}

    def run():
        result["rc"] = server.run_jsonl(in_stream, out)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    for i in range(25):
        writer.write(
            json.dumps({"id": i, "embedding": emb[i % len(emb)].tolist()})
            + "\n"
        )
    writer.flush()
    # Let some queries into flight, then preempt WITHOUT closing stdin —
    # exactly the SIGTERM timing (the handler only sets the flag).
    time.sleep(0.3)
    preempt.request()
    t.join(timeout=30.0)
    assert not t.is_alive()
    writer.close()
    in_stream.close()
    assert result["rc"] == EXIT_PREEMPTED
    recs = [json.loads(ln) for ln in out.getvalue().splitlines()]
    summary = recs[-1]
    assert summary["event"] == "serve_drain"
    answers = recs[:-1]
    # Zero drops: every admitted query has exactly one answer, in order.
    assert [a["id"] for a in answers] == list(range(len(answers)))
    assert summary["answered"] == len(answers) == summary["queries"]
    assert all("neighbors" in a for a in answers)


def test_zero_recompile_steady_state_strict_guard(rng, monkeypatch):
    """100 mixed-size queries after warmup under the strict compile
    guard: a single post-warmup XLA compile would raise.  The counters
    (signature set + executable cache size) are the proof — the
    ``NPAIRLOSS_PIPELINE_SYNC_GUARD``-style counted assertion."""
    monkeypatch.setenv("NPAIRLOSS_SERVE_COMPILE_GUARD", "strict")
    emb, lab = make_gallery(rng, ids=6, per_id=4, dim=8)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1, 4, 8)))
    engine.warmup()
    warm = engine.compile_stats()
    assert warm["warmed"] and warm["compiles_after_warmup"] == 0
    rng2 = np.random.default_rng(1)
    served = 0
    while served < 100:
        n = int(rng2.integers(1, 9))
        out = engine.query(
            rng2.standard_normal((n, emb.shape[1])).astype(np.float32)
        )
        assert out["rows"].shape == (n, 3)
        served += n
    stats = engine.compile_stats()
    assert stats["compiles_after_warmup"] == 0
    # and the cache holds exactly the warmed buckets, nothing more
    assert stats["executable_cache_size"] in (None, 3)


def test_unwarmed_bucket_trips_strict_guard(rng, monkeypatch):
    """The guard has teeth: serving a bucket warmup never compiled
    raises instead of silently eating a hot-path compile."""
    from npairloss_tpu.serve.engine import ServeCompileError

    monkeypatch.setenv("NPAIRLOSS_SERVE_COMPILE_GUARD", "strict")
    emb, lab = make_gallery(rng, ids=4, per_id=4, dim=8)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=2, buckets=(1, 4)))
    engine.warmup()
    engine.cfg = EngineConfig(top_k=2, buckets=(1, 2, 4))  # sneak a bucket
    with pytest.raises(ServeCompileError):
        engine.query(emb[:2])


def test_serve_metrics_rows_and_spans(rng, tmp_path):
    """Per-window serve metrics rows + serve/* spans land through the
    run-telemetry pipeline (docs/OBSERVABILITY.md)."""
    from npairloss_tpu.obs import RunTelemetry

    with RunTelemetry(str(tmp_path / "run"), metrics=True) as tel:
        emb, server, _ = _jsonl_server(rng, metrics_window=5,
                                       telemetry=tel)
        lines = "".join(
            json.dumps({"id": i, "embedding": emb[i % len(emb)].tolist()})
            + "\n" for i in range(12)
        )
        rc = server.run_jsonl(io.StringIO(lines), io.StringIO())
        assert rc == 0
        names = {e["name"]
                 for e in tel.tracer.to_chrome_trace()["traceEvents"]}
        assert {"serve/admit", "serve/dispatch", "serve/topk",
                "serve/warmup"} <= names
    rows = [json.loads(ln) for ln in
            open(tmp_path / "run" / "metrics.jsonl")]
    serve_rows = [r for r in rows if r["phase"] == "serve"
                  and "qps" in r]
    assert serve_rows, rows
    assert {"qps", "p50_ms", "p99_ms", "queue_depth"} <= set(serve_rows[0])


def test_backpressure_surfaces_as_error_answer(rng):
    """A rejected query is ANSWERED with an error record, not dropped."""
    emb, server, _ = _jsonl_server(rng)
    server.batcher.cfg = BatcherConfig(max_batch=1, max_delay_ms=0.0,
                                       max_queue=1)
    server.batcher._q.maxsize = 1
    release = threading.Event()
    orig = server._dispatch

    def slow(items):
        release.wait(timeout=10.0)
        return orig(items)

    server.batcher._dispatch_fn = slow
    server.batcher.start()
    try:
        futs, errors = [], 0
        for i in range(12):
            try:
                futs.append(server.batcher.submit(
                    {"id": i, "embedding": emb[0].tolist()}
                ))
            except QueueFullError:
                errors += 1
        assert errors > 0
        release.set()
        for f in futs:
            assert "neighbors" in f.result(timeout=10.0)
    finally:
        release.set()
        server.batcher.close()


# -- snapshot -> answers (restore_for_inference + encode path) --------------


def test_restore_for_inference_and_encode_path(rng, tmp_path):
    """The online path end-to-end in-process: train a tiny model,
    snapshot it, restore WITHOUT a Solver, serve raw-'input' queries
    whose encodings match the solver's own eval-mode forward."""
    import jax.numpy as jnp

    from npairloss_tpu.models import get_model
    from npairloss_tpu.ops.npair_loss import NPairLossConfig
    from npairloss_tpu.train import (
        Solver,
        SolverConfig,
        restore_for_inference,
    )
    from conftest import make_identity_batch

    solver = Solver(
        get_model("mlp", hidden=(16,), embedding_dim=8),
        NPairLossConfig(),
        SolverConfig(base_lr=0.1, lr_policy="fixed", display=0,
                     snapshot=0,
                     snapshot_prefix=str(tmp_path / "m_")),
        input_shape=(8,),
    )
    (f,), (l,) = make_identity_batch(rng, 4, 2, 8)
    solver.step(f, l)
    path = solver.save_snapshot(1)
    state = restore_for_inference(path)
    assert set(state) == {"params", "batch_stats"}
    # build a gallery from the solver's own embeddings and serve it
    emb, _ = solver.apply_model(
        solver.state["params"], solver.state["batch_stats"],
        jnp.asarray(f), train=False,
    )
    emb = np.asarray(emb)
    idx = GalleryIndex.build(emb, l)
    engine = QueryEngine(
        idx, EngineConfig(top_k=3, buckets=(1, 4)),
        model=solver.model, state=state,
    )
    engine.warmup(input_shape=(8,))
    out_io = io.StringIO()
    server = RetrievalServer(engine, BatcherConfig(max_batch=4),
                             ServerConfig(metrics_window=0))
    lines = "".join(
        json.dumps({"id": i, "input": f[i].tolist()}) + "\n"
        for i in range(4)
    )
    rc = server.run_jsonl(io.StringIO(lines), out_io)
    assert rc == 0
    recs = [json.loads(ln) for ln in out_io.getvalue().splitlines()]
    for r in recs[:-1]:
        # the encoded query's nearest gallery row is itself
        assert r["neighbors"][0]["row"] == r["id"]
        assert r["neighbors"][0]["score"] == pytest.approx(1.0, abs=1e-5)
    assert recs[-1]["compiles_after_warmup"] == 0


def test_restore_for_inference_rejects_corrupt_snapshot(rng, tmp_path):
    from npairloss_tpu.models import get_model
    from npairloss_tpu.ops.npair_loss import NPairLossConfig
    from npairloss_tpu.train import (
        Solver,
        SolverConfig,
        restore_for_inference,
    )
    from conftest import make_identity_batch

    solver = Solver(
        get_model("mlp", hidden=(8,), embedding_dim=4),
        NPairLossConfig(),
        SolverConfig(base_lr=0.1, lr_policy="fixed", display=0,
                     snapshot=0,
                     snapshot_prefix=str(tmp_path / "m_")),
        input_shape=(8,),
    )
    (f,), (l,) = make_identity_batch(rng, 4, 2, 8)
    solver.step(f, l)
    path = solver.save_snapshot(1)
    # poison the manifest's params checksums -> verification must refuse
    import json as _json

    mpath = os.path.join(path, "manifest.json")
    manifest = _json.load(open(mpath))
    for k, rec in manifest["arrays"].items():
        if k.startswith("['params']"):
            rec["crc32"] = (rec["crc32"] + 1) & 0xFFFFFFFF
    _json.dump(manifest, open(mpath, "w"))
    with pytest.raises(SnapshotValidationError):
        restore_for_inference(path)


# -- review regressions -----------------------------------------------------


def test_engine_add_on_mesh_reoffsets_shards(rng):
    """add() that grows padded_size changes every shard's row extent;
    the retraced sharded top-k must compute offsets from the NEW local
    shard shape, not the one captured at engine build (stale offsets
    serve wrong rows/labels/ids)."""
    from npairloss_tpu.parallel import data_parallel_mesh

    mesh = data_parallel_mesh()
    emb, lab = make_gallery(rng, ids=5, per_id=2, dim=8)  # 10 -> pad 16
    idx = GalleryIndex.build(emb, lab, mesh=mesh)
    engine = QueryEngine(idx, EngineConfig(top_k=4, buckets=(4,)))
    q = np.asarray(idx._host_emb[:4])
    engine.query(q)  # trace the original layout first
    add_emb, add_lab = make_gallery(rng, ids=7, per_id=1, dim=8)
    idx.add(add_emb, add_lab)  # 17 rows -> pad 24: shard extent 2 -> 3
    out = engine.query(np.asarray(idx._host_emb), normalize=False)
    sims = idx._host_emb @ idx._host_emb.T
    for i in range(idx.size):
        want = np.argsort(-sims[i], kind="stable")[:4]
        np.testing.assert_array_equal(out["rows"][i], want, str(i))
        np.testing.assert_array_equal(
            out["labels"][i], idx._host_labels[want], str(i)
        )


def test_index_save_overwrite_never_destroys_committed_data(rng, tmp_path):
    """Re-committing onto an existing index renames the old dir aside
    and deletes it only AFTER the new commit: a crash at the commit
    point must leave the original arrays intact on disk, never an empty
    prefix (the --add-to re-commit is the production path here)."""
    from npairloss_tpu.resilience import failpoints
    from npairloss_tpu.resilience.failpoints import InjectedFault

    emb, lab = make_gallery(rng, ids=4, per_id=2)
    idx = GalleryIndex.build(emb, lab)
    path = str(tmp_path / "g.gidx")
    idx.save(path)
    original = np.load(os.path.join(path, "emb.npy"))
    idx.add(rng.standard_normal((3, emb.shape[1])).astype(np.float32),
            np.arange(3).astype(np.int32))
    with failpoints.armed("index.commit.crash"):
        with pytest.raises(InjectedFault):
            idx.save(path)
    # the committed name is mid-swap, but the old data survives aside
    aside = [d for d in os.listdir(tmp_path)
             if "-prev" in d and d.startswith("g.gidx")]
    assert len(aside) == 1, aside
    kept = np.load(str(tmp_path / aside[0] / "emb.npy"))
    np.testing.assert_array_equal(kept, original)
    # a clean retry commits the new index and clears the debris
    idx.save(path)
    reloaded = GalleryIndex.load(path)
    assert reloaded.size == idx.size
    assert not [d for d in os.listdir(tmp_path) if "-prev" in d]


def test_bad_record_answers_alone_coriders_served(rng):
    """One malformed record in a coalesced micro-batch answers with an
    error WITHOUT failing its co-riders, and the drain summary counts
    it as an error, not an answered query."""
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(8,)))
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=8, max_delay_ms=50.0),
        ServerConfig(metrics_window=0),
    )
    recs = [
        {"id": 0, "embedding": emb[0].tolist()},
        {"id": 1},  # missing field
        {"id": 2, "embedding": emb[1][:5].tolist()},  # wrong dim
        {"id": 3, "embedding": emb[2].tolist()},
    ]
    out_io = io.StringIO()
    rc = server.run_jsonl(
        io.StringIO("".join(json.dumps(r) + "\n" for r in recs)), out_io
    )
    assert rc == 0
    lines = [json.loads(ln) for ln in out_io.getvalue().splitlines()]
    by_id = {a["id"]: a for a in lines[:-1]}
    assert by_id[0]["neighbors"] and by_id[3]["neighbors"]
    assert "error" in by_id[1] and "field" in by_id[1]["error"]
    assert "error" in by_id[2] and "shape" in by_id[2]["error"]
    drain = lines[-1]
    assert drain["answered"] == 2 and drain["errors"] == 2, drain


def test_submit_close_race_leaves_no_hung_future():
    """A submit racing with close() must never land its item behind the
    _STOP sentinel (a hung future = a dropped admitted query).  Stress
    the window: every future a submitter got back must resolve."""
    batcher = MicroBatcher(
        lambda items: [x for x in items],
        BatcherConfig(max_batch=4, max_delay_ms=1.0, max_queue=512),
    ).start()
    futures, stop = [], threading.Event()
    flock = threading.Lock()

    def pound():
        while not stop.is_set():
            try:
                fut = batcher.submit("x")
            except QueueFullError:
                continue
            with flock:
                futures.append(fut)

    threads = [threading.Thread(target=pound) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    batcher.close(drain=True)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    assert futures
    for fut in futures:  # resolved == dispatched (drain) — none hung
        assert fut.result(timeout=5.0) == "x"


def test_dispatch_encodes_raw_inputs_as_one_batch(rng):
    """Raw-'input' co-riders encode as ONE stacked dispatch — per-record
    encodes would serialize device round-trips inside the batch and
    defeat the micro-batcher entirely."""
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    inner = QueryEngine(idx, EngineConfig(top_k=3, buckets=(8,)))

    class CountingEngine:
        index = idx
        encode_calls = 0

        def encode(self, x):
            CountingEngine.encode_calls += 1
            return x / np.maximum(
                np.linalg.norm(x, axis=1, keepdims=True), 1e-12
            )

        def query(self, q, normalize=True):
            return inner.query(q, normalize=normalize)

        def compile_stats(self):
            return inner.compile_stats()

    server = RetrievalServer(CountingEngine(),
                             cfg=ServerConfig(metrics_window=0))
    answers = server._dispatch([
        {"id": i, "input": emb[i].tolist()} for i in range(3)
    ] + [{"id": 3, "embedding": emb[3].tolist()}])
    assert CountingEngine.encode_calls == 1
    for i, a in enumerate(answers):
        assert a["id"] == i and a["neighbors"][0]["row"] == i

def test_jsonl_burst_then_idle_answers_every_line(rng):
    """A burst of lines followed by quiet must all answer WITHOUT
    waiting for EOF: lines read ahead into the stream buffer may never
    make the fd readable again, so the reader must not gate line
    consumption on fd-level readiness."""
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(8,)))
    engine.warmup()
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=8, max_delay_ms=5.0),
        ServerConfig(metrics_window=0, poll_s=0.02),
    )
    r_fd, w_fd = os.pipe()
    reader = os.fdopen(r_fd, "r")
    out_io = io.StringIO()
    rc = [None]
    t = threading.Thread(
        target=lambda: rc.__setitem__(0, server.run_jsonl(reader, out_io))
    )
    t.start()
    try:
        burst = "".join(
            json.dumps({"id": i, "embedding": emb[i].tolist()}) + "\n"
            for i in range(20)
        ).encode()
        os.write(w_fd, burst)  # one burst, writer stays open (no EOF)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if out_io.getvalue().count("\n") >= 20:
                break
            time.sleep(0.05)
        answered = [json.loads(ln) for ln in out_io.getvalue().splitlines()]
        assert len(answered) == 20, (
            f"only {len(answered)} answers while idle (writer still open)"
        )
        assert {a["id"] for a in answered} == set(range(20))
    finally:
        os.close(w_fd)  # EOF ends the run
        t.join(timeout=10.0)
    assert rc[0] == 0


def test_handle_many_coalesces_one_request_body(rng):
    """handle_many admits every record before waiting on any, so an
    N-record HTTP body coalesces into shared micro-batches instead of
    N sequential batches-of-1 each paying the deadline."""
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(4,)))
    engine.warmup()
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=4, max_delay_ms=500.0),
        ServerConfig(metrics_window=0),
    )
    server.batcher.start()
    try:
        recs = [{"id": i, "embedding": emb[i].tolist()} for i in range(4)]
        t0 = time.monotonic()
        answers = server.handle_many(recs)
        dt = time.monotonic() - t0
    finally:
        server.batcher.close(drain=True)
    for i, a in enumerate(answers):
        assert a["id"] == i and a["neighbors"][0]["row"] == i
    # all 4 filled the bucket and dispatched as ONE batch immediately —
    # sequential handling would pay the 500ms deadline per record
    assert server.batcher.batches == 1, server.batcher.batches
    assert dt < 2.0, f"coalesced body took {dt:.2f}s"


def test_warmup_compiles_each_bucket_exactly_once(rng):
    """warmup must pay ONE XLA compile per bucket program — an AOT
    lower().compile() whose executable jit's dispatch cache ignores
    would silently double every bucket's compile cost (counted via
    jax.monitoring backend-compile events, not eyeballed)."""
    import jax.monitoring

    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1, 4)))
    compiles = []

    def _listener(name, dur, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(_listener)
    try:
        engine.warmup()
    finally:
        jax.monitoring.unregister_event_duration_listener(_listener)
    assert len(compiles) == len(engine.cfg.buckets), (
        f"{len(compiles)} backend compiles for "
        f"{len(engine.cfg.buckets)} buckets"
    )
    assert engine.compile_stats()["compiles_after_warmup"] == 0


def test_submit_counter_exact_under_concurrency(rng):
    """self.queries increments under the lock: concurrent HTTP request
    threads must never lose an increment (the drain summary invariant
    queries == answered + errors + rejected depends on it)."""
    emb, lab = make_gallery(rng)
    idx = GalleryIndex.build(emb, lab)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(8,)))
    engine.warmup()
    server = RetrievalServer(
        engine, BatcherConfig(max_batch=8, max_delay_ms=1.0,
                              max_queue=4096),
        ServerConfig(metrics_window=0),
    )
    server.batcher.start()
    n_threads, per = 8, 50

    def _hammer(t):
        for i in range(per):
            server.handle({"id": t * per + i,
                           "embedding": emb[i % emb.shape[0]].tolist()})

    threads = [threading.Thread(target=_hammer, args=(t,))
               for t in range(n_threads)]
    try:
        for t in threads:
            t.start()
    finally:
        for t in threads:
            t.join(timeout=60.0)
        server.batcher.close(drain=True)
    s = server.summary()
    assert s["queries"] == n_threads * per
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"]


def test_add_rejects_mismatched_ids(rng):
    emb, lab = make_gallery(rng, ids=4, per_id=2)
    idx = GalleryIndex.build(emb, lab)
    with pytest.raises(ValueError, match="ids"):
        idx.add(rng.standard_normal((3, emb.shape[1])).astype(np.float32),
                np.arange(3).astype(np.int32),
                ids=np.arange(7, dtype=np.int64))

def test_rejected_queries_counted_once_in_summary(rng):
    """A backpressure rejection counts ONCE — in ``rejected``, never
    also in ``errors`` — so the drain invariant queries == answered +
    errors + rejected holds with rejections actually occurring."""
    emb, server, _ = _jsonl_server(rng)
    server.batcher.cfg = BatcherConfig(max_batch=1, max_delay_ms=0.0,
                                       max_queue=1)
    server.batcher._q.maxsize = 1
    release = threading.Event()
    orig = server._dispatch

    def slow(items):
        release.wait(timeout=10.0)
        return orig(items)

    server.batcher._dispatch_fn = slow
    server.batcher.start()
    try:
        threading.Timer(0.3, release.set).start()
        answers = server.handle_many(
            [{"id": i, "embedding": emb[0].tolist()} for i in range(12)]
        )
    finally:
        release.set()
        server.batcher.close(drain=True)
    s = server.summary()
    assert s["rejected"] > 0, s
    assert sum(1 for a in answers if "error" in a) == s["rejected"]
    assert s["errors"] == 0, s
    assert s["queries"] == s["answered"] + s["errors"] + s["rejected"], s
