"""Serving windows: the program's ``RetrievalServer.submit`` on raw-input
records, in process, one replica, under an open loop at a fixed rate or
a closed loop of callers.  Every request is timed by the harness itself,
an open-loop request from when it was DUE.
"""

from __future__ import annotations

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from npairloss_tpu.serve.batcher import QueueFullError

from benchmarks.harness import tracing, traffic, weights


def _fields(d):
    return {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}


def engine_config(engine: dict):
    """``EngineConfig`` from EVERY key of a mix's ``engine`` dict (lists
    as tuples): a key the program learns later reaches it from the data
    file alone, and one it lacks is the dataclass's own error."""
    from npairloss_tpu.serve.engine import EngineConfig

    return EngineConfig(**_fields(engine))


def batcher_config(mix: dict):
    """``BatcherConfig`` from every key of the mix's ``batcher`` dict;
    ``max_batch`` is the engine's last bucket."""
    from npairloss_tpu.serve.batcher import BatcherConfig

    return BatcherConfig(max_batch=mix["engine"]["buckets"][-1],
                         **_fields(mix["batcher"]))


def build_server(cell, seed, trace: bool):
    """(server, context) with the engine warmed on the cell's own shapes."""
    from npairloss_tpu.serve.engine import QueryEngine
    from npairloss_tpu.serve.index import GalleryIndex
    from npairloss_tpu.serve.ivf import IVFIndex
    from npairloss_tpu.serve.server import RetrievalServer, ServerConfig

    cfg, mix, adapter = cell.config, cell.traffic, cell.adapter
    g = mix["gallery"]
    gallery, glabels = weights.mixture_gallery(
        g["seed"], g["rows"], cfg["embedding_dim"], g["centres"])
    if g["index"] == "ivf":
        index = IVFIndex.build_ivf(gallery, glabels, normalize=False,
                                   clusters=g["clusters"], seed=g["seed"])
    else:
        index = GalleryIndex.build(gallery, glabels, normalize=False)
    params = weights.make_params(adapter, cfg, seed)
    host_params = weights.widened(params)
    state = {"params": adapter.to_program(params, xp=jnp), "batch_stats": {}}
    qtracer = None
    if trace:
        from npairloss_tpu.obs.qtrace.core import QueryTracer

        qtracer = QueryTracer()
    engine = QueryEngine(index, engine_config(mix["engine"]),
                         model=adapter.build_model(cfg), state=state)
    for x in adapter.warm_inputs(cfg, mix):
        engine.warmup(x)
    server = RetrievalServer(
        engine, batcher_config(mix), ServerConfig(metrics_window=0),
        input_shape=adapter.input_shape(cfg), qtrace=qtracer)
    server.replicaset.start()
    pool = adapter.query_pool(cfg, mix, seed)
    ctx = {"gallery": gallery, "host_params": host_params, "pool": pool,
           "qtracer": qtracer, "index": index,
           "cap": getattr(getattr(index, "layout", None), "cap", None)}
    return server, ctx


class Ledger:
    """What the harness saw of every request: due, sent, done, answer."""

    def __init__(self, n):
        self.due = [0.0] * n
        self.sent = [0.0] * n
        self.done = [None] * n
        self.key = [0] * n
        self.answer = [None] * n
        self.refused = 0
        self.qt = [None] * n
        self.lock = threading.Lock()
        self.outstanding = 0
        self.idle = threading.Event()

    def extend(self, n):
        """Room for ``n`` more requests."""
        for name, empty in (("due", 0.0), ("sent", 0.0), ("done", None), ("key", 0),
                            ("answer", None), ("qt", None)):
            getattr(self, name).extend([empty] * n)

    def note(self, i, fut):
        self.done[i] = time.perf_counter()
        try:
            self.answer[i] = fut.result()
        except Exception as exc:  # noqa: BLE001 - a failed request is data
            self.answer[i] = {"error": repr(exc)}

    def hold(self):
        with self.lock:
            self.outstanding += 1
            self.idle.clear()

    def release(self):
        with self.lock:
            self.outstanding -= 1
            if self.outstanding == 0:
                self.idle.set()

    def finish(self, i, fut):
        self.note(i, fut)
        self.release()


def _record(i, key, pool, qtracer, ledger):
    rec = {"id": i, "input": pool[key]}
    if qtracer is not None:
        rec["_qt"] = ledger.qt[i] = qtracer.begin(i)
    return rec


def _send(server, ledger, i, rec):
    ledger.hold()
    try:
        fut, t = server.submit(rec)
    except QueueFullError as exc:
        ledger.sent[i] = ledger.done[i] = time.perf_counter()
        ledger.answer[i] = {"error": repr(exc)}
        ledger.refused += 1
        ledger.release()
        return
    ledger.sent[i] = t
    fut.add_done_callback(lambda f, i=i: ledger.finish(i, f))


def open_window(server, ctx, mix, seed, seconds):
    """Open loop: the schedule and every record exist before the window;
    the generator sleeps to absolute deadlines on its own thread."""
    plan = traffic.open_loop(mix, seed, seconds)
    ledger = Ledger(len(plan))
    records = []
    for i, (due, key) in enumerate(plan):
        ledger.key[i] = key
        records.append(_record(i, key, ctx["pool"], ctx["qtracer"], ledger))
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter() + 0.05

    def generate():
        for i, (due, _key) in enumerate(plan):
            target = t0 + due
            ledger.due[i] = target
            wait = target - time.perf_counter()
            if wait > 0:
                with tracing.annotate("bench/idle_wait"):
                    time.sleep(wait)
            with tracing.annotate("bench/dispatch"):
                _send(server, ledger, i, records[i])

    thread = threading.Thread(target=generate, name="bench-generator")
    thread.start()
    thread.join()
    with tracing.annotate("bench/wait_answer"):
        ledger.idle.wait(timeout=60.0)
    # all the work over all the time: the window runs to its last answer
    t1 = max([t0 + seconds] + [d for d in ledger.done if d is not None])
    return ledger, {"t0": t0, "t1": t1, "seconds": t1 - t0, "offered": len(plan)}


_CHUNK = 16384  # records a closed loop makes at a time


def closed_window(server, ctx, mix, seed, seconds):
    """Closed loop: ``callers`` requests in flight; a caller sends its next
    image when its answer returns, until the clock passes ``seconds``; the
    window ends when the last answer is back, and counts all of them: it
    runs to the clock and the drain of what is in flight, whatever the
    server sustains.  Records are made ``_CHUNK`` at a time (they point
    into the pool, so they cost no memory), the first chunk before the
    window and another whenever the callers have used up those made so
    far: at most one pause of some tens of milliseconds in 16,384 answers,
    and none in a window that answers fewer."""
    callers = mix["callers"]
    ledger, records = Ledger(0), []

    def more():
        lo = len(records)
        ledger.extend(_CHUNK)
        for i, key in enumerate(traffic.closed_loop(mix, seed + lo, _CHUNK), lo):
            ledger.key[i] = key
            records.append(_record(i, key, ctx["pool"], ctx["qtracer"], ledger))

    more()
    nxt = [callers]
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def chain(i):
        def done(fut):
            ledger.note(i, fut)
            if time.perf_counter() < deadline:
                with ledger.lock:
                    j = nxt[0]
                    nxt[0] += 1
                    if j == len(records):
                        more()
                launch(j)
            ledger.release()  # after the next launch holds: never idle between
        return done

    def launch(i):
        ledger.due[i] = time.perf_counter()
        ledger.hold()
        fut, t = server.submit(records[i])
        ledger.sent[i] = t
        fut.add_done_callback(chain(i))

    for i in range(callers):
        launch(i)
    while time.perf_counter() < deadline:
        with tracing.annotate("bench/wait_answer"):
            time.sleep(0.05)
    with tracing.annotate("bench/wait_answer"):
        ledger.idle.wait(timeout=120.0)
    sent = nxt[0]
    done = [d for d in ledger.done[:sent] if d is not None]
    t1 = max(done) if done else time.perf_counter()
    for name in ("due", "sent", "done", "key", "answer", "qt"):
        setattr(ledger, name, getattr(ledger, name)[:sent])
    return ledger, {"t0": t0, "t1": t1, "seconds": t1 - t0, "offered": sent}
