"""``dispatch_overlap_share``: the reader averages ``inflight`` over the
``serve/batch`` spans that end inside the serving window, reads nothing
of a program whose spans carry no such argument, and reads a real
batcher's backlog."""

import bench_path  # noqa: F401  (repo root on sys.path)

import threading

import pytest

from benchmarks.harness import loader
from npairloss_tpu.obs import tracing

SPAN = "serve/batch"


@pytest.fixture
def tracer():
    """A tracer whose origin is 100 s on a clock that stands still,
    installed for the test and taken away after it."""
    tr = tracing.SpanTracer(max_events=8, clock=lambda: 100.0)
    prev = tracing.install(tr)
    yield tr
    tracing.install(prev)


def _put(tr, name, start_s, end_s, **args):
    tr._append(tr.complete_event(name, tr.to_us(start_s), tr.to_us(end_s), **args))


def _ctx(t0=101.0, t1=103.0, batches=4):
    return {"serve": {"window": {"t0": t0, "t1": t1}, "batches": batches}}


def _read(ctx):
    spec = loader.metric_spec("dispatch_overlap_share")
    assert spec["args"] == {"span": SPAN, "arg": "inflight"}
    return loader.reader(spec["reader"])(ctx, **spec["args"])


def test_the_share_is_the_mean_of_inflight_in_the_window(tracer):
    _put(tracer, SPAN, 100.5, 100.6, inflight=0)      # warm-up: before the window
    _put(tracer, SPAN, 100.99, 101.01, inflight=1)    # ends inside: counts
    _put(tracer, SPAN, 102.0, 102.1, inflight=1)
    _put(tracer, SPAN, 102.5, 102.6, inflight=0)
    _put(tracer, SPAN, 102.99, 103.5, inflight=0)     # ends after: the next window's
    _put(tracer, "serve/dispatch", 102.0, 102.1, inflight=0)  # another name
    assert _read(_ctx()) == pytest.approx(2 / 3)


def test_nothing_and_never_zero_without_a_reading(tracer):
    assert _read(_ctx()) is None                          # no span at all
    _put(tracer, SPAN, 101.1, 101.2, size=32, drained=31)  # the parent's spans
    assert _read(_ctx()) is None
    _put(tracer, SPAN, 101.3, 101.4, inflight=0)
    assert _read(_ctx()) == 0.0                           # a reading of none
    assert _read(_ctx(batches=0)) is None
    assert _read({}) is None                              # a training cell
    for _ in range(7):                                    # past the cap of 8
        _put(tracer, SPAN, 101.5, 101.6, inflight=1)
    assert tracer.dropped == 1 and _read(_ctx()) is None
    tracing.install(None)
    assert _read(_ctx()) is None


def test_it_reads_a_real_batchers_backlog_and_a_lone_request():
    """Eight queued before the start, in batches of two: each batch's
    finish waits until the next batch is launched, so every batch but
    the first is launched while the one before it is out (3 of 4); a
    lone request later finds nothing out (0)."""
    import time

    from npairloss_tpu.serve import BatcherConfig, MicroBatcher

    tr = tracing.SpanTracer()
    prev = tracing.install(tr)
    launched = [threading.Event() for _ in range(5)]
    calls = []

    def dispatch(items):
        k = len(calls)
        calls.append(items)
        launched[k].set()

        def finish():
            if k < 3:  # the fourth batch is the backlog's last
                assert launched[k + 1].wait(timeout=10.0)
            return items
        return finish

    b = MicroBatcher(dispatch, BatcherConfig(max_batch=2, max_delay_ms=0.0,
                                             max_queue=16))
    try:
        t0 = time.perf_counter()
        futs = [b.submit(i) for i in range(8)]
        b.start()
        assert [f.result(timeout=10.0) for f in futs] == list(range(8))
        win = {"t0": t0, "t1": time.perf_counter()}
        assert _read({"serve": {"window": win, "batches": 4}}) == \
            pytest.approx(3 / 4)
    finally:
        for ev in launched:
            ev.set()
        b.close()
    lone = MicroBatcher(lambda items: items, BatcherConfig(max_batch=2)).start()
    try:
        t1 = time.perf_counter()
        assert lone.submit(9).result(timeout=10.0) == 9
        win = {"t0": t1, "t1": time.perf_counter()}
        assert _read({"serve": {"window": win, "batches": 1}}) == 0.0
    finally:
        lone.close()
        tracing.install(prev)
