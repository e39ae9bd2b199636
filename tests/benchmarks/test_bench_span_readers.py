"""The readers of the program's own spans and regions: ``span_ms_batch``
on hand-made events of the process's tracer (clipped to the serving
window, one name or a list, nothing when the tracer dropped events or is
not there), ``region_ms`` on a hand-made reduction, and the six serving
metrics after a window at rehearsal size with a ``QueryTracer``."""

import bench_path  # noqa: F401  (repo root on sys.path)

import gc

import pytest

from bench_drive import toy_cell
from benchmarks.harness import loader
from npairloss_tpu.obs import tracing

SERVE_METRICS = ("dispatch_host_ms_batch", "encode_host_ms_batch",
                 "topk_host_ms_batch", "device_wait_ms_batch",
                 "assemble_ms_batch", "reply_ms_batch")


@pytest.fixture
def tracer():
    """A tracer whose origin is 100 s on a clock that stands still,
    installed for the test and taken away after it."""
    tr = tracing.SpanTracer(max_events=8, clock=lambda: 100.0)
    prev = tracing.install(tr)
    yield tr
    tracing.install(prev)


def _put(tr, name, start_s, end_s):
    tr._append(tr.complete_event(name, tr.to_us(start_s), tr.to_us(end_s)))


def _ctx(t0=101.0, t1=103.0, batches=4):
    return {"serve": {"window": {"t0": t0, "t1": t1}, "batches": batches}}


def test_span_ms_batch_clips_to_the_window_by_span_end(tracer):
    _put(tracer, "serve/dispatch", 100.2, 100.9)    # warm-up: before the window
    _put(tracer, "serve/dispatch", 100.995, 101.005)  # ends inside: counts whole
    _put(tracer, "serve/dispatch", 102.0, 102.006)
    _put(tracer, "serve/dispatch", 102.999, 103.5)  # ends after: the next window's
    _put(tracer, "serve/encode", 102.0, 102.002)    # another name
    read = loader.reader("span_ms_batch")
    assert read(_ctx(), span="serve/dispatch") == pytest.approx(16.0 / 4)
    assert read(_ctx(), span="serve/encode") == pytest.approx(2.0 / 4)
    assert read(_ctx(), span="serve/reply") is None   # no such span: nothing, not 0


def test_span_ms_batch_sums_a_list_of_names(tracer):
    _put(tracer, "serve/encode/wait", 101.0, 101.001)
    _put(tracer, "serve/topk/wait", 101.5, 101.503)
    _put(tracer, "serve/topk", 101.4, 101.504)
    got = loader.reader("span_ms_batch")(
        _ctx(), span=["serve/encode/wait", "serve/topk/wait"])
    assert got == pytest.approx(4.0 / 4)


def test_span_ms_batch_reads_nothing_from_a_tracer_that_dropped(tracer):
    for i in range(9):  # one over the cap of 8
        _put(tracer, "serve/dispatch", 101.0 + i / 10, 101.05 + i / 10)
    assert tracer.dropped == 1
    assert loader.reader("span_ms_batch")(_ctx(), span="serve/dispatch") is None


def test_span_ms_batch_reads_nothing_without_tracer_window_or_batches(tracer):
    _put(tracer, "serve/dispatch", 101.0, 101.005)
    read = loader.reader("span_ms_batch")
    assert read(_ctx(batches=0), span="serve/dispatch") is None
    assert read({}, span="serve/dispatch") is None        # a training cell
    tracing.install(None)
    assert read(_ctx(), span="serve/dispatch") is None


BY_OP = {
    "GoogLeNetEmbedding/lrn/fusion.2": 0.20,
    "GoogLeNetEmbedding/lrn/reduce_window_sum.37": 0.10,
    "GoogLeNetEmbedding/conv2/Conv_0/fusion.759": 0.25,
    "GoogLeNetEmbedding/conv2_reduce/Conv_0/fusion.1": 0.03,
    "GoogLeNetEmbedding/conv1/Conv_0/copy_add_fusion": 0.10,
    "GoogLeNetEmbedding/inception_3a/conv1/Conv_0/fusion.9": 0.07,
    "GoogLeNetEmbedding/inception_4e/b3x3/Conv_0/fusion.4": 0.05,
    "GoogLeNetEmbedding/pool2/select_and_scatter.129": 0.14,
    "GoogLeNetEmbedding/pool4/reduce_window_max.3": 0.01,
    "GoogLeNetEmbedding/inception_3a/pool/reduce_window_max.7": 0.02,
    "GoogLeNetEmbedding/select_and_scatter.128": 0.30,   # in no region
    "npair/sim/dot_general": 0.01,
    "copy.5": 0.04,
}
ROOT = "GoogLeNetEmbedding/"


@pytest.mark.parametrize("prefixes,want_s", [
    ([ROOT + "lrn/"], 0.30),
    ([ROOT + "conv2/"], 0.25),                       # not conv2_reduce/
    ([ROOT + "conv1/", ROOT + "conv2_reduce/", ROOT + "conv2/"], 0.38),  # not 3a/conv1
    ([ROOT + "inception_"], 0.14),
    ([ROOT + f"pool{i}/" for i in (1, 2, 3, 4)], 0.15),   # not a block's pool branch
    ([ROOT + "absent/"], None),
])
def test_region_ms_per_step(prefixes, want_s):
    ctx = {"trace": {"by_op": BY_OP}, "traced": {"window": {"steps": 10}}}
    got = loader.reader("region_ms")(ctx, prefixes=prefixes, per="step")
    assert got == (pytest.approx(1e3 * want_s / 10) if want_s else None)


def test_region_ms_per_batch_and_nothing_without_a_trace():
    ctx = {"trace": {"by_op": {"serve/encode/GoogLeNetEmbedding/lrn/reduce-window.2": 0.1}},
           "traced": {"window": {"steps": 7}, "batches": 50}}
    read = loader.reader("region_ms")
    assert read(ctx, prefixes=["serve/encode/GoogLeNetEmbedding/lrn/"],
                per="batch") == pytest.approx(2.0)
    assert read(dict(ctx, trace=None), prefixes=["serve/"], per="batch") is None
    assert read(dict(ctx, traced={"window": {"steps": 0}, "batches": 0}),
                prefixes=["serve/"], per="batch") is None


def test_region_of_keeps_a_backward_operation_in_its_forward_region():
    from benchmarks.harness import trace_reduce

    scope = "jit(step)/jit(main)/transpose(jvp(GoogLeNetEmbedding))/pool2/select_and_scatter_add"
    assert trace_reduce.region_of(scope) == "GoogLeNetEmbedding/pool2"


@pytest.mark.parametrize("name", ["googlenet_serve_ivf_rate", "googlenet_serve_flat_sat"])
def test_the_six_serving_metrics_read_a_window_with_a_query_tracer(name):
    """A window at rehearsal size, in process, tracer installed and no
    profiler: every serving metric reads a positive number, a dispatch
    holds its children, and the dispatcher thread's four spans cover its
    window."""
    from benchmarks.harness import serve_window

    cell = toy_cell(name)
    mix = cell.traffic
    prev = tracing.install(None)
    try:
        server, ctx = serve_window.build_server(cell, 2**31 + 7, trace=True)
        tracer = tracing.current()
        assert tracer is ctx["qtracer"].tracer  # the QueryTracer installed its own
        loop = serve_window.open_window if mix["loop"] == "open" \
            else serve_window.closed_window
        ledger, win = loop(server, ctx, mix, 2**31 + 7, 1.0)
        batches = server.replicaset.batches
        server.replicaset.close(drain=True)
        assert batches > 0 and all("neighbors" in a for a in ledger.answer)
        ctx_m = {"serve": {"window": win, "batches": batches}}
        got = {}
        for m in SERVE_METRICS:
            spec = loader.metric_spec(m)
            got[m] = loader.reader(spec["reader"])(ctx_m, **spec["args"])
        assert all(v is not None and v > 0 for v in got.values()), got
        # device waits lie inside encode and topk; those and the merge
        # inside the dispatch, whose rest is its own host work
        assert got["device_wait_ms_batch"] <= \
            got["encode_host_ms_batch"] + got["topk_host_ms_batch"]
        assert got["encode_host_ms_batch"] + got["topk_host_ms_batch"] \
            + got["assemble_ms_batch"] <= got["dispatch_host_ms_batch"]
        # every rider's tree names the dispatch it rode
        assert all(q.batch is not None for q in ledger.qt)
        # idle + batch + dispatch + reply: the dispatcher thread, whole
        lo, hi = tracer.to_us(win["t0"]), tracer.to_us(win["t1"])
        covered = sum(min(e["ts"] + e["dur"], hi) - max(e["ts"], lo)
                      for e in tracer.events_since(0)[0]
                      if e["name"] in ("serve/idle", "serve/batch",
                                       "serve/dispatch", "serve/reply")
                      and e["ts"] < hi and e["ts"] + e["dur"] > lo)
        assert 0.9 <= covered / (hi - lo) <= 1.0 + 1e-9
    finally:
        gc.unfreeze()
        tracing.install(prev)
