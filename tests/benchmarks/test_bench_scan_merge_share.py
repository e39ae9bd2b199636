"""``scan_merge_block_share``: the reader sums what the flat scan's own
spans carry, reads nothing of a program that carries none, and reads a
real engine's dispatches."""

import bench_path  # noqa: F401  (repo root on sys.path)

import numpy as np
import pytest

from benchmarks.harness import loader
from npairloss_tpu.obs import tracing

SPAN = "serve/topk/scan"
WINDOW = {"t0": 101.0, "t1": 103.0}


@pytest.fixture
def tracer():
    tr = tracing.SpanTracer(max_events=8, clock=lambda: 100.0)
    prev = tracing.install(tr)
    yield tr
    tracing.install(prev)


def _put(tr, name, start_s, end_s, **args):
    tr._append(tr.complete_event(name, tr.to_us(start_s), tr.to_us(end_s), **args))


def _read(ctx):
    spec = loader.metric_spec("scan_merge_block_share")
    assert spec["args"] == {"span": SPAN}
    return loader.reader(spec["reader"])(ctx, **spec["args"])


def test_the_share_is_merged_over_walked_in_the_window(tracer):
    _put(tracer, SPAN, 100.5, 100.5, scan_blocks=489, scan_blocks_merged=1)    # warm-up
    _put(tracer, SPAN, 101.2, 101.2, scan_blocks=489, scan_blocks_merged=40)
    _put(tracer, SPAN, 102.0, 102.0, scan_blocks=489, scan_blocks_merged=58)
    _put(tracer, SPAN, 103.4, 103.4, scan_blocks=489, scan_blocks_merged=489)  # the next window's
    _put(tracer, "serve/topk", 102.5, 102.6, scan_blocks=5, scan_blocks_merged=5)
    ctx = {"serve": {"window": WINDOW}}
    assert _read(ctx) == pytest.approx(98 / 978)
    assert _read({"serve": {}}) is None and _read({}) is None
    tracing.install(None)
    assert _read(ctx) is None


def test_nothing_and_never_zero_of_a_program_that_counts_no_blocks(tracer):
    ctx = {"serve": {"window": WINDOW}}
    assert _read(ctx) is None                                     # no span at all
    _put(tracer, "serve/topk", 101.1, 101.2, rows=1, bucket=1)    # the parent's spans
    _put(tracer, SPAN, 101.2, 101.2, rows=1)                      # no such arguments
    assert _read(ctx) is None
    for _ in range(8):
        _put(tracer, SPAN, 101.3, 101.3, scan_blocks=4, scan_blocks_merged=1)
    assert tracer.dropped == 2 and _read(ctx) is None


def test_it_reads_a_real_engines_dispatches():
    from npairloss_tpu.serve import EngineConfig, GalleryIndex, QueryEngine

    now = [100.0]
    tr = tracing.SpanTracer(clock=lambda: now[0])
    prev = tracing.install(tr)
    try:
        rng = np.random.default_rng(5)
        emb = rng.standard_normal((1024, 16)).astype(np.float32)
        eng = QueryEngine(GalleryIndex.build(emb, np.arange(1024)),
                          EngineConfig(top_k=10, buckets=(1,), gallery_block=16))
        eng.warmup()        # outside the window: a zero row merges one block
        now[0] = 102.0
        for i in range(3):
            eng.query(emb[i:i + 1])
        share = _read({"serve": {"window": WINDOW}})
    finally:
        tracing.install(prev)
    # 32 turns a scan; a single row in random order merges ~10 (ln 32 + 0.58) of them
    assert 3 / 96 < share < 0.8
