"""Token records on the serving path: ``RetrievalServer.submit`` ->
``MicroBatcher`` -> ``QueryEngine.encode`` with ``length_buckets`` and a
``token_budget``, on the tiny Olmo-Hybrid preset; and that a float-input
engine is what it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from npairloss_tpu.models import get_model
from npairloss_tpu.serve.batcher import BatcherConfig, MicroBatcher
from npairloss_tpu.serve.engine import (EngineConfig, QueryEngine,
                                        ServeCompileError)
from npairloss_tpu.serve.index import GalleryIndex
from npairloss_tpu.serve.server import RetrievalServer, ServerConfig

VOCAB = 512
CFG = dict(top_k=5, buckets=(1, 4), length_buckets=(16, 32, 64), token_budget=128)
# (rows bucket, length bucket) within 128 tokens: 1 x {16, 32, 64}, 4 x {16, 32}
WARM_PAIRS = 5


def _gallery(rows=256, dim=64):
    g = np.random.default_rng(0).standard_normal((rows, dim)).astype(np.float32)
    g /= np.linalg.norm(g, axis=1, keepdims=True)
    return GalleryIndex.build(g, np.arange(rows, dtype=np.int32), normalize=False)


@pytest.fixture(scope="module")
def strict_env():
    mp = pytest.MonkeyPatch()
    mp.setenv("NPAIRLOSS_SERVE_COMPILE_GUARD", "strict")
    yield
    mp.undo()


@pytest.fixture(scope="module")
def engine(strict_env):
    model = get_model("olmo_hybrid")
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = QueryEngine(_gallery(), EngineConfig(**CFG), model=model,
                      state={"params": params, "batch_stats": {}})
    eng.warmup()
    return eng


def _server(engine, start=True):
    srv = RetrievalServer(engine,
                          BatcherConfig(max_batch=4, max_delay_ms=40.0, max_queue=64),
                          ServerConfig(metrics_window=0))
    if start:
        srv.replicaset.start()
    return srv


@pytest.fixture
def server(engine):
    srv = _server(engine)
    yield srv
    srv.replicaset.close()


def _docs(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).astype(np.int32) for n in lengths]


def _encode_spans(tr):
    return [ev["args"] for ev in tr.events_since(0)[0]
            if ev["name"] == "serve/encode" and ev["ph"] == "X"]


def test_warmup_compiles_every_pair_within_the_budget(engine):
    stats = engine.compile_stats()
    assert stats["compiles_total"] == len(CFG["buckets"]) + WARM_PAIRS
    assert stats["compiles_after_warmup"] == 0
    # warm-up's dummies are whole rows: nothing of them is padding
    assert stats["tokens_encoded"] == stats["tokens_padded"] > 0


def test_token_records_are_answered_in_the_smallest_bucket_that_holds_them(
        engine, server, tracer):
    lengths = (5, 16, 17, 40, 64, 3, 33, 64)
    before = engine.compile_stats()
    futs = [server.submit({"id": i, "input": d.tolist()})[0]
            for i, d in enumerate(_docs(lengths))]
    answers = [f.result(timeout=60) for f in futs]
    assert [a["id"] for a in answers] == list(range(len(lengths)))
    assert all(len(a["neighbors"]) == CFG["top_k"] for a in answers)
    spans = _encode_spans(tracer)
    assert sum(s["rows"] for s in spans) == len(lengths)
    assert sum(s["tokens"] for s in spans) == sum(lengths)
    for s in spans:
        assert s["length_bucket"] in CFG["length_buckets"]
        assert s["padded_tokens"] == s["bucket"] * s["length_bucket"] <= CFG["token_budget"]
        assert s["tokens"] <= s["padded_tokens"]
    after = engine.compile_stats()
    # the strict guard is on: a compile here would have failed the batch
    assert after["compiles_after_warmup"] == before["compiles_after_warmup"]
    assert after["tokens_encoded"] - before["tokens_encoded"] == sum(lengths)
    assert after["tokens_padded"] - before["tokens_padded"] == \
        sum(s["padded_tokens"] for s in spans)
    # shown where compiles_after_warmup is shown
    for view in (server.summary(), server.healthz()):
        assert view["tokens_encoded"] == after["tokens_encoded"]
        assert view["tokens_padded"] == after["tokens_padded"]
        assert view["compiles_after_warmup"] == after["compiles_after_warmup"]


def test_a_row_alone_takes_its_own_length_bucket(engine, tracer):
    for n, want in ((1, 16), (16, 16), (17, 32), (32, 32), (33, 64), (64, 64)):
        engine.encode(_docs((n,)))
        assert _encode_spans(tracer)[-1]["length_bucket"] == want
    with pytest.raises(ValueError, match="exceeds the largest length bucket"):
        engine.encode(_docs((65,)))


def test_a_dispatch_outside_the_warmed_pairs_is_the_strict_guards_error(engine):
    # 4 rows x 64 = 256 tokens: past the budget, so never warmed
    with pytest.raises(ServeCompileError):
        engine.encode(_docs((64, 64, 10)))


@pytest.mark.parametrize("bad,why", [
    ([1.5, 2.0], "integer ids"),
    ([[1, 2], [3, 4]], "1-D"),
    ([], "takes 1 to 64"),
    (list(range(65)), "takes 1 to 64"),
    ([VOCAB], "outside the vocabulary"),
    ([3, -1], "outside the vocabulary"),
    ("tokens", "integer ids"),
])
def test_a_malformed_record_fails_alone(server, bad, why):
    good = _docs((9, 12))
    futs = [server.submit({"id": "a", "input": good[0].tolist()})[0],
            server.submit({"id": "bad", "input": bad})[0],
            server.submit({"id": "b", "input": good[1].tolist()})[0]]
    a, wrong, b = (f.result(timeout=60) for f in futs)
    assert why in wrong["error"] and "neighbors" not in wrong
    assert len(a["neighbors"]) == len(b["neighbors"]) == CFG["top_k"]


def _rec(n):
    return {"input": list(range(n))}


@pytest.mark.parametrize("lengths,rides,why", [
    ((10, 12), 1, "4 rows x 16 = 64 padded tokens for 32 alone"),
    ((10, 12, 3), 1, "64 for 48"),
    ((10, 12, 3, 16), 4, "64 for 64: the bucket is full"),
    ((10, 12, 3, 16, 9), 4, "the engine's last rows bucket is 4"),
    ((10, 12, 3, 30), 1, "4 x 32 = 128 for 80"),
    ((30, 17, 32, 20), 4, "128 for 128, and within the budget"),
    ((40, 40, 40, 40), 1, "256 for 256, but the budget is 128"),
    ((10, 33), 1, "4 x 64 = 256: past the budget and past 80"),
    ((64,), 1, "the head of a turn always goes"),
])
def test_the_budget_is_counted_as_the_dispatch_would_run(server, lengths, rides, why):
    """The hook counts a dispatch as it would RUN (rows bucket x length
    bucket) and admits the longest prefix that stays within the budget
    and within what its rows would run padded alone."""
    assert server.batcher._fits == server._token_coriders
    assert server._token_coriders([_rec(n) for n in lengths]) == rides, why


def test_what_parse_will_refuse_counts_one_token(server):
    # it rides along to fail alone, whatever it rides with
    bad = [{"input": None}, {"id": 1}, _rec(65)]
    assert server._token_coriders([_rec(10)] + bad) == 4
    assert server._token_coriders([_rec(40)] + bad) == 1


def _alone(engine, lengths):
    return sum(engine.padded_tokens([n]) for n in lengths)


def test_backlog_of_mixed_lengths_never_leaves_the_budget(engine, tracer):
    """Thirty documents queued before the dispatcher starts, long beside
    short: every dispatch stays within the budget (a pair outside it was
    never warmed and the strict guard would fail the batch), none runs
    more padded tokens than its rows would alone, every answer comes, in
    order; and the four of one length bucket that meet ride together."""
    lengths = [64, 5, 40, 7, 9, 33, 64, 12, 3, 16] * 3
    lengths[11:15] = [20, 17, 32, 25]   # four of the 32 bucket, adjacent
    compiles = engine.compiles_after_warmup
    srv = _server(engine, start=False)
    futs = [srv.submit({"id": i, "input": d.tolist()})[0]
            for i, d in enumerate(_docs(lengths, seed=2))]
    srv.replicaset.start()
    try:
        answers = [f.result(timeout=120) for f in futs]
    finally:
        srv.replicaset.close()
    assert [a["id"] for a in answers] == list(range(len(lengths)))
    assert all("error" not in a for a in answers)
    spans = _encode_spans(tracer)
    assert sum(s["rows"] for s in spans) == len(lengths)
    at = 0
    for s in spans:   # order of service is submission's
        rows = lengths[at:at + s["rows"]]
        at += s["rows"]
        assert s["tokens"] == sum(rows)
        assert s["padded_tokens"] <= min(CFG["token_budget"], _alone(engine, rows))
    assert (4, 32) in [(s["rows"], s["length_bucket"]) for s in spans]
    assert engine.compiles_after_warmup == compiles


@pytest.mark.parametrize("bucket,lengths", [(16, (5, 16, 9, 1)), (32, (17, 32, 20, 30))])
def test_four_documents_of_one_length_bucket_ride_in_one_dispatch(
        engine, tracer, bucket, lengths):
    srv = _server(engine, start=False)
    futs = [srv.submit({"id": i, "input": d.tolist()})[0]
            for i, d in enumerate(_docs(lengths, seed=3))]
    srv.replicaset.start()
    try:
        assert [f.result(timeout=60)["id"] for f in futs] == [0, 1, 2, 3]
    finally:
        srv.replicaset.close()
    (span,) = _encode_spans(tracer)
    assert (span["rows"], span["bucket"], span["length_bucket"]) == (4, 4, bucket)
    assert span["padded_tokens"] == 4 * bucket == _alone(engine, lengths)


def _only(value, rides):
    """A hook in the batcher's form: of a run of ``value`` at the front,
    ``rides`` of them go together or one goes alone (a pair may not fit
    a bucket that three fill)."""
    def fits(items):
        run = next((k for k, x in enumerate(items) if x != items[0]), len(items))
        return rides if items[0] == value and run >= rides else 1
    return fits


def test_fits_holds_coriders_back_and_keeps_order():
    """The batcher alone: ``fits`` judges the candidates together and
    admits a prefix; what it refuses is held back IN ORDER, heads the
    next turns and is judged again with what is queued behind it; the
    head of a turn always goes; order is submission's."""
    batches = []

    def dispatch(items):
        batches.append(list(items))
        return items

    b = MicroBatcher(dispatch, BatcherConfig(max_batch=4, max_delay_ms=500.0, max_queue=32),
                     fits=_only(4, rides=3))
    order = (7, 4, 4, 3, 4, 4, 4, 9, 4, 4)
    futs = [b.submit(n) for n in order]
    assert b.queue_depth == len(order)
    b.start()
    try:
        assert [f.result(timeout=10.0) for f in futs] == list(order)
    finally:
        b.close()
    # 7 alone, holding 4, 4, 3; two 4s do not make three, so each goes
    # alone; 3 alone; the three 4s together, holding 9; ...
    assert batches == [[7], [4], [4], [3], [4, 4, 4], [9], [4], [4]]
    assert b.dispatched == len(order) and b.batches == 8 and b.queue_depth == 0


@pytest.mark.parametrize("kwargs,why", [
    (dict(length_buckets=(32, 16)), "ascending"),
    (dict(length_buckets=(16, 16)), "ascending"),
    (dict(length_buckets=(0, 16)), "positive"),
    (dict(length_buckets=(16,), token_budget=-1), ">= 0"),
    (dict(buckets=(2, 4), length_buckets=(16, 64), token_budget=100), "cannot hold"),
])
def test_engine_config_refuses_a_wrong_token_setting(kwargs, why):
    with pytest.raises(ValueError, match=why):
        EngineConfig(**kwargs)


def test_a_token_budget_is_no_keyword_of_a_float_engine():
    with pytest.raises(TypeError, match="needs length_buckets"):
        EngineConfig(token_budget=64)


def test_a_float_input_engine_is_what_it_was():
    """``length_buckets`` empty: no token field shows anywhere, the
    batcher gets no ``fits``, float records are parsed as they were."""
    cfg = EngineConfig()
    assert cfg.length_buckets == () and cfg.token_budget == 0
    idx = _gallery(rows=32, dim=8)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1, 4)))
    engine.warmup()
    server = RetrievalServer(engine, BatcherConfig(max_batch=4, max_delay_ms=20.0),
                             ServerConfig(metrics_window=0))
    assert server.batcher._fits is None
    server.replicaset.start()
    try:
        row = np.asarray(idx.emb[3], np.float32)
        ans = server.submit({"id": 0, "embedding": row.tolist()})[0].result(timeout=30)
    finally:
        server.replicaset.close()
    assert ans["neighbors"][0]["row"] == 3
    for view in (engine.compile_stats(), server.summary(), server.healthz()):
        assert "tokens_encoded" not in view and "tokens_padded" not in view


def test_a_float_model_engine_encodes_as_it_did(tracer):
    """A float-input model behind the same engine: one program a rows
    bucket, float32 padding, and the ``serve/encode`` span carries no
    token argument."""
    model = get_model("mlp", hidden=(16,), embedding_dim=8)
    x = np.random.default_rng(0).standard_normal((3, 12)).astype(np.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)["params"]
    engine = QueryEngine(_gallery(rows=32, dim=8), EngineConfig(top_k=3, buckets=(1, 4)),
                         model=model, state={"params": params, "batch_stats": {}})
    engine.warmup((12,))
    assert engine.compile_stats()["compiles_total"] == 4  # 2 top-k + 2 encode
    emb = engine.encode(x)
    assert emb.shape == (3, 8)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-5)
    args = _encode_spans(tracer)[-1]
    assert set(args) == {"rows", "bucket"}


def test_a_closed_server_and_its_engine_go_by_reference_counting_alone():
    """A closed batcher drops its owner's callbacks (the cycle server ->
    batcher -> server's bound methods), so the engine's device memory is
    released when the last name goes, with no collector pass: a host that
    froze the collector (``gc.freeze`` before a timed window) gets the
    memory back all the same."""
    import gc
    import weakref

    idx = _gallery(rows=32, dim=8)
    engine = QueryEngine(idx, EngineConfig(top_k=3, buckets=(1,)))
    server = RetrievalServer(engine, BatcherConfig(max_batch=1, max_delay_ms=1.0),
                             ServerConfig(metrics_window=0))
    server.replicaset.start()
    row = np.asarray(idx.emb[5], np.float32).tolist()
    assert server.submit({"id": 0, "embedding": row})[0].result(timeout=30)["neighbors"]
    server.replicaset.close()
    refs = [weakref.ref(o) for o in (server, engine, idx)]
    gc.disable()
    try:
        del server, engine, idx
        assert [r() for r in refs] == [None, None, None]
    finally:
        gc.enable()
