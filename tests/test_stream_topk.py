"""serve/engine.py::_stream_topk — a turn's gallery blocks are merged only
when they can change the answer, and the answer stays the dense one bit
for bit.

Every case holds small integers, so a score is exact in float32 and in
bfloat16 alike and ties are everywhere: a skipped block that should have
been merged, or a tie broken the other way, shows as a wrong row.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from npairloss_tpu.serve import EngineConfig, GalleryIndex, QueryEngine
from npairloss_tpu.serve.engine import (
    _NEG_FILL, _SCAN_GROUP, _scored_matmul, _stream_topk)

D = 8


def _ints(rng, shape, lo, hi):
    return rng.integers(lo, hi + 1, shape).astype(np.float32)


def _duplicates(rng):
    # one row copied into every turn: exact ties across each skip boundary
    emb = _ints(rng, (64, D), -3, 3)
    emb[5::8] = emb[5]
    return dict(emb=emb, turn=8, k=10)


def _quantised(rng):
    # scores take five values
    emb = np.zeros((96, D), np.float32)
    emb[:, 0] = rng.integers(-2, 3, 96)
    q = np.zeros((3, D), np.float32)
    q[:, 0] = 1.0
    return dict(emb=emb, q=q, turn=16, k=10)


def _invalid_rows(rng):
    return dict(emb=_ints(rng, (80, D), -3, 3), turn=16, k=10,
                valid=rng.random(80) < 0.6)


def _clamped_last_block(rng):
    # 75 = 4 x 16 + 11: the last turn starts at 59 and masks rows 59..63
    return dict(emb=_ints(rng, (75, D), -3, 3), turn=16, k=10)


def _fewer_valid_than_k(rng):
    valid = np.zeros(48, bool)
    valid[[3, 20, 21, 40]] = True
    return dict(emb=_ints(rng, (48, D), -3, 3), turn=16, k=10, valid=valid)


def _zero_padding_rows(rng):
    # a bucket of 8 with 3 real rows, as _query_bucketed pads it
    q = np.zeros((8, D), np.float32)
    q[:3] = _ints(rng, (3, D), -2, 2)
    return dict(emb=_ints(rng, (64, D), -3, 3), q=q, turn=8, k=10)


def _k_larger_than_a_turn(rng):
    return dict(emb=_ints(rng, (40, D), -3, 3), turn=4, k=10)


def _one_turn(rng):
    return dict(emb=_ints(rng, (24, D), -3, 3), turn=4096, k=10)


CASES = {f.__name__[1:]: f for f in (
    _duplicates, _quantised, _invalid_rows, _clamped_last_block,
    _fewer_valid_than_k, _zero_padding_rows, _k_larger_than_a_turn, _one_turn)}


def _dense(q, emb, valid, k, scoring):
    sims = _scored_matmul(q, emb, scoring)
    sims = jnp.where(valid[None, :], sims, jnp.float32(_NEG_FILL))
    return jax.lax.top_k(sims, k)


def _both(case, scoring):
    emb = jnp.asarray(case["emb"])
    n = emb.shape[0]
    q = jnp.asarray(case.get("q", _ints(np.random.default_rng(1), (4, D), -2, 2)))
    valid = jnp.asarray(case.get("valid", np.ones(n, bool)))
    k, block = case["k"], case["turn"] // _SCAN_GROUP  # the rows a turn walks
    stream = jax.jit(functools.partial(_stream_topk, k=k, block=block, scoring=scoring))
    s, r, scan = stream(q, emb, None, valid)
    ds, dr = jax.jit(functools.partial(_dense, k=k, scoring=scoring))(q, emb, valid)
    return (np.asarray(s), np.asarray(r), np.asarray(scan),
            np.asarray(ds), np.asarray(dr), -(-n // min(case["turn"], n)))


@pytest.mark.parametrize("scoring", ["fp32", "bf16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_streamed_answer_is_the_dense_one_bit_for_bit(name, scoring):
    case = CASES[name](np.random.default_rng(7))
    s, r, scan, ds, dr, turns = _both(case, scoring)
    np.testing.assert_array_equal(s, ds)
    filled = ds > _NEG_FILL
    np.testing.assert_array_equal(r[filled], dr[filled])
    # a slot no valid row filled keeps the carry's row 0, as it always did
    assert not r[~filled].any()
    assert scan[0] == turns and 1 <= scan[1] <= turns
    if name == "fewer_valid_than_k":
        assert (~filled).sum() == 6 * s.shape[0]


@pytest.mark.parametrize("order,merged", [("ascending", 6), ("descending", 1),
                                          ("constant", 1)])
def test_the_counter_counts_the_turns_whose_merge_ran(order, merged):
    n, turn = 88, 16  # 6 turns, the last one clamped
    col = {"ascending": np.arange(n), "descending": np.arange(n)[::-1],
           "constant": np.full(n, 3)}[order]
    emb = np.zeros((n, D), np.float32)
    emb[:, 0] = col
    q = np.zeros((2, D), np.float32)
    q[0, 0] = 1.0  # the second row is a bucket's zero padding
    s, r, scan, ds, dr, turns = _both(dict(emb=emb, q=q, turn=turn, k=10), "fp32")
    assert turns == 6 and list(scan) == [6, merged]
    np.testing.assert_array_equal(s, ds)
    np.testing.assert_array_equal(r, dr)


def _scan_spans(tr):
    events, _next, _dropped = tr.events_since(0)
    return [ev["args"] for ev in events if ev["name"] == "serve/topk/scan"]


@pytest.mark.parametrize("shards", [1, 4])
def test_the_engine_answers_densely_and_its_span_carries_the_count(shards, tracer):
    from npairloss_tpu.parallel import data_parallel_mesh

    rng = np.random.default_rng(11)
    emb = _ints(rng, (512, D), -3, 3)
    emb[9::16] = emb[9]  # ties across blocks AND across shards
    mesh = data_parallel_mesh(jax.devices()[:4]) if shards == 4 else None
    idx = GalleryIndex.build(emb, np.arange(512), mesh=mesh, normalize=False)
    eng = QueryEngine(idx, EngineConfig(top_k=10, buckets=(8,),
                                        gallery_block=8 // _SCAN_GROUP))
    q = _ints(rng, (3, D), -2, 2)
    out = eng.query(q, normalize=False)
    ds, dr = _dense(jnp.asarray(q), jnp.asarray(emb), jnp.ones(512, bool), 10, "fp32")
    np.testing.assert_array_equal(out["scores"], np.asarray(ds))
    np.testing.assert_array_equal(out["rows"], np.asarray(dr))
    (args,) = _scan_spans(tracer)
    assert args["scan_blocks"] == 64  # 512 rows in turns of 8, on 1 or 4 shards
    assert 2 * shards <= args["scan_blocks_merged"] < 64


def test_an_ivf_engine_writes_no_scan_span(tracer):
    from npairloss_tpu.serve.ivf import IVFIndex

    rng = np.random.default_rng(3)
    emb = rng.standard_normal((64, D)).astype(np.float32)
    idx = IVFIndex.build_ivf(emb, np.arange(64), clusters=4, seed=0)
    eng = QueryEngine(idx, EngineConfig(top_k=5, buckets=(1,), probes=4))
    assert eng.query(emb[:1])["rows"][0, 0] == 0
    assert _scan_spans(tracer) == []
