"""Parity tests: Pallas blockwise kernels vs the dense path.

The blockwise path (ops.pallas_npair) must reproduce the dense
``npair_loss_with_aux`` loss, gradient, counts and metrics exactly (up to
fp32 reduction-order noise) for every absolute mining configuration,
including pool sizes that do not divide the block size (padding path).
Kernels run in Pallas interpreter mode on the CPU test backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_identity_batch
from npairloss_tpu.ops.npair_loss import (
    MiningMethod,
    MiningRegion,
    NPairLossConfig,
    REFERENCE_CONFIG,
    npair_loss_with_aux,
)
from npairloss_tpu.ops.metrics import retrieval_metrics
from npairloss_tpu.ops.pallas_npair import (
    blockwise_npair_loss_with_aux,
    blockwise_retrieval_metrics,
    blockwise_supported,
)

ABS_CONFIGS = [
    NPairLossConfig(),  # proto defaults: LOCAL/RAND both sides
    NPairLossConfig(
        ap_mining_method=MiningMethod.HARD,
        an_mining_method=MiningMethod.HARD,
        margin_ident=0.1,
        margin_diff=-0.05,
    ),
    NPairLossConfig(
        ap_mining_method=MiningMethod.EASY,
        an_mining_method=MiningMethod.EASY,
        margin_ident=-0.02,
    ),
    NPairLossConfig(
        ap_mining_region=MiningRegion.GLOBAL,
        ap_mining_method=MiningMethod.HARD,
        an_mining_region=MiningRegion.GLOBAL,
        an_mining_method=MiningMethod.EASY,
        margin_diff=0.03,
    ),
    NPairLossConfig(
        ap_mining_method=MiningMethod.EASY,
        an_mining_method=MiningMethod.HARD,
        grad_mode="true",
    ),
]


@pytest.mark.parametrize("cfg", ABS_CONFIGS)
@pytest.mark.parametrize("block", [4, 5, 64])
def test_blockwise_matches_dense(rng, cfg, block):
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=2, dim=16)
    loss_d, aux_d = npair_loss_with_aux(jnp.asarray(f), jnp.asarray(l), cfg)
    loss_b, aux_b = blockwise_npair_loss_with_aux(
        jnp.asarray(f), jnp.asarray(l), cfg, block_size=block
    )
    np.testing.assert_allclose(loss_b, loss_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux_b["ident_num"], aux_d["ident_num"])
    np.testing.assert_allclose(aux_b["diff_num"], aux_d["diff_num"])
    np.testing.assert_allclose(
        aux_b["pos_threshold"], aux_d["pos_threshold"], rtol=1e-6
    )
    np.testing.assert_allclose(
        aux_b["neg_threshold"], aux_d["neg_threshold"], rtol=1e-6
    )


@pytest.mark.parametrize("cfg", ABS_CONFIGS)
def test_blockwise_grad_matches_dense(rng, cfg):
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=2, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)

    gd = jax.grad(lambda x: npair_loss_with_aux(x, l, cfg)[0])(f)
    gb = jax.grad(
        lambda x: blockwise_npair_loss_with_aux(x, l, cfg, block_size=5)[0]
    )(f)
    np.testing.assert_allclose(gb, gd, rtol=1e-5, atol=1e-7)


REL_CONFIGS = [
    # The shipped def.prototxt mining config — the flagship workload
    # (GLOBAL/RELATIVE_HARD AP): previously dense-only on one chip, now
    # streamed via radix selection so the 32k stretch runs blockwise.
    REFERENCE_CONFIG,
    # LOCAL relative on both sides, fraction-valued sn.
    NPairLossConfig(
        ap_mining_method=MiningMethod.RELATIVE_EASY, identsn=-0.5,
        an_mining_method=MiningMethod.RELATIVE_HARD, diffsn=-0.3,
    ),
    # Positive sn = absolute rank from the sorted top (cu:285-287).
    NPairLossConfig(
        ap_mining_method=MiningMethod.RELATIVE_HARD, identsn=1.0,
        an_mining_method=MiningMethod.RELATIVE_EASY, diffsn=2.0,
        margin_diff=0.02,
    ),
    # GLOBAL relative on the AN side (block-wide rank, cu:327-334).
    NPairLossConfig(
        an_mining_region=MiningRegion.GLOBAL,
        an_mining_method=MiningMethod.RELATIVE_HARD, diffsn=-0.25,
    ),
]


# Every config runs at block 5 (a non-divisor of N=18 — exercises the
# padding path); the exact-tiling shape (block 6 divides N=18 — no
# padded rows anywhere) is pinned once rather than per-config:
# interpret-mode Pallas executes each grid cell in Python, so the full
# cfg x block product costs minutes for no added coverage (the block
# size only affects tiling, not mining semantics).
@pytest.mark.parametrize(
    "cfg_idx,block",
    [(i, 5) for i in range(len(REL_CONFIGS))] + [(0, 6)],
)
def test_blockwise_relative_matches_dense(rng, cfg_idx, block):  # slow-ok: the blockwise-vs-dense mining-grid parity oracle — tier-1's core contract
    """RELATIVE_* thresholds via streamed radix selection must equal the
    dense path's host-sort semantics exactly — loss, aux and grads."""
    cfg = REL_CONFIGS[cfg_idx]
    assert blockwise_supported(cfg)
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=3, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)
    loss_d, aux_d = npair_loss_with_aux(f, l, cfg)
    loss_b, aux_b = blockwise_npair_loss_with_aux(f, l, cfg, block_size=block)
    np.testing.assert_allclose(loss_b, loss_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux_b["ident_num"], aux_d["ident_num"])
    np.testing.assert_allclose(aux_b["diff_num"], aux_d["diff_num"])
    # Radix selection is bit-exact on the streamed population, but the
    # streamed sim tiles themselves can differ from the one big dense
    # matmul by 1 ULP (different XLA kernel shapes accumulate in a
    # different order) — hence rtol, not equality.
    np.testing.assert_allclose(
        aux_b["pos_threshold"], aux_d["pos_threshold"], rtol=1e-6
    )
    np.testing.assert_allclose(
        aux_b["neg_threshold"], aux_d["neg_threshold"], rtol=1e-6
    )
    gd = jax.grad(lambda x: npair_loss_with_aux(x, l, cfg)[0])(f)
    gb = jax.grad(
        lambda x: blockwise_npair_loss_with_aux(x, l, cfg, block_size=block)[0]
    )(f)
    np.testing.assert_allclose(gb, gd, rtol=1e-5, atol=1e-7)


def test_blockwise_sim_cache_bit_identical(rng):  # slow-ok: sim-cache bit-identity is the streaming engine's correctness bar
    """The similarity cache (ops.pallas_npair sim_cache) stores exactly
    the fp32 values the recompute path produces, so cached and uncached
    runs must agree BIT-FOR-BIT — loss, aux monitors and gradients — on
    the flagship relative config (which exercises stats, radix-digit,
    loss and both backward sweeps).  Auto mode enables the cache at test
    shapes, so this test is also what keeps the recompute path covered."""
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=3, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)

    outs = {}
    for cache in (True, False):
        def fn(x, cache=cache):
            return blockwise_npair_loss_with_aux(
                x, l, REFERENCE_CONFIG, block_size=5, sim_cache=cache
            )
        (loss, aux), grad = jax.value_and_grad(fn, has_aux=True)(f)
        outs[cache] = (np.asarray(loss), aux, np.asarray(grad))

    loss_on, aux_on, grad_on = outs[True]
    loss_off, aux_off, grad_off = outs[False]
    assert loss_on == loss_off
    assert np.array_equal(grad_on, grad_off)
    for k in aux_on:
        assert np.array_equal(
            np.asarray(aux_on[k]), np.asarray(aux_off[k])
        ), k


@pytest.mark.parametrize("bn,bm", [(4, 7), (7, 4)])
def test_blockwise_sim_cache_asymmetric_tiles(rng, bn, bm):  # slow-ok: ragged-tile cache parity guards the production block shapes
    """Cached sweeps with q_block != block exercise the _simblock index
    maps on a non-square tile grid (incl. padding on both axes); must
    still match the dense path on the flagship config."""
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=3, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)

    def fn(x):
        return blockwise_npair_loss_with_aux(
            x, l, REFERENCE_CONFIG, block_size=bm, q_block_size=bn,
            sim_cache=True,
        )[0]

    loss_d, _ = npair_loss_with_aux(f, l, REFERENCE_CONFIG)
    gd = jax.grad(lambda x: npair_loss_with_aux(x, l, REFERENCE_CONFIG)[0])(f)
    np.testing.assert_allclose(fn(f), loss_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jax.grad(fn)(f), gd, rtol=1e-5, atol=1e-7)


def test_blockwise_global_relative_int32_overflow_guard():
    """GLOBAL RELATIVE rank targets sum pair counts over the whole block:
    beyond 2^31 pairs int32 wraps and would silently mis-rank (caught in
    review) — without x64 the trace must fail loudly instead."""
    cfg = NPairLossConfig(
        an_mining_region=MiningRegion.GLOBAL,
        an_mining_method=MiningMethod.RELATIVE_HARD,
        diffsn=-0.3,
    )
    n = 50_000  # n*n > 2^31 - 1
    f = jax.ShapeDtypeStruct((n, 8), jnp.float32)
    l = jax.ShapeDtypeStruct((n,), jnp.int32)
    with pytest.raises(NotImplementedError, match="2\\^31|64-bit"):
        jax.eval_shape(
            lambda f_, l_: blockwise_npair_loss_with_aux(
                f_, l_, cfg, block_size=512
            )[0],
            f, l,
        )
    # Under the bound the same config traces fine.
    small = jax.ShapeDtypeStruct((64, 8), jnp.float32)
    small_l = jax.ShapeDtypeStruct((64,), jnp.int32)
    jax.eval_shape(
        lambda f_, l_: blockwise_npair_loss_with_aux(
            f_, l_, cfg, block_size=32
        )[0],
        small, small_l,
    )


def test_blockwise_relative_clamp_quirk(rng):  # slow-ok: pins the reference's -FLT_MAX clamp quirk bit-exactly
    """A negative-valued relative threshold clamps to -FLT_MAX (cu:288
    etc.); all-negative features force the quirk on the blockwise path."""
    cfg = NPairLossConfig(
        ap_mining_method=MiningMethod.RELATIVE_HARD, identsn=-0.9,
        an_mining_method=MiningMethod.RELATIVE_HARD, diffsn=-0.9,
    )
    (f,), (l,) = make_identity_batch(rng, num_ids=5, imgs_per_id=2, dim=8)
    f = -np.abs(f)
    f, l = jnp.asarray(f), jnp.asarray(l)
    loss_d, aux_d = npair_loss_with_aux(f, l, cfg)
    loss_b, aux_b = blockwise_npair_loss_with_aux(f, l, cfg, block_size=4)
    np.testing.assert_allclose(loss_b, loss_d, rtol=1e-6)
    # The clamp replaces the looked-up value with -FLT_MAX exactly.
    np.testing.assert_allclose(
        aux_b["pos_threshold"], aux_d["pos_threshold"], rtol=1e-6
    )


@pytest.mark.slow  # ~115s over 4 params; tier-1 budget, run with -m slow
@pytest.mark.parametrize("region", [MiningRegion.LOCAL, MiningRegion.GLOBAL])
@pytest.mark.parametrize("imgs_per_id", [9, 11])
def test_blockwise_pos_topk_fallback_boundary(rng, region, imgs_per_id):
    """The sparse-positive fast path guards on cnt_s <= K: a group of 9
    (cnt_s = 8) fits the 8-slot buffer exactly, a group of 11 overflows
    and the lax.cond must fall back to radix selection — parity with the
    dense path must hold on BOTH sides of the boundary."""
    cfg = NPairLossConfig(
        ap_mining_region=region,
        ap_mining_method=MiningMethod.RELATIVE_HARD, identsn=-0.3,
        an_mining_method=MiningMethod.HARD, margin_diff=-0.05,
    )
    (f,), (l,) = make_identity_batch(
        rng, num_ids=3, imgs_per_id=imgs_per_id, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)
    loss_d, aux_d = npair_loss_with_aux(f, l, cfg)
    loss_b, aux_b = blockwise_npair_loss_with_aux(
        f, l, cfg, block_size=5, pos_topk=8)
    np.testing.assert_allclose(loss_b, loss_d, rtol=1e-5, atol=1e-6)
    # rtol covers the tile-vs-dense matmul's few-ULP reduction noise
    # (see test_blockwise_relative_matches_dense); the selection itself
    # is exact on the streamed sims.
    np.testing.assert_allclose(
        aux_b["pos_threshold"], aux_d["pos_threshold"], rtol=1e-5)
    np.testing.assert_allclose(aux_b["ident_num"], aux_d["ident_num"])
    gd = jax.grad(lambda x: npair_loss_with_aux(x, l, cfg)[0])(f)
    gb = jax.grad(lambda x: blockwise_npair_loss_with_aux(
        x, l, cfg, block_size=5, pos_topk=8)[0])(f)
    np.testing.assert_allclose(gb, gd, rtol=1e-5, atol=1e-7)


def test_blockwise_pos_topk_disabled_matches(rng):
    """pos_topk=0 forces the pure radix path (no K-slot buffer in the
    stats sweep) — it must stay exact, it is the fallback's substrate."""
    cfg = REFERENCE_CONFIG
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=2, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)
    loss_d, aux_d = npair_loss_with_aux(f, l, cfg)
    loss_b, aux_b = blockwise_npair_loss_with_aux(
        f, l, cfg, block_size=5, pos_topk=0)
    np.testing.assert_allclose(loss_b, loss_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        aux_b["pos_threshold"], aux_d["pos_threshold"], rtol=1e-6)


@pytest.mark.slow  # ~28s; tier-1 budget, run with -m slow
def test_blockwise_pos_topk_with_sim_cache(rng):
    """Fast path + fp32 sim cache together (the 32k stretch shape):
    cached and uncached must agree bit-for-bit, and both must match the
    dense oracle."""
    cfg = REFERENCE_CONFIG
    (f,), (l,) = make_identity_batch(rng, num_ids=8, imgs_per_id=2, dim=12)
    f, l = jnp.asarray(f), jnp.asarray(l)
    loss_d, _ = npair_loss_with_aux(f, l, cfg)
    loss_c, aux_c = blockwise_npair_loss_with_aux(
        f, l, cfg, block_size=4, sim_cache=True)
    loss_n, aux_n = blockwise_npair_loss_with_aux(
        f, l, cfg, block_size=4, sim_cache=False)
    assert float(loss_c) == float(loss_n)
    np.testing.assert_array_equal(
        aux_c["pos_threshold"], aux_n["pos_threshold"])
    np.testing.assert_allclose(loss_c, loss_d, rtol=1e-5, atol=1e-6)
    gc = jax.grad(lambda x: blockwise_npair_loss_with_aux(
        x, l, cfg, block_size=4, sim_cache=True)[0])(f)
    gn = jax.grad(lambda x: blockwise_npair_loss_with_aux(
        x, l, cfg, block_size=4, sim_cache=False)[0])(f)
    np.testing.assert_array_equal(gc, gn)


def test_blockwise_zero_count_queries(rng):
    """Unique labels -> no positives anywhere -> loss must be exactly 0
    (the reference's zero-count guard, cu:133-154, cu:162-169)."""
    f = rng.standard_normal((8, 16)).astype(np.float32)
    l = np.arange(8, dtype=np.int32)
    loss, aux = blockwise_npair_loss_with_aux(
        jnp.asarray(f), jnp.asarray(l), NPairLossConfig(), block_size=4
    )
    assert float(loss) == 0.0
    np.testing.assert_array_equal(aux["ident_num"], np.zeros(8))
    # "reference" grad mode: p3 keeps diff-type entries alive for
    # identNum==0 queries (cu:133-146) — the gradient is NONZERO and must
    # match the dense path exactly.
    g_block = jax.grad(
        lambda x: blockwise_npair_loss_with_aux(
            x, jnp.asarray(l), NPairLossConfig(), block_size=4
        )[0]
    )(jnp.asarray(f))
    g_dense = jax.grad(
        lambda x: npair_loss_with_aux(x, jnp.asarray(l), NPairLossConfig())[0]
    )(jnp.asarray(f))
    np.testing.assert_allclose(g_block, g_dense, rtol=1e-5, atol=1e-7)
    # "true" grad mode: autodiff of the guarded log gives exactly 0 for
    # zero-loss queries.
    cfg_true = NPairLossConfig(grad_mode="true")
    g_true = jax.grad(
        lambda x: blockwise_npair_loss_with_aux(
            x, jnp.asarray(l), cfg_true, block_size=4
        )[0]
    )(jnp.asarray(f))
    np.testing.assert_array_equal(np.asarray(g_true), np.zeros_like(f))


def test_blockwise_float_labels_match_dense(rng):
    """Float labels are legal (Caffe labels are Dtype); distinct float
    values like 0.2 vs 0.7 must stay distinct identities — an int cast
    would merge them (caught by review)."""
    f = jnp.asarray(rng.standard_normal((6, 8)).astype(np.float32))
    l = jnp.asarray(np.array([0.2, 0.2, 0.7, 0.7, 1.2, 1.2], np.float32))
    cfg = NPairLossConfig()
    loss_d, _ = npair_loss_with_aux(f, l, cfg)
    loss_b, _ = blockwise_npair_loss_with_aux(f, l, cfg, block_size=4)
    np.testing.assert_allclose(loss_b, loss_d, rtol=1e-6)
    gd = jax.grad(lambda x: npair_loss_with_aux(x, l, cfg)[0])(f)
    gb = jax.grad(
        lambda x: blockwise_npair_loss_with_aux(x, l, cfg, block_size=4)[0]
    )(f)
    np.testing.assert_allclose(gb, gd, rtol=1e-5, atol=1e-7)
    m = blockwise_retrieval_metrics(f, l, (1,), block_size=4)
    _, aux = npair_loss_with_aux(f, l, cfg)
    dense_m = retrieval_metrics(aux, l, f, (1,))
    np.testing.assert_allclose(m["retrieve_top1"], dense_m["retrieve_top1"])


def test_blockwise_batch_of_one_grad_finite(rng):
    """Batch of 1: only the (excluded) self pair exists, so max_all is
    -FLT_MAX and sim_exp overflows to +inf — the backward weight tile
    must mask where-based or inf * 0 poisons the gemms with NaN (the
    dense path's cu:152-154 hazard; caught live on this kernel)."""
    f = jnp.asarray(rng.standard_normal((1, 8)).astype(np.float32))
    l = jnp.asarray(np.array([3], np.int32))
    for cfg in (NPairLossConfig(), NPairLossConfig(grad_mode="true")):
        loss, _ = blockwise_npair_loss_with_aux(f, l, cfg, block_size=4)
        assert float(loss) == 0.0
        g = jax.grad(
            lambda x: blockwise_npair_loss_with_aux(x, l, cfg, block_size=4)[0]
        )(f)
        np.testing.assert_array_equal(np.asarray(g), np.zeros_like(g))


@pytest.mark.parametrize("block", [4, 7, 64])
def test_blockwise_metrics_match_dense(rng, block):
    (f,), (l,) = make_identity_batch(rng, num_ids=8, imgs_per_id=3, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)
    _, aux = npair_loss_with_aux(f, l, NPairLossConfig())
    dense = retrieval_metrics(aux, l, f, (1, 5, 10))
    streamed = blockwise_retrieval_metrics(f, l, (1, 5, 10), block_size=block)
    for k, v in dense.items():
        np.testing.assert_allclose(streamed[k], v, rtol=1e-6, err_msg=k)


def test_blockwise_under_jit(rng):
    (f,), (l,) = make_identity_batch(rng, num_ids=6, imgs_per_id=2, dim=16)
    f, l = jnp.asarray(f), jnp.asarray(l)
    cfg = NPairLossConfig(
        ap_mining_method=MiningMethod.HARD, an_mining_method=MiningMethod.HARD
    )

    @jax.jit
    def step(x):
        return jax.value_and_grad(
            lambda y: blockwise_npair_loss_with_aux(y, l, cfg, block_size=4)[0]
        )(x)

    loss, g = step(f)
    loss_d, g_d = jax.value_and_grad(
        lambda y: npair_loss_with_aux(y, l, cfg)[0]
    )(f)
    np.testing.assert_allclose(loss, loss_d, rtol=1e-5)
    np.testing.assert_allclose(g, g_d, rtol=1e-5, atol=1e-7)


# -- sim-cache auto gate -------------------------------------------------------


class _FakeDev:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "fake", stats

    def memory_stats(self):
        return self._stats


def test_sim_cache_auto_is_budgeted_and_logged(caplog):
    import logging

    from npairloss_tpu.ops.npair_loss import (
        SIM_CACHE_AUTO_BYTES,
        _SIM_CACHE_LOGGED,
        resolve_sim_cache_auto,
    )

    _SIM_CACHE_LOGGED.clear()
    with caplog.at_level(logging.INFO, logger="npairloss_tpu"):
        assert resolve_sim_cache_auto(1 << 20, "testengine") is True
    assert any("auto-enabling" in r.message for r in caplog.records)
    # Beyond any budget: never auto-enables.
    assert resolve_sim_cache_auto(SIM_CACHE_AUTO_BYTES + 1, "t2") is False
    # Logged once per (engine, size): a second identical call is silent.
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="npairloss_tpu"):
        resolve_sim_cache_auto(1 << 20, "testengine")
    assert not caplog.records


def test_sim_cache_auto_sizes_against_reported_memory(monkeypatch):
    """1/5 of the device's reported bytes_limit (16 GiB: rejects the
    32k pool's 4.0 GiB slice, admits the 24k pool's 2.25 GiB); the CPU
    gets its fixed reference budget; an ACCELERATOR that reports no
    memory is an error, not a 2 GiB guess."""
    from npairloss_tpu.ops.npair_loss import (
        SIM_CACHE_CPU_BYTES,
        resolve_sim_cache_auto,
    )

    def with_dev(platform, stats):
        monkeypatch.setattr(jax, "devices",
                            lambda: [_FakeDev(platform, stats)])

    gib = 1 << 30
    with_dev("tpu", {"bytes_limit": 16 * gib})
    assert resolve_sim_cache_auto(32768 * 32768 * 4, "t") is False
    assert resolve_sim_cache_auto(24576 * 24576 * 4, "t") is True
    with_dev("cpu", None)
    assert resolve_sim_cache_auto(SIM_CACHE_CPU_BYTES + 1, "t") is False
    assert resolve_sim_cache_auto(1 * gib, "t") is True
    for stats in (None, {}, {"bytes_limit": 0}):
        with_dev("tpu", stats)
        with pytest.raises(RuntimeError, match="no memory bytes_limit"):
            resolve_sim_cache_auto(1 * gib, "t")
