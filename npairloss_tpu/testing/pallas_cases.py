"""Production-geometry cases for every public Pallas entry point.

One table, two readers:

* ``tests/test_pallas_tpu_lowering.py`` cross-lowers every case for the
  TPU on the CPU (``interpret=False``; shapes only, no data), so a
  CPU-only change cannot re-break Mosaic's block-shape rules;
* ``scripts/chip_kernel_check.py`` Mosaic-compiles and RUNS every case
  on the chip and checks it against the XLA reference the CPU suite
  already uses (dense engine, ``models.layers`` LRN / ``reduce_window``,
  the ``engine._ivf_probe_topk`` scan).

Each case is ``fn(*args)`` (the kernel path, compiled — never
interpreted) beside ``ref(*args)`` (plain XLA) over the same seeded
inputs.  Geometries are the ones the flagship trainer and the 1M×128
IVF tier dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Flagship trainer / stretch-pool geometry.
POOL, DIM, BLOCK = 4096, 512, 512
# The two LRN sites at the benchmark's shapes: the trainer's batch 480
# (batch on the lanes) and serving's single image (a pixel a row), in
# the policy's bf16; float32 (``fp32_parity``) once through each view.
LRN_CASES = (
    ((480, 56, 56, 64), "bfloat16"), ((480, 56, 56, 192), "bfloat16"),
    ((1, 56, 56, 64), "bfloat16"), ((1, 56, 56, 192), "bfloat16"),
    ((120, 56, 56, 64), "float32"), ((8, 56, 56, 192), "float32"),
)
# GoogLeNet stem activation at batch 120.
STEM_SHAPE = (120, 112, 112, 64)
# 1M x 128 gallery: 1,024 clusters, largest cluster 2,976 rows.
PROBE_B, PROBE_KC, PROBE_CAP, PROBE_D = 8, 1024, 2976, 128
PROBE_K, PROBE_PROBES, PROBE_SHARDS = 10, 32, 4


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """``specs`` are the argument ShapeDtypeStructs (all the lowering
    test needs); ``make_args(rng)`` draws matching host arrays;
    ``check(got, want)`` raises AssertionError on a parity miss and
    returns the worst error it saw."""

    name: str
    fn: Callable
    ref: Callable
    specs: Tuple[jax.ShapeDtypeStruct, ...]
    make_args: Callable[[np.random.Generator], Tuple[np.ndarray, ...]]
    check: Callable


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def rel_err(got, want) -> float:
    """max |got - want| over max |want| (1 when want is all zero)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = float(np.abs(want).max()) or 1.0
    return float(np.abs(got - want).max()) / scale


def _check_rel(tol: float):
    def check(got, want) -> float:
        worst = 0.0
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.shape == w.shape, (g.shape, w.shape)
            assert np.isfinite(np.asarray(g, np.float32)).all()
            worst = max(worst, rel_err(g, w))
        assert worst <= tol, f"relative error {worst:.3e} > {tol:.1e}"
        return worst
    return check


# -- blockwise loss engine -----------------------------------------------------

# Mining is a comparison against a threshold, and on the chip the two
# engines' fp32-HIGHEST similarity gemms (XLA's vs Mosaic's) round the
# last bits differently — so a pair whose similarity sits within
# rounding of its threshold can be selected by one engine and not the
# other.  That is a tie, not a kernel error; it moves the two gradient
# rows the pair touches by one pair's weight (~1e-4 of the gradient
# scale at pool 4096).  Rows touched by such a tie are excluded from
# the 1e-5 bar, counted, and held to a one-pair bound instead.
TIE_WINDOW = 1e-6
TIE_ROW_BOUND = 1e-2
TIE_ROWS_MAX = 0.25


def _check_grad(got, want) -> float:
    (g_loss, g_grad), (w_loss, w_grad, tied) = got, want
    g_grad, w_grad, tied = (np.asarray(a) for a in (g_grad, w_grad, tied))
    assert np.isfinite(g_grad).all() and np.isfinite(float(g_loss))
    err_loss = rel_err(g_loss, w_loss)
    assert err_loss <= 1e-5, f"loss relative error {err_loss:.3e}"
    share = float(tied.mean())
    assert share <= TIE_ROWS_MAX, f"{share:.1%} of rows sit on a tie"
    scale = float(np.abs(w_grad).max()) or 1.0
    err = np.abs(g_grad.astype(np.float64) - w_grad).max(axis=1) / scale
    clean = float(err[~tied].max())
    assert clean <= 1e-5, f"grad relative error {clean:.3e} > 1e-5"
    if tied.any():
        worst = float(err[tied].max())
        assert worst <= TIE_ROW_BOUND, f"tied-row error {worst:.3e}"
    return clean


def _blockwise_cases() -> List[KernelCase]:
    from npairloss_tpu.ops.npair_loss import (
        REFERENCE_CONFIG,
        MiningMethod,
        NPairLossConfig,
        npair_loss,
        npair_loss_with_aux,
    )
    from npairloss_tpu.ops.pallas_npair import blockwise_npair_loss

    abs_cfg = NPairLossConfig(margin_diff=-0.05,
                              an_mining_method=MiningMethod.HARD)
    variants = (
        ("abs", abs_cfg, {}),
        ("flagship", REFERENCE_CONFIG, {}),
        ("flagship_radix", REFERENCE_CONFIG, {"pos_topk": 0}),
        ("flagship_nocache", REFERENCE_CONFIG, {"sim_cache": False}),
    )
    specs = (_sds((POOL, DIM), jnp.float32), _sds((POOL,), jnp.int32))

    def make_args(rng):
        f = rng.standard_normal((POOL, DIM)).astype(np.float32)
        f /= np.linalg.norm(f, axis=1, keepdims=True)
        return f, np.repeat(np.arange(POOL // 2), 2).astype(np.int32)

    cases = []
    for name, cfg, kw in variants:
        def loss(x, labels, cfg=cfg, kw=kw):
            return blockwise_npair_loss(
                x, labels, cfg, block_size=BLOCK, interpret=False, **kw)

        def dense(x, labels, cfg=cfg):
            return npair_loss(x, labels, cfg)

        def dense_grad(x, labels, cfg=cfg):
            (val, aux), grad = jax.value_and_grad(
                lambda v: npair_loss_with_aux(v, labels, cfg),
                has_aux=True)(x)
            # Rows a threshold tie touches (see TIE_WINDOW): the pair
            # (q, b) moves query row q and database row b.
            eye = jnp.eye(POOL, dtype=bool)
            same = (labels[:, None] == labels[None, :]) & ~eye
            diff = labels[:, None] != labels[None, :]
            sim = aux["sim"]
            near_p = jnp.abs(sim - (aux["pos_threshold"]
                                    + cfg.margin_ident)[:, None])
            near_n = jnp.abs(sim - (aux["neg_threshold"]
                                    + cfg.margin_diff)[:, None])
            tie = jnp.zeros_like(eye)
            if cfg.ap_mining_method != MiningMethod.RAND:
                tie |= same & (near_p <= TIE_WINDOW)
            if cfg.an_mining_method != MiningMethod.RAND:
                tie |= diff & (near_n <= TIE_WINDOW)
            return val, grad, tie.any(axis=1) | tie.any(axis=0)

        cases.append(KernelCase(
            f"blockwise_{name}_fwd", loss, dense, specs, make_args,
            _check_rel(1e-5)))
        cases.append(KernelCase(
            f"blockwise_{name}_grad", jax.value_and_grad(loss),
            dense_grad, specs, make_args, _check_grad))
    return cases


# -- GoogLeNet stem ------------------------------------------------------------


def _check_lrn(tol: float):
    """Worst absolute and relative (to max(|want|, 1e-3)) error of the
    kernel against the ``reduce_window`` body run on the same device."""
    def check(got, want) -> dict:
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        assert got.shape == want.shape and np.isfinite(got).all()
        diff = np.abs(got - want)
        err = {"max_abs": float(diff.max()),
               "max_rel": float((diff / np.maximum(np.abs(want), 1e-3)).max())}
        assert err["max_rel"] <= tol, f"{err} > {tol:.1e}"
        return err
    return check


def _stem_cases() -> List[KernelCase]:
    from npairloss_tpu.models.layers import local_response_norm_xla
    from npairloss_tpu.ops import pallas_stem as ps

    def grad_of(lrn):
        return lambda x, w: jax.grad(lambda v: (
            lrn(v).astype(jnp.float32) * w.astype(jnp.float32)).sum())(x)

    kernel = lambda v: ps.fused_lrn(v, interpret=False)
    cases = []
    for shape, dtype in LRN_CASES:
        specs = (_sds(shape, dtype), _sds(shape, dtype))

        def make_args(rng, shape=shape, dtype=dtype):
            return tuple(rng.standard_normal(shape, dtype=np.float32)
                         .astype(jnp.dtype(dtype)) for _ in range(2))

        # one ulp of the rounded result; float32: the same identity in
        # both bodies, Mosaic's and XLA's sqrt / rsqrt expansions apart
        tol = 2 ** -7 if dtype == "bfloat16" else 1e-5
        tag = f"n{shape[0]}_c{shape[-1]}_{dtype}"
        cases.append(KernelCase(
            f"lrn_fwd_{tag}", lambda x, w: kernel(x),
            lambda x, w: local_response_norm_xla(x),
            specs, make_args, _check_lrn(tol)))
        cases.append(KernelCase(
            f"lrn_grad_{tag}", grad_of(kernel),
            grad_of(local_response_norm_xla),
            specs, make_args, _check_lrn(2 * tol)))

    specs = (_sds(STEM_SHAPE, jnp.float32), _sds(STEM_SHAPE[-1:],
                                                 jnp.float32))

    def make_stem(rng):
        return (rng.standard_normal(STEM_SHAPE).astype(np.float32),
                rng.standard_normal(STEM_SHAPE[-1:]).astype(np.float32))

    cases.append(KernelCase(
        "bias_relu",
        lambda x, b: ps.fused_bias_relu(x, b, interpret=False),
        lambda x, b: jnp.maximum(x + b, 0.0),
        specs, make_stem, _check_rel(1e-6)))
    cases.append(KernelCase(
        "bias_relu_pool",
        lambda x, b: ps.fused_bias_relu_pool(x, b, interpret=False),
        lambda x, b: ps._reference_bias_relu_pool(x, b, 3, 2),
        specs, make_stem, _check_rel(1e-6)))
    return cases


# -- IVF probe -----------------------------------------------------------------


def _check_probe(got, want) -> float:
    """Scores within 1e-6 of the score scale; identical recall@{1,10}
    against the scan's answer (rows may differ only where scores tie)."""
    (gs, gr), (ws, wr) = got, want
    gs, gr, ws, wr = (np.asarray(a) for a in (gs, gr, ws, wr))
    assert gs.shape == ws.shape and gr.shape == wr.shape
    live = ws > -1e30
    scale = max(1.0, float(np.abs(ws[live]).max()))
    err = float(np.abs(gs - ws)[live].max()) / scale
    assert err <= 1e-6, f"probe score error {err:.3e} of scale > 1e-6"
    assert (gs > -1e30).sum() == live.sum()
    for k in (1, 10):
        hit = np.array([len(set(a[:k]) & set(b[:k]))
                        for a, b in zip(gr, wr)])
        ties = np.isclose(gs[:, :k], ws[:, :k], atol=1e-6 * scale).all(1)
        assert ((hit == min(k, gr.shape[1])) | ties).all(), f"recall@{k}"
    return err


def _probe_cases() -> List[KernelCase]:
    from npairloss_tpu.ops.pallas_ivf import fused_probe_topk
    from npairloss_tpu.serve.engine import _ivf_probe_topk

    dtypes = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
    cases = []
    variants = [(s, PROBE_KC) for s in dtypes]
    # The shard_map-local form: this shard owns a quarter of the
    # clusters and the shard offset g0 is traced.
    variants.append(("fp32", PROBE_KC // PROBE_SHARDS))
    for scoring, kc_local in variants:
        sharded = kc_local != PROBE_KC
        specs = (
            _sds((PROBE_B, PROBE_D), jnp.float32),
            _sds((kc_local, PROBE_CAP, PROBE_D), dtypes[scoring]),
            _sds((kc_local, PROBE_CAP), jnp.int32),
            _sds((PROBE_KC, PROBE_D), jnp.float32),
            _sds((PROBE_KC,), jnp.bool_),
            _sds((kc_local,), jnp.float32),
            _sds((), jnp.int32),
        )

        def make_args(rng, scoring=scoring, kc_local=kc_local,
                      sharded=sharded):
            g0 = kc_local if sharded else 0  # the second shard
            cents = rng.standard_normal(
                (PROBE_KC, PROBE_D)).astype(np.float32)
            cents /= np.linalg.norm(cents, axis=1, keepdims=True)
            packed = (cents[g0:g0 + kc_local, None, :]
                      + 0.3 * rng.standard_normal(
                          (kc_local, PROBE_CAP, PROBE_D), np.float32))
            packed /= np.linalg.norm(packed, axis=2, keepdims=True)
            rows = np.arange(kc_local * PROBE_CAP, dtype=np.int32).reshape(
                kc_local, PROBE_CAP)
            fill = rng.integers(1, PROBE_CAP + 1, kc_local)  # ragged
            fill[3] = 0                                       # one empty
            dead = np.arange(PROBE_CAP)[None, :] >= fill[:, None]
            rows[dead] = -1
            packed[dead] = 0.0
            cvalid = np.ones(PROBE_KC, bool)
            cvalid[g0 + 3] = False
            scale = np.ones(kc_local, np.float32)
            if scoring == "bf16":
                packed = packed.astype(jnp.bfloat16)
            elif scoring == "int8":
                scale = np.maximum(
                    np.abs(packed).max(axis=(1, 2)) / 127.0, 1e-12
                ).astype(np.float32)
                packed = np.clip(np.round(
                    packed / scale[:, None, None]), -127, 127
                ).astype(np.int8)
            # Queries are gallery rows (self-match is the top answer).
            pick = rng.integers(0, kc_local, PROBE_B)
            q = np.stack([
                np.asarray(packed[c, 0], np.float32) * scale[c]
                for c in pick])
            q /= np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
            return (q.astype(np.float32), packed, rows, cents, cvalid,
                    scale, np.int32(g0))

        def run(impl, q, packed, rows, cents, cvalid, scale, g0,
                scoring=scoring):
            kw = dict(k=PROBE_K, probes=PROBE_PROBES, scoring=scoring,
                      g0=g0)
            sc = scale if scoring == "int8" else None
            if impl == "fused":
                return fused_probe_topk(q, packed, rows, cents, cvalid,
                                        sc, interpret=False, **kw)
            return _ivf_probe_topk(q, packed, rows, cents, cvalid, sc,
                                   **kw)

        name = f"probe_{scoring}" + ("_shard_local" if sharded else "")
        cases.append(KernelCase(
            name,
            lambda *a, run=run: run("fused", *a),
            lambda *a, run=run: run("scan", *a),
            specs, make_args, _check_probe))
    return cases


def kernel_cases(groups: Sequence[str] = ("blockwise", "stem", "probe")
                 ) -> List[KernelCase]:
    """Every case of the named groups, in a stable order."""
    makers = {"blockwise": _blockwise_cases, "stem": _stem_cases,
              "probe": _probe_cases}
    out: List[KernelCase] = []
    for g in groups:
        out.extend(makers[g]())
    return out
