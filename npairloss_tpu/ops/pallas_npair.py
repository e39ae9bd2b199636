"""Fused blockwise N-pair loss as Pallas TPU kernels.

The dense path (``ops.npair_loss``) materializes the full N x M pair
matrix (M = pool size) in HBM — the TPU transplant of the reference's
``_innerProd`` workspace blob (reference: npair_multi_class_loss.cu:218,
cpp:55-64).  At the 32k-batch stretch config that matrix is gigabytes,
and HBM bandwidth (not MXU FLOPs) dominates: the matrix is written once
and re-read by every stage (stats, selection, exp, reductions).

These kernels never materialize it.  Queries and pool both stream
through VMEM in (BN x BM) tiles over a 2-D grid; each tile is produced
on the MXU and consumed in-register by the fused mask ->
threshold-compare -> exp -> row-sum pipeline — the flash-attention trick
transplanted to contrastive similarity (SURVEY.md §5.7), as explicit
Pallas kernels for fusion control the XLA autofuser cannot guarantee
across a gemm:

  * ``_stats_kernel``  — running per-query min-within / max-between /
    max-all (the mining statistics of cu:229-265; the reference runs
    this O(N*M) scan on the *host*, one float at a time).
  * ``_loss_kernel``   — selection mask from absolute thresholds
    (cu:69-122), stabilized exp (cu:124-156), running I_q/D_q sums and
    pair counts (cu:355-378).
  * ``_gq_kernel`` / ``_gdb_kernel`` — recompute the weight tile
    w = (-p1+p2+p3) * g/N (Get_Query_Diff_Part, cu:405-419) and
    accumulate the two gemms of cu:448-460: query-role grad w @ pool
    (pool axis innermost) and database-role grad w^T @ feats (query
    axis innermost), so each output block stays VMEM-resident across
    its whole accumulation.

Mining-method support matches the ring path (``parallel.ring``): ALL
methods are exact.  Absolute (HARD / EASY / RAND) thresholds stream as
min/max reductions inside ``_stats_kernel``; RELATIVE_* thresholds —
rank statistics over the full pair population, which the reference
obtains by sorting the whole matrix on the host (cu:266-273) — are
recovered exactly by MSD radix selection (``ops.rank_select``): a few
extra streamed passes over the pair tiles, each histogramming one
RADIX_BITS-bit digit of the monotone sortable float key via
scatter-free compare-and-reduce, narrow the target rank to a single
bit pattern without ever materializing the population.  When only the
POSITIVE side is relative (the flagship def.prototxt config), the
sparse-positive fast path (``pos_topk``) skips those passes entirely:
identity-balanced sampling gives each query only a handful of
positives, so the stats sweep keeps a K-slot buffer of the largest
same-label sims (``_accum_topk``) and the AP threshold is an N x K
sort — the flagship config then costs the same sweeps as absolute
mining, with a runtime ``lax.cond`` fallback to radix selection for
labels that overflow the buffer.

**Similarity cache**: every sweep above recomputes its sim tiles with a
fp32-HIGHEST MXU matmul (6 bf16 passes) plus a full stream of the feats
and pool tiles.  When the fp32 tile matrix fits HBM (``sim_cache``,
auto-enabled below ``SIM_CACHE_AUTO_BYTES``), the stats sweep writes
each tile out once and every later sweep — radix digits, loss, both
backward gemms — streams the cached tiles back instead, turning the
selection/loss sweeps from matmul-bound into purely bandwidth-bound
(at a 32k pool: ~4.3 GB read per sweep instead of a ~1.1e12-FLOP
fp32-HIGHEST matmul plus ~8.6 GB of operand re-streaming).  Cached and
recompute paths are bit-identical — the cache stores exactly the fp32
values ``_sim_tile`` produces.  Beyond the threshold the engine keeps
the original O(N x block) recompute behavior, which is the mode the
"too big to materialize" docstring above describes.

On non-TPU backends the kernels run in Pallas interpreter mode, which is
how the CPU test suite checks bit-parity against the dense path.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from npairloss_tpu.ops.npair_loss import (
    FLT_MAX,
    SIM_CACHE_AUTO_BYTES,
    resolve_sim_cache_auto,
    MiningMethod,
    MiningRegion,
    NPairLossConfig,
    _clamp_negative,
    _relative_pos,
    absolute_thresholds,
    active_matmul_precision,
    matmul_precision_ctx,
    selection_predicates,
    topk_relative_threshold,
)
from npairloss_tpu.ops.pallas_mode import default_interpret
from npairloss_tpu.ops.rank_select import (
    NUM_DIGITS,
    RADIX_BINS,
    digit_of,
    population_count_dtype,
    prefix_matches,
    radix_begin,
    radix_finish,
    radix_update,
    sortable_key,
)

_RELATIVE = (MiningMethod.RELATIVE_HARD, MiningMethod.RELATIVE_EASY)

# Mosaic's default scoped-VMEM budget is 16 MiB; the flagship stats
# sweep at 512x512 tiles (sim tile out + K-slot top-k + digit-0
# histogram, all double-buffered) needs 16.3 MiB.  v5e has 128 MiB of
# VMEM per core, so every sweep gets a 32 MiB budget.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 << 20)


def blockwise_supported(cfg: NPairLossConfig) -> bool:
    """Every mining configuration streams (RELATIVE_* via radix select),
    matching the ring path's support matrix."""
    return True


def _canon_labels(labels: jax.Array) -> jax.Array:
    """Kernel-friendly labels WITHOUT collapsing identities: float labels
    stay float32 (the dense path compares raw labels — int32 truncation
    would merge e.g. 0.2 and 0.7), ints become int32."""
    if jnp.issubdtype(labels.dtype, jnp.floating):
        return labels.astype(jnp.float32)
    return labels.astype(jnp.int32)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad_rows(x: jax.Array, block: int) -> jax.Array:
    n = x.shape[0]
    np_ = ((n + block - 1) // block) * block
    if np_ == n:
        return x
    pad = [(0, np_ - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _row(x):
    """Per-query/per-pool scalar vectors travel as (1, N): the lane axis
    carries the index, so TPU (8,128) tiling stores them compactly — a
    (N, 1) layout would lane-pad every query to 128 floats and blow VMEM
    at large N."""
    return x.reshape(1, -1)


def _tile_masks(scal_ref, labels_ref, pool_labels_ref, qi, ii, bn: int, bm: int):
    """(same, diff) bool masks for tile (qi, ii) of the N x M pair grid.

    Self-pair exclusion (cu:54): global pool column ``self_offset + row``
    is this query's own embedding.  Padded rows (>= n_real) and padded
    columns (>= m_real) are in neither mask, so every downstream
    reduction and weight tile ignores them.
    """
    m_real = scal_ref[0]
    self_offset = scal_ref[1]
    n_real = scal_ref[2]
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1) + ii * bm
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0) + qi * bn
    valid = (col < m_real) & (row < n_real)
    not_self = col != (row + self_offset)
    same_lbl = labels_ref[:].T == pool_labels_ref[:]
    same = same_lbl & valid & not_self
    diff = (~same_lbl) & valid & not_self
    return same, diff


# Every kernel gemm reads the trace-time precision ContextVar
# (ops.npair_loss.active_matmul_precision): HIGHEST by default — the
# TPU default mode would truncate to bf16 and break bit-parity with
# the dense path (cu:218 semantics) — and the single-pass bf16 mode
# when ``blockwise_npair_loss(matmul_precision="default")`` wraps the
# trace in ``matmul_precision_ctx``.  Kernels are rebuilt at every
# trace, so the setting is captured per-computation and thread-safely.
_precision_ctx = matmul_precision_ctx


def _sim_tile(feats_ref, pool_ref):
    return jnp.dot(
        feats_ref[:],
        pool_ref[:].T,
        preferred_element_type=jnp.float32,
        precision=active_matmul_precision(),
    )


def _sim_kernel(body, extra: Optional[str] = None):
    """Build the cached/uncached kernel signatures around a sim-consuming
    ``body(scal_ref, labels_ref, pool_labels_ref, sims, extra_ref, rest)``.

    The uncached kernel streams feats+pool and recomputes the sim tile on
    the MXU; the cached kernel streams the sim tile itself plus — when
    ``extra`` is "feats"/"pool" — the one dense operand the body still
    multiplies against (the backward gemms).  Returns ``make(cached)``.
    """

    def make(cached: bool):
        if cached and extra is None:
            def kernel(scal_ref, labels_ref, pool_labels_ref, sims_ref,
                       *rest):
                body(scal_ref, labels_ref, pool_labels_ref, sims_ref[:],
                     None, rest)
        elif cached:
            def kernel(scal_ref, labels_ref, pool_labels_ref, sims_ref,
                       extra_ref, *rest):
                body(scal_ref, labels_ref, pool_labels_ref, sims_ref[:],
                     extra_ref, rest)
        else:
            def kernel(scal_ref, feats_ref, labels_ref, pool_ref,
                       pool_labels_ref, *rest):
                extra_ref = {"feats": feats_ref, "pool": pool_ref,
                             None: None}[extra]
                body(scal_ref, labels_ref, pool_labels_ref,
                     _sim_tile(feats_ref, pool_ref), extra_ref, rest)
        return kernel

    return make


def _selection(sims, same, diff, pt, nt, cfg: NPairLossConfig):
    """Tile selection via the shared quirk-exact predicates of cu:80-119
    (ops.npair_loss.selection_predicates); cfg is static, so the
    branches resolve at trace time."""
    pos_sel, neg_sel = selection_predicates(sims, pt, nt, cfg)
    return same & pos_sel, diff & neg_sel


# ---------------------------------------------------------------------------
# Kernels.  Grid convention: the output-resident axis is OUTER, the
# accumulation axis is INNER, so each output block is initialized once
# (inner index == 0) and accumulates in VMEM across the inner loop.
# ---------------------------------------------------------------------------


def _accum_digit_hist(out_ref, sims, mask, digit: int, prefix=None):
    """Accumulate the (RADIX_BINS, bn) histogram of one radix digit over
    a masked tile into ``out_ref`` — kernel-side compare-and-reduce (no
    scatter): one lane-reduction per bin, each written to its own
    static output row (row-wise ref updates keep the Mosaic op surface
    to the same relayouts the stats kernel already uses).  ``prefix``
    (optional, (bn, 1) uint32) restricts to entries whose higher digits
    match."""
    key = sortable_key(sims)
    m = mask
    if prefix is not None:
        m = m & prefix_matches(key, prefix, digit)
    d = jnp.where(m, digit_of(key, digit), RADIX_BINS)
    for b in range(RADIX_BINS):
        out_ref[b:b + 1, :] += (
            (d == b).sum(axis=1, keepdims=True).astype(jnp.int32).T
        )


def _accum_topk(out_ref, sims, mask, k: int):
    """Maintain the K largest masked sims per query across pool tiles.

    ``out_ref`` is a (K, bn) revisited output holding the running
    K-largest buffer (queries on lanes, slots on sublanes).  Per tile:
    K rounds of (row-max, remove exactly one occurrence) extract the
    tile's K largest — duplicate values are distinct candidates, so
    removal is by max-index-among-equals, never by value — then the
    same loop over the (2K, bn) concat merges tile and buffer.  Values
    come from the SAME ``sims`` the sweep computes, so thresholds built
    from the buffer are bit-identical to streamed radix selection.
    Cost: ~4K VPU passes per tile, beside a 2*D-MAC matmul."""
    bn, bm = sims.shape
    neg = jnp.float32(-FLT_MAX)
    vals = jnp.where(mask, sims, neg)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
    rows = []
    for _ in range(k):
        mx = vals.max(axis=1, keepdims=True)  # (bn, 1)
        mi = jnp.where(vals == mx, iota, -1).max(axis=1, keepdims=True)
        vals = jnp.where(iota == mi, neg, vals)
        rows.append(mx.T)
    work = jnp.concatenate([out_ref[:]] + rows, axis=0)  # (2K, bn)
    iota2 = jax.lax.broadcasted_iota(jnp.int32, (2 * k, bn), 0)
    for t in range(k):
        mx = work.max(axis=0, keepdims=True)  # (1, bn)
        mi = jnp.where(work == mx, iota2, -1).max(axis=0, keepdims=True)
        work = jnp.where(iota2 == mi, neg, work)
        out_ref[t:t + 1, :] = mx


def _make_stats_kernel(hist_same: bool, hist_diff: bool,
                       emit_sims: bool = False, topk_same: int = 0):
    """Mining-stats kernel; optionally also the digit-0 radix histograms
    for RELATIVE_* sides (digit 0 needs no prefix, so accumulating it in
    this sweep saves one whole pass per relative side), and optionally
    the fp32 sim tiles themselves (the similarity cache later sweeps
    stream instead of recomputing)."""

    def kernel(scal_ref, feats_ref, labels_ref, pool_ref, pool_labels_ref,
               *out_refs):
        (min_w_ref, max_b_ref, max_a_ref, cnt_s_ref, cnt_d_ref), rest = (
            out_refs[:5], list(out_refs[5:]))
        h_s_ref = rest.pop(0) if hist_same else None
        h_d_ref = rest.pop(0) if hist_diff else None
        topk_ref = rest.pop(0) if topk_same else None
        sims_out_ref = rest.pop(0) if emit_sims else None
        # grid = (num_q_blocks, num_pool_blocks)
        qi, ii = pl.program_id(0), pl.program_id(1)
        bn, bm = feats_ref.shape[0], pool_ref.shape[0]
        neg = jnp.float32(-FLT_MAX)
        pos = jnp.float32(FLT_MAX)

        @pl.when(ii == 0)
        def _():
            min_w_ref[:] = jnp.full_like(min_w_ref, pos)
            max_b_ref[:] = jnp.full_like(max_b_ref, neg)
            max_a_ref[:] = jnp.full_like(max_a_ref, neg)
            cnt_s_ref[:] = jnp.zeros_like(cnt_s_ref)
            cnt_d_ref[:] = jnp.zeros_like(cnt_d_ref)
            if h_s_ref is not None:
                h_s_ref[:] = jnp.zeros_like(h_s_ref)
            if h_d_ref is not None:
                h_d_ref[:] = jnp.zeros_like(h_d_ref)
            if topk_ref is not None:
                topk_ref[:] = jnp.full_like(topk_ref, neg)

        sims = _sim_tile(feats_ref, pool_ref)
        if sims_out_ref is not None:
            sims_out_ref[:] = sims
        same, diff = _tile_masks(
            scal_ref, labels_ref, pool_labels_ref, qi, ii, bn, bm
        )
        min_w_ref[:] = jnp.minimum(
            min_w_ref[:],
            jnp.where(same, sims, pos).min(axis=1, keepdims=True).T,
        )
        max_b_ref[:] = jnp.maximum(
            max_b_ref[:],
            jnp.where(diff, sims, neg).max(axis=1, keepdims=True).T,
        )
        max_a_ref[:] = jnp.maximum(
            max_a_ref[:],
            jnp.where(same | diff, sims, neg).max(axis=1, keepdims=True).T,
        )
        # Pair-population sizes (the ragged list sizes of cu:266-273)
        # feed the RELATIVE_* rank targets.
        cnt_s_ref[:] += same.sum(axis=1, keepdims=True).astype(jnp.int32).T
        cnt_d_ref[:] += diff.sum(axis=1, keepdims=True).astype(jnp.int32).T
        if h_s_ref is not None:
            _accum_digit_hist(h_s_ref, sims, same, 0)
        if h_d_ref is not None:
            _accum_digit_hist(h_d_ref, sims, diff, 0)
        if topk_ref is not None:
            _accum_topk(topk_ref, sims, same, topk_same)

    return kernel


def _make_hist_kernel(sides, digit: int, cached: bool = False):
    """Radix digit-histogram kernel for digits >= 1: one fused sweep
    produces the sim tile — MXU recompute, or a streamed read of the
    similarity cache when ``cached`` — and accumulates the prefix-matched
    digit histogram for every active RELATIVE side (the streamed
    counterpart of the reference's host std::sort, cu:266-273).

    ``sides``: tuple of bools — use_same per side, in output order.
    Inputs after the data refs: one (1, bn) uint32 prefix vector per
    side; outputs: one (RADIX_BINS, bn) int32 histogram per side.
    """

    def body(scal_ref, labels_ref, pool_labels_ref, sims, _extra, rest):
        prefix_refs = rest[:len(sides)]
        out_refs = rest[len(sides):]
        qi, ii = pl.program_id(0), pl.program_id(1)
        bn, bm = sims.shape

        @pl.when(ii == 0)
        def _():
            for o in out_refs:
                o[:] = jnp.zeros_like(o)

        same, diff = _tile_masks(
            scal_ref, labels_ref, pool_labels_ref, qi, ii, bn, bm
        )
        for use_same, p_ref, o_ref in zip(sides, prefix_refs, out_refs):
            mask = same if use_same else diff
            _accum_digit_hist(o_ref, sims, mask, digit, p_ref[:].T)

    return _sim_kernel(body)(cached)


def _make_loss_kernel(cfg: NPairLossConfig, cached: bool = False):
    def body(scal_ref, labels_ref, pool_labels_ref, sims, _extra, rest):
        (pos_thr_ref, neg_thr_ref, max_all_ref,
         isum_ref, dsum_ref, inum_ref, dnum_ref) = rest
        qi, ii = pl.program_id(0), pl.program_id(1)
        bn, bm = sims.shape

        @pl.when(ii == 0)
        def _():
            isum_ref[:] = jnp.zeros_like(isum_ref)
            dsum_ref[:] = jnp.zeros_like(dsum_ref)
            inum_ref[:] = jnp.zeros_like(inum_ref)
            dnum_ref[:] = jnp.zeros_like(dnum_ref)

        same, diff = _tile_masks(
            scal_ref, labels_ref, pool_labels_ref, qi, ii, bn, bm
        )
        pt = pos_thr_ref[:].T + jnp.float32(cfg.margin_ident)
        nt = neg_thr_ref[:].T + jnp.float32(cfg.margin_diff)
        sel_pos, sel_neg = _selection(sims, same, diff, pt, nt, cfg)
        sim_exp = jnp.exp(sims - max_all_ref[:].T)
        isum_ref[:] += jnp.where(sel_pos, sim_exp, 0.0).sum(1, keepdims=True).T
        dsum_ref[:] += jnp.where(sel_neg, sim_exp, 0.0).sum(1, keepdims=True).T
        inum_ref[:] += sel_pos.sum(1, keepdims=True).astype(jnp.float32).T
        dnum_ref[:] += sel_neg.sum(1, keepdims=True).astype(jnp.float32).T

    return _sim_kernel(body)(cached)


def _weight_tile(cfg, scal_ref, labels_ref, pool_labels_ref, sims,
                 pos_thr_ref, neg_thr_ref, max_all_ref,
                 isum_ref, asum_ref, valid_ref, g_ref, qi, ii):
    """w = (-p1+p2+p3) * valid * g/N for one tile (cu:405-446).

    ``sims`` is the tile's fp32 similarity block — recomputed on the MXU
    or streamed from the similarity cache by the caller.

    valid_ref is all-ones in "reference" grad mode — the reference keeps
    diff-type entries alive for identNum==0 queries (cu:133-146), so p3
    still contributes — and the zero-loss-query mask in "true" mode,
    where autodiff of the guarded log (cu:162-169) yields exactly 0.
    """
    bn, bm = sims.shape
    same, diff = _tile_masks(scal_ref, labels_ref, pool_labels_ref, qi, ii, bn, bm)
    pt = pos_thr_ref[:].T + jnp.float32(cfg.margin_ident)
    nt = neg_thr_ref[:].T + jnp.float32(cfg.margin_diff)
    sel_pos, sel_neg = _selection(sims, same, diff, pt, nt, cfg)
    # -p1+p2+p3 factors into per-query coefficients (keeps the live
    # (bn, bm) temporaries to sims/coef/w so big tiles fit VMEM):
    #   selected positive: a_q = -1/I_q + 1/(I+D)_q
    #   selected negative: b_q =          1/(I+D)_q
    # each 0-guarded per cu:412-417.
    def inv(den):
        ok = den != 0
        return jnp.where(ok, 1.0 / jnp.where(ok, den, 1.0), 0.0)

    # dot_normalizer = query count in backward (cu:427); n_real = scal[2].
    scale = (g_ref[0] / scal_ref[2].astype(jnp.float32)) * valid_ref[:].T
    a_q = (-inv(isum_ref[:].T) + inv(asum_ref[:].T)) * scale
    b_q = inv(asum_ref[:].T) * scale
    coef = jnp.where(sel_pos, a_q, jnp.where(sel_neg, b_q, 0.0))
    # Masking must be where-based, not multiplicative: a query with no
    # pairs has max_all = -FLT_MAX, so sim_exp overflows to +inf and
    # inf * 0 would poison the gemms with NaN (same hazard the dense
    # path guards, cu:152-154 semantics).
    return jnp.where(
        sel_pos | sel_neg, jnp.exp(sims - max_all_ref[:].T) * coef, 0.0
    )


def _make_gq_kernel(cfg: NPairLossConfig, cached: bool = False):
    def body(scal_ref, labels_ref, pool_labels_ref, sims, pool_ref, rest):
        (pos_thr_ref, neg_thr_ref, max_all_ref, isum_ref, asum_ref,
         valid_ref, g_ref, gq_ref) = rest
        # grid = (num_q_blocks, num_pool_blocks): pool axis accumulates.
        qi, ii = pl.program_id(0), pl.program_id(1)

        @pl.when(ii == 0)
        def _():
            gq_ref[:] = jnp.zeros_like(gq_ref)

        w = _weight_tile(
            cfg, scal_ref, labels_ref, pool_labels_ref, sims,
            pos_thr_ref, neg_thr_ref, max_all_ref, isum_ref, asum_ref,
            valid_ref, g_ref, qi, ii,
        )
        gq_ref[:] += jnp.dot(
            w, pool_ref[:],
            preferred_element_type=jnp.float32,
            precision=active_matmul_precision(),
        )

    return _sim_kernel(body, extra="pool")(cached)


def _make_gdb_kernel(cfg: NPairLossConfig, cached: bool = False):
    def body(scal_ref, labels_ref, pool_labels_ref, sims, feats_ref, rest):
        (pos_thr_ref, neg_thr_ref, max_all_ref, isum_ref, asum_ref,
         valid_ref, g_ref, gdb_ref) = rest
        # grid = (num_pool_blocks, num_q_blocks): query axis accumulates.
        ii, qi = pl.program_id(0), pl.program_id(1)

        @pl.when(qi == 0)
        def _():
            gdb_ref[:] = jnp.zeros_like(gdb_ref)

        w = _weight_tile(
            cfg, scal_ref, labels_ref, pool_labels_ref, sims,
            pos_thr_ref, neg_thr_ref, max_all_ref, isum_ref, asum_ref,
            valid_ref, g_ref, qi, ii,
        )
        gdb_ref[:] += jnp.dot(
            w.T, feats_ref[:],
            preferred_element_type=jnp.float32,
            precision=active_matmul_precision(),
        )

    return _sim_kernel(body, extra="feats")(cached)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _qblock(shape, qpos: int):
    """Matrix BlockSpec indexed by the grid's query axis at ``qpos``."""
    if qpos == 0:
        return pl.BlockSpec(shape, lambda q, i: (q, 0), memory_space=pltpu.VMEM)
    return pl.BlockSpec(shape, lambda i, q: (q, 0), memory_space=pltpu.VMEM)


def _qvec(b: int, qpos: int):
    """(1, b) row-vector BlockSpec indexed by the grid's query axis."""
    if qpos == 0:
        return pl.BlockSpec((1, b), lambda q, i: (0, q), memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, b), lambda i, q: (0, q), memory_space=pltpu.VMEM)


def _pblock(shape, ppos: int):
    """Matrix BlockSpec indexed by the grid's pool axis at ``ppos``."""
    if ppos == 0:
        return pl.BlockSpec(shape, lambda i, q: (i, 0), memory_space=pltpu.VMEM)
    return pl.BlockSpec(shape, lambda q, i: (i, 0), memory_space=pltpu.VMEM)


def _pvec(b: int, ppos: int):
    """(1, b) row-vector BlockSpec indexed by the grid's pool axis."""
    if ppos == 0:
        return pl.BlockSpec((1, b), lambda i, q: (0, i), memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, b), lambda q, i: (0, i), memory_space=pltpu.VMEM)


def _data_specs(bn: int, bm: int, dim: int, q_axis: int):
    """Specs for (scalars, feats, labels, pool, pool_labels) with the
    query axis at grid position ``q_axis`` (pool axis at the other)."""
    p_axis = 1 - q_axis
    return [
        _smem_spec(),
        _qblock((bn, dim), q_axis),
        _qvec(bn, q_axis),
        _pblock((bm, dim), p_axis),
        _pvec(bm, p_axis),
    ]


def _simblock(bn: int, bm: int, q_axis: int):
    """(bn, bm) tile of the cached N x M similarity matrix, query axis at
    grid position ``q_axis``."""
    if q_axis == 0:
        return pl.BlockSpec(
            (bn, bm), lambda q, i: (q, i), memory_space=pltpu.VMEM
        )
    return pl.BlockSpec(
        (bn, bm), lambda i, q: (q, i), memory_space=pltpu.VMEM
    )


def _cached_data_specs(bn: int, bm: int, q_axis: int):
    """Specs for (scalars, labels, pool_labels, sims_cache) — the cached
    sweeps stream sim tiles instead of feats/pool operands."""
    return [
        _smem_spec(),
        _qvec(bn, q_axis),
        _pvec(bm, 1 - q_axis),
        _simblock(bn, bm, q_axis),
    ]


def _hist_block(bn: int):
    """(RADIX_BINS, bn) histogram BlockSpec indexed by the grid's query
    axis (bins on sublanes, queries on lanes)."""
    return pl.BlockSpec(
        (RADIX_BINS, bn), lambda q, i: (0, q), memory_space=pltpu.VMEM
    )


def _run_stats(feats_p, labels_p, pool_p, pool_labels_p, scal,
               bn, bm, interpret, hist_same=False, hist_diff=False,
               emit_sims=False, topk_same=0):
    npq, dim = feats_p.shape[0] // bn, feats_p.shape[1]
    npi = pool_p.shape[0] // bm
    n_p, m_p = feats_p.shape[0], pool_p.shape[0]
    n_hists = int(hist_same) + int(hist_diff)
    out_specs = [_qvec(bn, 0)] * 5 + [_hist_block(bn)] * n_hists
    out_shape = (
        [jax.ShapeDtypeStruct((1, n_p), jnp.float32)] * 3
        + [jax.ShapeDtypeStruct((1, n_p), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((RADIX_BINS, n_p), jnp.int32)] * n_hists
    )
    if topk_same:
        out_specs.append(pl.BlockSpec(
            (topk_same, bn), lambda q, i: (0, q), memory_space=pltpu.VMEM
        ))
        out_shape.append(
            jax.ShapeDtypeStruct((topk_same, n_p), jnp.float32))
    if emit_sims:
        out_specs.append(_simblock(bn, bm, 0))
        out_shape.append(jax.ShapeDtypeStruct((n_p, m_p), jnp.float32))
    out = pl.pallas_call(
        _make_stats_kernel(hist_same, hist_diff, emit_sims, topk_same),
        grid=(npq, npi),
        in_specs=_data_specs(bn, bm, dim, 0),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(scal, feats_p, _row(labels_p), pool_p, _row(pool_labels_p))
    flat = [o[0, :] for o in out[:5]]
    sims_cache = out[-1] if emit_sims else None
    topk = out[5 + n_hists].T if topk_same else None  # -> [n_p, K]
    hists = [o.T for o in out[5:5 + n_hists]]  # -> [n_p, RADIX_BINS]
    h_s = hists.pop(0) if hist_same else None
    h_d = hists.pop(0) if hist_diff else None
    return (*flat, h_s, h_d, topk, sims_cache)


def _run_hist(feats_p, labels_p, pool_p, pool_labels_p, scal,
              use_same_flags, prefixes_p, digit, bn, bm, interpret,
              sims_cache=None):
    """One fused sweep -> per-side [n_p, RADIX_BINS] digit histograms."""
    npq, dim = feats_p.shape[0] // bn, feats_p.shape[1]
    npi = pool_p.shape[0] // bm
    n_p = feats_p.shape[0]
    k = len(use_same_flags)
    cached = sims_cache is not None
    if cached:
        in_specs = _cached_data_specs(bn, bm, 0) + [_qvec(bn, 0)] * k
        args = (scal, _row(labels_p), _row(pool_labels_p), sims_cache,
                *[_row(p) for p in prefixes_p])
    else:
        in_specs = _data_specs(bn, bm, dim, 0) + [_qvec(bn, 0)] * k
        args = (scal, feats_p, _row(labels_p), pool_p, _row(pool_labels_p),
                *[_row(p) for p in prefixes_p])
    out = pl.pallas_call(
        _make_hist_kernel(tuple(use_same_flags), digit, cached),
        grid=(npq, npi),
        in_specs=in_specs,
        out_specs=[_hist_block(bn)] * k,
        out_shape=[
            jax.ShapeDtypeStruct((RADIX_BINS, n_p), jnp.int32)
        ] * k,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    return [o.T for o in out]


def _run_loss(feats_p, labels_p, pool_p, pool_labels_p, scal,
              pos_thr_p, neg_thr_p, max_all_p, cfg, bn, bm, interpret,
              sims_cache=None):
    npq, dim = feats_p.shape[0] // bn, feats_p.shape[1]
    npi = pool_p.shape[0] // bm
    cached = sims_cache is not None
    if cached:
        specs = _cached_data_specs(bn, bm, 0) + [_qvec(bn, 0)] * 3
        args = (scal, _row(labels_p), _row(pool_labels_p), sims_cache,
                _row(pos_thr_p), _row(neg_thr_p), _row(max_all_p))
    else:
        specs = _data_specs(bn, bm, dim, 0) + [_qvec(bn, 0)] * 3
        args = (scal, feats_p, _row(labels_p), pool_p, _row(pool_labels_p),
                _row(pos_thr_p), _row(neg_thr_p), _row(max_all_p))
    out = pl.pallas_call(
        _make_loss_kernel(cfg, cached),
        grid=(npq, npi),
        in_specs=specs,
        out_specs=[_qvec(bn, 0)] * 4,
        out_shape=[jax.ShapeDtypeStruct((1, feats_p.shape[0]), jnp.float32)] * 4,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*args)
    return tuple(o[0, :] for o in out)


def _run_bwd(feats_p, labels_p, pool_p, pool_labels_p, scal,
             pos_thr_p, neg_thr_p, max_all_p, ident_sum_p, all_sum_p,
             valid_p, g, cfg, bn, bm, interpret, sims_cache=None):
    npq, dim = feats_p.shape[0] // bn, feats_p.shape[1]
    npi = pool_p.shape[0] // bm
    g_arr = jnp.asarray(g, jnp.float32).reshape(1)
    cached = sims_cache is not None
    qvecs = (
        _row(pos_thr_p), _row(neg_thr_p), _row(max_all_p),
        _row(ident_sum_p), _row(all_sum_p), _row(valid_p), g_arr,
    )
    if cached:
        # gq still streams pool tiles (for w @ pool); gdb streams feats
        # (for w^T @ feats) — but neither recomputes the sim matmul.
        gq_args = (scal, _row(labels_p), _row(pool_labels_p), sims_cache,
                   pool_p) + qvecs
        gq_specs = (_cached_data_specs(bn, bm, 0) + [_pblock((bm, dim), 1)]
                    + [_qvec(bn, 0)] * 6 + [_smem_spec()])
        gdb_args = (scal, _row(labels_p), _row(pool_labels_p), sims_cache,
                    feats_p) + qvecs
        gdb_specs = (_cached_data_specs(bn, bm, 1) + [_qblock((bn, dim), 1)]
                     + [_qvec(bn, 1)] * 6 + [_smem_spec()])
    else:
        gq_args = (scal, feats_p, _row(labels_p), pool_p,
                   _row(pool_labels_p)) + qvecs
        gq_specs = (_data_specs(bn, bm, dim, 0)
                    + [_qvec(bn, 0)] * 6 + [_smem_spec()])
        gdb_args = gq_args
        gdb_specs = (_data_specs(bn, bm, dim, 1)
                     + [_qvec(bn, 1)] * 6 + [_smem_spec()])
    gq = pl.pallas_call(
        _make_gq_kernel(cfg, cached),
        grid=(npq, npi),
        in_specs=gq_specs,
        out_specs=_qblock((bn, dim), 0),
        out_shape=jax.ShapeDtypeStruct((feats_p.shape[0], dim), jnp.float32),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*gq_args)
    gdb = pl.pallas_call(
        _make_gdb_kernel(cfg, cached),
        grid=(npi, npq),
        in_specs=gdb_specs,
        out_specs=_pblock((bm, dim), 0),
        out_shape=jax.ShapeDtypeStruct((pool_p.shape[0], dim), jnp.float32),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(*gdb_args)
    return gq, gdb


# ---------------------------------------------------------------------------
# Streamed RELATIVE_* thresholds: exact MSD radix selection over tiles
# ---------------------------------------------------------------------------


def _thresholds(feats_p, labels_p, pool_p, pool_labels_p, scal,
                min_w, max_b, cnt_s, cnt_d, h0_s, h0_d,
                cfg, bn, bm, interpret, n, sims_cache=None,
                topk_same=None):
    """(pos_thr, neg_thr) for ANY mining config: absolute methods from the
    streamed min/max stats, RELATIVE_* via exact stepwise radix selection.

    Reproduces the dense ``_local/_global_relative_threshold`` semantics
    (ascending sort + ``_relative_pos`` index + ``< 0 -> -FLT_MAX``
    clamp, reference cu:275-337) via ops.rank_select, entirely inside
    Pallas sweeps: the digit-0 histograms ride the stats kernel for free
    (digit 0 needs no prefix), and each remaining digit is one fused
    ``_make_hist_kernel`` sweep — sim tile on the MXU, prefix-matched
    compare-and-reduce histogram on the VPU, shared across the AP and AN
    sides.  So relative mining costs NUM_DIGITS - 1 extra kernel sweeps
    whether one or both sides are relative.  GLOBAL ranks over the whole
    flattened population (cu:296, cu:327), LOCAL per query; populations
    beyond 2^31 pairs need 64-bit counts (jax_enable_x64) or fail loudly
    at trace time.

    ``topk_same`` ([n_p, K] kernel-extracted K-largest same-label sims,
    or None) arms the sparse-positive fast path: identity-balanced
    batches give each query only a handful of positives, so when every
    ``cnt_s`` fits the K-slot buffer the AP threshold is an N x K sort
    (``topk_relative_threshold``) and the AP side drops out of the
    digit sweeps entirely — the flagship GLOBAL/RELATIVE_HARD config
    then costs the same sweeps as absolute mining.  A ``lax.cond``
    falls back to the radix path at runtime when some label group
    overflows the buffer, so arbitrary label multiplicity stays exact.
    """
    pos_thr, neg_thr = absolute_thresholds(min_w, max_b, cfg)
    ap_rel = cfg.ap_mining_method in _RELATIVE
    an_rel = cfg.an_mining_method in _RELATIVE
    if not (ap_rel or an_rel):
        return pos_thr, neg_thr

    # Fast path only pays off when AP is the ONLY relative side: the
    # digit sweeps are shared across sides, so with AN also relative
    # dropping AP saves zero sweeps while doubling the cond's compiled
    # pipeline.  _blockwise_fwd_impl skips the buffer in that case too.
    if ap_rel and not an_rel and topk_same is not None:
        def radix(include_ap):
            return _radix_thresholds(
                feats_p, labels_p, pool_p, pool_labels_p, scal,
                pos_thr, neg_thr, cnt_s, cnt_d, h0_s, h0_d,
                cfg, bn, bm, interpret, n, sims_cache,
                include_ap=include_ap, include_an=an_rel)

        kcap = topk_same.shape[1]
        fits = cnt_s.max() <= kcap

        def fast(_):
            p = topk_relative_threshold(
                topk_same[:n], cnt_s, cfg.identsn, cfg.ap_mining_region,
                count_dtype=population_count_dtype(n * n))
            return p, radix(False)[1]

        return jax.lax.cond(fits, fast, lambda _: radix(True), 0)

    return _radix_thresholds(
        feats_p, labels_p, pool_p, pool_labels_p, scal,
        pos_thr, neg_thr, cnt_s, cnt_d, h0_s, h0_d,
        cfg, bn, bm, interpret, n, sims_cache,
        include_ap=ap_rel, include_an=an_rel)


def _radix_thresholds(feats_p, labels_p, pool_p, pool_labels_p, scal,
                      pos_thr, neg_thr, cnt_s, cnt_d, h0_s, h0_d,
                      cfg, bn, bm, interpret, n, sims_cache,
                      include_ap, include_an):
    """The streamed radix-selection path of ``_thresholds`` (see there),
    restricted to the requested sides."""
    sides = {}
    if include_ap:
        sides["ap"] = (True, cfg.identsn, cfg.ap_mining_region, cnt_s, h0_s)
    if include_an:
        sides["an"] = (False, cfg.diffsn, cfg.an_mining_region, cnt_d, h0_d)
    if not sides:
        return pos_thr, neg_thr

    def prep_hist(side, hist):
        _, _, region, _, _ = sides[side]
        hist = hist[:n]
        if region == MiningRegion.GLOBAL:
            cdt = population_count_dtype(n * n)
            hist = jnp.broadcast_to(
                hist.sum(axis=0, keepdims=True, dtype=cdt), (n, RADIX_BINS)
            )
        return hist

    states, empties = {}, {}
    for s, (use_same, sn, region, counts, hist0) in sides.items():
        if region == MiningRegion.GLOBAL:
            # Self-pool population is at most n x n pairs; beyond int32
            # the counts (and the rank walk) must be 64-bit or fail.
            cdt = population_count_dtype(n * n)
            total = counts.astype(cdt).sum()
            k = jnp.broadcast_to(_relative_pos(total[None], sn)[0], (n,))
            empties[s] = jnp.broadcast_to(total == 0, (n,))
        else:
            k = _relative_pos(counts, sn)
            empties[s] = counts == 0
        states[s] = radix_update(radix_begin(k), prep_hist(s, hist0))

    names = list(sides)
    use_same_flags = [sides[s][0] for s in names]
    for digit in range(1, NUM_DIGITS):
        prefixes_p = [_pad_rows(states[s][1], bn) for s in names]
        hists = _run_hist(
            feats_p, labels_p, pool_p, pool_labels_p, scal,
            use_same_flags, prefixes_p, digit, bn, bm, interpret,
            sims_cache=sims_cache,
        )
        for s, h in zip(names, hists):
            states[s] = radix_update(states[s], prep_hist(s, h))

    vals = {
        s: _clamp_negative(radix_finish(states[s], empties[s]))
        for s in sides
    }
    return vals.get("ap", pos_thr), vals.get("an", neg_thr)


# ---------------------------------------------------------------------------
# Public API: self-pool loss with custom VJP (dense-path parity, G = 1)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _blockwise_core(features, labels, cfg, bn, bm, interpret, cache,
                    pos_topk, matmul_precision):
    out, _ = _blockwise_fwd_impl(
        features, labels, cfg, bn, bm, interpret, cache, pos_topk,
        matmul_precision
    )
    return out


def _blockwise_fwd_impl(features, labels, cfg, bn, bm, interpret, cache,
                        pos_topk=0, matmul_precision=None):
    with _precision_ctx(matmul_precision):
        return _blockwise_fwd_traced(
            features, labels, cfg, bn, bm, interpret, cache, pos_topk)


def _blockwise_fwd_traced(features, labels, cfg, bn, bm, interpret, cache,
                          pos_topk=0):
    features = features.astype(jnp.float32)
    labels_i = _canon_labels(labels)
    n = features.shape[0]
    feats_p = _pad_rows(features, bn)
    labels_qp = _pad_rows(labels_i, bn)
    pool_p = _pad_rows(features, bm)
    pool_labels_p = _pad_rows(labels_i, bm)
    scal = jnp.array([n, 0, n], jnp.int32)  # [m_real, self_offset, n_real]

    ap_rel = cfg.ap_mining_method in _RELATIVE
    an_rel = cfg.an_mining_method in _RELATIVE
    (min_w, max_b, max_all, cnt_s, cnt_d, h0_s, h0_d, topk_same,
     sims_cache) = _run_stats(
        feats_p, labels_qp, pool_p, pool_labels_p, scal, bn, bm, interpret,
        hist_same=ap_rel,
        hist_diff=an_rel,
        emit_sims=cache,
        # The buffer only pays when AP is the sole relative side (see
        # _thresholds).
        topk_same=pos_topk if ap_rel and not an_rel else 0,
    )
    min_w, max_b, max_all = min_w[:n], max_b[:n], max_all[:n]
    pos_thr, neg_thr = _thresholds(
        feats_p, labels_qp, pool_p, pool_labels_p, scal,
        min_w, max_b, cnt_s[:n], cnt_d[:n], h0_s, h0_d,
        cfg, bn, bm, interpret, n, sims_cache=sims_cache,
        topk_same=topk_same,
    )
    out = _run_loss(
        feats_p, labels_qp, pool_p, pool_labels_p, scal,
        _pad_rows(pos_thr, bn), _pad_rows(neg_thr, bn), _pad_rows(max_all, bn),
        cfg, bn, bm, interpret, sims_cache=sims_cache,
    )
    isum, dsum, inum, dnum = (o[:n] for o in out)
    all_sum = isum + dsum
    valid = (isum != 0) & (all_sum != 0)
    log_q = jnp.where(valid, jnp.log(jnp.where(valid, isum / all_sum, 1.0)), 0.0)
    loss = -log_q.sum() / jnp.float32(n)

    aux = {
        "ident_num": inum,
        "diff_num": dnum,
        "pos_threshold": pos_thr,
        "neg_threshold": neg_thr,
    }
    residuals = {
        "features": features,
        "labels": labels,
        "pos_thr": pos_thr,
        "neg_thr": neg_thr,
        "max_all": max_all,
        "ident_sum": isum,
        "all_sum": all_sum,
        # The cached sim tiles ride the residuals so the backward sweeps
        # read instead of recomputing; None when caching is off.
        "sims": sims_cache,
    }
    return (loss, aux), residuals


def _blockwise_fwd(features, labels, cfg, bn, bm, interpret, cache,
                   pos_topk, matmul_precision):
    return _blockwise_fwd_impl(
        features, labels, cfg, bn, bm, interpret, cache, pos_topk,
        matmul_precision
    )


def _blockwise_bwd(cfg, bn, bm, interpret, cache, pos_topk,
                   matmul_precision, res, cotangents):
    with _precision_ctx(matmul_precision):
        return _blockwise_bwd_traced(
            cfg, bn, bm, interpret, cache, pos_topk, res, cotangents)


def _blockwise_bwd_traced(cfg, bn, bm, interpret, cache, pos_topk, res,
                          cotangents):
    g, _ = cotangents  # aux outputs are monitors
    features = res["features"]
    labels = res["labels"]
    labels_i = _canon_labels(labels)
    n = features.shape[0]
    if cfg.grad_mode == "reference":
        valid = jnp.ones((n,), jnp.float32)
    else:
        valid = (
            (res["ident_sum"] != 0) & (res["all_sum"] != 0)
        ).astype(jnp.float32)
    scal = jnp.array([n, 0, n], jnp.int32)
    gq, gdb = _run_bwd(
        _pad_rows(features, bn), _pad_rows(labels_i, bn),
        _pad_rows(features, bm), _pad_rows(labels_i, bm), scal,
        _pad_rows(res["pos_thr"], bn), _pad_rows(res["neg_thr"], bn),
        _pad_rows(res["max_all"], bn), _pad_rows(res["ident_sum"], bn),
        _pad_rows(res["all_sum"], bn), _pad_rows(valid, bn),
        g, cfg, bn, bm, interpret, sims_cache=res["sims"],
    )
    gq, gdb = gq[:n], gdb[:n]
    if cfg.grad_mode == "reference":
        # G = 1 specialization of cu:462-497: allreduce is the identity,
        # 1/G = 1, own rows are the whole database grad; 0.5/0.5 merge.
        d_features = 0.5 * gdb + 0.5 * gq
    else:
        d_features = gq + gdb
    if jnp.issubdtype(labels.dtype, jnp.floating):
        d_labels = jnp.zeros(labels.shape, labels.dtype)
    else:
        d_labels = np.zeros(labels.shape, jax.dtypes.float0)
    return d_features, d_labels


_blockwise_core.defvjp(_blockwise_fwd, _blockwise_bwd)


def blockwise_npair_loss_with_aux(
    features: jax.Array,
    labels: jax.Array,
    cfg: NPairLossConfig = NPairLossConfig(),
    block_size: int = 512,
    q_block_size: Optional[int] = None,
    interpret: Optional[bool] = None,
    sim_cache: Optional[bool] = None,
    pos_topk: Optional[int] = None,
    matmul_precision: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """N-pair loss over a self-pool too large for the dense N x N matrix.

    Semantically identical (loss and gradient) to
    ``npair_loss_with_aux(features, labels, cfg)`` for every mining
    configuration (RELATIVE_* thresholds via streamed radix selection),
    but peak memory is O(q_block x D + block x D + q_block x
    block) VMEM per tile — the pair matrix is produced and consumed
    tile-by-tile inside Pallas kernels.  ``aux`` carries the
    streaming-computable monitors (pair counts, thresholds) — the full
    similarity matrices of the dense aux are exactly what this path
    exists to avoid.

    ``sim_cache``: materialize the fp32 sim tiles once (in the stats
    sweep) and stream them back in every later sweep instead of
    recomputing the fp32-HIGHEST matmul — bit-identical, much faster,
    but holds the N x N fp32 matrix in HBM through the step.  Default
    ``None`` auto-enables it when that matrix is at most
    ``SIM_CACHE_AUTO_BYTES``; pass ``False`` to force the O(N x block)
    streaming-memory behavior.

    ``pos_topk``: K-slot sparse-positive fast path for RELATIVE_* AP
    mining (see ``_thresholds``): the stats sweep extracts each query's
    K largest same-label sims, and when every query's positive count
    fits the buffer the AP threshold needs no digit sweeps — the
    flagship config then streams as few passes as absolute mining.  A
    runtime ``lax.cond`` falls back to radix selection when a label
    group overflows, so the result is exact for any labels.  Default
    ``None`` = auto (8 slots — covers per-query positive counts up to
    8, i.e. identity-balanced sampling with up to NINE images per
    identity in the pool); 0 disables the buffer entirely.

    ``matmul_precision``: ``None``/``"highest"`` for oracle bit-parity;
    ``"default"`` opts every kernel gemm into the ~6x single-pass bf16
    MXU mode (see ``ops.npair_loss.resolve_matmul_precision`` — a
    throughput mode, not a parity mode).
    """
    if interpret is None:
        interpret = default_interpret()
    n = features.shape[0]
    bm = int(min(block_size, max(n, 1)))
    bn = int(min(q_block_size or block_size, max(n, 1)))
    if not interpret:
        # Mosaic requires block dims divisible by the (8, 128) tiling
        # (unless equal to the full padded dim); the block index appears
        # as both a sublane dim (matrix tiles) and a lane dim ((1, b)
        # stat vectors), so round to 128.  _pad_rows absorbs overshoot.
        bn, bm = _round_up(bn, 128), _round_up(bm, 128)
    if sim_cache is None:
        n_p, m_p = _round_up(n, bn), _round_up(n, bm)
        sim_cache = resolve_sim_cache_auto(n_p * m_p * 4, "blockwise")
    if pos_topk is None:
        pos_topk = 8
    if int(pos_topk) < 0:
        raise ValueError(f"pos_topk must be >= 0, got {pos_topk}")
    # fp32 (8, 128) tiling: the K-slot buffer's sublane dim must be a
    # multiple of 8 (extra slots just carry more padding).
    pos_topk = _round_up(int(pos_topk), 8) if pos_topk else 0
    return _blockwise_core(
        features, labels, cfg, bn, bm, interpret, bool(sim_cache),
        pos_topk, matmul_precision
    )


def blockwise_npair_loss(features, labels, cfg=NPairLossConfig(),
                         block_size: int = 512,
                         q_block_size: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         sim_cache: Optional[bool] = None,
                         pos_topk: Optional[int] = None,
                         matmul_precision: Optional[str] = None) -> jax.Array:
    """Scalar blockwise N-pair loss (see ``blockwise_npair_loss_with_aux``)."""
    return blockwise_npair_loss_with_aux(
        features, labels, cfg, block_size, q_block_size, interpret,
        sim_cache, pos_topk, matmul_precision
    )[0]


# ---------------------------------------------------------------------------
# Streamed retrieval metrics (pure-JAX scan; no N x M matrix)
# ---------------------------------------------------------------------------


def blockwise_retrieval_metrics(
    features: jax.Array,
    labels: jax.Array,
    top_ks: Sequence[int] = (1, 5, 10),
    block_size: int = 512,
) -> Dict[str, jax.Array]:
    """Recall@k + feature_asum with the reference's exact threshold/tie
    semantics (cu:182-197), streaming the pool in blocks via lax.scan.

    Keeps a running top-(k_max+1) list per query (exp is monotone, so raw
    similarities give identical ranks to the reference's exp'd rows).
    """
    features = features.astype(jnp.float32)
    labels = _canon_labels(labels)
    n = features.shape[0]
    neg = jnp.float32(-FLT_MAX)
    k_max = max(top_ks)
    block = int(min(block_size, max(n, 1)))
    pool = _pad_rows(features, block)
    pool_labels = _pad_rows(labels, block)
    nblocks = pool.shape[0] // block
    pool = pool.reshape(nblocks, block, -1)
    pool_labels = pool_labels.reshape(nblocks, block)

    def step(carry, blk):
        top_sims, top_same = carry
        bf, bl, idx = blk
        sims = jnp.dot(
            features, bf.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        col = idx * block + jnp.arange(block, dtype=jnp.int32)[None, :]
        row = jnp.arange(n, dtype=jnp.int32)[:, None]
        nonself = (col != row) & (col < n)
        same = (labels[:, None] == bl[None, :]) & nonself
        cat_sims = jnp.concatenate(
            [top_sims, jnp.where(nonself, sims, neg)], axis=1
        )
        cat_same = jnp.concatenate([top_same, same], axis=1)
        top_sims, idx2 = jax.lax.top_k(cat_sims, top_sims.shape[1])
        top_same = jnp.take_along_axis(cat_same, idx2, axis=1)
        return (top_sims, top_same), None

    carry = (
        jnp.full((n, k_max + 1), neg),
        jnp.zeros((n, k_max + 1), bool),
    )
    (top_sims, top_same), _ = jax.lax.scan(
        step, carry,
        (pool, pool_labels, jnp.arange(nblocks, dtype=jnp.int32)),
    )

    out: Dict[str, jax.Array] = {}
    for k in top_ks:
        thr_idx = min(k, n - 2)
        thr = top_sims[:, thr_idx]
        hit = jnp.any((top_sims > thr[:, None]) & top_same, axis=1)
        out[f"retrieve_top{k}"] = hit.sum().astype(jnp.float32) / jnp.float32(n)
    out["feature_asum"] = jnp.abs(features).sum() / jnp.float32(n)
    return out
