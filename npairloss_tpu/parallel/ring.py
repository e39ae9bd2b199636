"""Ring-blockwise N-pair loss: pod-scale negative pools without the matrix.

The reference materializes the full N x (N*G) pair-similarity matrix after
an MPI_Allgather of every rank's embeddings (reference:
npair_multi_class_loss.cu:17-43, cu:218).  That is O(N^2 G) memory per
rank — fine at G=8, fatal for the 32k-batch stretch config
(BASELINE.json) where the gathered pool no longer fits HBM.

This module is the contrastive-learning transplant of ring attention
(SURVEY.md §5.7): instead of gathering the pool, each shard's feature
block circulates around the mesh axis via ``jax.lax.ppermute`` while
every shard streams its N x N_block similarity tile on the MXU,
reducing online.  Memory is O(N x N_block); the interconnect carries
each block exactly G-1 hops per pass, and XLA overlaps the ppermute
with the tile matmul.

Three ring passes per step:

  1. **stats**: per-query min-within / max-between / max-all running
     reductions (the mining statistics of cu:229-265) — plus running
     top-(k+1) similarity/label lists for Recall@k.
  2. **loss**: selection mask from the absolute thresholds, stabilized
     exp, running I_q/D_q sums (cu:343-388 semantics).
  3. **backward**: the weight tile w = (-p1+p2+p3)*g/N is recomputed
     per block; the query-role grad accumulates locally while the
     database-role grad rides the ring WITH its feature block, arriving
     at the block's owner as the full cross-shard sum — exactly what the
     reference's MPI_Allreduce produces (cu:462-489) — then merged
     0.5/0.5 with the query-role grad (cu:492-497).

Mining-method support: ALL methods are exact.  Absolute (HARD / EASY /
RAND) thresholds are streamed min/max reductions.  RELATIVE_* needs
rank statistics over the full pair population — the reference sorts the
whole N x (N*G) block on the host (cu:266-273); here the k-th smallest
masked pair value is recovered EXACTLY by MSD radix selection over
sortable float bit-keys: NUM_DIGITS ring passes, each histogramming one
RADIX_BITS-bit digit of the monotone uint32 key via scatter-free
compare-and-reduce, narrow to the target element's exact bit pattern
(SURVEY.md §7's "distributed top-k" growth path).  When both sides
are relative, that costs NUM_DIGITS-1 extra passes total — the digit-0
histogram rides the stats pass for free, and later digits share one
pass across sides.  When only the POSITIVE side is relative (the
flagship def.prototxt config), the sparse-positive fast path applies:
identity-balanced sampling gives each query only a handful of
positives, so the stats pass keeps a K-slot buffer of the largest
same-label sims and the AP threshold is an N x K sort — ZERO extra
ring passes, with a mesh-uniform runtime fallback to radix selection
for labels that overflow the buffer.

Memory is O(N x N_block) with ``sim_cache=False``.  By default
(``sim_cache=None``) the engine keeps this shard's (G, N, N) fp32
slice of the pair matrix from the stats pass whenever it fits under
``SIM_CACHE_AUTO_BYTES`` — the later passes then replay the cached
tiles (the radix/loss passes with NO ppermute and no matmul recompute,
the backward ring reusing tiles while the gradient still travels), at
the cost of holding that slice through the step (and through the
model backward, via the VJP residuals).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
    selection_mask,
    topk_relative_threshold,
)
from npairloss_tpu.ops.rank_select import (
    NUM_DIGITS,
    RADIX_BINS,
    masked_digit_hist,
    population_count_dtype,
    radix_begin,
    radix_finish,
    radix_update,
)

_RELATIVE = (MiningMethod.RELATIVE_HARD, MiningMethod.RELATIVE_EASY)


def ring_supported(cfg: NPairLossConfig) -> bool:
    """Every mining configuration streams (RELATIVE_* via radix select)."""
    return True


def _check_cfg(cfg: NPairLossConfig) -> None:
    pass  # all configs supported; kept for API stability


# Every ring gemm (sim tiles + the two gradient-role gemms) reads the
# trace-time precision ContextVar shared with the other engines —
# see ops.npair_loss.matmul_precision_ctx / active_matmul_precision.
_precision_ctx = matmul_precision_ctx


def _tile(
    feats: jax.Array, block_f: jax.Array
) -> jax.Array:
    """One N x N_block similarity tile on the MXU, fp32 accumulate."""
    return jnp.dot(
        feats,
        block_f.T,
        preferred_element_type=jnp.float32,
        precision=active_matmul_precision(),
    )


def _block_masks(
    labels: jax.Array,
    block_labels: jax.Array,
    my_rank: jax.Array,
    block_rank: jax.Array,
    n_local: int,
) -> Tuple[jax.Array, jax.Array]:
    """same/diff masks for one tile; self-pair excluded when the tile is
    this shard's own block (cu:54 semantics on the tiled grid)."""
    same_lbl = labels[:, None] == block_labels[None, :]
    eye = jnp.eye(n_local, dtype=bool)
    self_pair = jnp.where(my_rank == block_rank, eye, jnp.zeros_like(eye))
    same = same_lbl & ~self_pair
    diff = (~same_lbl) & ~self_pair
    return same, diff


def _pvary(tree, axis_name: str):
    """Mark fresh (replicated) carry values as device-varying so the scan
    carry type stays stable under shard_map's manual-axes tracking."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.pcast(x, (axis_name,), to="varying"), tree
    )


def _ring_scan(axis_name: str, body, carry, rotating):
    """Run ``body(carry, rotating, step) -> (carry, rotating)`` G times,
    ppermuting ``rotating`` one hop forward between steps.  Shard r
    therefore sees block (r - step) mod G at step ``step``; after G hops
    every rotating value is back at its owner."""
    g = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % g) for i in range(g)]
    carry = _pvary(carry, axis_name)

    def step_fn(state, step):
        carry, rotating = state
        carry, rotating = body(carry, rotating, step)
        # comm/ scope = the fleet observatory's exchange-path marker
        # (obs.fleet.comms): the hop's collective-permutes carry it in
        # their HLO op_name metadata; the program itself is unchanged.
        with jax.named_scope("comm/ppermute"):
            rotating = jax.tree_util.tree_map(
                lambda x: jax.lax.ppermute(x, axis_name, perm), rotating
            )
        return (carry, rotating), None

    (carry, rotating), _ = jax.lax.scan(
        step_fn, (carry, rotating), jnp.arange(g)
    )
    return carry, rotating


def _cache_scan(cache, accum, carry, axis_name: str):
    """Replay the cached hop tiles locally — ``accum(carry, sims,
    block_labels, block_rank) -> carry`` over the stats pass's hop order.
    No sim recompute, no ppermute: the pass costs one stream of the
    cached slice."""
    def step_fn(c, inp):
        sims, bl, br = inp
        return accum(c, sims, bl, br), None

    carry, _ = jax.lax.scan(
        step_fn, _pvary(carry, axis_name),
        (cache["sims_cache"], cache["labels_cache"], cache["rank_cache"]),
    )
    return carry


# ---------------------------------------------------------------------------
# Pass 1: mining statistics + retrieval top-k
# ---------------------------------------------------------------------------


def _stats_pass(
    feats, labels, my_rank, axis_name: str, top_k_max: int,
    hist0_same: bool = False, hist0_diff: bool = False,
    emit_sims: bool = False, topk_same_k: int = 0,
):
    """Mining statistics in one ring pass; optionally also the digit-0
    radix histograms for RELATIVE_* sides — digit 0 needs no prefix, so
    accumulating it here saves one whole ring pass per relative side —
    and optionally the per-shard similarity cache: the (G, N, N) stack
    of this shard's sim tiles in hop order, plus each hop's block labels
    and rank.  The rotation schedule is deterministic (shard r sees
    block (r - s) mod G at step s), so every later pass can replay the
    cache instead of recomputing tiles — and the selection/loss passes
    then need no ppermute at all."""
    n_local = feats.shape[0]
    g = jax.lax.axis_size(axis_name)
    neg = jnp.float32(-FLT_MAX)
    pos = jnp.float32(FLT_MAX)
    zero_prefix = jnp.zeros((n_local,), jnp.uint32)

    carry = {
        "min_within": jnp.full((n_local,), pos),
        "max_between": jnp.full((n_local,), neg),
        "max_all": jnp.full((n_local,), neg),
        # Pair-population sizes per query, for RELATIVE rank targets
        # (the list sizes of cu:266-273).
        "count_same": jnp.zeros((n_local,), jnp.int32),
        "count_diff": jnp.zeros((n_local,), jnp.int32),
        # Running top-(k+1) non-self sims and a same-label flag for each,
        # for the Recall@k threshold semantics (cu:190-197).
        "top_sims": jnp.full((n_local, top_k_max + 1), neg),
        "top_same": jnp.zeros((n_local, top_k_max + 1), bool),
    }
    if hist0_same:
        carry["hist0_same"] = jnp.zeros((n_local, RADIX_BINS), jnp.int32)
    if hist0_diff:
        carry["hist0_diff"] = jnp.zeros((n_local, RADIX_BINS), jnp.int32)
    if topk_same_k:
        # Sparse-positive fast path: the K largest same-label sims per
        # query, maintained across hops (values are the SAME tile sims
        # the stats/histograms read, so thresholds built from the buffer
        # are bit-identical to radix selection over the ring).
        carry["topk_same"] = jnp.full((n_local, topk_same_k), neg)
    if emit_sims:
        carry["sims_cache"] = jnp.zeros((g, n_local, n_local), jnp.float32)
        carry["labels_cache"] = jnp.zeros((g,) + labels.shape, labels.dtype)
        carry["rank_cache"] = jnp.zeros((g,), jnp.int32)
    rotating = {
        "f": feats,
        "l": labels,
        "rank": my_rank,
    }

    def body(c, rot, step):
        sims = _tile(feats, rot["f"])
        same, diff = _block_masks(labels, rot["l"], my_rank, rot["rank"], n_local)
        c = dict(c)
        if emit_sims:
            c["sims_cache"] = c["sims_cache"].at[step].set(sims)
            c["labels_cache"] = c["labels_cache"].at[step].set(rot["l"])
            c["rank_cache"] = c["rank_cache"].at[step].set(rot["rank"])
        c["min_within"] = jnp.minimum(
            c["min_within"], jnp.where(same, sims, pos).min(axis=1)
        )
        c["max_between"] = jnp.maximum(
            c["max_between"], jnp.where(diff, sims, neg).max(axis=1)
        )
        c["max_all"] = jnp.maximum(
            c["max_all"], jnp.where(same | diff, sims, neg).max(axis=1)
        )
        c["count_same"] = c["count_same"] + same.sum(axis=1, dtype=jnp.int32)
        c["count_diff"] = c["count_diff"] + diff.sum(axis=1, dtype=jnp.int32)
        if hist0_same:
            c["hist0_same"] = c["hist0_same"] + masked_digit_hist(
                sims, same, zero_prefix, 0
            )
        if hist0_diff:
            c["hist0_diff"] = c["hist0_diff"] + masked_digit_hist(
                sims, diff, zero_prefix, 0
            )
        if topk_same_k:
            c["topk_same"] = jax.lax.top_k(
                jnp.concatenate(
                    [c["topk_same"], jnp.where(same, sims, neg)], axis=1
                ),
                topk_same_k,
            )[0]
        nonself = same | diff
        cat_sims = jnp.concatenate(
            [c["top_sims"], jnp.where(nonself, sims, neg)], axis=1
        )
        cat_same = jnp.concatenate([c["top_same"], same], axis=1)
        top_sims, idx = jax.lax.top_k(cat_sims, c["top_sims"].shape[1])
        c["top_sims"] = top_sims
        c["top_same"] = jnp.take_along_axis(cat_same, idx, axis=1)
        return c, rot

    carry, _ = _ring_scan(axis_name, body, carry, rotating)
    return carry


# ---------------------------------------------------------------------------
# Streamed RELATIVE thresholds: exact MSD radix selection over the ring
# ---------------------------------------------------------------------------


def _multi_digit_hist_pass(
    feats, labels, my_rank, axis_name: str, sides, digit: int, cache=None,
):
    """One pass accumulating masked digit histograms for EVERY active
    RELATIVE side at once — the N x N_block sim tile (the expensive
    part) is computed once and feeds both masks.  With the similarity
    cache the pass is a LOCAL scan over the cached tiles (no sim
    recompute, no ppermute); without it, one ring rotation.

    ``sides``: dict side-name -> (use_same, prefix).
    Returns dict side-name -> int32 [N, RADIX_BINS].
    """
    n_local = feats.shape[0]
    carry = {s: jnp.zeros((n_local, RADIX_BINS), jnp.int32) for s in sides}

    def accum(c, sims, blk_labels, blk_rank):
        same, diff = _block_masks(
            labels, blk_labels, my_rank, blk_rank, n_local
        )
        c = dict(c)
        for s, (use_same, prefix) in sides.items():
            mask = same if use_same else diff
            c[s] = c[s] + masked_digit_hist(sims, mask, prefix, digit)
        return c

    if cache is not None:
        return _cache_scan(cache, accum, carry, axis_name)

    rotating = {"f": feats, "l": labels, "rank": my_rank}

    def body(c, rot, step):
        return accum(c, _tile(feats, rot["f"]), rot["l"], rot["rank"]), rot

    carry, _ = _ring_scan(axis_name, body, carry, rotating)
    return carry


def _ring_thresholds(
    feats, labels, my_rank, axis_name: str, cfg: NPairLossConfig, stats,
    cache=None,
):
    """(pos_thr, neg_thr) for any mining config: absolute from streamed
    min/max stats, RELATIVE_* via exact stepwise radix selection.

    Reproduces the dense ``_local/_global_relative_threshold`` semantics
    (ascending sort + ``_relative_pos`` index + ``< 0 -> -FLT_MAX``
    clamp, reference cu:275-337) without the pair matrix.  GLOBAL region
    ranks over this rank's whole flattened N x (N*G) block (cu:296,
    cu:327), LOCAL per query; block populations beyond 2^31 pairs use
    64-bit counts (requires jax_enable_x64) or fail loudly at trace
    time — int32 would wrap and silently mis-rank.

    Cost: the digit-0 histogram comes FREE from the stats pass (digit 0
    has no prefix), and later digits share one ring pass per digit
    across the AP and AN sides — so RELATIVE mining costs NUM_DIGITS-1
    extra ring passes total whether one or both sides are relative.
    """
    pos_thr, neg_thr = absolute_thresholds(
        stats["min_within"], stats["max_between"], cfg
    )
    ap_rel = cfg.ap_mining_method in _RELATIVE
    an_rel = cfg.an_mining_method in _RELATIVE
    if not (ap_rel or an_rel):
        return pos_thr, neg_thr

    # Sparse-positive fast path (see ops.pallas_npair._thresholds): when
    # AP is the only relative side and every query's positive count fits
    # the stats pass's K-slot buffer, the per-rank threshold is an
    # N x K sort — zero extra ring passes.  The cond predicate must be
    # IDENTICAL on every shard (the radix branch runs ppermute
    # collectives; shards disagreeing on the branch would deadlock), so
    # the overflow check is pmax-reduced over the mesh axis.
    if ap_rel and not an_rel and "topk_same" in stats:
        def radix(include_ap):
            return _ring_radix_thresholds(
                feats, labels, my_rank, axis_name, cfg, stats, cache,
                pos_thr, neg_thr, include_ap=include_ap,
                include_an=an_rel)

        kcap = stats["topk_same"].shape[1]
        # comm marker (obs.fleet.comms): pmax lowers to a (scalar)
        # all-reduce — unscoped, its bytes would be silently absorbed
        # by the grad-sync allreduce CLAIM in the fleet reconciliation
        # instead of being marker-attributed.
        with jax.named_scope("comm/allreduce"):
            fits = jax.lax.pmax(
                stats["count_same"].max(), axis_name) <= kcap

        def fast(_):
            n_local = feats.shape[0]
            g = jax.lax.axis_size(axis_name)
            p = topk_relative_threshold(
                stats["topk_same"], stats["count_same"], cfg.identsn,
                cfg.ap_mining_region,
                count_dtype=population_count_dtype(n_local * n_local * g))
            return p, radix(False)[1]

        return jax.lax.cond(fits, fast, lambda _: radix(True), 0)

    return _ring_radix_thresholds(
        feats, labels, my_rank, axis_name, cfg, stats, cache,
        pos_thr, neg_thr, include_ap=ap_rel, include_an=an_rel)


def _ring_radix_thresholds(
    feats, labels, my_rank, axis_name: str, cfg: NPairLossConfig, stats,
    cache, pos_thr, neg_thr, include_ap, include_an,
):
    """The streamed radix-selection path of ``_ring_thresholds`` (see
    there), restricted to the requested sides."""
    sides = {}
    if include_ap:
        sides["ap"] = (True, cfg.identsn, cfg.ap_mining_region,
                       stats["count_same"], stats["hist0_same"])
    if include_an:
        sides["an"] = (False, cfg.diffsn, cfg.an_mining_region,
                       stats["count_diff"], stats["hist0_diff"])
    if not sides:
        return pos_thr, neg_thr

    n_local = feats.shape[0]
    g = jax.lax.axis_size(axis_name)

    def prep_hist(side, hist):
        """Global-region sides rank over the whole block: sum the
        per-query histograms (in the overflow-safe dtype) and share."""
        _, _, region, _, _ = sides[side]
        if region == MiningRegion.GLOBAL:
            cdt = population_count_dtype(n_local * n_local * g)
            hist = jnp.broadcast_to(
                hist.sum(axis=0, keepdims=True, dtype=cdt),
                (n_local, RADIX_BINS),
            )
        return hist

    states, empties = {}, {}
    for s, (use_same, sn, region, counts, hist0) in sides.items():
        if region == MiningRegion.GLOBAL:
            cdt = population_count_dtype(n_local * n_local * g)
            total = counts.astype(cdt).sum()
            k = jnp.broadcast_to(_relative_pos(total[None], sn)[0], (n_local,))
            empties[s] = jnp.broadcast_to(total == 0, (n_local,))
        else:
            k = _relative_pos(counts, sn)
            empties[s] = counts == 0
        states[s] = radix_update(radix_begin(k), prep_hist(s, hist0))

    for digit in range(1, NUM_DIGITS):
        hists = _multi_digit_hist_pass(
            feats, labels, my_rank, axis_name,
            {s: (sides[s][0], states[s][1]) for s in sides}, digit,
            cache=cache,
        )
        for s in sides:
            states[s] = radix_update(states[s], prep_hist(s, hists[s]))

    vals = {
        s: _clamp_negative(radix_finish(states[s], empties[s]))
        for s in sides
    }
    return vals.get("ap", pos_thr), vals.get("an", neg_thr)


# ---------------------------------------------------------------------------
# Pass 2: selection + stabilized exp sums (+ counts)
# ---------------------------------------------------------------------------


def _loss_pass(
    feats, labels, my_rank, pos_thr, neg_thr, max_all, cfg, axis_name: str,
    cache=None,
):
    n_local = feats.shape[0]
    carry = {
        "ident_sum": jnp.zeros((n_local,), jnp.float32),
        "diff_sum": jnp.zeros((n_local,), jnp.float32),
        "ident_num": jnp.zeros((n_local,), jnp.float32),
        "diff_num": jnp.zeros((n_local,), jnp.float32),
    }

    def accum(c, sims, blk_labels, blk_rank):
        same, diff = _block_masks(labels, blk_labels, my_rank, blk_rank, n_local)
        sel = selection_mask(sims, same, diff, pos_thr, neg_thr, cfg)
        sel_pos = same & sel
        sel_neg = diff & sel
        sim_exp = jnp.exp(sims - max_all[:, None])
        c = dict(c)
        c["ident_sum"] = c["ident_sum"] + jnp.where(sel_pos, sim_exp, 0.0).sum(1)
        c["diff_sum"] = c["diff_sum"] + jnp.where(sel_neg, sim_exp, 0.0).sum(1)
        c["ident_num"] = c["ident_num"] + sel_pos.sum(1).astype(jnp.float32)
        c["diff_num"] = c["diff_num"] + sel_neg.sum(1).astype(jnp.float32)
        return c

    if cache is not None:
        return _cache_scan(cache, accum, carry, axis_name)

    rotating = {"f": feats, "l": labels, "rank": my_rank}

    def body(c, rot, step):
        return accum(c, _tile(feats, rot["f"]), rot["l"], rot["rank"]), rot

    carry, _ = _ring_scan(axis_name, body, carry, rotating)
    return carry


# ---------------------------------------------------------------------------
# Pass 3 (backward): ring allreduce of database-role grads
# ---------------------------------------------------------------------------


def _backward_pass(
    feats,
    labels,
    my_rank,
    pos_thr,
    neg_thr,
    max_all,
    ident_sum,
    all_sum,
    cfg,
    axis_name: str,
    g_loss,
    grad_mode: str,
    cache=None,
):
    n_local, dim = feats.shape
    num_shards = jax.lax.axis_size(axis_name)

    def weight_tile(sims, same, diff):
        sel = selection_mask(sims, same, diff, pos_thr, neg_thr, cfg)
        sim_exp = jnp.exp(sims - max_all[:, None])
        exp_pos = jnp.where(same & sel, sim_exp, 0.0)
        exp_neg = jnp.where(diff & sel, sim_exp, 0.0)

        def safe(num, den):
            ok = den != 0
            return jnp.where(
                ok[:, None], num / jnp.where(ok, den, 1.0)[:, None], 0.0
            )

        p1 = safe(exp_pos, ident_sum)
        p2 = safe(exp_pos, all_sum)
        p3 = safe(exp_neg, all_sum)
        w = (-p1 + p2 + p3) * (g_loss / jnp.float32(n_local))
        if grad_mode != "reference":
            # "true" autodiff of the guarded log (cu:162-169 semantics)
            # gives exactly 0 for zero-loss queries; the reference path
            # keeps p3 alive for identNum==0 queries (cu:133-146).
            valid = (ident_sum != 0) & (all_sum != 0)
            w = jnp.where(valid[:, None], w, 0.0)
        return w

    carry = {"grad_query": jnp.zeros((n_local, dim), jnp.float32)}
    rotating = {
        "f": feats,
        "l": labels,
        "rank": my_rank,
        # The database-role grad for the block travels WITH the block;
        # after G hops it returns to the owner holding the full sum —
        # the ring equivalent of MPI_Allreduce(SUM) (cu:467-488).
        "grad_db": jnp.zeros((n_local, dim), jnp.float32),
    }

    rotating["grad_db"] = jax.lax.pcast(
        rotating["grad_db"], (axis_name,), to="varying")

    def body(c, rot, step):
        # The block still has to rotate (its feats feed the two gemms and
        # the traveling grad rides with it), but the sim tile can replay
        # from the cache: hop order here matches the stats pass exactly.
        if cache is not None:
            sims = cache["sims_cache"][step]
        else:
            sims = _tile(feats, rot["f"])
        same, diff = _block_masks(labels, rot["l"], my_rank, rot["rank"], n_local)
        w = weight_tile(sims, same, diff)
        c = dict(c)
        c["grad_query"] = c["grad_query"] + jnp.dot(
            w, rot["f"],
            preferred_element_type=jnp.float32,
            precision=active_matmul_precision(),
        )
        rot = dict(rot)
        rot["grad_db"] = rot["grad_db"] + jnp.dot(
            w.T, feats,
            preferred_element_type=jnp.float32,
            precision=active_matmul_precision(),
        )
        return c, rot

    carry, rotating = _ring_scan(axis_name, body, carry, rotating)
    # After G hops every block is back home: rotating["grad_db"] is this
    # shard's database-role grad summed over all shards.
    grad_db = rotating["grad_db"]
    grad_query = carry["grad_query"]
    if grad_mode == "reference":
        # 1/G allreduce scale (cu:474) + 0.5/0.5 role merge (cu:492-497).
        return 0.5 * grad_db / jnp.float32(num_shards) + 0.5 * grad_query
    return grad_query + grad_db


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _ring_core(features, labels, cfg, axis_name, top_ks, sim_cache,
               pos_topk, matmul_precision):
    out, _ = _ring_fwd_impl(
        features, labels, cfg, axis_name, top_ks, sim_cache, pos_topk,
        matmul_precision
    )
    return out


def _ring_fwd_impl(features, labels, cfg, axis_name, top_ks, sim_cache,
                   pos_topk=0, matmul_precision=None):
    with _precision_ctx(matmul_precision):
        return _ring_fwd_traced(
            features, labels, cfg, axis_name, top_ks, sim_cache, pos_topk)


def _ring_fwd_traced(features, labels, cfg, axis_name, top_ks, sim_cache,
                     pos_topk=0):
    features = features.astype(jnp.float32)
    n_local = features.shape[0]
    my_rank = jax.lax.axis_index(axis_name).astype(jnp.int32)

    ap_rel = cfg.ap_mining_method in _RELATIVE
    an_rel = cfg.an_mining_method in _RELATIVE
    top_k_max = max(top_ks) if top_ks else 1
    stats = _stats_pass(
        features, labels, my_rank, axis_name, top_k_max,
        hist0_same=ap_rel,
        hist0_diff=an_rel,
        emit_sims=sim_cache,
        # The K-slot buffer only pays when AP is the sole relative side
        # (see _ring_thresholds).
        topk_same_k=pos_topk if ap_rel and not an_rel else 0,
    )
    cache = None
    if sim_cache:
        cache = {k: stats[k]
                 for k in ("sims_cache", "labels_cache", "rank_cache")}
    pos_thr, neg_thr = _ring_thresholds(
        features, labels, my_rank, axis_name, cfg, stats, cache=cache
    )
    sums = _loss_pass(
        features, labels, my_rank, pos_thr, neg_thr, stats["max_all"],
        cfg, axis_name, cache=cache,
    )
    ident_sum = sums["ident_sum"]
    all_sum = ident_sum + sums["diff_sum"]
    valid = (ident_sum != 0) & (all_sum != 0)
    log_q = jnp.where(
        valid, jnp.log(jnp.where(valid, ident_sum / all_sum, 1.0)), 0.0
    )
    loss = -log_q.sum() / jnp.float32(n_local)

    # Recall@k from the streamed top-(k+1) lists.  Threshold = the
    # descending-sorted value at index min(k, size-1) over the exp'd row
    # (cu:190); exp is monotone, so raw-sim comparison is equivalent.
    n_total_minus1 = n_local * jax.lax.axis_size(axis_name) - 1
    metrics: Dict[str, jax.Array] = {}
    for k in top_ks:
        thr_idx = jnp.minimum(k, n_total_minus1 - 1)
        thr = jnp.take_along_axis(
            stats["top_sims"], jnp.full((n_local, 1), thr_idx), axis=1
        )[:, 0]
        hit = jnp.any(
            (stats["top_sims"] > thr[:, None]) & stats["top_same"], axis=1
        )
        metrics[f"retrieve_top{k}"] = (
            hit.sum().astype(jnp.float32) / jnp.float32(n_local)
        )
    metrics["feature_asum"] = (
        jnp.abs(features).sum() / jnp.float32(n_local)
    )
    metrics["ident_num"] = sums["ident_num"].sum()
    metrics["diff_num"] = sums["diff_num"].sum()

    residuals = {
        "features": features,
        "labels": labels,
        "pos_thr": pos_thr,
        "neg_thr": neg_thr,
        "max_all": stats["max_all"],
        "ident_sum": ident_sum,
        "all_sum": all_sum,
        # The cached sim tiles ride the residuals so the backward ring
        # replays instead of recomputing; None when caching is off.
        "cache": cache,
    }
    return (loss, metrics), residuals


def _ring_fwd(features, labels, cfg, axis_name, top_ks, sim_cache,
              pos_topk, matmul_precision):
    return _ring_fwd_impl(
        features, labels, cfg, axis_name, top_ks, sim_cache, pos_topk,
        matmul_precision
    )


def _ring_bwd(cfg, axis_name, top_ks, sim_cache, pos_topk,
              matmul_precision, res, cotangents):
    with _precision_ctx(matmul_precision):
        return _ring_bwd_traced(
            cfg, axis_name, top_ks, sim_cache, pos_topk, res, cotangents)


def _ring_bwd_traced(cfg, axis_name, top_ks, sim_cache, pos_topk, res,
                     cotangents):
    g_loss, _ = cotangents  # metrics are monitors, non-differentiable
    my_rank = jax.lax.axis_index(axis_name).astype(jnp.int32)
    d_features = _backward_pass(
        res["features"],
        res["labels"],
        my_rank,
        res["pos_thr"],
        res["neg_thr"],
        res["max_all"],
        res["ident_sum"],
        res["all_sum"],
        cfg,
        axis_name,
        g_loss,
        cfg.grad_mode,
        cache=res["cache"],
    )
    labels = res["labels"]
    if jnp.issubdtype(labels.dtype, jnp.floating):
        d_labels = jnp.zeros(labels.shape, labels.dtype)
    else:
        d_labels = np.zeros(labels.shape, jax.dtypes.float0)
    return d_features, d_labels


_ring_core.defvjp(_ring_fwd, _ring_bwd)


def ring_npair_loss_and_metrics(
    features: jax.Array,
    labels: jax.Array,
    cfg: NPairLossConfig = NPairLossConfig(),
    axis_name: str = "dp",
    top_ks: Sequence[int] = (1, 5, 10),
    sim_cache: Optional[bool] = None,
    pos_topk: Optional[int] = None,
    matmul_precision: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Blockwise-ring N-pair loss + retrieval metrics for one shard.

    Call under ``shard_map`` over ``axis_name``.  Semantically identical
    to ``npair_loss_with_aux`` + ``retrieval_metrics`` for absolute
    mining methods, but the pool is never gathered: blocks stream over
    the ring, and memory is O(N x N_block) — unless ``sim_cache`` is
    active (the default when the (G, N, N) slice fits, see below).

    Gradient semantics follow ``cfg.grad_mode`` exactly like the dense
    path ("reference": 0.5/0.5 role merge with the 1/G allreduce scale).

    ``sim_cache``: keep this shard's (G, N, N) stack of sim tiles from
    the stats pass and replay it in the later passes — the radix-digit
    and loss passes then run locally with no ppermute and no fp32
    matmul recompute, and the backward ring reuses the tiles.
    Bit-identical to recompute.  Default ``None`` auto-enables when the
    slice is at most ``SIM_CACHE_AUTO_BYTES``; ``False`` restores pure
    O(N x N_block) streaming memory.

    ``pos_topk``: K-slot sparse-positive fast path for RELATIVE_* AP
    mining (see ``_ring_thresholds``): the stats pass keeps each
    query's K largest same-label sims, and when every positive count
    fits the buffer the AP threshold costs zero extra ring passes — the
    flagship config then streams as few passes as absolute mining.  A
    mesh-uniform ``lax.cond`` falls back to radix selection when a
    label group overflows.  Default ``None`` = auto (8 slots); 0
    disables the buffer.

    ``matmul_precision``: ``None``/``"highest"`` for oracle bit-parity;
    ``"default"`` opts every ring gemm into the ~6x single-pass bf16
    MXU mode (see ``ops.npair_loss.resolve_matmul_precision``).
    """
    _check_cfg(cfg)
    if sim_cache is None:
        g = jax.lax.axis_size(axis_name)
        n = features.shape[0]
        sim_cache = resolve_sim_cache_auto(g * n * n * 4, "ring")
    pos_topk = 8 if pos_topk is None else int(pos_topk)
    if pos_topk < 0:
        raise ValueError(f"pos_topk must be >= 0, got {pos_topk}")
    return _ring_core(
        features, labels, cfg, axis_name, tuple(top_ks), bool(sim_cache),
        pos_topk, matmul_precision
    )
