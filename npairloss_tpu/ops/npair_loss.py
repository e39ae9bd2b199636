"""TPU-native multi-class N-pair metric-learning loss.

Re-implements — as ONE pure, jit-compatible JAX function — the semantics of
the reference Caffe CUDA+MPI layer ``NPairMultiClassLossLayer``
(reference: npair_multi_class_loss.cu:207-499).  Where the reference runs

    MPI_Allgather -> cuBLAS gemm -> 2 CUDA mask kernels
    -> an O(N^2 G) *CPU* mining loop with std::sort
    -> selection kernel -> exp/stabilize kernel -> gemv reductions
    -> loss kernel -> host-side metric loop,

with device<->host round-trips between every stage, this implementation is a
single XLA graph: ``jax.lax.all_gather`` over the mesh axis replaces
MPI_Allgather (cu:17-43), the similarity matrix hits the MXU as one matmul
(cu:218), mining statistics become masked fixed-shape sorts/reductions
(cu:222-337), and the loss is a numerically-stabilized masked softmax
(cu:362-388).  The analytic backward (cu:420-499) — including its
non-obvious 0.5/0.5 query-role/database-role averaging and 1/G allreduce
scaling — is provided as a ``jax.custom_vjp``.

Mining semantics grid (cu:277-337 thresholds, cu:69-122 selection):

  region  = GLOBAL(0) | LOCAL(1)                 # over this rank's N x N*G block
  method  = HARD | EASY | RAND | RELATIVE_HARD | RELATIVE_EASY

Reference quirks that are preserved bit-for-bit (each has a named test):
  * RAND selects ALL pairs — there is no randomness (cu:88-89, cu:109-110).
  * RELATIVE thresholds whose looked-up value is < 0 clamp to -FLT_MAX
    (cu:288, cu:303, cu:319, cu:334).
  * sn >= 0 means an absolute rank from the sorted top; sn < 0 means the top
    |sn| fraction, with C truncation-toward-zero (cu:285-287 etc.).
  * Zero-count queries contribute exactly 0 loss (cu:133-154, cu:162-169).
  * The self-pair (local row q == gathered column rank*N + q) is excluded
    from both masks (cu:54).
  * The backward's dot_normalizer is N (query count), while the forward's is
    1 (cu:216 vs cu:427).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import enum
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from npairloss_tpu.ops.rank_select import masked_digit_hist, radix_select

FLT_MAX = float(np.finfo(np.float32).max)

# Auto-enable a streaming engine's fp32 similarity cache when the cached
# slice is at most this many bytes.  Shared by ops.pallas_npair and
# parallel.ring.  ``resolve_sim_cache_auto`` additionally caps the
# budget at 1/5 of the device's reported memory: the cache is a VJP
# residual that stays live through the whole trunk backward beside the
# trunk's own activations, so it may only take a minor share of HBM
# (3.2 GiB on a 16 GiB v5e: admits the 24k pool's 2.25 GiB slice,
# rejects the 32k pool's 4.0 GiB).  Pass ``sim_cache=True`` to override.
SIM_CACHE_AUTO_BYTES = 6 << 30
# The CPU backend reports no memory stats; tests and CPU rehearsals get
# this fixed reference budget instead.
SIM_CACHE_CPU_BYTES = 2 << 30

_SIM_CACHE_LOGGED = set()


def resolve_sim_cache_auto(cache_bytes: int, engine: str) -> bool:
    """Decide whether a streaming engine's fp32 sim cache auto-enables.

    The budget is 1/5 of the device's reported ``bytes_limit`` (see
    ``SIM_CACHE_AUTO_BYTES``), capped at that constant.  An accelerator
    that reports no memory stats is an error — guessing its HBM would
    size a multi-GiB residual against a number nobody measured; only
    the CPU backend gets the fixed ``SIM_CACHE_CPU_BYTES`` reference.
    Every auto-enable is logged ONCE per (engine, size) so an OOM
    regression is attributable to the cache.  Explicit
    ``sim_cache=True/False`` never reaches here.
    """
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        budget = SIM_CACHE_CPU_BYTES
    else:
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if limit <= 0:
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                "memory bytes_limit; pass sim_cache=True/False "
                "explicitly instead of the auto gate")
        budget = min(SIM_CACHE_AUTO_BYTES, limit // 5)
    enable = cache_bytes <= budget
    key = (engine, cache_bytes, enable)
    if enable and key not in _SIM_CACHE_LOGGED:
        _SIM_CACHE_LOGGED.add(key)
        import logging

        logging.getLogger("npairloss_tpu").info(
            "%s: auto-enabling fp32 similarity cache (%.0f MiB <= budget "
            "%.0f MiB); pass sim_cache=False if HBM-tight",
            engine, cache_bytes / 2**20, budget / 2**20,
        )
    return enable


class MiningRegion(enum.IntEnum):
    """Where a threshold is computed (caffe.proto:8-11)."""

    GLOBAL = 0  # one threshold from this rank's whole N x N*G block
    LOCAL = 1  # a per-query threshold


class MiningMethod(enum.IntEnum):
    """How pairs are selected against the threshold (caffe.proto:12-18)."""

    HARD = 0
    EASY = 1
    RAND = 2  # reference quirk: selects ALL pairs, no randomness (cu:88,109)
    RELATIVE_HARD = 3
    RELATIVE_EASY = 4


_RELATIVE = (MiningMethod.RELATIVE_HARD, MiningMethod.RELATIVE_EASY)
_ABSOLUTE = (MiningMethod.HARD, MiningMethod.EASY, MiningMethod.RAND)


@dataclasses.dataclass(frozen=True)
class NPairLossConfig:
    """Static loss configuration — mirrors NPairLossParameter (caffe.proto:3-23).

    Defaults match the proto defaults exactly.
    """

    margin_ident: float = 0.0
    margin_diff: float = 0.0
    identsn: float = -1.0
    diffsn: float = -1.0
    ap_mining_region: MiningRegion = MiningRegion.LOCAL
    ap_mining_method: MiningMethod = MiningMethod.RAND
    an_mining_region: MiningRegion = MiningRegion.LOCAL
    an_mining_method: MiningMethod = MiningMethod.RAND
    # Gradient semantics. "reference" reproduces cu:420-499 exactly:
    #   dF_local = 0.5 * query-role grad + 0.5 * (1/G) * psum(database-role grad)
    # "true" lets JAX autodiff produce the mathematically exact gradient of the
    # mean loss (query-role + database-role summed, no 0.5/1G rescale).
    grad_mode: str = "reference"

    def __post_init__(self):
        if self.grad_mode not in ("reference", "true"):
            raise ValueError(
                f"grad_mode must be 'reference' or 'true', got {self.grad_mode!r}"
            )


# The exact mining configuration the reference ships (usage/def.prototxt:
# 137-146): all positives at-or-below the block-wide top similarity (i.e.
# every positive), negatives harder than the per-query hardest positive
# minus 0.05.
REFERENCE_CONFIG = NPairLossConfig(
    margin_ident=0.0,
    margin_diff=-0.05,
    identsn=-0.0,
    diffsn=-0.3,
    ap_mining_region=MiningRegion.GLOBAL,
    ap_mining_method=MiningMethod.RELATIVE_HARD,
    an_mining_region=MiningRegion.LOCAL,
    an_mining_method=MiningMethod.HARD,
)


# ---------------------------------------------------------------------------
# Mask construction (reference: GetLabelDiffMtx kernel, cu:44-66)
# ---------------------------------------------------------------------------


def pair_masks(
    local_labels: jax.Array, total_labels: jax.Array, rank: jax.Array, n_local: int
) -> Tuple[jax.Array, jax.Array]:
    """Same-label / different-label 0-1 masks over the N x (N*G) pair grid.

    The self pair — local row q against gathered column ``rank*n_local + q`` —
    is excluded from both masks (cu:54).
    """
    same_lbl = local_labels[:, None] == total_labels[None, :]
    col = jnp.arange(total_labels.shape[0], dtype=jnp.int32)[None, :]
    row_global = jnp.arange(n_local, dtype=jnp.int32)[:, None] + rank * n_local
    not_self = col != row_global
    same = same_lbl & not_self
    diff = (~same_lbl) & not_self
    return same, diff


# ---------------------------------------------------------------------------
# Mining statistics + threshold selection (cu:222-337)
# ---------------------------------------------------------------------------


def _relative_pos(count: jax.Array, sn: float) -> jax.Array:
    """Sorted-list index for RELATIVE_{HARD,EASY} mining.

    The reference indexes an ascending-sorted similarity list with
      sn >= 0 : size - 1 - int(sn)            (absolute rank from the top)
      sn <  0 : int(size - 1 + sn * size)     (top |sn| fraction)
    using C truncation-toward-zero (cu:285-287, cu:300-302, cu:316-318,
    cu:331-333).  Out-of-range indices are UB in the reference; we clamp.

    An int64 ``count`` (GLOBAL-region pair populations beyond 2^31, only
    representable under jax_enable_x64) keeps 64-bit index math; the
    fraction path then uses float64 so the truncated rank stays exact.
    """
    big = count.dtype == jnp.int64
    idt = jnp.int64 if big else jnp.int32
    count = count.astype(idt)
    if sn >= 0:
        pos = count - 1 - int(sn)
    else:
        fdt = jnp.float64 if big else jnp.float32
        cf = count.astype(fdt)
        pos = jnp.trunc(cf - 1.0 + fdt(sn) * cf).astype(idt)
    return jnp.clip(pos, 0, jnp.maximum(count - 1, 0))


def _clamp_negative(value: jax.Array) -> jax.Array:
    """Reference quirk: a relative threshold < 0 becomes -FLT_MAX (cu:288 etc.)."""
    return jnp.where(value >= 0, value, jnp.float32(-FLT_MAX))


def _local_relative_threshold(
    sims: jax.Array, mask: jax.Array, sn: float
) -> jax.Array:
    """Per-query threshold: the ``_relative_pos``-th smallest masked row
    entry, recovered exactly by MSD radix selection over the materialized
    sims (the reference's per-query ascending std::sort, cu:269-273, needs
    only ONE rank statistic — a full sort is O(M log M) work and, on TPU,
    a bitonic network; NUM_DIGITS fused compare-and-reduce passes over the
    row recover the identical element)."""
    count = mask.sum(axis=1)
    k = _relative_pos(count, sn)
    val = radix_select(
        lambda prefix, digit: masked_digit_hist(sims, mask, prefix, digit),
        k,
        count == 0,
    )
    return _clamp_negative(val)


def _global_relative_threshold(sims: jax.Array, mask: jax.Array, sn: float) -> jax.Array:
    """Scalar threshold: the ``_relative_pos``-th smallest masked entry of
    the WHOLE block (the reference's global ascending std::sort of the
    flattened pair population, cu:266-268), via the same radix selection
    with the block flattened to a single population row."""
    flat = sims.reshape(1, -1)
    fmask = mask.reshape(1, -1)
    count = fmask.sum(axis=1)
    k = _relative_pos(count, sn)
    val = radix_select(
        lambda prefix, digit: masked_digit_hist(flat, fmask, prefix, digit),
        k,
        count == 0,
    )
    return _clamp_negative(val[0])


def topk_relative_threshold(
    topk: jax.Array, counts: jax.Array, sn: float, region: "MiningRegion",
    count_dtype=jnp.int32,
) -> jax.Array:
    """RELATIVE_{HARD,EASY} threshold from per-query K-largest candidate
    buffers — the sparse-candidate fast path for the POSITIVE side.

    With identity-balanced batches each query has only
    ``img_num_per_identity*G - 1`` same-label candidates among the whole
    pool (def.prototxt:25-26 makes that 2 per identity), so when every
    query's candidate count fits a K-slot buffer, the buffer IS the
    complete per-query candidate list and the reference's ascending
    sorted-list indexing (cu:285-287 / cu:300-302) reduces to a sort of
    N x K values — no full-population selection needed.  The buffer must
    hold values bit-identical to the engine's sim computation (the
    streaming engines extract them inside the same kernel sweep that
    computes the sims), so the selected element matches the streamed
    radix selection exactly.

    Args:
      topk: [N, K] the K largest candidate sims per query, padded with
        ``-FLT_MAX``.  Finite sims only — a ``-inf`` candidate would
        sort below the padding sentinel and shift the index arithmetic.
      counts: int [N] true candidate count per query; only valid when
        ``counts.max() <= K`` (callers guard with ``lax.cond``).
      sn: the identsn/diffsn rank parameter (see ``_relative_pos``).
      region: LOCAL (per-query list, cu:285) or GLOBAL (one list over
        the whole population, cu:300).
      count_dtype: the dtype the RADIX path would rank the same
        population in (``population_count_dtype`` of the full pair
        population) — GLOBAL rank arithmetic must run in the identical
        int/float widths or the ``lax.cond`` fast/fallback branches
        could select ranks differing by one near fractional-sn
        boundaries (int64 -> float64 ``_relative_pos``, int32 ->
        float32).  LOCAL ranks are per-query int32 in both paths.

    Returns: float32 [N] thresholds (GLOBAL broadcasts one value), with
    the reference's empty -> +FLT_MAX and ``< 0 -> -FLT_MAX`` quirks.
    """
    n, kcap = topk.shape
    if region == MiningRegion.GLOBAL:
        # The buffer's n*K candidates always fit int32, but the rank
        # arithmetic mirrors the radix path's dtype (see above).
        total = counts.astype(count_dtype).sum()
        k = _relative_pos(total[None], sn)[0].astype(jnp.int32)
        total32 = total.astype(jnp.int32)  # <= n*K, always representable
        flat = jnp.sort(topk.reshape(-1))  # ascending, padding first
        pos = jnp.int32(flat.shape[0]) - total32 + k
        val = flat[jnp.clip(pos, 0, flat.shape[0] - 1)]
        val = jnp.where(total32 == 0, jnp.float32(FLT_MAX), val)
        return _clamp_negative(jnp.broadcast_to(val, (n,)))
    counts = counts.astype(jnp.int32)
    k = _relative_pos(counts, sn)
    asc = jnp.sort(topk, axis=1)  # ascending, padding first
    pos = jnp.int32(kcap) - counts + k
    val = jnp.take_along_axis(
        asc, jnp.clip(pos, 0, kcap - 1)[:, None], axis=1
    )[:, 0]
    val = jnp.where(counts == 0, jnp.float32(FLT_MAX), val)
    return _clamp_negative(val)


def mining_thresholds(
    sims: jax.Array, same: jax.Array, diff: jax.Array, cfg: NPairLossConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(pos_thr[N], neg_thr[N], max_all[N]) per the reference's 8-branch grid.

    Absolute (HARD/EASY/RAND) thresholds (cu:279, cu:296, cu:310, cu:327):
      AP LOCAL  : per-query max between-class sim     (hardest negative)
      AP GLOBAL : block-wide max between-class sim
      AN LOCAL  : per-query min within-class sim      (hardest positive)
      AN GLOBAL : block-wide min within-class sim
    RELATIVE thresholds index the ascending-sorted sim lists (see
    ``_relative_pos``).  ``max_all`` is the per-query max over all non-self
    sims, used for exp stabilization (cu:229-258).
    """
    n = sims.shape[0]
    neg_fill = jnp.float32(-FLT_MAX)
    pos_fill = jnp.float32(FLT_MAX)

    max_between = jnp.where(diff, sims, neg_fill).max(axis=1)  # cu:252-255
    min_within = jnp.where(same, sims, pos_fill).min(axis=1)  # cu:242-245
    max_all = jnp.where(same | diff, sims, neg_fill).max(axis=1)  # cu:246-257

    # AP (positive-pair) threshold, cu:277-306.
    if cfg.ap_mining_region == MiningRegion.LOCAL:
        if cfg.ap_mining_method in _RELATIVE:
            pos_thr = _local_relative_threshold(sims, same, cfg.identsn)
        else:
            pos_thr = max_between
    else:  # GLOBAL
        if cfg.ap_mining_method in _RELATIVE:
            pos_thr = jnp.broadcast_to(
                _global_relative_threshold(sims, same, cfg.identsn), (n,)
            )
        else:
            pos_thr = jnp.broadcast_to(jnp.where(diff, sims, neg_fill).max(), (n,))

    # AN (negative-pair) threshold, cu:307-337.
    if cfg.an_mining_region == MiningRegion.LOCAL:
        if cfg.an_mining_method in _RELATIVE:
            neg_thr = _local_relative_threshold(sims, diff, cfg.diffsn)
        else:
            neg_thr = min_within
    else:  # GLOBAL
        if cfg.an_mining_method in _RELATIVE:
            neg_thr = jnp.broadcast_to(
                _global_relative_threshold(sims, diff, cfg.diffsn), (n,)
            )
        else:
            neg_thr = jnp.broadcast_to(jnp.where(same, sims, pos_fill).min(), (n,))

    return pos_thr, neg_thr, max_all


def streaming_supported(cfg: "NPairLossConfig") -> bool:
    """True when the mining config needs only single-pass min/max thresholds
    (absolute methods).  Both streaming engines (parallel.ring and
    ops.pallas_npair) support EVERY config — RELATIVE_* via exact radix
    selection — but a False here means the config pays 4 extra streamed
    passes over the pair tiles per relative threshold; use this as the
    cost signal, not a support gate."""
    return (
        cfg.ap_mining_method in _ABSOLUTE and cfg.an_mining_method in _ABSOLUTE
    )


def absolute_thresholds(
    min_within: jax.Array, max_between: jax.Array, cfg: "NPairLossConfig"
) -> Tuple[jax.Array, jax.Array]:
    """(pos_thr, neg_thr) from streamed per-query stats, absolute methods
    only (cu:279, 296, 310, 327).  GLOBAL region means this rank's whole
    N x (N*G) block — each rank's own extremum, no cross-rank reduction —
    so it reduces over the query axis of the streamed stats."""
    if cfg.ap_mining_region == MiningRegion.LOCAL:
        pos_thr = max_between
    else:
        pos_thr = jnp.broadcast_to(max_between.max(), max_between.shape)
    if cfg.an_mining_region == MiningRegion.LOCAL:
        neg_thr = min_within
    else:
        neg_thr = jnp.broadcast_to(min_within.min(), min_within.shape)
    return pos_thr, neg_thr


# ---------------------------------------------------------------------------
# Pair selection (reference: GetSampledPairMtx kernel, cu:69-122)
# ---------------------------------------------------------------------------


def selection_predicates(
    sims: jax.Array, pt: jax.Array, nt: jax.Array, cfg: NPairLossConfig
) -> Tuple[jax.Array, jax.Array]:
    """(pos_sel, neg_sel) comparison predicates of cu:80-119 against the
    margin-adjusted thresholds ``pt``/``nt`` (broadcastable to sims).

    The single home of the quirk-sensitive comparison directions — shared
    by the dense path, the ring path and the Pallas-blockwise kernels so
    the three can never desynchronize.
    """
    m = cfg.ap_mining_method
    if m == MiningMethod.HARD:
        pos_sel = sims < pt
    elif m == MiningMethod.EASY:
        pos_sel = sims >= pt
    elif m == MiningMethod.RAND:  # quirk: ALL (cu:88-89)
        pos_sel = jnp.ones_like(sims, dtype=bool)
    elif m == MiningMethod.RELATIVE_HARD:
        pos_sel = sims <= pt
    else:  # RELATIVE_EASY
        pos_sel = sims >= pt

    m = cfg.an_mining_method
    if m == MiningMethod.HARD:
        neg_sel = sims > nt
    elif m == MiningMethod.EASY:
        neg_sel = sims <= nt
    elif m == MiningMethod.RAND:  # quirk: ALL (cu:109-110)
        neg_sel = jnp.ones_like(sims, dtype=bool)
    elif m == MiningMethod.RELATIVE_HARD:
        neg_sel = sims >= nt
    else:  # RELATIVE_EASY
        neg_sel = sims <= nt

    return pos_sel, neg_sel


def selection_mask(
    sims: jax.Array,
    same: jax.Array,
    diff: jax.Array,
    pos_thr: jax.Array,
    neg_thr: jax.Array,
    cfg: NPairLossConfig,
) -> jax.Array:
    """0/1 per-pair selection mask; exact comparison operators of cu:80-119."""
    pt = (pos_thr + jnp.float32(cfg.margin_ident))[:, None]
    nt = (neg_thr + jnp.float32(cfg.margin_diff))[:, None]
    pos_sel, neg_sel = selection_predicates(sims, pt, nt, cfg)
    return jnp.where(same, pos_sel, jnp.where(diff, neg_sel, False))


# ---------------------------------------------------------------------------
# Forward core
# ---------------------------------------------------------------------------


def resolve_matmul_precision(precision: Optional[str]) -> jax.lax.Precision:
    """Engine-wide similarity-matmul precision knob.

    ``"highest"`` (default everywhere) keeps full fp32 on the MXU — the
    ~6-pass bf16 decomposition that bit-matches the reference's cuBLAS
    sgemm (cu:218) and the NumPy oracle.  ``"default"`` opts into the
    single-pass bf16-multiply/fp32-accumulate MXU mode: ~6x faster sim
    and backward gemms, at ~1e-3-level sim rounding — mined thresholds
    and selections then differ from the oracle near decision boundaries,
    so this is a THROUGHPUT mode, not a parity mode (training-quality
    pinned by test, bit-parity deliberately not claimed).
    """
    if precision is None:
        return jax.lax.Precision.HIGHEST
    try:
        return {
            "highest": jax.lax.Precision.HIGHEST,
            "default": jax.lax.Precision.DEFAULT,
        }[precision]
    except KeyError:
        raise ValueError(
            f"matmul_precision must be 'highest' or 'default', got "
            f"{precision!r}") from None


# Trace-time precision for the streaming engines' kernel gemms (the
# dense engine threads the string directly).  A ContextVar — not a
# module global — so concurrent traces in different threads cannot
# cross-contaminate: each engine wraps its fwd/bwd tracing in
# ``matmul_precision_ctx`` and the kernel bodies read
# ``active_matmul_precision()`` while being traced inside it.
_MATMUL_PRECISION_VAR = contextvars.ContextVar(
    "npair_matmul_precision", default=jax.lax.Precision.HIGHEST)


@contextlib.contextmanager
def matmul_precision_ctx(matmul_precision: Optional[str]):
    token = _MATMUL_PRECISION_VAR.set(
        resolve_matmul_precision(matmul_precision))
    try:
        yield
    finally:
        _MATMUL_PRECISION_VAR.reset(token)


def active_matmul_precision() -> jax.lax.Precision:
    return _MATMUL_PRECISION_VAR.get()


def _forward_core(
    features: jax.Array,
    labels: jax.Array,
    cfg: NPairLossConfig,
    axis_name: Optional[str],
    matmul_precision: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Shared forward; returns (loss, aux-for-metrics, residuals-for-vjp)."""
    features = features.astype(jnp.float32)
    n_local = features.shape[0]

    if axis_name is None:
        total_features = features
        total_labels = labels
        rank = jnp.int32(0)
        num_shards = 1
    else:
        # MPI_Allgather of features and labels (cu:17-43) as in-graph ICI
        # collectives; rank-r block lands at rows [r*N, (r+1)*N) exactly as
        # MPI_Allgather orders recvbuf.  The nested comm/ scope is the
        # fleet observatory's exchange-path marker (obs.fleet.comms):
        # collective bytes whose op_name carries it are attributed to a
        # declared exchange path; metadata-only, the program is
        # unchanged.
        with jax.named_scope("npair/gather"), \
                jax.named_scope("comm/all_gather"):
            total_features = jax.lax.all_gather(
                features, axis_name, axis=0, tiled=True
            )
            total_labels = jax.lax.all_gather(
                labels, axis_name, axis=0, tiled=True
            )
        rank = jax.lax.axis_index(axis_name).astype(jnp.int32)
        num_shards = jax.lax.axis_size(axis_name)

    # Similarity matrix S = F_local @ F_total^T on the MXU (cu:218,
    # dot_normalizer = 1 in forward per cu:216).  HIGHEST (the default —
    # see resolve_matmul_precision) keeps full fp32 on the MXU; the TPU
    # default mode would truncate fp32 operands to bf16 and break
    # bit-parity with the oracle.
    with jax.named_scope("npair/sim"):
        sims = jnp.dot(
            features,
            total_features.T,
            preferred_element_type=jnp.float32,
            precision=resolve_matmul_precision(matmul_precision),
        )

    with jax.named_scope("npair/mine"):
        same, diff = pair_masks(labels, total_labels, rank, n_local)
        pos_thr, neg_thr, max_all = mining_thresholds(sims, same, diff, cfg)
    with jax.named_scope("npair/select"):
        sel = selection_mask(sims, same, diff, pos_thr, neg_thr, cfg)

    sel_pos = same & sel  # _tmp_Select_Ident, cu:355
    sel_neg = diff & sel  # _tmp_Select_Diff, cu:358
    ident_num = sel_pos.sum(axis=1).astype(jnp.float32)  # identNum, cu:357
    diff_num = sel_neg.sum(axis=1).astype(jnp.float32)  # diffNum, cu:360

    # Stabilized exponentials (Minus_Querywise_Maxval, cu:124-156).  The
    # pre-selection exp'd matrix feeds the retrieval metric (cu:132).
    # Masking must be where-based, not multiplicative: a query with no pairs
    # at all has max_all = -FLT_MAX, so sim_exp overflows to +inf and
    # inf * 0 would poison the row sums with NaN — the reference kernel
    # zeroes non-pair entries before its gemv reductions (cu:152-154).
    with jax.named_scope("npair/loss"):
        sim_exp = jnp.exp(sims - max_all[:, None])
        exp_pos = jnp.where(sel_pos, sim_exp, 0.0)  # _innerProd_temp1, cu:373
        exp_neg = jnp.where(sel_neg, sim_exp, 0.0)  # _innerProd_temp2, cu:376

        ident_sum = exp_pos.sum(axis=1)  # loss_ident_value I_q, cu:375
        all_sum = ident_sum + exp_neg.sum(axis=1)  # I_q + D_q, cu:380

        # ManipulateDIVandLOG (cu:158-171): zero-count queries contribute 0.
        valid = (ident_sum != 0) & (all_sum != 0)
        log_q = jnp.where(
            valid, jnp.log(jnp.where(valid, ident_sum / all_sum, 1.0)), 0.0
        )
        loss = -log_q.sum() / jnp.float32(n_local)  # cu:384-385

    aux = {
        "sim": sims,
        "sim_exp": sim_exp,
        "total_labels": total_labels,
        "rank": rank,
        "ident_num": ident_num,
        "diff_num": diff_num,
        "pos_threshold": pos_thr,
        "neg_threshold": neg_thr,
    }
    residuals = {
        "features": features,
        "total_features": total_features,
        "exp_pos": exp_pos,
        "exp_neg": exp_neg,
        "ident_sum": ident_sum,
        "all_sum": all_sum,
        "rank": rank,
        "num_shards": num_shards,
    }
    return loss, aux, residuals


def _reference_backward(
    res: Dict[str, Any], g: jax.Array, axis_name: Optional[str],
    matmul_precision: Optional[str] = None,
) -> jax.Array:
    """Analytic backward with the reference's exact scaling (cu:420-499).

    part1 = exp_pos / I_q,  part2 = exp_pos / (I+D)_q,  part3 = exp_neg / (I+D)_q
    (Get_Query_Diff_Part, cu:438-446, each 0-guarded per cu:412-417);
    query-role grad  = (-p1+p2+p3) @ F_total * lw/N         (cu:448-453)
    db-role grad     = (-p1+p2+p3)^T @ F_local * lw/N       (cu:455-460)
    db-role grad     = psum(db-role) / G                    (MPI_Allreduce + 1/G, cu:462-489)
    final            = 0.5 * db_role[rank*N:(rank+1)*N] + 0.5 * query_role  (cu:492-497)
    """
    features = res["features"]
    total_features = res["total_features"]
    n_local = features.shape[0]

    def _safe_div(num, den):
        ok = den != 0
        return jnp.where(ok[:, None], num / jnp.where(ok, den, 1.0)[:, None], 0.0)

    p1 = _safe_div(res["exp_pos"], res["ident_sum"])
    p2 = _safe_div(res["exp_pos"], res["all_sum"])
    p3 = _safe_div(res["exp_neg"], res["all_sum"])
    # dot_normalizer is the query count in backward (cu:427), unlike forward.
    w = (-p1 + p2 + p3) * (g / jnp.float32(n_local))

    prec = resolve_matmul_precision(matmul_precision)
    grad_query = jnp.dot(
        w,
        total_features,
        preferred_element_type=jnp.float32,
        precision=prec,
    )
    grad_db = jnp.dot(
        w.T,
        features,
        preferred_element_type=jnp.float32,
        precision=prec,
    )

    if axis_name is not None:
        # MPI_Allreduce of the database-role grads (cu:462-489); the
        # comm/ scope marks the exchange path for fleet attribution.
        with jax.named_scope("comm/allreduce"):
            grad_db = jax.lax.psum(grad_db, axis_name)
    grad_db = grad_db / jnp.float32(res["num_shards"])

    own_rows = jax.lax.dynamic_slice_in_dim(
        grad_db, res["rank"] * n_local, n_local, axis=0
    )
    return 0.5 * own_rows + 0.5 * grad_query


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _npair_core(features, labels, cfg: NPairLossConfig,
                axis_name: Optional[str], matmul_precision: Optional[str]):
    loss, aux, _ = _forward_core(
        features, labels, cfg, axis_name, matmul_precision)
    return loss, aux


def _npair_core_fwd(features, labels, cfg, axis_name, matmul_precision):
    loss, aux, res = _forward_core(
        features, labels, cfg, axis_name, matmul_precision)
    res["labels"] = labels
    return (loss, aux), res


def _npair_core_bwd(cfg, axis_name, matmul_precision, res, cotangents):
    g, _ = cotangents  # aux outputs are non-differentiable monitors
    d_features = _reference_backward(res, g, axis_name, matmul_precision)
    labels = res["labels"]
    if jnp.issubdtype(labels.dtype, jnp.floating):
        d_labels = jnp.zeros(labels.shape, labels.dtype)
    else:
        d_labels = np.zeros(labels.shape, jax.dtypes.float0)
    return d_features, d_labels


_npair_core.defvjp(_npair_core_fwd, _npair_core_bwd)


def npair_loss_with_aux(
    features: jax.Array,
    labels: jax.Array,
    cfg: NPairLossConfig = NPairLossConfig(),
    axis_name: Optional[str] = None,
    matmul_precision: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-class N-pair loss with mining; returns (loss, aux).

    Args:
      features: [N_local, D] embedding batch of this shard (typically
        L2-normalized upstream, matching the reference's L2Normalize bottom,
        def.prototxt:115-126).
      labels: [N_local] identity labels (int or float).
      cfg: static mining/margin configuration.
      axis_name: mesh axis to all-gather the negative pool over; ``None``
        means single-shard (G = 1).
      matmul_precision: sim/backward gemm MXU precision — ``None``/
        ``"highest"`` for oracle bit-parity, ``"default"`` for the ~6x
        faster single-pass bf16 mode (``resolve_matmul_precision``).

    The returned ``aux`` feeds the retrieval metrics (``ops.metrics``); it is
    NOT differentiable — gradients flow only through the loss, mirroring the
    reference where thresholds, masks and counts are constants in backward.
    """
    if cfg.grad_mode == "reference":
        return _npair_core(features, labels, cfg, axis_name,
                           matmul_precision)
    loss, aux, _ = _forward_core(
        features,
        jax.lax.stop_gradient(labels),
        cfg,
        axis_name,
        matmul_precision,
    )
    return loss, jax.lax.stop_gradient(aux)


def npair_loss(
    features: jax.Array,
    labels: jax.Array,
    cfg: NPairLossConfig = NPairLossConfig(),
    axis_name: Optional[str] = None,
    matmul_precision: Optional[str] = None,
) -> jax.Array:
    """Scalar multi-class N-pair loss (see ``npair_loss_with_aux``)."""
    return npair_loss_with_aux(
        features, labels, cfg, axis_name, matmul_precision)[0]
