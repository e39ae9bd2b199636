"""Fused Pallas IVF probe kernel — gather + score + running top-k in
one VMEM pass (ROADMAP item 3's serving half).

The scan baseline (``serve/engine.py:_ivf_probe_topk``) is a
multi-dispatch pipeline: centroid gemm -> ``lax.top_k`` probe pick ->
a ``lax.scan`` of per-probe gather+score -> a running top-k merge.
Each probe step round-trips its ``(B, cap, D)`` gathered slab through
HBM, and the int8 mode is WORSE than fp32 on XLA CPU (~13x, measured
for the ``ivf_qps_1m`` row) because the scalarized gather-then-cast
never reaches an MXU-shaped program.

This module generalizes the ``pallas_npair`` sim-cache running-top-k
(``_accum_topk``) and the ``pallas_stem`` custom-kernel idioms to the
serving path:

  * the probe set still comes from one small centroid gemm + ``top_k``
    (stage 1 — identical XLA ops to the scan baseline, so the probe
    SET is bit-identical);
  * stage 2 is ONE Pallas kernel over grid ``(B, C)``: the probed
    cluster id rides a scalar-prefetch operand, so the pipeline DMA
    fetches exactly the ``(cap, D)`` cluster tile each step needs
    (gather-by-index-map — the TPU-v4 embedding-lookup pattern), the
    MXU scores it against the query row in the configured dtype, and a
    duplicate-safe extract-max merge maintains the running ``(1, kl)``
    best in VMEM — the gathered slab never touches HBM;
  * the int8 variant reads the per-cluster scale from SMEM and dequants
    the tile's PRODUCT inside the kernel (cast-to-bf16 gemm x scalar
    scale — the exact arithmetic of the scan baseline, now MXU-shaped).

Dispatch count for the probe path drops from 4 pipeline stages to 2
(declared in :data:`PROBE_IMPLS`).

Parity contract (tests/test_pallas_ivf.py, ci.sh interpret smoke):
scores match the scan baseline to 1e-6 and recall@{1,10} vs the
brute-force oracle is identical across fp32/bf16/int8, including
ragged tails, empty/padded clusters, and ``probes > n_clusters`` —
exercised in interpret mode on CPU, so tier-1 proves the kernel
without hardware.

Like every Pallas module here: interpret mode off-TPU by default
(``ops.pallas_mode``), so the same code path runs under CPU tests and
Mosaic-compiles on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from npairloss_tpu.ops.pallas_mode import default_interpret

# The probe-impl registry — the single source of truth the CLI flag
# vocabulary (cli._PROBE_IMPL_CHOICES) and the tests enumerate
# from (pinned by the staticcheck ``vocab`` pass, the _PRECISION_CHOICES
# pattern).  ``dispatch_count`` is the declared number of device
# pipeline stages on the probe path (centroid-select / gather / score /
# merge for the scan; centroid-select / fused kernel for the Pallas
# path) — pinned by tests/test_pallas_ivf.py.
PROBE_IMPLS = {
    "scan": {"dispatch_count": 4, "pallas": False},
    "fused": {"dispatch_count": 2, "pallas": True},
    "auto": {"dispatch_count": 0, "pallas": False},
}

_NEG_FILL = float(-np.finfo(np.float32).max)

_LANES = 128
# ``cap`` is the LANE axis of the kernel's (1, cap) score row and row-id
# block, so it is lane-aligned (which also covers every scoring dtype's
# sublane tile: fp32 8, bf16 16, int8 32).  ``serve.ivf`` pads every
# packed slab's cap to it at placement time, so the per-dispatch re-pad
# below is a no-op at production geometry.
CAP_ALIGN = _LANES
# Query rows fed to the MXU per step: the one real row broadcast to a
# full sublane tile (16 covers the bf16 tile too), so the scoring gemm
# never has a sub-tile M.
_Q_ROWS = 16


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def resolve_probe_impl(impl: str, platform: Optional[str] = None) -> str:
    """``auto`` -> the per-platform pick: the fused kernel where Mosaic
    compiles it (TPU), the scan baseline elsewhere (interpret-mode
    emulation is a parity harness, not a serving path); resolved once
    at engine build (``serve/engine.py``)."""
    if impl not in PROBE_IMPLS:
        raise ValueError(
            f"probe_impl must be one of {sorted(PROBE_IMPLS)}, "
            f"got {impl!r}")
    if impl != "auto":
        return impl
    platform = platform or jax.default_backend()
    return "fused" if platform == "tpu" else "scan"


def probe_dispatch_count(impl: str,
                         platform: Optional[str] = None) -> int:
    """The declared probe-path dispatch count for a (resolved) impl."""
    return PROBE_IMPLS[resolve_probe_impl(impl, platform)][
        "dispatch_count"]


def _probe_kernel(lids_ref, oks_ref, *rest, c: int, kl: int,
                  kl_pad: int, cap_pad: int, scoring: str):
    """One (query b, probe j) grid step: score the prefetched cluster
    tile and merge it into the revisited running top-kl buffer.

    ``rest`` is (scale_ref?, q_ref, tile_ref, rows_ref, out_s_ref,
    out_r_ref): the int8 per-cluster scale table travels as a third
    scalar-prefetch operand; fp32/bf16 omit it.  Per-query operands
    carry a unit middle axis — (B, 1, W) arrays in (1, 1, W) blocks —
    so each block's last two dims equal the array's (Mosaic's block
    rule) while the grid still walks one query row per step.
    """
    if scoring == "int8":
        scale_ref, q_ref, tile_ref, rows_ref, out_s_ref, out_r_ref = rest
    else:
        scale_ref = None
        q_ref, tile_ref, rows_ref, out_s_ref, out_r_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)
    neg = jnp.float32(_NEG_FILL)

    @pl.when(j == 0)
    def _():
        out_s_ref[0] = jnp.full((1, kl_pad), neg, jnp.float32)
        out_r_ref[0] = jnp.zeros((1, kl_pad), jnp.int32)

    flat = b * c + j
    ok = oks_ref[flat] > 0
    g = tile_ref[0]    # (cap_pad, d_pad) in the scoring dtype
    qv = jnp.broadcast_to(q_ref[0], (_Q_ROWS, q_ref.shape[2]))
    # The scoring gemm — same arithmetic as the scan baseline's einsum,
    # fp32-accumulated on the MXU; int8 dequants INSIDE the kernel:
    # bf16-cast gemm (+-127 is bf16-exact) x the per-cluster scale
    # scalar read from SMEM.
    if scoring == "fp32":
        sims = jax.lax.dot_general(
            qv, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    else:
        sims = jax.lax.dot_general(
            qv.astype(jnp.bfloat16), g.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale_ref is not None:
            sims = sims * scale_ref[lids_ref[flat]]
    sims = sims[0:1]     # every broadcast row is the same query
    rvals = rows_ref[0]  # (1, cap_pad) int32, -1 = pad
    vals = jnp.where((rvals >= 0) & ok, sims, neg)
    # Merge candidates in [running buffer, tile-ascending] order and
    # extract the kl largest by repeated (max, remove-ONE-occurrence)
    # — the pallas_npair ``_accum_topk`` loop, extended to carry row
    # ids.  Lowest-index-wins among equals keeps ``lax.top_k``'s
    # tie-break: the running best beats an equal tile candidate and
    # lower cluster positions beat higher, exactly like the baseline's
    # best-first concat.  Both pieces are lane multiples, so the concat
    # is tile-aligned.
    work_v = jnp.concatenate([out_s_ref[0], vals], axis=1)
    work_r = jnp.concatenate([out_r_ref[0], rvals], axis=1)
    w = kl_pad + cap_pad
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kl_pad), 1)
    imin = jnp.int32(np.iinfo(np.int32).min)
    new_s = jnp.full((1, kl_pad), neg, jnp.float32)
    new_r = jnp.zeros((1, kl_pad), jnp.int32)
    for t in range(kl):
        mx = work_v.max(axis=1, keepdims=True)
        mi = jnp.where(work_v == mx, iota, jnp.int32(w)).min(
            axis=1, keepdims=True)
        rr = jnp.where(iota == mi, work_r, imin).max(
            axis=1, keepdims=True)
        work_v = jnp.where(iota == mi, neg, work_v)
        # Slot t by select, not by a 1-lane concat Mosaic cannot tile.
        new_s = jnp.where(slot == t, mx, new_s)
        new_r = jnp.where(slot == t, rr, new_r)
    out_s_ref[0] = new_s
    out_r_ref[0] = new_r


def fused_probe_topk(q, packed, rows, centroids, cvalid, scale=None, *,
                     k: int, probes: int, scoring: str, g0,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in fused twin of ``engine._ivf_probe_topk``: same operands,
    same ``(B, kl)`` scores + GLOBAL gallery rows, same probe set and
    masking semantics — the gather/score/merge scan replaced by one
    Pallas kernel.  ``g0`` may be traced (the shard_map per-shard
    offset)."""
    kc_full = centroids.shape[0]
    kc_local, cap, d = packed.shape
    c = min(int(probes), kc_full)
    kl = min(int(k), c * cap)
    bq = q.shape[0]
    if interpret is None:
        interpret = default_interpret()

    with jax.named_scope("serve/probe"):
        # Stage 1 — identical XLA ops to the scan baseline, so the
        # probe SET is bit-identical: one small (B, KC) gemm, invalid
        # centroids masked, top-C pick.
        cs = jnp.dot(
            q, centroids.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        cs = jnp.where(cvalid[None, :], cs, jnp.float32(_NEG_FILL))
        _, probe = jax.lax.top_k(cs, c)  # (B, c) global cluster ids
        owned = (probe >= g0) & (probe < g0 + kc_local)
        lids = jnp.where(owned, probe - g0, 0).astype(jnp.int32)

    # Tile-align the operands for the kernel's block shapes.  At
    # production geometry (D a lane multiple, cap pre-padded to
    # CAP_ALIGN by IVFIndex._place) every pad below is width zero — no
    # per-dispatch copy of the slab.
    cap_pad = _round_up(cap, CAP_ALIGN)
    d_pad = _round_up(d, _LANES)
    kl_pad = _round_up(kl, _LANES)
    if cap_pad != cap or d_pad != d:
        packed = jnp.pad(
            packed, ((0, 0), (0, cap_pad - cap), (0, d_pad - d)))
    if cap_pad != cap:
        rows = jnp.pad(rows, ((0, 0), (0, cap_pad - cap)),
                       constant_values=-1)
    qp = jnp.pad(q, ((0, 0), (0, d_pad - d))) if d_pad != d else q

    with_scale = scoring == "int8" and scale is not None
    n_prefetch = 3 if with_scale else 2
    kernel = functools.partial(
        _probe_kernel, c=c, kl=kl, kl_pad=kl_pad, cap_pad=cap_pad,
        scoring=scoring if with_scale or scoring != "int8" else "bf16")
    # Index maps see the scalar-prefetch refs after the grid indices:
    # the probed cluster id IS the block index — the in-kernel gather.
    tile_idx = (lambda b, j, lids_r, *_p: (lids_r[b * c + j], 0, 0))
    q_idx = (lambda b, j, *_p: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(bq, c),  # b outer, j inner: outputs revisit consecutively
        in_specs=[
            pl.BlockSpec((1, 1, d_pad), q_idx),
            pl.BlockSpec((1, cap_pad, d_pad), tile_idx),
            pl.BlockSpec((1, 1, cap_pad), tile_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, kl_pad), q_idx),
            pl.BlockSpec((1, 1, kl_pad), q_idx),
        ],
    )
    args = [lids.reshape(-1), owned.astype(jnp.int32).reshape(-1)]
    if with_scale:
        args.append(scale.astype(jnp.float32))
    args += [qp[:, None, :], packed, rows[:, None, :]]
    with jax.named_scope("serve/probe_fused"):
        s, r = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bq, 1, kl_pad), jnp.float32),
                jax.ShapeDtypeStruct((bq, 1, kl_pad), jnp.int32),
            ],
            interpret=interpret,
        )(*args)
    return s[:, 0, :kl], r[:, 0, :kl]
