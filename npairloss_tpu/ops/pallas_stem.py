"""Pallas TPU kernels for the GoogLeNet stem's VPU-bound tail.

The perf observatory (``prof --step train``, obs/perf) attributes the
flagship trunk's non-MXU time to the stem's elementwise chain: the two
across-channel LRN layers (square -> windowed sum -> pow -> scale — a
VPU reduce XLA cannot fuse into any matmul, measured at ~25% of the
prototxt-parity step, PROFILE.md) and the conv epilogues (bias + ReLU,
bias + ReLU + 3x3/s2 max-pool) whose intermediates XLA materializes to
HBM between the conv gemm and the pool reduce.  These kernels fuse each
chain into ONE VMEM pass:

* :func:`fused_lrn`        — x^2 -> channel-window sum -> rsqrt-pow ->
  scale in a single tile visit, with an analytic custom VJP whose
  backward is a second one-pass kernel (the transpose window).
* :func:`fused_bias_relu`  — conv epilogue: bias add + ReLU fused (the
  conv itself stays an XLA gemm — the MXU half is already optimal).
* :func:`fused_bias_relu_pool` — stem epilogue: bias + ReLU + max-pool
  in one pass, so the pre-pool activation never round-trips HBM.

**Denominator cache** (the ``sim_cache`` pattern of
``ops/pallas_npair.py`` transplanted): the LRN backward needs the
forward's denominator ``d = k + a*W(x^2)``.  When the fp32 ``d`` tensor
fits the auto budget (``LRN_CACHE_AUTO_BYTES``), the forward kernel
writes it out once and the backward streams it back (``cache=True``);
beyond the budget the backward recomputes the window sum from ``x``
(``cache=False``) — one extra VPU pass instead of an HBM-resident
tensor.  Cached and recompute paths are bit-identical (the cache stores
exactly the fp32 values the forward produced); ``cache=None`` picks by
size, mirroring ``resolve_sim_cache_auto``.

On non-TPU backends every kernel runs in Pallas interpreter mode, which
is how the CPU suite checks parity against the XLA reference
(``models.layers.local_response_norm`` / bias+relu+``reduce_window``)
— forward AND backward, including ragged row/channel tiles
(tests/test_pallas_stem.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU lowering)

from npairloss_tpu.ops.pallas_mode import default_interpret

# fp32 bytes of the LRN denominator tensor below which the forward
# caches it for the backward (the pallas_npair SIM_CACHE_AUTO_BYTES
# pattern at stem-activation scale: the batch-120 pool1 site is ~385 MB
# — cached on a 16 GB chip, recomputed only when an operator forces
# cache=False or the tensor outgrows the budget at very large batch).
LRN_CACHE_AUTO_BYTES = 2 << 30

_BLOCK_ROWS = 256
_LANES = 128


def resolve_lrn_cache_auto(nbytes: int, cache: Optional[bool]) -> bool:
    """Explicit ``cache`` wins; None = auto by the fp32 denominator
    size (same contract shape as ops.npair_loss.resolve_sim_cache_auto)."""
    if cache is not None:
        return bool(cache)
    return nbytes <= LRN_CACHE_AUTO_BYTES


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad2d(x: jax.Array, rows: int, cols: int) -> jax.Array:
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def _win_sum(v: jax.Array, lo: int, hi: int) -> jax.Array:
    """Channel-axis windowed sum with zero fill: out[:, i] =
    sum_{d=-lo..hi} v[:, i+d].  Static shapes (lo+hi+1 shifted adds) —
    the in-register form of the reduce_window the XLA reference uses.
    Zero fill matches reduce_window's zero padding, and the zero-padded
    channel tail (c..cpad) contributes zeros exactly like the columns
    beyond the real C would."""
    c = v.shape[1]
    vp = jnp.pad(v, ((0, 0), (lo, hi)))
    out = vp[:, 0:c]
    for o in range(1, lo + hi + 1):
        out = out + vp[:, o:o + c]
    return out


def _d_pow_negbeta(d: jax.Array, beta: float) -> jax.Array:
    """d^-beta; beta=0.75 uses the two-fast-VPU-op identity
    (sqrt(rsqrt(d)))^3 the XLA reference uses (models/layers.py), so
    the kernel stays bit-comparable to it."""
    if beta == 0.75:
        r = jnp.sqrt(jax.lax.rsqrt(d))
        return r * r * r
    return jnp.exp(jnp.float32(-beta) * jnp.log(d))


class _LRNParams(NamedTuple):
    """Hashable nondiff bundle for the custom_vjp (trace-time config)."""

    size: int
    alpha: float
    beta: float
    k: float
    cached: bool
    interpret: bool


# -- LRN forward/backward kernels -------------------------------------------


def _lrn_fwd_kernel(x_ref, o_ref, *, p: _LRNParams):
    x = x_ref[:].astype(jnp.float32)
    win = _win_sum(x * x, p.size // 2, p.size - 1 - p.size // 2)
    d = p.k + (p.alpha / p.size) * win
    o_ref[:] = (x * _d_pow_negbeta(d, p.beta)).astype(o_ref.dtype)


def _lrn_fwd_cached_kernel(x_ref, o_ref, d_ref, *, p: _LRNParams):
    x = x_ref[:].astype(jnp.float32)
    win = _win_sum(x * x, p.size // 2, p.size - 1 - p.size // 2)
    d = p.k + (p.alpha / p.size) * win
    d_ref[:] = d
    o_ref[:] = (x * _d_pow_negbeta(d, p.beta)).astype(o_ref.dtype)


def _lrn_bwd_kernel(x_ref, g_ref, o_ref, *, p: _LRNParams):
    """dx from (x, g), recomputing d (cache=False).

    With y_i = x_i d_i^-b and d_i = k + a * W(x^2)_i (W the forward
    window, a = alpha/size):
        dx_j = g_j d_j^-b - 2ab x_j * W^T(g x d^{-b-1})_j
    where W^T is the window with (lo, hi) swapped — symmetric for odd
    sizes, exact either way."""
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    win = _win_sum(x * x, p.size // 2, p.size - 1 - p.size // 2)
    d = p.k + (p.alpha / p.size) * win
    o_ref[:] = _lrn_bwd_math(x, g, d, p).astype(o_ref.dtype)


def _lrn_bwd_cached_kernel(x_ref, g_ref, d_ref, o_ref, *, p: _LRNParams):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    o_ref[:] = _lrn_bwd_math(x, g, d_ref[:], p).astype(o_ref.dtype)


def _lrn_bwd_math(x, g, d, p: _LRNParams):
    f = _d_pow_negbeta(d, p.beta)
    # g * x * d^{-b-1}, then the TRANSPOSE window (hi, lo swapped).
    t = _win_sum(g * x * (f / d),
                 p.size - 1 - p.size // 2, p.size // 2)
    return g * f - (2.0 * p.alpha / p.size * p.beta) * x * t


def _lrn_grid(rpad: int, cpad: int):
    """(grid, block_rows) over the PADDED row count (``_lrn_pad_geometry``
    guarantees rpad is either < _BLOCK_ROWS or a multiple of it)."""
    br = _BLOCK_ROWS if rpad >= _BLOCK_ROWS else rpad
    return (rpad // br,), br


def _lrn_fwd_call(x2: jax.Array, p: _LRNParams):
    """Padded 2-D forward dispatch; returns (out2, d2_or_None) at the
    PADDED geometry (the caller slices)."""
    rows, cpad = x2.shape
    grid, br = _lrn_grid(rows, cpad)
    spec = pl.BlockSpec((br, cpad), lambda i: (i, 0))
    if p.cached:
        out2, d2 = pl.pallas_call(
            functools.partial(_lrn_fwd_cached_kernel, p=p),
            grid=grid,
            in_specs=[spec],
            out_specs=(spec, spec),
            out_shape=(
                jax.ShapeDtypeStruct((rows, cpad), x2.dtype),
                jax.ShapeDtypeStruct((rows, cpad), jnp.float32),
            ),
            interpret=p.interpret,
        )(x2)
        return out2, d2
    out2 = pl.pallas_call(
        functools.partial(_lrn_fwd_kernel, p=p),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cpad), x2.dtype),
        interpret=p.interpret,
    )(x2)
    return out2, None


def _lrn_bwd_call(x2: jax.Array, g2: jax.Array, d2: Optional[jax.Array],
                  p: _LRNParams) -> jax.Array:
    rows, cpad = x2.shape
    grid, br = _lrn_grid(rows, cpad)
    spec = pl.BlockSpec((br, cpad), lambda i: (i, 0))
    if d2 is not None:
        return pl.pallas_call(
            functools.partial(_lrn_bwd_cached_kernel, p=p),
            grid=grid,
            in_specs=[spec, spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((rows, cpad), x2.dtype),
            interpret=p.interpret,
        )(x2, g2, d2)
    return pl.pallas_call(
        functools.partial(_lrn_bwd_kernel, p=p),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cpad), x2.dtype),
        interpret=p.interpret,
    )(x2, g2)


def _lrn_pad_geometry(shape) -> Tuple[int, int, int, int]:
    """(rows, c, rpad, cpad) of the 2-D channels-last view: channels
    lane-padded to 128, rows padded to one 16-sublane block (small
    inputs) or a _BLOCK_ROWS multiple (16 divides _BLOCK_ROWS, so both
    shapes satisfy the bf16 (16, 128) min tile)."""
    c = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    rows = max(rows, 1)
    cpad = _round_up(c, _LANES)
    if rows >= _BLOCK_ROWS:
        rpad = _round_up(rows, _BLOCK_ROWS)
    else:
        rpad = _round_up(rows, 16)
    return rows, c, rpad, cpad


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fused_lrn(x: jax.Array, p: _LRNParams) -> jax.Array:
    # The PRIMAL body (no-grad forwards: extract/test/eval/serve) —
    # the denominator cache is purely a backward residual, so dispatch
    # uncached here; only the vjp fwd below pays for (and keeps) d.
    out, _ = _fused_lrn_fwd_impl(x, p._replace(cached=False))
    return out


def _fused_lrn_fwd_impl(x: jax.Array, p: _LRNParams):
    rows, c, rpad, cpad = _lrn_pad_geometry(x.shape)
    x2 = _pad2d(x.reshape(rows, c), rpad, cpad)
    out2, d2 = _lrn_fwd_call(x2, p)
    out = out2[:rows, :c].reshape(x.shape)
    return out, d2  # d2 stays padded — the backward re-uses it as-is


def _fused_lrn_vjp_fwd(x, p: _LRNParams):
    out, d2 = _fused_lrn_fwd_impl(x, p)
    return out, (x, d2)


def _fused_lrn_vjp_bwd(p: _LRNParams, res, g):
    x, d2 = res
    rows, c, rpad, cpad = _lrn_pad_geometry(x.shape)
    x2 = _pad2d(x.reshape(rows, c), rpad, cpad)
    g2 = _pad2d(g.reshape(rows, c).astype(x.dtype), rpad, cpad)
    dx2 = _lrn_bwd_call(x2, g2, d2, p)
    return (dx2[:rows, :c].reshape(x.shape),)


_fused_lrn.defvjp(_fused_lrn_vjp_fwd, _fused_lrn_vjp_bwd)


def fused_lrn(
    x: jax.Array,
    size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    k: float = 1.0,
    cache: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Across-channel LRN (Caffe semantics, channels-last) as one fused
    Pallas pass — drop-in for ``models.layers.local_response_norm``.

    ``cache`` controls the denominator cache (None = auto by size, the
    ops/pallas_npair sim-cache pattern); ``interpret`` forces/forbids
    Pallas interpreter mode (None = auto: interpret off-TPU)."""
    if interpret is None:
        interpret = default_interpret()
    # Budget the cache at the tensor the cached kernel ACTUALLY writes:
    # the padded (rpad, cpad) fp32 denominator (lane padding alone is
    # 2x at a C=64 site), not the logical x.size.
    _, _, rpad, cpad = _lrn_pad_geometry(x.shape)
    cached = resolve_lrn_cache_auto(rpad * cpad * 4, cache)
    p = _LRNParams(int(size), float(alpha), float(beta), float(k),
                   bool(cached), bool(interpret))
    return _fused_lrn(x, p)


# -- conv epilogues ----------------------------------------------------------


def _bias_relu_kernel(x_ref, b_ref, o_ref):
    y = x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


class _EpiParams(NamedTuple):
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_bias_relu(x: jax.Array, bias: jax.Array,
                     p: _EpiParams) -> jax.Array:
    rows, c, rpad, cpad = _lrn_pad_geometry(x.shape)
    x2 = _pad2d(x.reshape(rows, c), rpad, cpad)
    b2 = _pad2d(bias.reshape(1, c), 1, cpad)
    grid, br = _lrn_grid(rpad, cpad)
    out2 = pl.pallas_call(
        _bias_relu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, cpad), lambda i: (i, 0)),
            pl.BlockSpec((1, cpad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, cpad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rpad, cpad), x.dtype),
        interpret=p.interpret,
    )(x2, b2)
    return out2[:rows, :c].reshape(x.shape)


def _fused_bias_relu_vjp_fwd(x, bias, p: _EpiParams):
    out = _fused_bias_relu(x, bias, p)
    return out, (out, bias)


def _fused_bias_relu_vjp_bwd(p: _EpiParams, res, g):
    # The backward of bias+ReLU is a mask + a channel reduce — XLA
    # fuses that chain fine on its own; the Pallas win is the forward's
    # single VMEM visit.  Residual = the OUTPUT (its sign IS the mask),
    # same bytes the XLA relu residual would hold (+ the tiny bias, for
    # its cotangent dtype — custom_vjp requires db.dtype == bias.dtype,
    # which a policy rule may set to non-fp32).
    out, bias = res
    mask = out > 0
    dx = jnp.where(mask, g, jnp.zeros_like(g))
    axes = tuple(range(g.ndim - 1))
    db = dx.astype(jnp.float32).sum(axis=axes).astype(bias.dtype)
    return dx, db


_fused_bias_relu.defvjp(_fused_bias_relu_vjp_fwd, _fused_bias_relu_vjp_bwd)


def fused_bias_relu(x: jax.Array, bias: jax.Array,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Conv epilogue: ``relu(x + bias)`` (bias broadcast over the last
    axis) in one fused VMEM pass, with an XLA backward."""
    if interpret is None:
        interpret = default_interpret()
    return _fused_bias_relu(x, bias, _EpiParams(bool(interpret)))


def _same_pads(n: int, window: int, stride: int) -> Tuple[int, int, int]:
    """(out, pad_lo, pad_hi) of XLA SAME pooling on an axis of size n."""
    out = -(-n // stride)
    total = max((out - 1) * stride + window - n, 0)
    return out, total // 2, total - total // 2


class _PoolParams(NamedTuple):
    window: int
    stride: int
    interpret: bool


# Pool-kernel padding: below every real activation in any float dtype
# the stem runs (bf16 and up), so a padded cell never wins a window.
_POOL_PAD = -1e30
_POOL_BLOCK_ROWS = 8


def _bias_relu_pool_kernel(x_ref, halo_ref, b_ref, o_ref, *,
                           p: _PoolParams):
    """One (image, row-tile) step over the phase-split view.

    The wrapper reshapes the padded activation to (N, HB, s, WB, s*C):
    input row ``s*i + ph`` is block row ``i`` phase ``ph``; input
    column ``s*j + pw`` is sublane ``j``, lane group ``pw``.  Window
    offset (di, dj) of output (i, j) is then block row ``i + di//s``
    phase ``di%s``, sublane ``j + dj//s`` lane group ``dj%s`` — every
    access a contiguous static slice of the block: no strided value
    gather, and the one-row overhang (``di//s == 1``) comes from the
    single-row ``halo_ref`` block that follows this tile.

    bias + ReLU is monotone, so it commutes with max: pool the raw
    tile, then apply the epilogue once to the pooled values —
    bit-identical to relu(x + b) followed by reduce_window.
    """
    s = p.stride
    th, wo, c = o_ref.shape[1], o_ref.shape[2], o_ref.shape[3]

    def tap(ref, ph, dj):
        return ref[0, :, ph, pl.ds(dj // s, wo),
                   pl.ds((dj % s) * c, c)].astype(jnp.float32)

    m = None
    for di in range(p.window):
        for dj in range(p.window):
            t = tap(x_ref, di % s, dj)
            if di // s:  # one block row down: shift up, halo row last
                below = tap(halo_ref, di % s, dj)
                t = (jnp.concatenate([t[1:], below], axis=0)
                     if th > 1 else below)
            m = t if m is None else jnp.maximum(m, t)
    b = b_ref[:].astype(jnp.float32).reshape(1, 1, c)
    o_ref[0] = jnp.maximum(m + b, 0.0).astype(o_ref.dtype)


def _reference_bias_relu_pool(x, bias, window: int, stride: int):
    y = jnp.maximum(x.astype(jnp.float32)
                    + bias.astype(jnp.float32), 0.0)
    out = jax.lax.reduce_window(
        y, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "SAME",
    )
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_bias_relu_pool(x: jax.Array, bias: jax.Array,
                          p: _PoolParams) -> jax.Array:
    n, h, w, c = x.shape
    s = p.stride
    over = (p.window - 1) // s  # block rows/cols a window overhangs
    if over > 1:
        raise ValueError(
            f"fused pool needs window <= 2*stride, got window="
            f"{p.window} stride={s}")
    ho, ph_lo, _ = _same_pads(h, p.window, s)
    wo, pw_lo, _ = _same_pads(w, p.window, s)
    hb, wb = ho + over, wo + over
    cpad = _round_up(c, _LANES)
    # ONE pad: SAME's leading pad, trailing fill to whole s-blocks (+
    # the overhang block), and the lane pad.
    xp = jnp.pad(
        x,
        ((0, 0), (ph_lo, hb * s - h - ph_lo),
         (pw_lo, wb * s - w - pw_lo), (0, cpad - c)),
        constant_values=_POOL_PAD,
    ).reshape(n, hb, s, wb, s * cpad)
    b2 = _pad2d(bias.reshape(1, c), 1, cpad)
    th = max(t for t in range(1, _POOL_BLOCK_ROWS + 1) if ho % t == 0)
    out = pl.pallas_call(
        functools.partial(_bias_relu_pool_kernel, p=p),
        grid=(n, ho // th),
        in_specs=[
            pl.BlockSpec((1, th, s, wb, s * cpad),
                         lambda i, t: (i, t, 0, 0, 0)),
            # The row below the tile (block size 1: the index IS the
            # row); the overhang block keeps it in range on the last
            # tile, and with no overhang it is loaded but unused.
            pl.BlockSpec((1, 1, s, wb, s * cpad),
                         lambda i, t: (i, (t + 1) * th * over, 0, 0, 0)),
            pl.BlockSpec((1, cpad), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, th, wo, cpad),
                               lambda i, t: (i, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, cpad), x.dtype),
        interpret=p.interpret,
    )(xp, xp, b2)
    return out[..., :c]


def _fused_bias_relu_pool_vjp_fwd(x, bias, p: _PoolParams):
    return _fused_bias_relu_pool(x, bias, p), (x, bias)


def _fused_bias_relu_pool_vjp_bwd(p: _PoolParams, res, g):
    # Max-pool backward is an argmax scatter — recomputed through XLA's
    # own reduce_window VJP (the fusion win is the forward's skipped
    # HBM round-trip of the pre-pool activation; the backward pays one
    # reference recompute, like remat).
    x, bias = res
    _, vjp = jax.vjp(
        lambda xx, bb: _reference_bias_relu_pool(xx, bb, p.window,
                                                 p.stride),
        x, bias,
    )
    dx, db = vjp(g)
    return dx, db.astype(bias.dtype)


_fused_bias_relu_pool.defvjp(_fused_bias_relu_pool_vjp_fwd,
                             _fused_bias_relu_pool_vjp_bwd)


def fused_bias_relu_pool(
    x: jax.Array,
    bias: jax.Array,
    window: int = 3,
    stride: int = 2,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stem epilogue: ``max_pool(relu(x + bias))`` (SAME padding,
    NHWC) in one fused pass — the pre-pool activation never leaves
    VMEM.  Backward recomputes through the XLA reference (remat-style)."""
    if interpret is None:
        interpret = default_interpret()
    return _fused_bias_relu_pool(
        x, bias, _PoolParams(int(window), int(stride), bool(interpret)))
