"""Pallas TPU kernels for the GoogLeNet stem's VPU-bound tail.

* :func:`fused_lrn` — across-channel LRN, forward and backward each ONE
  pass over the activation: x^2 -> channel-window sum -> pow -> scale in
  float32 registers, only the tensor's own dtype (bf16 under ``mxu``)
  crossing HBM.  ``models.layers.local_response_norm`` runs it on a TPU
  backend in place of XLA's ``reduce_window`` body (PERF.md, PR 26).
* :func:`fused_bias_relu` / :func:`fused_bias_relu_pool` — the conv
  epilogues behind ``GoogLeNetEmbedding.pallas_stem`` (bias + ReLU
  (+ 3x3/s2 max-pool) in one VMEM pass; the conv stays an XLA gemm).
  In no benchmark cell; speed on the chip: not measured.

Off the TPU the kernels run in Pallas interpreter mode: the CPU suite's
parity harness (tests/test_pallas_stem.py); LRN is never a model path there.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.custom_partitioning import custom_partitioning
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec

from npairloss_tpu.obs import tracing
from npairloss_tpu.ops.pallas_mode import default_interpret

_LANES = 128


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# -- LRN ---------------------------------------------------------------------
#
# LRN is independent per pixel, so the kernel may take the activation in
# the order the neighbouring XLA ops keep it in HBM; any other order costs
# a relayout copy of the whole tensor on each side of the call.  XLA's TPU
# layout assignment puts on the 128 lanes whichever of batch and channels
# pads them less (compiled HLO at batch 1..480, PERF.md PR 26): training
# is ``bf16[480,56,56,64]{0,3,2,1:T(8,128)(2,1)}``, serving channel-minor.
# Two views of one algorithm, by the same rule:
# * ``cols`` — (pixels, C, N) blocks: the batch on the lanes, the window
#   along the SUBLANES (the transpose is a bitcast of that layout);
# * ``rows`` — (N * pixels, C) blocks, the whole channel dimension as the
#   block's last: the window along the LANES (any rank, any C).
# Both cover ragged extents with a ``cdiv`` grid (no padded copy), move
# blocks of about ``_BLOCK_BYTES`` and compute on register-sized chunks.

_BLOCK_BYTES = 1 << 20   # one operand's block of a grid step
_CHUNK_ELEMS = 8 * 1024  # float32 elements of one in-register chunk
_COLS_CHUNK_ROWS = 64    # channels of one chunk in the ``cols`` view


class _LRNParams(NamedTuple):
    """Hashable static bundle (trace-time config)."""
    size: int
    alpha: float
    beta: float
    k: float
    interpret: bool

    @property
    def window(self) -> Tuple[int, int]:
        """(lo, hi): channels before / after the centre, Caffe's split."""
        return self.size // 2, self.size - 1 - self.size // 2


def _win_lanes(v: jax.Array, lo: int, hi: int) -> jax.Array:
    """out[:, i] = sum_{o=-lo..hi} v[:, i+o], zero fill at both ends:
    the in-register form of the reference's ``reduce_window``."""
    c = v.shape[1]
    vp = jnp.pad(v, ((0, 0), (lo, hi)))
    return functools.reduce(
        jnp.add, [vp[:, o:o + c] for o in range(lo + hi + 1)])


def _win_sublanes(v: jax.Array, lo: int, hi: int) -> jax.Array:
    """out[i, :] = sum_{o=-lo..hi} v[i+o, :] by sublane rotations, which
    wrap: the caller brings halo rows (neighbours, or zeros at the channel
    edges) and reads the centre.  Pairs are summed once and shifted
    together: 3 rotations for a window of 5."""
    rows = v.shape[0]
    shift = lambda a, o: pltpu.roll(a, (-o) % rows, 0) if o else a
    pair = v + shift(v, 1)  # pair[i] = v[i] + v[i+1]
    terms = [shift(pair, o) for o in range(-lo, hi, 2)]
    if (lo + hi) % 2 == 0:
        terms.append(shift(v, hi))
    return functools.reduce(jnp.add, terms)


def _lrn_math(x, g, p: _LRNParams, win, exact: bool):
    """y (``g`` None) or dx, float32 in and out.  With y_i = x_i d_i^-b,
    d_i = k + a W(x^2)_i, a = alpha/size and W the forward window:
        dx_j = g_j d_j^-b - 2ab x_j W^T(g x d^(-b-1))_j
    where W^T is the window with (lo, hi) swapped.  ``d`` is recomputed
    from x (float32 ``d`` in HBM: 4 B an element each way against 2).
    The powers are exp2 of a multiple of log d (one transcendental-unit
    op and a multiply each; 2e-6 of float64 on the chip) unless the
    result is float32 (``exact``): then the reference's
    (sqrt(rsqrt(d)))^3, 5e-7, at twice the vector ops — Mosaic expands
    rsqrt / sqrt into a dozen apiece, and the pass is VALU-bound."""
    lo, hi = p.window
    d = p.k + (p.alpha / p.size) * win(x * x, lo, hi)
    if exact and p.beta == 0.75:
        r2 = jax.lax.rsqrt(d)
        s = jnp.sqrt(r2)
        f = s * s * s
        f_over_d = f * (r2 * r2)
    else:
        t, log2e = jnp.log(d), 1.4426950408889634
        f = jnp.exp2(jnp.float32(-p.beta * log2e) * t)
        f_over_d = jnp.exp2(jnp.float32(-(p.beta + 1) * log2e) * t)
    if g is None:
        return x * f
    u = g * x * f_over_d
    return g * f - (2.0 * p.alpha / p.size * p.beta) * x * win(u, hi, lo)


def _sublane_tile(dtype) -> int:
    """Rows of one (sublane, lane) tile: 8 at 4 bytes, 16 at 2."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _lrn_cols_kernel(*refs, p: _LRNParams):
    """Blocks (pixels, C, N).  Per pixel and 128-lane column, channels go
    through the registers in chunks of ``_COLS_CHUNK_ROWS`` with a halo of
    sublane tiles: the neighbouring chunk's rows, or zeros past the ends."""
    *in_refs, o_ref = refs
    nb, c, n = o_ref.shape
    halo = _round_up(2 * max(p.window), 8)
    load = _round_up(halo, _sublane_tile(o_ref.dtype))

    def haloed(ref, i, c0, cr, l0, ln):
        r0, r1 = max(c0 - load, 0), min(c0 + cr + load, c)
        v = ref[i, r0:r1, l0:l0 + ln].astype(jnp.float32)
        v = jnp.pad(v, ((load - (c0 - r0), load - (r1 - c0 - cr)), (0, 0)))
        return v[load - halo:load + cr + halo]

    def pixel(i, carry):
        for l0 in range(0, n, _LANES):
            ln = min(_LANES, n - l0)
            for c0 in range(0, c, _COLS_CHUNK_ROWS):
                cr = min(_COLS_CHUNK_ROWS, c - c0)
                x, *g = (haloed(r, i, c0, cr, l0, ln) for r in in_refs)
                out = _lrn_math(x, g[0] if g else None, p, _win_sublanes,
                                o_ref.dtype == jnp.float32)
                o_ref[i, c0:c0 + cr, l0:l0 + ln] = (
                    out[halo:halo + cr].astype(o_ref.dtype))
        return carry

    jax.lax.fori_loop(0, nb, pixel, 0)


def _rows_chunk(c: int, dtype) -> int:
    tile = _sublane_tile(dtype)
    return max(tile, _CHUNK_ELEMS // _round_up(c, _LANES) // tile * tile)


def _lrn_rows_kernel(*refs, p: _LRNParams):
    """Blocks (rows, C), a pixel a row."""
    *in_refs, o_ref = refs
    br, c = o_ref.shape
    rs = min(_rows_chunk(c, o_ref.dtype), br)

    def chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * rs, rs), rs)
        x, *g = (r[rows, :].astype(jnp.float32) for r in in_refs)
        out = _lrn_math(x, g[0] if g else None, p, _win_lanes,
                        o_ref.dtype == jnp.float32)
        o_ref[rows, :] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, br // rs, chunk, 0)


@functools.partial(jax.jit, static_argnums=(0,))
def _lrn_call(p: _LRNParams, *operands: jax.Array) -> jax.Array:
    """y from (x,), dx from (x, g): one ``pallas_call`` over the view the
    shape picks.  Jitted for its trace cache (a step is traced 3 times)."""
    x = operands[0]
    shape, n, c, item = x.shape, x.shape[0], x.shape[-1], x.dtype.itemsize
    cols = (x.ndim > 2 and c % _sublane_tile(x.dtype) == 0
            and _round_up(n, _LANES) * c < _round_up(c, _LANES) * n)
    if cols:
        rows, lanes, kernel = math.prod(shape[1:-1]), n, _lrn_cols_kernel
        view = lambda a: jnp.moveaxis(a, 0, -1).reshape(rows, c, n)
        unview = lambda a: jnp.moveaxis(a.reshape(shape[1:] + (n,)), -1, 0)
        per = c * _round_up(n, _LANES) * item
        block = (min(rows, max(1, _BLOCK_BYTES // per)), c, n)
    else:
        rows, lanes, kernel = math.prod(shape[:-1]), c, _lrn_rows_kernel
        view = lambda a: a.reshape(rows, c)
        unview = lambda a: a.reshape(shape)
        rs = _rows_chunk(c, x.dtype)
        per = _round_up(c, _LANES) * item
        block = (rows if rows <= rs else min(
            max(rs, _BLOCK_BYTES // per // rs * rs), rows // rs * rs), c)
    tracing.instant("lrn/kernel", view="cols" if cols else "rows",
                    rows=rows, lanes=lanes, block_rows=block[0],
                    backward=len(operands) > 1)
    spec = pl.BlockSpec(block, lambda i: (i,) + (0,) * (len(block) - 1))
    out = pl.pallas_call(
        functools.partial(kernel, p=p),
        grid=(pl.cdiv(rows, block[0]),),
        in_specs=[spec] * len(operands),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows,) + block[1:], x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=p.interpret,
    )(*(view(a) for a in operands))
    return unview(out)


# Under GSPMD each device runs the kernel on its own pixels (a bare
# ``pallas_call`` is gathered whole): the leading dimensions' sharding
# stays, the channels are whole on every device.
def _pixel_sharding(p, mesh, arg_shapes, result_shape):
    spec = tuple(arg_shapes[0].sharding.spec)[:len(arg_shapes[0].shape) - 1]
    return NamedSharding(mesh, PartitionSpec(*spec))


def _partition(p, mesh, arg_shapes, result_shape):
    s = _pixel_sharding(p, mesh, arg_shapes, result_shape)
    return mesh, functools.partial(_lrn_call, p), s, (s,) * len(arg_shapes)


@functools.partial(custom_partitioning, static_argnums=(1,))
def _lrn_fwd_op(x, p: _LRNParams):
    return _lrn_call(p, x)


@functools.partial(custom_partitioning, static_argnums=(2,))
def _lrn_bwd_op(x, g, p: _LRNParams):
    return _lrn_call(p, x, g)


for _op, _rule in ((_lrn_fwd_op, "... c -> ... c"),
                   (_lrn_bwd_op, "... c, ... c -> ... c")):
    _op.def_partition(
        partition=_partition, infer_sharding_from_operands=_pixel_sharding,
        sharding_rule=_rule, need_replication_factors=("c",))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fused_lrn(x: jax.Array, p: _LRNParams) -> jax.Array:
    return _lrn_fwd_op(x, p)


def _fused_lrn_vjp_fwd(x, p: _LRNParams):
    return _lrn_fwd_op(x, p), x  # the residual: x alone


def _fused_lrn_vjp_bwd(p: _LRNParams, x, g):
    return (_lrn_bwd_op(x, g.astype(x.dtype), p),)


_fused_lrn.defvjp(_fused_lrn_vjp_fwd, _fused_lrn_vjp_bwd)


def fused_lrn(x: jax.Array, size: int = 5, alpha: float = 1e-4,
              beta: float = 0.75, k: float = 1.0,
              interpret: Optional[bool] = None) -> jax.Array:
    """Across-channel LRN (Caffe semantics, channels-last) as one fused
    Pallas pass each way — what ``models.layers.local_response_norm``
    runs on a TPU.  ``interpret`` forces / forbids Pallas interpreter
    mode (None = auto: interpret off-TPU)."""
    if interpret is None:
        interpret = default_interpret()
    p = _LRNParams(int(size), float(alpha), float(beta), float(k),
                   bool(interpret))
    return _fused_lrn(x, p)


# -- conv epilogues ----------------------------------------------------------

_BLOCK_ROWS = 256


def _pad2d(x: jax.Array, rows: int, cols: int) -> jax.Array:
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def _pad_geometry(shape) -> Tuple[int, int, int, int]:
    """(rows, c, rpad, cpad) of the 2-D channels-last view: channels
    lane-padded to 128, rows to 16 sublanes (small inputs) or a
    _BLOCK_ROWS multiple (both satisfy the bf16 (16, 128) min tile)."""
    c = shape[-1]
    rows = max(math.prod(shape[:-1]), 1)
    rpad = _round_up(rows, _BLOCK_ROWS if rows >= _BLOCK_ROWS else 16)
    return rows, c, rpad, _round_up(c, _LANES)


def _bias_relu_kernel(x_ref, b_ref, o_ref):
    y = x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


class _EpiParams(NamedTuple):
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_bias_relu(x: jax.Array, bias: jax.Array,
                     p: _EpiParams) -> jax.Array:
    rows, c, rpad, cpad = _pad_geometry(x.shape)
    x2 = _pad2d(x.reshape(rows, c), rpad, cpad)
    b2 = _pad2d(bias.reshape(1, c), 1, cpad)
    br = min(rpad, _BLOCK_ROWS)
    out2 = pl.pallas_call(
        _bias_relu_kernel,
        grid=(rpad // br,),
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
