"""Shared model building blocks (NHWC, bf16-friendly)."""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from npairloss_tpu.models.precision import (
    ModulePrecision,
    PrecisionPolicy,
    module_precision,
)


def local_response_norm(
    x: jax.Array,
    size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    k: float = 1.0,
) -> jax.Array:
    """Across-channel LRN (the classic GoogLeNet/AlexNet normalization).

    x: NHWC.  Matches Caffe LRN semantics: denominator
    (k + alpha/size * sum_{window} x^2)^beta over a channel window,
    float32 inside whatever the input's dtype.

    On a TPU backend this is the fused kernel (ops.pallas_stem.fused_lrn:
    one pass each way in the tensor's own dtype); elsewhere the
    ``reduce_window`` body below, the kernel's parity reference.
    """
    if jax.default_backend() == "tpu":
        from npairloss_tpu.ops.pallas_stem import fused_lrn

        return fused_lrn(x, size, alpha, beta, k)
    return local_response_norm_xla(x, size, alpha, beta, k)


def local_response_norm_xla(
    x: jax.Array,
    size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    k: float = 1.0,
) -> jax.Array:
    """LRN over plain XLA ops: what every non-TPU backend runs, and the
    parity reference of the kernel (tests, chip_kernel_check)."""
    xf = x.astype(jnp.float32)
    sq = xf * xf
    win = jax.lax.reduce_window(
        sq,
        0.0,
        jax.lax.add,
        window_dimensions=(1, 1, 1, size),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0), (0, 0), (0, 0), (size // 2, size - 1 - size // 2)),
    )
    d = k + (alpha / size) * win
    if beta == 0.75:
        # The reference's beta: d^-0.75 == (sqrt(rsqrt(d)))^3, two fast
        # VPU ops + two mults instead of the exp+log a generic pow
        # lowers to.  Differs from pow by a few float32 ulp — inside
        # oracle tolerance (tests/test_models.py LRN parity).
        r = jnp.sqrt(jax.lax.rsqrt(d))
        out = xf * (r * r * r)
    else:
        out = xf / jnp.power(d, beta)
    return out.astype(x.dtype)


class _EpilogueConv(nn.Module):
    """``nn.Conv``-compatible parameter tree (``kernel`` + ``bias``)
    that returns the PRE-BIAS conv output and the bias separately, so a
    Pallas epilogue (ops.pallas_stem) can fuse bias + ReLU (+ pool) in
    one VMEM pass.  Named ``Conv_0`` by the caller, checkpoints
    interchange with the plain ``nn.Conv`` path byte-for-byte."""

    features: int
    kernel: Tuple[int, int]
    strides: Tuple[int, int]
    padding: Any
    mp: ModulePrecision

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel
        kernel = self.param(
            "kernel", nn.initializers.xavier_uniform(),
            (kh, kw, x.shape[-1], self.features), self.mp.param_dtype,
        )
        bias = self.param(
            "bias", nn.initializers.constant(0.2),
            (self.features,), self.mp.param_dtype,
        )
        pad = self.padding
        if not isinstance(pad, str):
            pad = tuple(tuple(p) for p in pad)
        y = jax.lax.conv_general_dilated(
            x.astype(self.mp.compute_dtype),
            kernel.astype(self.mp.compute_dtype),
            window_strides=self.strides,
            padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.mp.precision,
        )
        return y, bias


class ConvBlock(nn.Module):
    """Conv + bias + ReLU, Caffe-style 'xavier' init (def.prototxt:98-110).

    ``use_bn=True`` switches to conv (no bias) + BatchNorm + ReLU — the
    Inception-BN recipe.  A BN-free Inception-v1 from random init
    collapses (all embeddings align; the original needed aux classifiers
    + ImageNet schedules), so the BN variant is what trains from scratch.

    ``policy`` (models.precision.PrecisionPolicy) resolves this module's
    param/compute dtypes and MXU matmul precision by regex over its own
    flax path; with no policy the block is HLO-identical to the
    pre-policy constructors (``dtype`` compute over fp32 params, no
    explicit precision).  ``fused_epilogue`` routes bias+ReLU through
    the one-VMEM-pass Pallas kernel (ops.pallas_stem), and ``fuse_pool``
    =(window, stride) additionally folds the following SAME max-pool
    into the same pass (the caller must then skip its own pool).
    """

    features: int
    kernel: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: Any = "SAME"
    dtype: Any = jnp.float32
    use_bn: bool = False
    policy: Optional[PrecisionPolicy] = None
    fused_epilogue: bool = False
    fuse_pool: Optional[Tuple[int, int]] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        mp = module_precision(self.policy, self.path, self.dtype)
        if self.fused_epilogue and not self.use_bn:
            from npairloss_tpu.ops.pallas_stem import (
                fused_bias_relu,
                fused_bias_relu_pool,
            )

            y, bias = _EpilogueConv(
                self.features, self.kernel, self.strides, self.padding,
                mp, name="Conv_0",
            )(x)
            if self.fuse_pool is not None:
                return fused_bias_relu_pool(y, bias, *self.fuse_pool)
            return fused_bias_relu(y, bias)
        x = nn.Conv(
            self.features,
            self.kernel,
            strides=self.strides,
            padding=self.padding,
            dtype=mp.compute_dtype,
            param_dtype=mp.param_dtype,
            precision=mp.precision,
            use_bias=not self.use_bn,
            kernel_init=nn.initializers.xavier_uniform(),
            bias_init=nn.initializers.constant(0.2),
        )(x)
        if self.use_bn:
            x = nn.BatchNorm(
                use_running_average=not train, momentum=0.9,
                dtype=mp.compute_dtype,
            )(x)
        return nn.relu(x)


def space_to_depth(x: jax.Array, block: int = 2) -> jax.Array:
    """NHWC space-to-depth: (N,H,W,C) -> (N,H/b,W/b,b*b*C).

    Pixel (bh+dh, bw+dw, c) lands in output channel (dh*b+dw)*C + c —
    the layout `conv1_kernel_to_s2d` (below) assumes.
    """
    n, h, w, c = x.shape
    if h % block or w % block:
        raise ValueError(
            f"space_to_depth needs H, W divisible by {block}, got {h}x{w} "
            "(the s2d stem requires even input dims; use the plain trunk "
            "for odd crops)"
        )
    x = x.reshape(n, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // block, w // block, block * block * c)


def conv1_kernel_to_s2d(kernel):
    """Convert a (7,7,C,F) stem kernel to its (4,4,4C,F) s2d equivalent.

    With Flax SAME padding a 7x7/s2 stem computes
    ``o[i] = sum_p W[p] x[2i + p - 2]`` (pad_lo=2).  Writing
    ``p - 2 = 2u + d`` (d in {0,1}) turns it into a 4x4/s1 conv over the
    space_to_depth(2) grid with offsets u in {-1..2} — i.e. pad (1,2) —
    where s2d channel ``(dh*2+dw)*C + c`` holds pixel parity (dh, dw).
    With kernel index u_k = u+1, source tap p = 2*u_k + d; the one slot
    with p = 7 (u_k=3, d=1) is zero.  The map is injective, so the
    conversion is lossless.  Shared by the GoogLeNet and ResNet
    ``stem_s2d`` variants.
    """
    kernel = np.asarray(kernel)
    kh, kw, cin, cout = kernel.shape
    if (kh, kw) != (7, 7):
        raise ValueError(f"expected a 7x7 stem kernel, got {kernel.shape}")
    out = np.zeros((4, 4, 4 * cin, cout), dtype=kernel.dtype)
    for u in range(4):
        for v in range(4):
            for dh in range(2):
                for dw in range(2):
                    p, q = 2 * u + dh, 2 * v + dw
                    if 0 <= p < 7 and 0 <= q < 7:
                        d = (dh * 2 + dw) * cin
                        out[u, v, d : d + cin, :] = kernel[p, q, :, :]
    return out


def max_pool(x, window=3, stride=2, padding="SAME"):
    return nn.max_pool(x, (window, window), strides=(stride, stride), padding=padding)


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))
