"""GoogLeNet (Inception v1) embedding backbone in Flax.

The reference net (usage/def.prototxt:1, "GoogleNet") is the standard
Inception-v1 trunk truncated at pool5/7x7_s1 — the 1024-d pooled feature is
the embedding, L2-normalized before the loss (def.prototxt:115-126).  This
is a fresh Flax NHWC implementation designed for the MXU (bf16 activations,
conv+relu fused by XLA), not a translation of the prototxt layer list.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from npairloss_tpu.models.layers import (
    ConvBlock,
    global_avg_pool,
    local_response_norm,
    max_pool,
    space_to_depth,
)
from npairloss_tpu.models.precision import PrecisionPolicy
from npairloss_tpu.ops.normalize import l2_normalize

# Inception block channel plans: (1x1, 3x3red, 3x3, 5x5red, 5x5, pool_proj).
_INCEPTION_PLAN = {
    "3a": (64, 96, 128, 16, 32, 32),
    "3b": (128, 128, 192, 32, 96, 64),
    "4a": (192, 96, 208, 16, 48, 64),
    "4b": (160, 112, 224, 24, 64, 64),
    "4c": (128, 128, 256, 24, 64, 64),
    "4d": (112, 144, 288, 32, 64, 64),
    "4e": (256, 160, 320, 32, 128, 128),
    "5a": (256, 160, 320, 32, 128, 128),
    "5b": (384, 192, 384, 48, 128, 128),
}


class Inception(nn.Module):
    plan: Tuple[int, int, int, int, int, int]
    dtype: Any = jnp.float32
    use_bn: bool = False
    # Mixed-precision policy, threaded into every ConvBlock (each block
    # regex-resolves its own path against the policy's rules).
    policy: Optional[PrecisionPolicy] = None
    # Merge the three 1x1 convs that read the block input (b1x1,
    # b3x3_reduce, b5x5_reduce) into ONE conv with p1+p3r+p5r output
    # channels, then slice.  Same dot products, same per-channel
    # ReLU/BN — exact algebra — but the MXU sees one gemm with a full
    # lane tile instead of three thin ones (e.g. 3a: 64/96/16 -> 176;
    # a 16-channel conv occupies 1/8 of the 128-lane systolic axis).
    # Checkpoints interchange via ``fuse_inception_1x1_params``.
    fuse_1x1: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        p1, p3r, p3, p5r, p5, pp = self.plan
        conv = lambda f, k, name: ConvBlock(
            f, k, dtype=self.dtype, use_bn=self.use_bn,
            policy=self.policy, name=name,
        )
        if self.fuse_1x1:
            fused = conv(p1 + p3r + p5r, (1, 1), "fused_1x1")(x, train)
            b1 = fused[..., :p1]
            b3 = fused[..., p1:p1 + p3r]
            b5 = fused[..., p1 + p3r:]
        else:
            b1 = conv(p1, (1, 1), "b1x1")(x, train)
            b3 = conv(p3r, (1, 1), "b3x3_reduce")(x, train)
            b5 = conv(p5r, (1, 1), "b5x5_reduce")(x, train)
        b3 = conv(p3, (3, 3), "b3x3")(b3, train)
        b5 = conv(p5, (5, 5), "b5x5")(b5, train)
        bp = max_pool(x, 3, 1, "SAME")
        bp = conv(pp, (1, 1), "pool_proj")(bp, train)
        return jnp.concatenate([b1, b3, b5, bp], axis=-1)


class GoogLeNetEmbedding(nn.Module):
    """Inception-v1 trunk -> pool5 (1024-d) -> optional L2 normalize.

    Input: NHWC images (224x224x3 canonical).  ``normalize=True`` matches
    the reference's L2Normalize-before-loss topology.
    """

    dtype: Any = jnp.bfloat16
    normalize: bool = True
    use_lrn: bool = True
    # Inception-BN: BatchNorm after every conv (bias dropped), LRN off —
    # the variant that trains from scratch; the BN-free v1 trunk collapses
    # at random init (see ACCURACY.md).  Parameter-parity with the
    # reference's prototxt trunk keeps use_bn=False the default.
    use_bn: bool = False
    # Rematerialize each inception block in the backward pass: trades
    # ~25% more trunk FLOPs for O(stage) activation memory, lifting the
    # batch ceiling / relieving HBM pressure at large per-chip batches
    # (the measured MFU decay from batch 120 -> 480, PROFILE.md).
    # Numerically identical to remat=False.
    remat: bool = False
    # Fused inception 1x1s (see Inception.fuse_1x1): exact algebra,
    # better MXU lane occupancy on the thin reduce branches; weights
    # interchange via fuse_inception_1x1_params.
    fuse_1x1: bool = False
    # Caffe-exact conv1 padding: Caffe pads the 7x7/s2 stem symmetrically
    # (pad: 3, usage/def.prototxt:100) while SAME uses (2, 3) at 224 —
    # same output shape, border-pixel differences only.  Set True when
    # running imported .caffemodel weights for closest-to-source
    # inference (pool layers already agree: SAME's right-biased padding
    # reproduces Caffe's pad-0 ceil pooling at these shapes).
    caffe_pad: bool = False
    # Space-to-depth stem: the 7x7/s2 conv over 3 input channels maps
    # poorly onto the 128-lane MXU (contraction depth 7*7*3 = 147 with
    # C_in=3 on the lane axis).  stem_s2d=True rewrites it as the exact
    # algebraic equivalent: space_to_depth(2) then a 4x4/s1 conv over 12
    # channels (pad (1,2), mirroring SAME's (2,3) on the full grid) —
    # same function, better tiling.  Weights
    # convert losslessly both ways via `conv1_kernel_to_s2d`.
    stem_s2d: bool = False
    # Declarative mixed-precision policy (models.precision): resolves
    # every ConvBlock's param/compute dtypes + MXU matmul precision by
    # regex over the module path, and the trunk's entry/exit casts from
    # its compute/output dtypes.  None keeps the pre-policy ``dtype``
    # behavior (HLO-identical).
    policy: Optional[PrecisionPolicy] = None
    # Pallas conv epilogues (ops.pallas_stem): conv1's bias + ReLU +
    # max-pool and conv2_reduce / conv2's bias + ReLU, each in one VMEM
    # pass.  Bias-LRN trunks only (the BN trunk has no conv biases);
    # parameter tree unchanged, interpret-mode parity-tested on CPU; in
    # no benchmark cell, speed on the chip: not measured.  (LRN picks
    # its own kernel by backend: layers.local_response_norm.)
    pallas_stem: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        use_lrn = self.use_lrn and not self.use_bn
        fuse_stem = self.pallas_stem and not self.use_bn
        compute_dtype = (self.policy.compute_dtype
                         if self.policy is not None else self.dtype)
        x = x.astype(compute_dtype)
        if self.stem_s2d:
            x = space_to_depth(x, 2)
            x = ConvBlock(
                64, (4, 4), (1, 1), padding=((1, 2), (1, 2)),
                dtype=self.dtype, use_bn=self.use_bn, policy=self.policy,
                fused_epilogue=fuse_stem,
                fuse_pool=(3, 2) if fuse_stem else None,
                name="conv1",
            )(x, train)
        else:
            x = ConvBlock(
                64, (7, 7), (2, 2),
                padding=((3, 3), (3, 3)) if self.caffe_pad else "SAME",
                dtype=self.dtype, use_bn=self.use_bn, policy=self.policy,
                fused_epilogue=fuse_stem,
                fuse_pool=(3, 2) if fuse_stem else None,
                name="conv1",
            )(x, train)
        # named_scope: the pools between the blocks and the LRNs are
        # trunk-top-level code (not flax submodules), so without a
        # scope their cost — the max-pool backward's select-and-scatter
        # above all — would land in the root region of the trace
        # instead of being attributable.  The prototxt's layer names;
        # metadata only, the program is unchanged.
        if not fuse_stem:
            with jax.named_scope("pool1"):
                x = max_pool(x, 3, 2)
        if use_lrn:
            with jax.named_scope("lrn"):
                x = local_response_norm(x)
        x = ConvBlock(
            64, (1, 1), dtype=self.dtype, use_bn=self.use_bn,
            policy=self.policy, fused_epilogue=fuse_stem,
            name="conv2_reduce",
        )(x, train)
        x = ConvBlock(
            192, (3, 3), dtype=self.dtype, use_bn=self.use_bn,
            policy=self.policy, fused_epilogue=fuse_stem, name="conv2"
        )(x, train)
        if use_lrn:
            with jax.named_scope("lrn"):
                x = local_response_norm(x)
        with jax.named_scope("pool2"):
            x = max_pool(x, 3, 2)
        # nn.remat checkpoints the block boundary: only each block's
        # input survives to the backward, its internals recompute.
        # ``train`` (argnum 2; 0 is the module) must be static — it
        # selects the BN branch at trace time.
        incep_cls = (
            nn.remat(Inception, static_argnums=(2,))
            if self.remat else Inception
        )
        incep = lambda key: incep_cls(
            _INCEPTION_PLAN[key], self.dtype, self.use_bn,
            policy=self.policy,
            fuse_1x1=self.fuse_1x1, name=f"inception_{key}",
        )
        x = incep("3a")(x, train)
        x = incep("3b")(x, train)
        with jax.named_scope("pool3"):
            x = max_pool(x, 3, 2)
        for key in ("4a", "4b", "4c", "4d", "4e"):
            x = incep(key)(x, train)
        with jax.named_scope("pool4"):
            x = max_pool(x, 3, 2)
        x = incep("5a")(x, train)
        x = incep("5b")(x, train)
        x = global_avg_pool(x)  # pool5/7x7_s1 -> (N, 1024)
        x = x.astype(self.policy.output_dtype
                     if self.policy is not None else jnp.float32)
        if self.normalize:
            x = l2_normalize(x)
        return x



def fuse_inception_1x1_params(params, batch_stats=None):
    """Convert plain-trunk variables to the ``fuse_1x1=True`` layout.

    Exact: the fused conv's kernel/bias (and BN scale/bias/mean/var —
    all per-output-channel) are the channel-wise concatenation of
    b1x1 ++ b3x3_reduce ++ b5x5_reduce, in the slice order
    ``Inception.__call__`` uses.  Returns (params, batch_stats) with
    the three branch entries replaced by one ``fused_1x1`` entry;
    ``batch_stats`` may be None (bias/LRN trunk).
    """
    import jax

    def convert_tree(tree):
        if tree is None:
            return None
        out = jax.tree_util.tree_map(lambda x: x, tree)  # deep-ish copy
        for block, sub in list(out.items()):
            if not block.startswith("inception_") or "b1x1" not in sub:
                continue
            parts = [sub.pop("b1x1"), sub.pop("b3x3_reduce"),
                     sub.pop("b5x5_reduce")]
            fused = {}
            for mod in parts[0]:  # "Conv_0" and, for BN trunks, "BatchNorm_0"
                fused[mod] = {
                    leaf: jnp.concatenate(
                        [p[mod][leaf] for p in parts], axis=-1
                    )
                    for leaf in parts[0][mod]
                }
            sub["fused_1x1"] = fused
        return out

    return convert_tree(params), convert_tree(batch_stats)
