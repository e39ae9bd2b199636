"""Adapter for the ``googlenet_v1`` family: the program's flagship trunk
(``googlenet_mxu``: space-to-depth stem, fused inception 1x1s) beside
the plain reference, and the two-way map between their parameter
layouts.  Both maps are exact re-arrangements (no arithmetic).

An adapter is everything the harness asks of a family, as explicit
functions (a family that lacks one fails on the attribute, no default
decides for it): what an input is (``input_shape``, ``warm_inputs``,
``query_pool``, ``train_batches``), what one input's forward pass
requires (``forward_flops``), the weights (``shapes``, ``init_scales``,
``post_init``, laid out by ``to_program`` / ``from_program``), the
program's model (``build_model``) and the plain reference (``embed``).
``post_init`` is handed one of ``weights.make_params``' groups of whole
layers (here always the whole tree: 28 MB), traced in float32 inside
the group's jitted call, before the cast to ``precision.params``."""

from __future__ import annotations

import numpy as np

from benchmarks.harness import weights
from benchmarks.reference import googlenet as ref

FUSED = ("b1x1", "b3x3_reduce", "b5x5_reduce")
embed = ref.embed


def input_shape(cfg):
    """One input, as ``Solver`` and ``RetrievalServer`` take it."""
    return (cfg["image_size"], cfg["image_size"], cfg["num_channels"])


def warm_inputs(cfg, mix):
    """What ``QueryEngine.warmup`` is called on, once each: every image
    has the one shape, so one call warms every bucket's program."""
    return [input_shape(cfg)]


def query_pool(cfg, mix, seed):
    """The serving pool: one unit-normal image for each key."""
    return weights.normal_pool(seed, mix["pool_images"], input_shape(cfg))


def train_batches(cfg, mix, seed):
    """The staged pool of identity-balanced batches: (images, labels)."""
    return weights.identity_batches(seed, mix["pool_batches"], mix["identities"],
                                    mix["per_identity"], input_shape(cfg))


def forward_flops(cfg, x=None):
    """Operations one image's forward pass requires; every image of a
    configuration has the one shape, so ``x`` tells nothing more."""
    return ref.forward_flops(cfg["image_size"], cfg["num_channels"])


def shapes(cfg):
    return ref.param_shapes(cfg["num_channels"])


GAIN = 1.1    # keeps the activation scale that the centring takes away
CENTRE = 0.5  # share of each output channel's mean tap taken out


def init_scales(cfg):
    """He-uniform kernels (bound GAIN * sqrt(6 / fan_in)), zero biases."""
    out = {}
    for name, leaf in shapes(cfg).items():
        k = leaf["kernel"]
        bound = GAIN * float(np.sqrt(6.0 / (k[0] * k[1] * k[2])))
        out[name] = {"kernel": ("uniform", bound), "bias": ("zeros", 0.0)}
    return out


def post_init(params):
    """Half-centred kernels past the stem: half of each output channel's
    mean tap is taken out.  Plain He weights (CENTRE 0) collapse every
    embedding of this ReLU trunk onto one direction (cosines of 0.9997
    between unrelated images): the loss's gradient is then a difference
    of nearly equal vectors and reads rounding alone.  Fully centred
    kernels (CENTRE 1) spread the embeddings (0.83 to 0.91) but make the
    untrained trunk chaotic: a rounding error grows with depth until a
    float8 trunk and a bfloat16 trunk read alike (measured on the chip,
    PR 24).  Half way keeps the cosines at 0.98 to 0.995 and the growth
    of errors moderate, as in a trained network.  The stem's eighth row
    and column of taps start at zero (see the reference)."""
    for name, leaf in params.items():
        k = leaf["kernel"]
        if name == "conv1":
            leaf["kernel"] = k.at[7, :, :, :].set(0.0).at[:, 7, :, :].set(0.0)
        else:
            leaf["kernel"] = k - CENTRE * k.mean(axis=(0, 1, 2), keepdims=True)
    return params


def build_model(cfg):
    from npairloss_tpu.models import get_model

    return get_model(cfg["program"]["model"], policy=cfg["program"]["precision"])


def to_program(params, xp=np):
    """Plain layout -> the program's flax tree."""
    out = {}
    for name, leaf in params.items():
        if "/" not in name:
            k = leaf["kernel"]
            if name == "conv1":  # (8,8,C,F)[2u+dh,2v+dw,c,f] -> (4,4,4C,F)
                c, f = k.shape[2], k.shape[3]
                k = k.reshape(4, 2, 4, 2, c, f).transpose(0, 2, 1, 3, 4, 5)
                k = k.reshape(4, 4, 4 * c, f)
            out[name] = {"Conv_0": {"kernel": k, "bias": leaf["bias"]}}
    for block in sorted({n.split("/")[0] for n in params if "/" in n}):
        g = lambda b: params[f"{block}/{b}"]
        out[block] = {
            "fused_1x1": {"Conv_0": {
                "kernel": xp.concatenate([g(b)["kernel"] for b in FUSED], -1),
                "bias": xp.concatenate([g(b)["bias"] for b in FUSED], -1)}},
            **{b: {"Conv_0": dict(g(b))} for b in ("b3x3", "b5x5", "pool_proj")},
        }
    return out


def from_program(tree, xp=np):
    """The program's flax tree -> plain layout."""
    out = {}
    for name, sub in tree.items():
        if not name.startswith("inception_"):
            k = sub["Conv_0"]["kernel"]
            if name == "conv1":
                c, f = k.shape[2] // 4, k.shape[3]
                k = k.reshape(4, 4, 2, 2, c, f).transpose(0, 2, 1, 3, 4, 5)
                k = k.reshape(8, 8, c, f)
            out[name] = {"kernel": k, "bias": sub["Conv_0"]["bias"]}
            continue
        for b in ("b3x3", "b5x5", "pool_proj"):
            out[f"{name}/{b}"] = dict(sub[b]["Conv_0"])
        widths = [ref.INCEPTION[name.split("_")[1]][i] for i in (0, 1, 3)]
        cuts = np.cumsum([0] + widths)
        for b, lo, hi in zip(FUSED, cuts[:-1], cuts[1:]):
            out[f"{name}/{b}"] = {
                "kernel": sub["fused_1x1"]["Conv_0"]["kernel"][..., lo:hi],
                "bias": sub["fused_1x1"]["Conv_0"]["bias"][..., lo:hi]}
    return out
