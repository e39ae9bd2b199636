"""Adapter for the scratch ``scratch_mlp`` family of
``test_a_configuration_is_added_as_files``: the program's ``mlp`` trunk
on a vector input, beside its plain reference.  The two parameter
layouts are the same tree.  ``post_init`` leaves a group as it is.  It
answers the optional ``stages`` too: two stages, one layer each."""

from __future__ import annotations

import numpy as np

from benchmarks.harness import weights
from benchmarks.reference import scratch_mlp as ref

embed = ref.embed
stages = ref.stages  # the optional question: the reference a layer at a time


def input_shape(cfg):
    return (cfg["input_dim"],)


def warm_inputs(cfg, mix):
    return [input_shape(cfg)]


def query_pool(cfg, mix, seed):
    return weights.normal_pool(seed, mix["pool_images"], input_shape(cfg))


def train_batches(cfg, mix, seed):
    return weights.identity_batches(seed, mix["pool_batches"], mix["identities"],
                                    mix["per_identity"], input_shape(cfg))


def forward_flops(cfg, x=None):
    """Two operations a weight: the vector through every dense layer."""
    return sum(2 * k[0] * k[1] for k in (s["kernel"] for s in shapes(cfg).values()))


def shapes(cfg):
    return ref.param_shapes(cfg["input_dim"], cfg["hidden"], cfg["embedding_dim"])


def init_scales(cfg):
    return {name: {"kernel": ("uniform", float(np.sqrt(6.0 / leaf["kernel"][0]))),
                   "bias": ("zeros", 0.0)}
            for name, leaf in shapes(cfg).items()}


def post_init(params):
    return params


def build_model(cfg):
    from npairloss_tpu.models import get_model

    return get_model(cfg["program"]["model"], policy=cfg["program"]["precision"],
                     hidden=tuple(cfg["hidden"]), embedding_dim=cfg["embedding_dim"])


def to_program(params, xp=np):
    return {name: dict(leaf) for name, leaf in params.items()}


from_program = to_program
