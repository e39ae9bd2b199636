"""Test configuration: run on a virtual 8-device CPU mesh.

Multi-chip semantics (all_gather negative pooling, psum gradient exchange)
are validated without TPU pods by forcing 8 host-platform devices, per
SURVEY.md §4 ("Distributed without a cluster").  Must run before jax imports.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# train/serve/index turn the persistent compile cache on by default;
# the suite keeps it off (JAX's own switch, inherited by CLI children)
# except where a test turns it on to test it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# jax may already be imported (e.g. by the jaxtyping pytest plugin) with
# JAX_PLATFORMS captured from the shell env — override via config too.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture
def tracer():
    """A fresh ``SpanTracer`` installed as the process's for one test."""
    from npairloss_tpu.obs import tracing

    tr = tracing.SpanTracer()
    prev = tracing.install(tr)
    yield tr
    tracing.install(prev)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_identity_batch(rng, num_ids, imgs_per_id, dim, num_shards=1, scale=1.0):
    """Identity-balanced batches (the MultibatchData contract,
    def.prototxt:25-27): every query has >= imgs_per_id - 1 local positives.

    Returns (features_per_shard, labels_per_shard) lists of length num_shards,
    with L2-normalized rows so similarities live in [-1, 1] like the
    reference's post-L2Normalize embeddings.
    """
    feats, labs = [], []
    for _ in range(num_shards):
        ids = rng.choice(10 * num_ids, size=num_ids, replace=False)
        lab = np.repeat(ids, imgs_per_id).astype(np.int32)
        f = rng.standard_normal((num_ids * imgs_per_id, dim)).astype(np.float32)
        f = scale * f / np.linalg.norm(f, axis=1, keepdims=True)
        perm = rng.permutation(len(lab))
        feats.append(f[perm])
        labs.append(lab[perm])
    return feats, labs
