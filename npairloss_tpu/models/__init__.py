"""Embedding model zoo.

The reference trains a GoogLeNet trunk truncated at pool5 with an
L2-normalized embedding (usage/def.prototxt); BASELINE.json adds ResNet-50
and ViT-B/16 configs.  ``get_model(name)`` is the registry the config
front-end and trainer resolve through.

``get_model(name, policy=...)`` threads a declarative mixed-precision
policy (models.precision: "mxu" / "bf16" / "fp32_parity" or a
PrecisionPolicy object) through the trunk: policy-aware trunks
(GoogLeNet family, ViT) resolve per-module dtypes/precision by regex
over their module paths; the rest honor the policy's compute dtype.
The FLAGSHIP trunk+policy pair — what the benchmark's ``googlenet_v1``
cells run and ``--precision mxu`` defaults to — is ``googlenet_mxu``
under the ``"mxu"`` policy (FLAGSHIP_TRUNK / FLAGSHIP_POLICY below).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Union

from npairloss_tpu.models.googlenet import (
    GoogLeNetEmbedding,
    fuse_inception_1x1_params,
)
from npairloss_tpu.models.mlp import MLPEmbedding
from npairloss_tpu.models.precision import (
    DEFAULT_POLICY,
    PrecisionPolicy,
    available_policies,
    get_policy,
)
from npairloss_tpu.models.resnet import ResNetEmbedding
from npairloss_tpu.models.vit import ViTEmbedding

# The flagship workload's trunk + policy: the parity-preserving MXU
# rewrites (s2d stem + fused inception 1x1s) under the single-pass-bf16
# mixed-precision policy.  Its step on the chip is the benchmark's cell
# 1 (``googlenet_train``; PERF_LEDGER.jsonl has every PR's reading).
# One home, so the CLI and the tests agree on what "flagship" means.
FLAGSHIP_TRUNK = "googlenet_mxu"
FLAGSHIP_POLICY = DEFAULT_POLICY

def _olmo_hybrid(**kwargs):
    from npairloss_tpu.models.olmo_hybrid import OlmoHybridEmbedding

    return OlmoHybridEmbedding(**kwargs)


_REGISTRY: Dict[str, Callable[..., Any]] = {
    "googlenet": GoogLeNetEmbedding,
    "googlenet_embedding": GoogLeNetEmbedding,
    # Inception-BN: the from-scratch-trainable GoogLeNet (BN after every
    # conv, no LRN) — use for training runs without pretrained weights.
    "googlenet_bn": lambda **kw: GoogLeNetEmbedding(use_bn=True, **kw),
    "inception_bn": lambda **kw: GoogLeNetEmbedding(use_bn=True, **kw),
    # Space-to-depth stem: algebraically identical trunk with the 7x7/s2
    # C_in=3 stem rewritten for MXU tiling (see googlenet.stem_s2d);
    # weights interchange with the plain trunk via conv1_kernel_to_s2d.
    "googlenet_s2d": lambda **kw: GoogLeNetEmbedding(stem_s2d=True, **kw),
    "googlenet_bn_s2d": lambda **kw: GoogLeNetEmbedding(
        use_bn=True, stem_s2d=True, **kw
    ),
    # Fused inception 1x1s (exact algebra, MXU lane occupancy — see
    # googlenet.Inception.fuse_1x1); weights interchange with the plain
    # trunk via fuse_inception_1x1_params.  "_mxu" stacks both
    # parity-preserving rewrites (s2d stem + fused 1x1s).
    "googlenet_fused": lambda **kw: GoogLeNetEmbedding(fuse_1x1=True, **kw),
    "googlenet_mxu": lambda **kw: GoogLeNetEmbedding(
        stem_s2d=True, fuse_1x1=True, **kw
    ),
    # Pallas conv epilogues on top of the MXU rewrites: conv bias + ReLU
    # (+ pool) in one pass (ops.pallas_stem; interpret-mode
    # parity-tested, in no benchmark cell).  Parameter tree identical to
    # googlenet_mxu.
    "googlenet_pallas": lambda **kw: GoogLeNetEmbedding(
        stem_s2d=True, fuse_1x1=True, pallas_stem=True, **kw
    ),
    # The headline trunk by its workload name: resolved THROUGH
    # FLAGSHIP_TRUNK at call time, so repointing the flagship repoints
    # --model flagship with it (a copy-pasted constructor here would
    # silently drift).
    "flagship": lambda **kw: _REGISTRY[FLAGSHIP_TRUNK](**kw),
    "resnet50": lambda **kw: ResNetEmbedding(stage_sizes=(3, 4, 6, 3), **kw),
    "resnet50_s2d": lambda **kw: ResNetEmbedding(
        stage_sizes=(3, 4, 6, 3), stem_s2d=True, **kw
    ),
    "resnet18": lambda **kw: ResNetEmbedding(stage_sizes=(2, 2, 2, 2), width=64, **kw),
    "vit_b16": ViTEmbedding,
    "mlp": MLPEmbedding,
    # Token tower (decoder stack as a document encoder: gated-delta-rule
    # and full-attention blocks, masked mean pool).  Resolved at call
    # time: the tower and its ops load only when a token model is built,
    # so a float-input process imports nothing of them.
    "olmo_hybrid": _olmo_hybrid,
}


# Registry names whose trunks thread the policy object all the way to
# per-module resolution; the rest (mlp, resnet) honor its compute dtype
# only.  Kept explicit so a silently-dropped policy is impossible — a
# new policy-aware trunk must be listed here to receive the object.
_POLICY_AWARE = {
    "googlenet", "googlenet_embedding", "googlenet_bn", "inception_bn",
    "googlenet_s2d", "googlenet_bn_s2d", "googlenet_fused",
    "googlenet_mxu", "googlenet_pallas", "flagship", "vit_b16",
}


def get_model(name: str,
              policy: Optional[Union[str, PrecisionPolicy]] = None,
              **kwargs):
    key = name.lower()
    if key not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    if policy is not None:
        pol = get_policy(policy)
        kwargs.setdefault("dtype", pol.compute_dtype)
        if key in _POLICY_AWARE:
            kwargs["policy"] = pol
    return _REGISTRY[key](**kwargs)


def flagship_model(policy: Optional[Union[str, PrecisionPolicy]] =
                   FLAGSHIP_POLICY, **kwargs):
    """The flagship trunk under the default (or given) policy — the ONE
    constructor the CLI flagship paths and the tests share."""
    return get_model(FLAGSHIP_TRUNK, policy=policy, **kwargs)


def jit_init(model, key, example_input, train: bool = False, **kwargs):
    """flax ``model.init`` as ONE compiled program.

    Eager init issues hundreds of small per-op dispatches, each traced
    and compiled on its own; one jitted program is a single compile
    that the persistent cache can serve on the next run.
    """
    import jax

    return jax.jit(
        lambda k, x: model.init(k, x, train=train, **kwargs)
    )(key, example_input)


def available_models():
    return sorted(_REGISTRY)
