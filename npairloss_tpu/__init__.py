"""npairloss_tpu — TPU-native multi-class N-pair metric-learning framework.

A ground-up JAX/XLA/Pallas/pjit re-design of the capabilities of the
reference Caffe CUDA+MPI layer ``NPairMultiClassLossLayer`` (quziyan/NPairLoss)
and its implied host framework.  This top-level module exports the compute
core: the mined N-pair loss with cross-chip global negative pooling,
in-training retrieval metrics, and L2 normalization.  Subpackages:
``parallel`` (device-mesh plumbing + ring negative pooling), ``config``
(prototxt front-end), ``data`` (identity-balanced pipeline with the
native C++ runtime), ``models`` (embedding zoo), ``train`` (solver
loop), ``utils`` (profiling + numeric debug guards), ``analysis``
(the jax-free staticcheck invariant linter).

The compute-core exports are LAZY (PEP 562): importing the package must
not import jax, so the jax-free entry points — ``python -m
npairloss_tpu staticcheck``, ``watch``, ``chip_smoke.py``'s parent, the
bench_check gates — run in a venv with no accelerator stack installed
at all (docs/STATICCHECK.md).  ``from npairloss_tpu import npair_loss``
works exactly as before; it just pays the jax import at first use
instead of at package import.
"""

import logging as _logging

# Library-logging etiquette: a package must never force output (or emit
# "No handlers could be found" warnings) in an embedding application
# that configured logging its own way.  The CLI adds a real handler only
# when the embedder has not (cli.cmd_train).
_logging.getLogger("npairloss_tpu").addHandler(_logging.NullHandler())

__version__ = "0.1.0"

# Export name -> defining submodule, resolved on first attribute access.
_LAZY_EXPORTS = {
    "REFERENCE_CONFIG": "npairloss_tpu.ops.npair_loss",
    "MiningMethod": "npairloss_tpu.ops.npair_loss",
    "MiningRegion": "npairloss_tpu.ops.npair_loss",
    "NPairLossConfig": "npairloss_tpu.ops.npair_loss",
    "npair_loss": "npairloss_tpu.ops.npair_loss",
    "npair_loss_with_aux": "npairloss_tpu.ops.npair_loss",
    "evaluate_embeddings": "npairloss_tpu.ops.eval_retrieval",
    "gallery_recall_at_k": "npairloss_tpu.ops.eval_retrieval",
    "retrieval_metrics": "npairloss_tpu.ops.metrics",
    "l2_normalize": "npairloss_tpu.ops.normalize",
    "blockwise_npair_loss": "npairloss_tpu.ops.pallas_npair",
    "blockwise_npair_loss_with_aux": "npairloss_tpu.ops.pallas_npair",
    "blockwise_retrieval_metrics": "npairloss_tpu.ops.pallas_npair",
}

__all__ = [
    "REFERENCE_CONFIG",
    "MiningMethod",
    "MiningRegion",
    "NPairLossConfig",
    "npair_loss",
    "npair_loss_with_aux",
    "blockwise_npair_loss",
    "blockwise_npair_loss_with_aux",
    "blockwise_retrieval_metrics",
    "retrieval_metrics",
    "gallery_recall_at_k",
    "evaluate_embeddings",
    "l2_normalize",
    "__version__",
]


def __getattr__(name):
    mod_name = _LAZY_EXPORTS.get(name)
    if mod_name is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(mod_name), name)
    globals()[name] = value  # cache: the next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY_EXPORTS))
