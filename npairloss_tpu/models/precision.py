"""Declarative mixed-precision policy for the model zoo.

One :class:`PrecisionPolicy` object answers, for every module in a
trunk, the three questions the MXU cares about: what dtype are the
parameters stored in, what dtype does the module compute in, and which
MXU precision mode do its gemms/convs run at.  Modules resolve their
answer by regex-matching their own flax module path against the
policy's ``rules`` — the same first-match-wins pattern partition-rule
systems use for sharding (SNIPPETS.md [3] ``match_partition_rules``) —
falling back to the policy-wide defaults.  This replaces the ad-hoc
``dtype=`` constructor split (``googlenet`` vs ``googlenet_mxu`` vs
``--bf16``) with one named, inspectable object threaded through
``models.get_model``, the trunk modules, and ``train.Solver``.

Shipped policies (``get_policy`` / ``available_policies``):

* ``"mxu"`` — THE FLAGSHIP DEFAULT.  bf16 compute over fp32 master
  params, explicit single-pass bf16 MXU precision on every conv/dense,
  and the loss engines' gemms in the same single-pass mode
  (``loss_matmul_precision="default"``; against the HIGHEST mode on
  the current chip: not measured).  Normalization
  arithmetic (LRN / LayerNorm / BatchNorm statistics, L2 normalize)
  stays fp32 — that is a property of the module implementations, which
  compute their statistics in fp32 regardless of the activation dtype.
  The policy/fp32 loss delta is bounded by test
  (tests/test_precision_policy.py).
* ``"bf16"`` — the pre-policy headline: bf16 compute, fp32 params,
  backend-default conv precision, oracle-parity (HIGHEST) loss gemms.
  Byte-compatible with the old ``dtype=jnp.bfloat16`` constructors.
* ``"fp32_parity"`` — the prototxt-parity fallback: fp32 everything,
  oracle-parity loss gemms.  HLO-identical to the pre-policy fp32
  trunk; this is the reference point every loss-delta bound in the
  test suite compares against.

Rules example (how a policy would pin one module family)::

    PrecisionPolicy(
        name="mxu_fp32stem",
        rules=(
            # conv1 keeps fp32 compute; everything else inherits the
            # policy-wide bf16 defaults.
            (r"(^|/)conv1(/|$)", {"compute_dtype": jnp.float32}),
        ),
    )

This module deliberately imports no sibling model code (the trunks
import *it*), and resolving a policy never touches jax state — it is a
pure description consumed at trace time.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

# The overridable per-module fields a rule may set.
_RULE_FIELDS = ("param_dtype", "compute_dtype", "matmul_precision")

# matmul_precision vocabulary: None = leave unset (the backend default),
# "default" = single-pass bf16-multiply/fp32-accumulate MXU mode,
# "highest" = full-fp32 multi-pass decomposition (oracle parity).  Same
# vocabulary as ops.npair_loss.resolve_matmul_precision, with None
# meaning "don't pass a precision at all" here (flax modules treat an
# explicit None the same way, so the distinction is only documentary).
_PRECISIONS = {
    None: None,
    "default": jax.lax.Precision.DEFAULT,
    "highest": jax.lax.Precision.HIGHEST,
}


@dataclasses.dataclass(frozen=True)
class ModulePrecision:
    """The resolved answer for ONE module: what ``nn.Conv``/``nn.Dense``
    should be constructed with."""

    param_dtype: Any
    compute_dtype: Any
    matmul_precision: Optional[str]

    @property
    def precision(self) -> Optional[jax.lax.Precision]:
        """The ``precision=`` argument for flax/lax ops (None = unset)."""
        return _PRECISIONS[self.matmul_precision]


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Declarative mixed-precision recipe for a whole trunk.

    ``rules`` is an ordered tuple of ``(regex, overrides)`` pairs
    matched (``re.search``) against the "/"-joined flax module path;
    the FIRST match wins and its overrides replace the policy-wide
    defaults for that module.  ``loss_matmul_precision`` is what the
    Solver hands the loss engines when the caller does not set
    ``matmul_precision`` explicitly (None = HIGHEST there — see
    ops.npair_loss.resolve_matmul_precision).
    """

    name: str
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    output_dtype: Any = jnp.float32
    matmul_precision: Optional[str] = None
    loss_matmul_precision: Optional[str] = None
    rules: Tuple[Tuple[str, Mapping[str, Any]], ...] = ()

    def __post_init__(self):
        for field, prec in (
            ("matmul_precision", self.matmul_precision),
            ("loss_matmul_precision", self.loss_matmul_precision),
        ):
            if prec not in _PRECISIONS:
                raise ValueError(
                    f"{field} must be one of "
                    f"{sorted(k for k in _PRECISIONS if k)} or None, "
                    f"got {prec!r}")
        for pat, over in self.rules:
            re.compile(pat)  # surface a bad regex at construction
            unknown = set(over) - set(_RULE_FIELDS)
            if unknown:
                raise ValueError(
                    f"rule {pat!r} sets unknown field(s) "
                    f"{sorted(unknown)}; allowed: {_RULE_FIELDS}")
            if "matmul_precision" in over and \
                    over["matmul_precision"] not in _PRECISIONS:
                raise ValueError(
                    f"rule {pat!r}: matmul_precision "
                    f"{over['matmul_precision']!r} not in "
                    f"{sorted(k for k in _PRECISIONS if k)}")

    def resolve(self, path: Union[str, Sequence[str], None]
                ) -> ModulePrecision:
        """Per-module precision for the module at ``path`` (a flax
        ``Module.path`` tuple or an already-joined string); first
        matching rule wins, else the policy-wide defaults."""
        name = path if isinstance(path, str) else "/".join(path or ())
        base = {
            "param_dtype": self.param_dtype,
            "compute_dtype": self.compute_dtype,
            "matmul_precision": self.matmul_precision,
        }
        for pat, over in self.rules:
            if re.search(pat, name) is not None:
                base.update(over)
                break
        return ModulePrecision(**base)

    def describe(self) -> Dict[str, Any]:
        """JSON-able summary (run manifests)."""
        return {
            "name": self.name,
            "param_dtype": jnp.dtype(self.param_dtype).name,
            "compute_dtype": jnp.dtype(self.compute_dtype).name,
            "output_dtype": jnp.dtype(self.output_dtype).name,
            "matmul_precision": self.matmul_precision,
            "loss_matmul_precision": self.loss_matmul_precision,
            "rules": [[pat, dict(over)] for pat, over in self.rules],
        }


# -- registry ----------------------------------------------------------------

_POLICIES: Dict[str, PrecisionPolicy] = {
    # The flagship default: wide single-pass bf16 gemms everywhere the
    # MXU runs, fp32 master params/updates, fp32 normalization (module-
    # internal).  The TPU-v4 paper (PAPERS.md) is explicit that this is
    # what the MXU rewards; the flagship's step on the current chip is
    # the benchmark's cell 1 (PERF_LEDGER.jsonl).
    "mxu": PrecisionPolicy(
        name="mxu",
        param_dtype=jnp.float32,
        compute_dtype=jnp.bfloat16,
        output_dtype=jnp.float32,
        matmul_precision="default",
        loss_matmul_precision="default",
    ),
    # The pre-policy bf16 headline, as a named object: bf16 compute,
    # backend-default conv precision, oracle-parity loss gemms.
    "bf16": PrecisionPolicy(
        name="bf16",
        param_dtype=jnp.float32,
        compute_dtype=jnp.bfloat16,
        output_dtype=jnp.float32,
        matmul_precision=None,
        loss_matmul_precision=None,
    ),
    # Prototxt-parity fallback: what every oracle/golden test compares
    # against.  HLO-identical to the pre-policy fp32 trunk.
    "fp32_parity": PrecisionPolicy(
        name="fp32_parity",
        param_dtype=jnp.float32,
        compute_dtype=jnp.float32,
        output_dtype=jnp.float32,
        matmul_precision=None,
        loss_matmul_precision=None,
    ),
}

# The policy the flagship workload (benchmark cell 1, CLI default when
# --precision is not given but a policy-aware entry point wants one)
# runs under.
DEFAULT_POLICY = "mxu"


def get_policy(name: Union[str, PrecisionPolicy]) -> PrecisionPolicy:
    """Resolve a policy name (or pass a policy through).  Unknown names
    raise with the known vocabulary — the CLI argparse choices build
    on this being loud."""
    if isinstance(name, PrecisionPolicy):
        return name
    key = str(name).lower()
    if key not in _POLICIES:
        raise KeyError(
            f"unknown precision policy {name!r}; have "
            f"{sorted(_POLICIES)}")
    return _POLICIES[key]


def available_policies() -> Sequence[str]:
    return sorted(_POLICIES)


def module_precision(policy: Optional[PrecisionPolicy],
                     path: Union[str, Sequence[str], None],
                     fallback_dtype: Any) -> ModulePrecision:
    """The one resolution helper modules call: with no policy attached,
    reproduce the pre-policy behavior exactly (``fallback_dtype``
    compute over fp32 params, no explicit precision) so a policy-less
    build stays HLO-identical to the old constructors."""
    if policy is None:
        return ModulePrecision(
            param_dtype=jnp.float32,
            compute_dtype=fallback_dtype,
            matmul_precision=None,
        )
    return policy.resolve(path)
