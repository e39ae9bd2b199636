"""Mixed-precision policy tests (models.precision, ISSUE 7).

Covers: registry + regex-rule resolution semantics, the CLI choice pin,
policy-vs-legacy-constructor bit-identity (fp32_parity / bf16), the
bf16-convolution HLO pin on the default (mxu) policy, the flagship
policy-vs-fp32 loss-delta bound, and the Solver's policy->loss-engine
precision threading.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from npairloss_tpu.models.precision import (
    DEFAULT_POLICY,
    ModulePrecision,
    PrecisionPolicy,
    available_policies,
    get_policy,
    module_precision,
)

# ---------------------------------------------------------------------------
# Registry + resolution (pure, no jit)
# ---------------------------------------------------------------------------


def test_registry_vocabulary():
    assert available_policies() == ["bf16", "fp32_parity", "mxu"]
    assert DEFAULT_POLICY == "mxu"
    with pytest.raises(KeyError, match="unknown precision policy"):
        get_policy("fp16")  # not a thing here; must list the vocabulary
    pol = get_policy("mxu")
    assert get_policy(pol) is pol  # objects pass through


def test_shipped_policy_contents():
    mxu = get_policy("mxu")
    assert mxu.compute_dtype == jnp.bfloat16
    assert mxu.param_dtype == jnp.float32
    assert mxu.output_dtype == jnp.float32
    assert mxu.matmul_precision == "default"
    assert mxu.loss_matmul_precision == "default"
    par = get_policy("fp32_parity")
    assert par.compute_dtype == jnp.float32
    assert par.matmul_precision is None
    assert par.loss_matmul_precision is None
    bf16 = get_policy("bf16")
    assert bf16.compute_dtype == jnp.bfloat16
    assert bf16.loss_matmul_precision is None


def test_rule_resolution_first_match_wins():
    pol = PrecisionPolicy(
        name="t",
        compute_dtype=jnp.bfloat16,
        matmul_precision="default",
        rules=(
            (r"(^|/)conv1(/|$)", {"compute_dtype": jnp.float32,
                                  "matmul_precision": "highest"}),
            (r"conv", {"matmul_precision": None}),
        ),
    )
    # First rule wins for conv1 (both patterns match).
    mp = pol.resolve(("conv1",))
    assert mp.compute_dtype == jnp.float32
    assert mp.matmul_precision == "highest"
    assert mp.precision == jax.lax.Precision.HIGHEST
    # Second rule for other convs; overrides only what it names.
    mp = pol.resolve("inception_3a/b3x3_reduce/conv2")
    assert mp.compute_dtype == jnp.bfloat16
    assert mp.matmul_precision is None and mp.precision is None
    # No rule -> policy-wide defaults.
    mp = pol.resolve(("head",))
    assert mp.matmul_precision == "default"
    assert mp.precision == jax.lax.Precision.DEFAULT
    # Tuple and string paths resolve identically.
    assert pol.resolve(("a", "conv1")) == pol.resolve("a/conv1")


def test_rule_validation_is_loud():
    with pytest.raises(ValueError, match="unknown field"):
        PrecisionPolicy(name="bad", rules=(("x", {"dtype": jnp.float32}),))
    with pytest.raises(ValueError, match="matmul_precision"):
        PrecisionPolicy(name="bad", rules=(("x", {"matmul_precision":
                                                  "fast"}),))
    with pytest.raises(re.error):
        PrecisionPolicy(name="bad", rules=(("(", {}),))
    with pytest.raises(ValueError, match="matmul_precision must be"):
        PrecisionPolicy(name="bad", matmul_precision="fastest")


def test_module_precision_fallback_matches_prepolicy_defaults():
    mp = module_precision(None, ("anything",), jnp.bfloat16)
    assert mp == ModulePrecision(param_dtype=jnp.float32,
                                 compute_dtype=jnp.bfloat16,
                                 matmul_precision=None)
    assert mp.precision is None


def test_describe_is_jsonable():
    import json

    d = get_policy("mxu").describe()
    json.dumps(d)
    assert d["name"] == "mxu" and d["compute_dtype"] == "bfloat16"


def test_cli_choices_pinned_to_registry():
    """cli._PRECISION_CHOICES is hardcoded (argparse must stay jax-free
    for the jax-free entry points); this pin makes drift a failure."""
    from npairloss_tpu.cli import _PRECISION_CHOICES

    assert sorted(_PRECISION_CHOICES) == available_policies()


# ---------------------------------------------------------------------------
# Model threading (small trunks: cheap jits)
# ---------------------------------------------------------------------------


def _tiny_vit(**kw):
    from npairloss_tpu.models import get_model

    return get_model("vit_b16", patch=8, hidden=32, depth=1, num_heads=2,
                     mlp_dim=64, **kw)


def test_policy_equals_legacy_dtype_constructors_tiny():
    """fp32_parity == dtype=fp32 and bf16 == dtype=bf16, bit for bit,
    on the ViT trunk (policy threaded through Dense/attention/patchify)
    and the MLP (compute-dtype-only threading)."""
    from npairloss_tpu.models import get_model, jit_init

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 16, 16, 3)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    for name, kw in (("vit", {}), ("mlp", {})):
        mk = _tiny_vit if name == "vit" else (
            lambda **k: get_model("mlp", hidden=(32,), embedding_dim=16,
                                  **k))
        v = jit_init(mk(dtype=jnp.float32), key, x)
        for policy, dtype in (("fp32_parity", jnp.float32),
                              ("bf16", jnp.bfloat16)):
            legacy = mk(dtype=dtype)
            poliy = mk(policy=policy)
            o1 = jax.jit(
                lambda v_, x_: legacy.apply(v_, x_, train=False))(v, x)
            o2 = jax.jit(
                lambda v_, x_: poliy.apply(v_, x_, train=False))(v, x)
            np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_vit_rules_resolve_at_named_submodule_paths():
    """A rule targeting "patchify" or "attn" must actually match: the
    ViT modules resolve at the NAMED submodule's path, not their own
    (a root-path resolution silently no-ops such rules)."""
    pol = PrecisionPolicy(
        name="pin",
        compute_dtype=jnp.bfloat16,
        rules=(
            (r"(^|/)patchify(/|$)", {"param_dtype": jnp.bfloat16}),
            (r"(^|/)attn(/|$)", {"param_dtype": jnp.float16}),
        ),
    )
    x = jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32)
    v = jax.eval_shape(
        lambda k, xx: _tiny_vit(policy=pol).init(k, xx, train=False),
        jax.random.PRNGKey(0), x)
    params = v["params"]
    assert params["patchify"]["kernel"].dtype == jnp.bfloat16
    assert params["block0"]["attn"]["query"]["kernel"].dtype == jnp.float16
    assert params["block0"]["mlp"]["Dense_0"]["kernel"].dtype == jnp.float32


def test_get_model_policy_sets_compute_dtype_everywhere():
    from npairloss_tpu.models import get_model

    m = get_model("mlp", policy="mxu")
    assert m.dtype == jnp.bfloat16  # compute dtype honored sans threading
    m = _tiny_vit(policy="mxu")
    assert m.policy is not None and m.policy.name == "mxu"


def test_default_policy_hlo_contains_bf16_convolutions():
    """THE pin of the tentpole's point: the flagship trunk under the
    default (mxu) policy lowers to bf16 convolutions (bf16 operands
    feeding conv ops), while fp32_parity lowers none.  Lowering only —
    no XLA compile — so this stays cheap."""
    from npairloss_tpu.models import FLAGSHIP_POLICY, flagship_model

    assert FLAGSHIP_POLICY == DEFAULT_POLICY
    x_sds = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    key = jax.random.PRNGKey(0)

    def conv_lines(model):
        vars_sds = jax.eval_shape(
            lambda k, xx: model.init(k, xx, train=False), key, x_sds)
        low = jax.jit(
            lambda v_, x_: model.apply(v_, x_, train=False)
        ).lower(vars_sds, x_sds)
        # Op lines only ("stablehlo.convolution"/HLO "convolution(") —
        # NOT MLIR #loc debug lines, which quote Python names (this
        # test's own name contains both "convolution" and "bf16"...).
        lines = [ln for ln in low.as_text(debug_info=True).splitlines()
                 if re.search(r"\bconvolution\b\s*\(|stablehlo\."
                              r"convolution", ln)]
        assert lines, "no convolutions in the lowered trunk?"
        return lines

    bf16_re = re.compile(r"\bbf16\b|xbf16>")
    bf16_lines = [ln for ln in conv_lines(flagship_model())
                  if bf16_re.search(ln)]
    assert bf16_lines, "default policy lowered no bf16 convolutions"
    fp32_lines = [ln for ln in
                  conv_lines(flagship_model(policy="fp32_parity"))
                  if bf16_re.search(ln)]
    assert not fp32_lines, "fp32_parity policy lowered bf16 convolutions"


@pytest.mark.slow
def test_flagship_policy_loss_delta_bounded():
    """Same flagship trunk, same params, same batch: |loss(mxu) -
    loss(fp32_parity)| stays small.  Slow-marked: two GoogLeNet jits
    (~12s); the tier-1 HLO pin covers the policy threading itself."""
    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.models import flagship_model, jit_init
    from npairloss_tpu.ops.npair_loss import npair_loss

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 32, 32, 3)).astype(np.float32))
    lab = jnp.asarray(np.repeat(np.arange(4), 2).astype(np.int32))
    key = jax.random.PRNGKey(0)
    m_pol = flagship_model()
    m_32 = flagship_model(policy="fp32_parity")
    v = jit_init(m_pol, key, x)  # fp32 master params: shared verbatim

    def loss_of(model, precision):
        def f(v_, x_, l_):
            emb = model.apply(v_, x_, train=False)
            return npair_loss(emb, l_, REFERENCE_CONFIG,
                              matmul_precision=precision)

        return float(jax.jit(f)(v, x, lab))

    l_pol = loss_of(m_pol, get_policy("mxu").loss_matmul_precision)
    l_32 = loss_of(m_32, None)
    assert np.isfinite(l_pol) and np.isfinite(l_32)
    # bf16 trunk rounding at 1024-d embeddings: the observed delta is
    # ~1e-3-level; 5e-2 is the "policies agree on the objective" bound,
    # far below any mining-decision flip at flagship margins.
    assert abs(l_pol - l_32) < 5e-2, (l_pol, l_32)


# ---------------------------------------------------------------------------
# Solver threading
# ---------------------------------------------------------------------------


def test_solver_precision_supplies_loss_matmul_precision():
    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver

    mk = lambda: get_model("mlp", hidden=(32,), embedding_dim=16,
                           policy="mxu")
    s = Solver(mk(), REFERENCE_CONFIG, precision="mxu",
               input_shape=(16, 16, 3))
    assert s.matmul_precision == "default"
    assert s.precision_policy.name == "mxu"
    # An explicit matmul_precision outranks the policy's default.
    s = Solver(mk(), REFERENCE_CONFIG, precision="mxu",
               matmul_precision="highest", input_shape=(16, 16, 3))
    assert s.matmul_precision == "highest"
    # No policy: everything stays None (oracle-parity engines).
    s = Solver(mk(), REFERENCE_CONFIG, input_shape=(16, 16, 3))
    assert s.precision_policy is None and s.matmul_precision is None


def test_solver_precision_trains_a_step():
    """End-to-end: a policy-built MLP + precision="mxu" Solver takes a
    finite step (the loss engines trace under the policy's single-pass
    precision)."""
    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 8, 8, 3)).astype(np.float32)
    lab = np.repeat(np.arange(4), 2).astype(np.int32)
    s = Solver(
        get_model("mlp", hidden=(16,), embedding_dim=8, policy="mxu"),
        REFERENCE_CONFIG,
        SolverConfig(display=0, snapshot=0),
        input_shape=(8, 8, 3),
        precision="mxu",
    )
    m = s.step(x, lab)
    assert np.isfinite(float(m["loss"]))
