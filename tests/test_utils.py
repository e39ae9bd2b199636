"""utils: profiling annotations/timer and numeric debug guards."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_identity_batch
from npairloss_tpu.ops.npair_loss import NPairLossConfig, npair_loss_with_aux
from npairloss_tpu.utils import (
    StepTimer,
    annotate,
    assert_all_finite,
    checked,
    debug_checks_enabled,
    enable_debug_checks,
    trace,
)


def test_named_scopes_reach_hlo(rng):
    """The stage annotations must survive into the lowered module so
    XProf timelines show the pipeline stages (plain ``as_text()``
    strips locations; ``debug_info=True`` keeps them)."""
    (f,), (l,) = make_identity_batch(rng, 4, 2, 8)
    text = jax.jit(
        lambda x: npair_loss_with_aux(x, jnp.asarray(l), NPairLossConfig())[0]
    ).lower(jnp.asarray(f)).as_text(debug_info=True)
    for scope in ("npair/sim", "npair/mine", "npair/select", "npair/loss"):
        assert scope in text, scope


def test_annotate_composes_under_jit():
    @jax.jit
    def f(x):
        with annotate("stage/a"):
            y = x * 2
        with annotate("stage/b"):
            return y + 1

    assert float(f(jnp.float32(3))) == 7.0


def test_step_timer():
    t = StepTimer(window=4)
    assert t.tick(10)["steps_per_sec"] == 0.0  # first tick only arms
    for _ in range(5):
        t.tick(10)
    s = t.stats()
    assert s["steps_per_sec"] > 0 and s["items_per_sec"] > 0
    assert len(t._durations) == 4  # window bounded
    t.reset()
    assert t.stats()["steps_per_sec"] == 0.0


@pytest.mark.slow  # ~46s (XProf profiler session); tier-1 budget, run with -m slow
def test_trace_writes_profile(tmp_path):
    with trace(str(tmp_path)):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    found = []
    for root, _, files in os.walk(tmp_path):
        found.extend(files)
    assert found, "no profile artifacts written"


def test_assert_all_finite():
    assert_all_finite({"a": jnp.ones(3), "b": 2.0}, "ok")
    with pytest.raises(FloatingPointError, match="bad"):
        assert_all_finite({"x": jnp.array([1.0, np.nan])}, "bad")
    # integer leaves are skipped
    assert_all_finite({"i": jnp.arange(3)})


def test_checked_catches_nan_under_jit():
    from jax.experimental import checkify

    f = checked(lambda x: jnp.log(x))  # jits internally
    assert np.isclose(float(f(jnp.float32(1.0))), 0.0)
    with pytest.raises(checkify.JaxRuntimeError):
        f(jnp.float32(-1.0))  # log of negative -> NaN


def test_checked_npair_loss_is_clean(rng):
    """The production loss must pass checkify's NaN/div tracking: the
    div/log guards (cu:162-169 semantics) hold under instrumentation."""
    (f,), (l,) = make_identity_batch(rng, 4, 2, 8)
    fn = checked(
        lambda x: npair_loss_with_aux(x, jnp.asarray(l), NPairLossConfig())[0]
    )
    assert np.isfinite(float(fn(jnp.asarray(f))))
    # including the degenerate all-unique-labels batch (zero-count guard)
    lu = jnp.arange(f.shape[0], dtype=jnp.int32)
    fn_u = checked(
        lambda x: npair_loss_with_aux(x, lu, NPairLossConfig())[0]
    )
    assert float(fn_u(jnp.asarray(f))) == 0.0


def test_solver_debug_checks_flag(rng):
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    solver = Solver(
        get_model("mlp", hidden=(16,), embedding_dim=8),
        NPairLossConfig(),
        SolverConfig(base_lr=0.1, lr_policy="fixed", display=0, snapshot=0),
        input_shape=(8,),
    )
    (f,), (l,) = make_identity_batch(rng, 4, 2, 8)
    enable_debug_checks(True)
    try:
        assert debug_checks_enabled()
        m = solver.step(f, l)  # finite case passes
        assert np.isfinite(float(m["loss"]))
        # poison the params -> next step must raise with the metric name
        solver.state["params"] = jax.tree_util.tree_map(
            lambda p: jnp.full_like(p, jnp.nan), solver.state["params"]
        )
        with pytest.raises(FloatingPointError):
            solver.step(f, l)
    finally:
        enable_debug_checks(False)


def test_time_scan_measures():
    """time_scan returns a sane ms/iter for every timed window."""
    import jax.numpy as jnp

    from npairloss_tpu.utils import profiling

    def body(acc, s):
        return acc + jnp.sin(s)

    windows = []
    ms = profiling.time_scan(body, jnp.float32(0.0), steps=3, repeats=3,
                             windows_out=windows)
    assert len(windows) == 3 and ms == min(windows) > 0
    with pytest.raises(ValueError):
        profiling.time_scan(body, jnp.float32(0.0), steps=0)
