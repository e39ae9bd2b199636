"""The chunked gated delta rule (``ops/gated_delta.py``) against the
recurrence taken token by token, and the 4-tap causal filter beside it.

Tolerances, float32 on the CPU: the two forms do the same sums in another
order (a chunk's unit-triangular solve against T rank-one updates), so
outputs of size ~1-3 agree to a few float32 roundings of the largest
term: 2e-5 absolute.  bfloat16 operands round q, k, v, the state and the
solved U to 8 bits of mantissa on their way into every product: 3e-2 on
outputs of size ~1.3 (measured 0.9e-2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from npairloss_tpu.ops import gated_delta
from npairloss_tpu.ops.short_conv import causal_short_conv

F32_ATOL = 2e-5
# jitted: op by op on the CPU every small primitive compiles by itself
gated_delta_rule = jax.jit(gated_delta.gated_delta_rule, static_argnames=("chunk",))
recurrent_gated_delta_rule = jax.jit(gated_delta.recurrent_gated_delta_rule)


def _inputs(seed, b, t, h, dk, dv, decay=1.0, beta_gain=2.0):
    """q scaled and k unit-norm per head, as the layer hands them; ``g``
    in (-decay, 0]; ``beta`` in (0, 2) (``linear_allow_neg_eigval``),
    pushed towards both ends by ``beta_gain``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)) + 0.7)  # correlated keys
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, t, h))
    beta = 2.0 * jax.nn.sigmoid(beta_gain * jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("t,chunk,decay", [
    (100, 16, 1.0),    # T not a multiple of C
    (100, 64, 0.01),   # weak decay: the state keeps everything
    (40, 64, 5.0),     # T < C, strong decay
    (128, 64, 1.0),    # whole chunks
    (200, 64, 0.1),
    (33, 16, 30.0),    # decay so strong that exp(gamma) underflows inside a chunk
])
def test_chunked_matches_the_recurrence(t, chunk, decay):
    args = _inputs(0, 2, t, 3, 8, 16, decay)
    want = recurrent_gated_delta_rule(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (2, t, 3, 16)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=F32_ATOL, rtol=0)


def test_beta_up_to_two_flips_a_stored_value():
    """beta = 2 writes v - 2 (S^T k): reading the same key back gives the
    NEGATIVE of what was there plus twice the new value; the chunked form
    holds that (negative eigenvalues of I - beta k k^T)."""
    q, k, v, g, beta = _inputs(3, 1, 70, 2, 8, 16, decay=0.05)
    beta = jnp.full_like(beta, 2.0)
    want = recurrent_gated_delta_rule(q, k, v, g, beta)
    got = gated_delta_rule(q, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5 * F32_ATOL, rtol=0)


def test_rows_of_differing_length():
    """A short row beside a long one reads as it does alone at its own
    length, to the bit: causal, and past ``lengths`` nothing is written."""
    args = _inputs(1, 2, 100, 3, 8, 16)
    lengths = jnp.array([100, 37])
    both = gated_delta_rule(*args, lengths=lengths)
    alone = gated_delta_rule(*(a[1:2, :37] for a in args))
    assert bool(jnp.all(both[1, :37] == alone[0]))
    whole = gated_delta_rule(*(a[0:1] for a in args))
    assert bool(jnp.all(both[0] == whole[0]))
    rec = recurrent_gated_delta_rule(*args, lengths=lengths)
    np.testing.assert_allclose(np.asarray(both[1, :37]), np.asarray(rec[1, :37]),
                               atol=F32_ATOL, rtol=0)


def test_gradient_is_finite_and_the_recurrences():
    """Plain autodiff through the chunked form (solve, scan and all)
    against autodiff through the token-by-token scan."""
    args = _inputs(1, 2, 100, 3, 8, 16)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    want = jax.grad(loss(recurrent_gated_delta_rule), argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(loss(gated_delta_rule), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # gradients of size ~1-10 through ~100 dependent steps
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                                   err_msg=name)


def test_bfloat16_operands_float32_state():
    q, k, v, g, beta = _inputs(1, 2, 100, 3, 8, 16)
    want = recurrent_gated_delta_rule(q, k, v, g, beta)
    got = gated_delta_rule(*(a.astype(jnp.bfloat16) for a in (q, k, v)), g, beta)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=3e-2, rtol=0)


def test_short_conv_is_causal_depthwise_with_zero_history():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 9, 3, 5)).astype(np.float32)
    taps = rng.standard_normal((4, 3, 5)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            src = t - (3 - j)  # the last tap is on the current token
            if src >= 0:
                want[:, t] += taps[j] * x[:, src]
    got = causal_short_conv(jnp.asarray(x), jnp.asarray(taps))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6, rtol=0)
    # a later token changes no earlier output
    x2 = x.copy()
    x2[:, 6:] += 1.0
    got2 = causal_short_conv(jnp.asarray(x2), jnp.asarray(taps))
    assert np.array_equal(np.asarray(got2)[:, :6], np.asarray(got)[:, :6])
