"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464) in chunked form.

The recurrence, a head at a time, state ``S`` (dk x dv), ``S_0 = 0``::

    S_t = a_t S_{t-1} + beta_t k_t (v_t - a_t S_{t-1}^T k_t)^T,   o_t = S_t^T q_t

with ``a_t = exp(g_t)``, ``g_t <= 0``.  Token by token that is T
dependent steps of rank-one work; :func:`gated_delta_rule` takes the
tokens ``chunk`` at a time.  Inside a chunk, with ``gamma`` the running
sum of ``g``, ``D_ij = exp(gamma_i - gamma_j)`` (j <= i) and ``A_ij =
beta_i D_ij (k_i . k_j)`` (j < i), the chunk entered in state ``S``::

    U  = (I + A)^-1 diag(beta) (V - diag(e^gamma) K S)
    O  = diag(e^gamma) Q S + (tril(Q K^T) * D) U
    S' = e^{gamma_C} S + (diag(e^{gamma_C - gamma}) K)^T U

(``u_i = beta_i (v_i - a_i S_{i-1}^T k_i)`` is what token i writes; every
exponent is <= 0).  What does not depend on ``S`` -- the Gram products,
the decays and the unit-triangular solve, split as ``U = W_v - W_k S`` --
is computed for all chunks at once; a ``lax.scan`` over the chunks then
carries the float32 state through three small products a chunk.

Matrix products take their operands in the inputs' dtype (bfloat16 on
the serving path) and accumulate in float32; the decays, the solve and
the state are float32.  Plain ``jnp`` / ``lax``: differentiable as it
stands.  :func:`recurrent_gated_delta_rule` is the token-by-token form,
the parity reference of the tests.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _masked(g, beta, lengths):
    """Past a row's length nothing decays and nothing is written: the
    state a row leaves is the state after its last true token."""
    if lengths is None:
        return g, beta
    live = (jnp.arange(g.shape[1])[None, :] < lengths[:, None])[..., None]
    return jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)


def recurrent_gated_delta_rule(q, k, v, g, beta,
                               lengths: Optional[jax.Array] = None):
    """The recurrence as written, one token a ``lax.scan`` step, float32.
    Shapes as :func:`gated_delta_rule`."""
    b, _, h, dk = q.shape
    g, beta = _masked(g.astype(_F32), beta.astype(_F32), lengths)
    hi = jax.lax.Precision.HIGHEST

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, s, precision=hi))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s, precision=hi)

    xs = tuple(jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), _F32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def gated_delta_rule(q, k, v, g, beta, lengths: Optional[jax.Array] = None,
                     chunk: int = 64):
    """``q``, ``k`` (B, T, H, dk) with ``q`` already scaled, ``v`` (B, T, H,
    dv), ``g`` (log decay, <= 0) and ``beta`` (B, T, H); ``lengths`` (B,)
    the true tokens of right-padded rows (None: every row is whole).
    Returns ``o`` (B, T, H, dv) in ``v``'s dtype.  Causal: a token's output
    depends on no later token, so padding at the end changes no true
    token's output; ``lengths`` only stops the padded tokens writing."""
    b, t, h, dk = q.shape
    dv, cd = v.shape[-1], v.dtype
    g, beta = _masked(g.astype(_F32), beta.astype(_F32), lengths)
    c = int(chunk)
    n = -(-t // c)
    if n * c != t:  # padded tokens: no decay (g 0), nothing written (beta 0)
        pad = lambda a: jnp.pad(a, ((0, 0), (0, n * c - t)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (pad(a) for a in (q, k, v, g, beta))
    # (B, T, H, d) -> (N, B, H, C, d): the scan runs over the leading axis
    chunks = lambda a: jnp.moveaxis(a.reshape(b, n, c, h, *a.shape[3:]), (1, 3), (0, 2))
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc, bc = chunks(g), chunks(beta)  # (N, B, H, C)
    mm = lambda eq, x, y: jnp.einsum(eq, x.astype(cd), y.astype(cd),
                                     preferred_element_type=_F32)

    gamma = jnp.cumsum(gc, axis=-1)
    idx = jnp.arange(c)
    lower = idx[:, None] >= idx[None, :]
    # the mask before the exponent: above the diagonal the difference is > 0
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    a_mat = bc[..., :, None] * decay * mm("...id,...jd->...ij", kc, kc)
    a_mat = jnp.where(idx[:, None] > idx[None, :], a_mat, 0.0)
    m_mat = decay * mm("...id,...jd->...ij", qc, kc)
    e_gamma = jnp.exp(gamma)
    rhs = jnp.concatenate([bc[..., None] * vc.astype(_F32),
                           (bc * e_gamma)[..., None] * kc.astype(_F32)], axis=-1)
    w = jax.scipy.linalg.solve_triangular(
        a_mat + jnp.eye(c, dtype=_F32), rhs, lower=True, unit_diagonal=True)
    w_v, w_k = w[..., :dv], w[..., dv:]
    q_in = qc.astype(_F32) * e_gamma[..., None]
    g_last = gamma[..., -1]
    k_out = kc.astype(_F32) * jnp.exp(g_last[..., None] - gamma)[..., None]

    def step(s, x):
        w_v, w_k, m_mat, q_in, k_out, g_last = x
        u = w_v - mm("...ck,...kv->...cv", w_k, s)
        o = mm("...ck,...kv->...cv", q_in, s) + mm("...ij,...jv->...iv", m_mat, u)
        s = jnp.exp(g_last)[..., None, None] * s + mm("...ck,...cv->...kv", k_out, u)
        return s, o.astype(cd)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), _F32),
                        (w_v, w_k, m_mat, q_in, k_out, g_last))
    # (N, B, H, C, dv) -> (B, T, H, dv)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * c, h, dv)
    return o[:, :t]
