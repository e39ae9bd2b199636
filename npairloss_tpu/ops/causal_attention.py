"""Causal softmax attention over right-padded rows, a block of queries
against a block of keys at a time.

A document of 8,192 tokens has 30 x 8,192^2 scores a layer: 8 GB in
float32.  :func:`causal_attention` walks the queries in blocks and, for
each, the key blocks at or before it with a running maximum and sum (the
online softmax), so that one block's scores are all that lives at once and
the key blocks wholly after a query block are not computed.  The softmax
is float32; the two products take their operands in the inputs' dtype and
accumulate in float32.  Padded rows come last and the mask is causal, so
no true token sees a padded one and no length is needed here.

Both loops are ``lax.scan`` (``lax.cond`` skips a masked block), so the
function differentiates by plain autodiff.  The maximum a row subtracts
is ``maximum(carried, this block's)``: a plain ``max`` over a whole row
of keys, subtracted from it, is what XLA's TPU pipeline rewrites into a
``reduce-window`` as wide as the row (measured: 117 ms a layer a
document where the two products take 4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def causal_attention(q, k, v, block: int = 512):
    """``q``, ``k``, ``v`` (B, T, H, d) -> (B, T, H, d) in ``v``'s dtype:
    ``softmax(q k^T / sqrt(d))`` over the keys at or before each query."""
    b, t, h, d = q.shape
    cd = v.dtype
    c = min(int(block), t)
    n = -(-t // c)
    if n * c != t:  # padded keys lie after every true query
        q, k, v = (jnp.pad(a, ((0, 0), (0, n * c - t), (0, 0), (0, 0)))
                   for a in (q, k, v))
    # (B, T, H, d) -> (N, B, c, H, d): the loops run over the leading axis
    blocks = lambda a: jnp.moveaxis(a.reshape(b, n, c, h, d), 1, 0)
    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    scale = 1.0 / float(d) ** 0.5
    pos = jnp.arange(c)
    diagonal = pos[:, None] >= pos[None, :]  # inside the block on the diagonal

    def one(args):
        i, qq = args

        def visit(carry, j, kk, vv):
            m, l, acc = carry
            s = jnp.einsum("bqhd,bkhd->bhqk", qq, kk,
                           preferred_element_type=_F32) * scale
            s = jnp.where((j < i) | diagonal, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            old = jnp.exp(m - m_new)
            acc = old[..., None] * acc + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(cd), vv, preferred_element_type=_F32)
            return m_new, old * l + jnp.sum(p, axis=-1), acc

        def step(carry, x):
            j, kk, vv = x
            return jax.lax.cond(j <= i, lambda: visit(carry, j, kk, vv),
                                lambda: carry), None

        # key block 0 holds key 0, which every query may see: the running
        # maximum is finite from the first visit on
        init = (jnp.full((b, h, c), -jnp.inf, _F32), jnp.zeros((b, h, c), _F32),
                jnp.zeros((b, h, c, d), _F32))
        (_, l, acc), _ = jax.lax.scan(step, init, (jnp.arange(n), kb, vb))
        return (acc / l[..., None]).astype(cd)  # (B, H, c, d)

    o = jax.lax.map(one, (jnp.arange(n), qb))  # (N, B, H, c, d)
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * c, h, d)[:, :t]
