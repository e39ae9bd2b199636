"""Plain exact retrieval: float32 dot products at HIGHEST precision,
the gallery streamed in row blocks so that a block is all the device
holds.  Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def exact_topk(queries, gallery, k, block=131072):
    """(scores, rows), each (Q, k): the k best gallery rows of every
    query by dot product, best first, ties to the lower row."""
    q = jnp.asarray(queries, jnp.float32)

    @jax.jit
    def one(qq, g):
        s = jnp.matmul(qq, g.T, precision=_HI)
        return jax.lax.top_k(s, min(k, g.shape[0]))

    best_s = np.full((q.shape[0], 0), 0.0, np.float32)
    best_r = np.zeros((q.shape[0], 0), np.int64)
    for lo in range(0, gallery.shape[0], block):
        s, r = one(q, jnp.asarray(gallery[lo:lo + block]))
        best_s = np.concatenate([best_s, np.asarray(s)], axis=1)
        best_r = np.concatenate([best_r, np.asarray(r, np.int64) + lo], axis=1)
        order = np.lexsort((best_r, -best_s), axis=1)[:, :k]
        best_s = np.take_along_axis(best_s, order, 1)
        best_r = np.take_along_axis(best_r, order, 1)
    return best_s, best_r


def dots(queries, gallery, rows):
    """Dot products of query i with gallery rows ``rows[i]`` (float64 on
    the host: a few thousand short products)."""
    g = np.asarray(gallery[np.asarray(rows)], np.float64)
    return np.einsum("qd,qkd->qk", np.asarray(queries, np.float64), g)
