"""Causal depthwise short convolution: the K-tap filter a linear-attention
layer runs over each channel of q, k and v before the recurrence.

``y_t = sum_j taps[j] * x_{t - (K-1-j)}`` with zeros before the first
token (the last tap is on the current token), no bias.  K shifted
multiply-adds on the vector unit: at K = 4 a convolution op would buy
nothing.  A token sees no later token, so right padding changes no true
token's output.
"""

from __future__ import annotations

import jax.numpy as jnp


def causal_short_conv(x, taps):
    """``x`` (B, T, ...), ``taps`` (K, ...) matching ``x``'s trailing axes;
    computed in float32, returned in ``x``'s dtype."""
    k, t = taps.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (k - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    taps = taps.astype(jnp.float32)
    y = taps[0] * xp[:, 0:t]
    for j in range(1, k):
        y = y + taps[j] * xp[:, j:j + t]
    return y.astype(x.dtype)
