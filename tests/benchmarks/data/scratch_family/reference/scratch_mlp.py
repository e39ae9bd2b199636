"""Plain MLP embedding: dense + ReLU layers, a dense head, L2-normalized;
float32 ``jax.numpy``, imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def param_shapes(input_dim, hidden, embedding_dim):
    widths = [input_dim, *hidden, embedding_dim]
    names = [f"dense{i}" for i in range(len(hidden))] + ["head"]
    return {name: {"kernel": (a, b), "bias": (b,)}
            for name, a, b in zip(names, widths, widths[1:])}


def _rounder(quant):
    """``quant`` rounds every product's operands to a narrower type."""
    return (lambda a: a.astype(quant).astype(jnp.float32)) if quant else (lambda a: a)


def embed(params, x, quant=None):
    q = _rounder(quant)
    x = x.reshape(x.shape[0], -1).astype(jnp.float32)
    for name in sorted(n for n in params if n != "head"):
        x = jax.nn.relu(jnp.matmul(q(x), q(params[name]["kernel"]), precision=_HI)
                        + params[name]["bias"])
    x = jnp.matmul(q(x), q(params["head"]["kernel"]), precision=_HI) + params["head"]["bias"]
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)


def dense(p, x, quant=None):
    """One hidden layer as a stage of ``stages``."""
    q = _rounder(quant)
    x = x.reshape(x.shape[0], -1).astype(jnp.float32)
    return jax.nn.relu(jnp.matmul(q(x), q(p["kernel"]), precision=_HI) + p["bias"])


def head(p, x, quant=None):
    q = _rounder(quant)
    x = jnp.matmul(q(x), q(p["kernel"]), precision=_HI) + p["bias"]
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)


def stages(params):
    """``embed`` a layer at a time: (sub-tree, function) pairs, the hidden
    layers in order and then the head."""
    hidden = sorted(n for n in params if n != "head")
    return [(params[n], dense) for n in hidden] + [(params["head"], head)]
