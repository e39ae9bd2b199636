"""Weights and images from the seed, made on the device in one jitted
call each, in float32 (the type the configurations train and serve)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def fold_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they exceed 32 signed bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def make_params(adapter, cfg, seed: int):
    """The plain-layout parameter dict of ``adapter``'s family."""
    shapes, scales = adapter.shapes(cfg), adapter.init_scales(cfg)
    names = [(n, l) for n in sorted(shapes) for l in sorted(shapes[n])]

    def build(key):
        out = {}
        for i, (n, l) in enumerate(names):
            kind, scale = scales[n][l]
            shape = tuple(shapes[n][l])
            k = jax.random.fold_in(key, i)
            if kind == "uniform":
                v = jax.random.uniform(k, shape, jnp.float32, -scale, scale)
            elif kind == "normal":
                v = scale * jax.random.normal(k, shape, jnp.float32)
            else:
                v = jnp.full(shape, 1.0 if kind == "ones" else 0.0, jnp.float32)
            out.setdefault(n, {})[l] = v
        return adapter.post_init(out)

    return jax.jit(build)(fold_seed(seed, 1))


def identity_batches(seed: int, count: int, identities: int, per_identity: int,
                     image_shape, noise: float = 0.6):
    """``count`` identity-balanced batches: every identity is a random
    image (unit normal pixels) and each of its rows adds ``noise`` times
    fresh normal pixels; rows are shuffled within the batch.  All rows of
    all batches differ.  Returns host arrays (images, labels)."""
    n = identities * per_identity

    def build(key):
        kc, kn, kp = jax.random.split(key, 3)
        centres = jax.random.normal(kc, (identities, *image_shape), jnp.float32)
        labels = jnp.repeat(jnp.arange(identities, dtype=jnp.int32), per_identity)
        perm = jax.random.permutation(kp, n)
        labels = labels[perm]
        x = centres[labels] + noise * jax.random.normal(
            kn, (n, *image_shape), jnp.float32)
        return x, labels

    fn = jax.jit(build)
    images, labels = [], []
    for b in range(count):
        x, lab = fn(fold_seed(seed, 100 + b))
        images.append(np.asarray(x))
        labels.append(np.asarray(lab) + b * identities)
    return images, labels


def image_pool(seed: int, count: int, image_shape):
    """``count`` seeded query images, unit normal pixels, on the host."""
    fn = jax.jit(lambda k: jax.random.normal(k, (count, *image_shape), jnp.float32))
    return np.asarray(fn(fold_seed(seed, 7)))


def mixture_gallery(seed: int, rows: int, dim: int, centres: int,
                    noise: float = 0.5, chunk: int = 262144):
    """Unit-norm gallery rows around ``centres`` Gaussian blobs (the
    generator of the program's chip smoke, at any width), made on the
    device a chunk of rows at a time and gathered on the host."""
    key = fold_seed(seed, 11)
    c = jax.jit(lambda k: jax.random.normal(k, (centres, dim), jnp.float32))(
        jax.random.fold_in(key, 0))

    @jax.jit
    def build(k, c):
        kl, kn = jax.random.split(k)
        lab = jax.random.randint(kl, (min(chunk, rows),), 0, centres, jnp.int32)
        x = c[lab] + noise * jax.random.normal(kn, (lab.shape[0], dim), jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)), lab

    out = np.empty((rows, dim), np.float32)
    labels = np.empty((rows,), np.int32)
    for i, lo in enumerate(range(0, rows, chunk)):
        x, lab = build(jax.random.fold_in(key, i + 1), c)
        n = min(chunk, rows - lo)
        out[lo:lo + n], labels[lo:lo + n] = np.asarray(x)[:n], np.asarray(lab)[:n]
    return out, labels
