"""Weights, inputs and galleries from the seed, made on the device in
jitted calls.  What an input IS belongs to the configuration's adapter
(``query_pool``, ``train_batches``); the generators here take a shape."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GROUP_BYTES = 2 ** 30  # float32 bytes one jitted call of make_params may hold


def fold_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed (they exceed 32 signed bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def make_params(adapter, cfg, seed: int):
    """The plain-layout parameter dict of ``adapter``'s family, in
    ``cfg["precision"]["params"]``.  Leaf i (layers and leaves in sorted
    order) is drawn in float32 from ``fold_in(key, i)``; whole layers, in
    order, fill a jitted call up to ``GROUP_BYTES`` of float32, which
    runs ``adapter.post_init`` on its layers and casts them before the
    next call starts: the device holds the finished tree and one float32
    group, never a second whole tree.  A tree under ``GROUP_BYTES`` is
    one call and one compile."""
    shapes, scales = adapter.shapes(cfg), adapter.init_scales(cfg)
    dtype = jnp.dtype(cfg["precision"]["params"])
    index, groups, room = 0, [], 0
    for n in sorted(shapes):
        leaves = [(index + j, n, l) for j, l in enumerate(sorted(shapes[n]))]
        index += len(leaves)
        size = sum(4 * int(np.prod(shapes[n][l])) for _, _, l in leaves)
        if not groups or size > room:
            groups.append([])
            room = GROUP_BYTES
        groups[-1] += leaves
        room -= size

    def build(group, key):
        out = {}
        for i, n, l in group:
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
        return jax.tree_util.tree_map(lambda v: v.astype(dtype), adapter.post_init(out))

    key, params = fold_seed(seed, 1), {}
    for group in groups:
        params.update(jax.jit(functools.partial(build, group))(key))
    return params


def widened(params):
    """Host copy of ``params`` in float32: what the plain reference reads,
    whatever type the program's weights live in."""
    return jax.tree_util.tree_map(lambda v: np.asarray(v, np.float32), params)


def identity_batches(seed: int, count: int, identities: int, per_identity: int,
                     shape, noise: float = 0.6):
    """``count`` identity-balanced batches of inputs of ``shape``: every
    identity is a random input (unit normal) and each of its rows adds
    ``noise`` times fresh normal values; rows are shuffled within the
    batch.  All rows of all batches differ.  Returns host arrays
    (inputs, labels)."""
    n = identities * per_identity

    def build(key):
        kc, kn, kp = jax.random.split(key, 3)
        centres = jax.random.normal(kc, (identities, *shape), jnp.float32)
        labels = jnp.repeat(jnp.arange(identities, dtype=jnp.int32), per_identity)
        perm = jax.random.permutation(kp, n)
        labels = labels[perm]
        x = centres[labels] + noise * jax.random.normal(
            kn, (n, *shape), jnp.float32)
        return x, labels

    fn = jax.jit(build)
    inputs, labels = [], []
    for b in range(count):
        x, lab = fn(fold_seed(seed, 100 + b))
        inputs.append(np.asarray(x))
        labels.append(np.asarray(lab) + b * identities)
    return inputs, labels


def normal_pool(seed: int, count: int, shape):
    """``count`` seeded unit-normal query inputs of ``shape``, on the host."""
    fn = jax.jit(lambda k: jax.random.normal(k, (count, *shape), jnp.float32))
    return list(np.asarray(fn(fold_seed(seed, 7))))


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
