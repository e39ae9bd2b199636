"""Plain GoogLeNet v1 (Szegedy et al., arXiv:1409.4842, Table 1) cut at
pool5 and L2-normalized: straightforward float32 ``jax.numpy``.

Imports nothing of the program.  Departures from the paper, each shared
with the configuration file's ``assumed`` list:

* the stem is an 8x8/stride-2 convolution padded (2, 4): the program's
  flagship trunk runs the 7x7/2 stem as a 4x4 convolution over a
  space-to-depth(2) grid, which has one more row and column of taps
  than the 7x7 kernel; the benchmark starts those taps at zero, so the
  first forward pass IS the paper's 7x7/2 stem, and training moves them;
* no auxiliary classifiers and no classifier head (the embedding model
  of the reference's ``usage/def.prototxt`` stops at ``pool5/7x7_s1``);
* pooling pads as TensorFlow's SAME does (right-biased), which at these
  sizes equals Caffe's pad-0 ceil-mode pooling.

``quant`` computes every convolution in a narrower type (operands
rounded forward, cotangents rounded backward; see ``quantizer``): the
low-precision control of the benchmark's ``correct`` (float8 for a
bfloat16 configuration).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# (1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj) -- Table 1.
INCEPTION = {
    "3a": (64, 96, 128, 16, 32, 32),
    "3b": (128, 128, 192, 32, 96, 64),
    "4a": (192, 96, 208, 16, 48, 64),
    "4b": (160, 112, 224, 24, 64, 64),
    "4c": (128, 128, 256, 24, 64, 64),
    "4d": (112, 144, 288, 32, 64, 64),
    "4e": (256, 160, 320, 32, 128, 128),
    "5a": (256, 160, 320, 32, 128, 128),
    "5b": (384, 192, 384, 48, 128, 128),
}
BRANCHES = ("b1x1", "b3x3_reduce", "b3x3", "b5x5_reduce", "b5x5", "pool_proj")
_HI = jax.lax.Precision.HIGHEST


def param_shapes(in_ch: int = 3):
    """{layer: {"kernel": shape, "bias": shape}} in the plain layout."""
    shapes = {
        "conv1": (8, 8, in_ch, 64),
        "conv2_reduce": (1, 1, 64, 64),
        "conv2": (3, 3, 64, 192),
    }
    c = 192
    for key, (p1, p3r, p3, p5r, p5, pp) in INCEPTION.items():
        block = f"inception_{key}"
        for name, (k, cin, cout) in zip(BRANCHES, (
                (1, c, p1), (1, c, p3r), (3, p3r, p3),
                (1, c, p5r), (5, p5r, p5), (1, c, pp))):
            shapes[f"{block}/{name}"] = (k, k, cin, cout)
        c = p1 + p3 + p5 + pp
    return {name: {"kernel": s, "bias": (s[-1],)} for name, s in shapes.items()}


def _same(size, stride):
    return -(-size // stride)


def forward_flops(image_size=224, in_ch=3):
    """Operations one image REQUIRES through GoogLeNet v1 to pool5
    (convolutions only; the paper's stem is 7x7/2; a multiply-add is two)."""
    total = 0

    def conv(hw, k, cin, cout):
        nonlocal total
        total += 2 * hw * hw * k * k * cin * cout

    hw = _same(image_size, 2)
    conv(hw, 7, in_ch, 64)
    hw = _same(hw, 2)
    conv(hw, 1, 64, 64)
    conv(hw, 3, 64, 192)
    hw = _same(hw, 2)
    c = 192
    for key, (p1, p3r, p3, p5r, p5, pp) in INCEPTION.items():
        if key in ("4a", "5a"):
            hw = _same(hw, 2)
        conv(hw, 1, c, p1)
        conv(hw, 1, c, p3r)
        conv(hw, 3, p3r, p3)
        conv(hw, 1, c, p5r)
        conv(hw, 5, p5r, p5)
        conv(hw, 1, c, pp)
        c = p1 + p3 + p5 + pp
    return total


def _round_to(x, dt):
    """float32 -> ``dt`` -> float32; an 8-bit float gets a per-tensor
    scale (largest magnitude onto the type's largest value)."""
    dt = jnp.dtype(dt)
    if dt.itemsize > 1:
        # not astype there and back: the TPU compiler takes such a pair
        # of converts out (excess precision is allowed), and the
        # "bfloat16" reference then IS the float32 one (read on the
        # chip, PR 24: embeddings equal to the last bit)
        info = jnp.finfo(dt)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)
    top = float(jnp.finfo(dt).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dt).astype(jnp.float32) * s


def quantizer(kind):
    """None, or ``(q, qb)`` that make a matrix product "computed in
    ``kind``": ``q`` rounds a product's operands to ``kind`` on the way
    forward (the gradient passes straight through, over the rounded
    operands); ``qb`` is the identity forward and rounds the cotangent
    that comes back into the product -- the operand of both backward
    products -- to the gradient type of that precision (float8_e5m2
    where ``kind`` is float8_e4m3fn, as float8 training recipes do;
    ``kind`` itself otherwise).  Accumulation stays float32."""
    if kind is None:
        return None
    grad_kind = "float8_e5m2" if jnp.dtype(kind) == jnp.dtype("float8_e4m3fn") else kind

    def q(x):
        return x + jax.lax.stop_gradient(_round_to(x, kind) - x)

    @jax.custom_vjp
    def qb(y):
        return y

    qb.defvjp(lambda y: (y, None), lambda _res, ct: (_round_to(ct, grad_kind),))
    return q, qb


def _conv(x, p, stride=1, padding="SAME", q=None):
    k = p["kernel"]
    if q is not None:
        x, k = q[0](x), q[0](k)
    y = jax.lax.conv_general_dilated(
        x, k, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI)
    if q is not None:
        y = q[1](y)
    return jax.nn.relu(y + p["bias"])


def _max_pool(x, window=3, stride=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "SAME")


def _lrn(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    half = size // 2
    win = jax.lax.reduce_window(
        x * x, 0.0, jax.lax.add, (1, 1, 1, size), (1, 1, 1, 1),
        ((0, 0), (0, 0), (0, 0), (half, size - 1 - half)))
    return x / jnp.power(k + (alpha / size) * win, beta)


def _inception(x, params, block, q):
    g = lambda name: params[f"{block}/{name}"]
    b1 = _conv(x, g("b1x1"), q=q)
    b3 = _conv(_conv(x, g("b3x3_reduce"), q=q), g("b3x3"), q=q)
    b5 = _conv(_conv(x, g("b5x5_reduce"), q=q), g("b5x5"), q=q)
    bp = _conv(_max_pool(x, 3, 1), g("pool_proj"), q=q)
    return jnp.concatenate([b1, b3, b5, bp], axis=-1)


def embed(params, x, quant=None):
    """(N, H, W, 3) float32 images -> (N, 1024) unit-norm embeddings."""
    q = quantizer(quant)
    x = x.astype(jnp.float32)
    x = _conv(x, params["conv1"], 2, ((2, 4), (2, 4)), q)
    x = _lrn(_max_pool(x))
    x = _conv(x, params["conv2_reduce"], q=q)
    x = _conv(x, params["conv2"], q=q)
    x = _max_pool(_lrn(x))
    for key in ("3a", "3b"):
        x = _inception(x, params, f"inception_{key}", q)
    x = _max_pool(x)
    for key in ("4a", "4b", "4c", "4d", "4e"):
        x = _inception(x, params, f"inception_{key}", q)
    x = _max_pool(x)
    for key in ("5a", "5b"):
        x = _inception(x, params, f"inception_{key}", q)
    x = jnp.mean(x, axis=(1, 2))
    return x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))
