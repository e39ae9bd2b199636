"""Olmo-Hybrid embedding tower: a decoder stack read as a document encoder.

Token embedding -> blocks whose mixer is, by ``layer_types``, a gated
delta rule (``ops/gated_delta.py``: Gated DeltaNet, arXiv:2412.06464) or
full causal attention -> final RMSNorm -> mean over each row's TRUE
tokens.  The block is the OLMo 2 / 3 reordered norm: ``h = x +
RMSNorm(Mixer(x))``, ``y = h + RMSNorm(FFN(h))``, SwiGLU feed-forward,
no bias anywhere; full attention has QK-norm over the whole projection
and no rotary embedding (position comes from the recurrent layers).

Inputs are ``(ids, lengths)``: int32 token rows right-padded to a common
length and each row's true length.  Both mixers are causal, so a true
token never sees a padded one; the lengths mask the pooling (and stop
the padded tokens writing to the recurrent state).

Parameters are used in the dtype they are given -- bfloat16-resident on
the serving path -- and never widened as a tree: each is cast to the
compute dtype (matrices) or float32 (norm weights, decays) where it is
used.  Matrix products take ``dtype`` operands and accumulate in
float32; norms, softmax, decays and the recurrent state are float32.  A
projection keeps its heads apart, ``(hidden, heads, width)``, so the
tree itself carries the head geometry.

Named scopes from the root split the device trace:
``block_<i>/gdn/{proj,conv,scan,out}``, ``block_<i>/attn/{proj,core,out}``,
``block_<i>/ffn/``, ``embed/``, ``pool/``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from npairloss_tpu.ops.causal_attention import causal_attention
from npairloss_tpu.ops.gated_delta import gated_delta_rule
from npairloss_tpu.ops.short_conv import causal_short_conv

LINEAR, FULL = "linear_attention", "full_attention"
_F32 = jnp.float32
_NORMAL = nn.initializers.normal(0.02)


def _rms(x, w, eps, axes=1):
    """RMSNorm over the last ``axes`` axes (which ``w`` spans), float32."""
    x = x.astype(_F32)
    over = tuple(range(-axes, 0))
    return x * jax.lax.rsqrt(jnp.mean(x * x, over, keepdims=True) + eps) \
        * w.astype(_F32)


def _dot(x, w, dtype, axes=1):
    """The last ``axes`` axes of ``x`` against the first of ``w``, operands
    in ``dtype``, float32 out."""
    lhs = tuple(range(x.ndim - axes, x.ndim))
    return jax.lax.dot_general(
        x.astype(dtype), w.astype(dtype),
        ((lhs, tuple(range(axes))), ((), ())), preferred_element_type=_F32)


def _taps_init(key, shape, dtype):
    """U(-1/sqrt(K), 1/sqrt(K)): a depthwise Conv1d's default, fan-in K."""
    bound = 1.0 / float(shape[0]) ** 0.5
    return jax.random.uniform(key, shape, _F32, -bound, bound).astype(dtype)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, _F32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """Inverse softplus of dt, log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, _F32, jnp.log(1e-3), jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class GatedDeltaMixer(nn.Module):
    heads: int
    key_dim: int
    value_dim: int
    taps: int
    eps: float
    chunk: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x, lengths):
        d, h, dk, dv = x.shape[-1], self.heads, self.key_dim, self.value_dim
        par = lambda name, init, *shape: self.param(name, init, shape, self.param_dtype)
        with jax.named_scope("proj"):
            proj = {n: _dot(x, par(n, _NORMAL, d, h, w), self.dtype)
                    for n, w in (("q", dk), ("k", dk), ("v", dv), ("g", dv))}
            beta = 2.0 * jax.nn.sigmoid(_dot(x, par("b", _NORMAL, d, h), self.dtype))
            g = -jnp.exp(par("A_log", _a_log_init, h).astype(_F32)) * jax.nn.softplus(
                _dot(x, par("a", _NORMAL, d, h), self.dtype)
                + par("dt_bias", _dt_bias_init, h).astype(_F32))
        with jax.named_scope("conv"):
            act = lambda n, w: jax.nn.silu(causal_short_conv(
                proj[n], par("conv_" + n, _taps_init, self.taps, h, w)))
            l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + self.eps)
            q = (l2(act("q", dk)) * (1.0 / float(dk) ** 0.5)).astype(self.dtype)
            k = l2(act("k", dk)).astype(self.dtype)
            v = act("v", dv).astype(self.dtype)
        with jax.named_scope("scan"):
            o = gated_delta_rule(q, k, v, g, beta, lengths, chunk=self.chunk)
        with jax.named_scope("out"):
            y = _rms(o, par("o_norm", nn.initializers.ones, dv), self.eps) \
                * jax.nn.silu(proj["g"])
            return _dot(y, par("o", _NORMAL, h, dv, d), self.dtype, axes=2)


class AttentionMixer(nn.Module):
    heads: int
    eps: float
    block: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        d, h = x.shape[-1], self.heads
        hd = d // h
        par = lambda name, init, *shape: self.param(name, init, shape, self.param_dtype)
        with jax.named_scope("proj"):
            q = _rms(_dot(x, par("q", _NORMAL, d, h, hd), self.dtype),
                     par("q_norm", nn.initializers.ones, h, hd), self.eps, axes=2)
            k = _rms(_dot(x, par("k", _NORMAL, d, h, hd), self.dtype),
                     par("k_norm", nn.initializers.ones, h, hd), self.eps, axes=2)
            v = _dot(x, par("v", _NORMAL, d, h, hd), self.dtype)
        with jax.named_scope("core"):
            o = causal_attention(q.astype(self.dtype), k.astype(self.dtype),
                                 v.astype(self.dtype), block=self.block)
        with jax.named_scope("out"):
            return _dot(o, par("o", _NORMAL, h, hd, d), self.dtype, axes=2)


class SwiGLU(nn.Module):
    width: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        par = lambda name, *shape: self.param(name, _NORMAL, shape, self.param_dtype)
        gate = _dot(x, par("gate", d, self.width), self.dtype)
        up = _dot(x, par("up", d, self.width), self.dtype)
        return _dot(jax.nn.silu(gate) * up, par("down", self.width, d), self.dtype)


@dataclasses.dataclass(frozen=True)
class _Dims:
    """What a block reads of the tower's fields."""

    intermediate: int
    num_heads: int
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_taps: int
    eps: float
    chunk: int
    attn_block: int
    dtype: Any
    param_dtype: Any


class HybridBlock(nn.Module):
    kind: str
    dims: Any  # a _Dims: what a block reads of the tower's fields

    @nn.compact
    def __call__(self, x, lengths):
        c = self.dims
        norm = lambda name, y: _rms(y, self.param(
            name, nn.initializers.ones, (x.shape[-1],), c.param_dtype), c.eps)
        if self.kind == LINEAR:
            mix = GatedDeltaMixer(
                c.linear_heads, c.linear_key_dim, c.linear_value_dim, c.conv_taps,
                c.eps, c.chunk, c.dtype, c.param_dtype, name="gdn")(x, lengths)
        elif self.kind == FULL:
            mix = AttentionMixer(c.num_heads, c.eps, c.attn_block, c.dtype,
                                 c.param_dtype, name="attn")(x)
        else:
            raise ValueError(f"layer type {self.kind!r}")
        h = x + norm("mixer_norm", mix)
        return h + norm("ffn_norm", SwiGLU(c.intermediate, c.dtype, c.param_dtype,
                                           name="ffn")(h))


class OlmoHybridEmbedding(nn.Module):
    """Defaults are a tiny preset for tests; the published sizes are
    arguments (``benchmarks/configs/olmo_hybrid_7b_l8.json``).  ``dtype``
    is the matmul operand type, ``param_dtype`` what ``init`` makes (a
    given tree is used as it is)."""

    vocab_size: int = 512
    hidden: int = 64
    intermediate: int = 128
    layer_types: Sequence[str] = (LINEAR, LINEAR, LINEAR, FULL)
    num_heads: int = 2
    linear_heads: int = 2
    linear_key_dim: int = 16
    linear_value_dim: int = 32
    conv_taps: int = 4
    eps: float = 1e-6
    chunk: int = 64
    attn_block: int = 512
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, lengths: Optional[jax.Array] = None,
                 train: bool = False):
        if lengths is None:
            lengths = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
        with jax.named_scope("embed"):
            table = self.param("table", _NORMAL,
                               (self.vocab_size, self.hidden), self.param_dtype)
            x = table[ids].astype(_F32)
        dims = _Dims(**{f.name: getattr(self, f.name)
                        for f in dataclasses.fields(_Dims)})
        for i, kind in enumerate(self.layer_types):
            x = HybridBlock(kind, dims, name=f"block_{i}")(x, lengths)
        with jax.named_scope("pool"):
            x = _rms(x, self.param("final_norm", nn.initializers.ones,
                                   (self.hidden,), self.param_dtype), self.eps)
            live = (jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
            return jnp.sum(jnp.where(live[..., None], x, 0.0), axis=1) \
                / jnp.maximum(lengths, 1)[:, None].astype(_F32)
