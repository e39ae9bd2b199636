"""Plain Olmo-Hybrid tower (allenai/Olmo-Hybrid-7B ``config.json``): token
embedding -> blocks whose mixer is a gated delta rule (Gated DeltaNet,
arXiv:2412.06464) or full causal attention, by ``layer_types`` -> final
RMSNorm -> mean over the tokens -> L2 normalize.  Straightforward float32
``jax.numpy`` at ``highest`` matmul precision; the recurrence runs token
by token in a ``lax.scan``.  Imports nothing of the program.

What the published config does not say is the family's convention, each
item in the configuration file's ``assumed`` list: the OLMo 2 / 3
reordered norm (``h = x + RMSNorm(Mixer(x))``), QK-norm over the whole
projection, no rotary embedding (``rope_theta`` is null), depthwise
filters without bias, mean pooling.

Every row of a call has the same length (``run_serve.embed_pool`` groups
the pool by shape), so nothing is padded here.  A full-attention layer
takes its heads one after another (``lax.map``): a head's T x T scores
are all it holds.

``quant`` rounds the operands of every matrix product, and q, k and v
on their way into the recurrence, to a narrower type
(``reference/googlenet.py::quantizer``): the low-precision control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.googlenet import quantizer

_HI = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"


EPS = 1e-6  # rms_norm_eps of the family (the adapter holds the config to it)


def param_shapes(cfg):
    """{layer: {leaf: shape}} in the plain layout.  A projection keeps its
    heads apart, (in, heads, width) or (heads, width, out), so that the
    tree itself says how many heads of what width a layer has; a depthwise
    filter is (taps, heads, width), the last tap on the current token."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    lh, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    if cfg["linear_num_key_heads"] != lh or cfg["num_key_value_heads"] != h:
        raise ValueError("grouped keys are not written down here")
    taps, ff = cfg["linear_conv_kernel_dim"], cfg["intermediate_size"]
    out = {"embed": {"table": (cfg["vocab_size"], d)}, "final_norm": {"weight": (d,)}}
    for i, kind in enumerate(cfg["layer_types"]):
        b = f"block_{i}"
        if kind == LINEAR:
            out[f"{b}/gdn"] = {
                "q": (d, lh, dk), "k": (d, lh, dk), "v": (d, lh, dv), "g": (d, lh, dv),
                "o": (lh, dv, d), "a": (d, lh), "b": (d, lh),
                "conv_q": (taps, lh, dk), "conv_k": (taps, lh, dk),
                "conv_v": (taps, lh, dv), "A_log": (lh,), "dt_bias": (lh,),
                "o_norm": (dv,)}
        elif kind == FULL:
            hd = d // h
            out[f"{b}/attn"] = {"q": (d, h, hd), "k": (d, h, hd), "v": (d, h, hd),
                                "o": (h, hd, d), "q_norm": (h, hd), "k_norm": (h, hd)}
        else:
            raise ValueError(f"layer type {kind!r}")
        out[f"{b}/ffn"] = {"gate": (d, ff), "up": (d, ff), "down": (ff, d)}
        out[f"{b}/norms"] = {"mixer": (d,), "ffn": (d,)}
    return out


MATRICES = {"gdn": ("q", "k", "v", "g", "o", "a", "b"), "attn": ("q", "k", "v", "o"),
            "ffn": ("gate", "up", "down")}


def matrix_params(cfg):
    """Weights that meet every token in a matrix product (the table, the
    filters and the norms left out)."""
    total = 0
    for name, leaves in param_shapes(cfg).items():
        for leaf in MATRICES.get(name.rpartition("/")[2], ()):
            n = 1
            for width in leaves[leaf]:
                n *= width
            total += n
    return total


def recurrence_flops_per_token(cfg):
    """One linear layer, one token: the state decays (dk x dv), and three
    products with it of 2 dk dv each (S^T k, k u^T, S^T q), on every head."""
    return (7 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
            * cfg["linear_num_value_heads"])


def forward_flops(cfg, x):
    """Operations the forward pass of ONE document of ``len(x)`` tokens
    requires: two a matrix weight a token; the scores and the weighted
    values of the causal half of every full layer (4 x T^2/2 x hidden);
    the recurrence of every linear layer."""
    t = int(len(x))
    kinds = cfg["layer_types"]
    return (2 * matrix_params(cfg) * t
            + kinds.count(FULL) * 2 * t * t * cfg["hidden_size"]
            + kinds.count(LINEAR) * recurrence_flops_per_token(cfg) * t)


def gated_delta_cost(cfg, tokens, bytes_per=2):
    """(operations, bytes) the recurrence requires for ``tokens`` tokens in
    ALL the linear layers: ``recurrence_flops_per_token``, and q, k, v, g
    and beta read once and o written once in the compute type (the state
    stays on the chip)."""
    layers = cfg["layer_types"].count(LINEAR)
    per_token = cfg["linear_num_value_heads"] * (
        2 * cfg["linear_key_head_dim"] + 2 * cfg["linear_value_head_dim"] + 2)
    return (layers * recurrence_flops_per_token(cfg) * int(tokens),
            layers * per_token * bytes_per * int(tokens))


def _mm(x, w, q, axes=1):
    """The last ``axes`` axes of ``x`` against the first of ``w``."""
    if q is not None:
        x, w = q[0](x), q[0](w)
    y = jnp.tensordot(x, w, axes=axes, precision=_HI)
    return y if q is None else q[1](y)


def rms_norm(x, w, axes=1):
    """Over the last ``axes`` axes, which ``w`` spans."""
    over = tuple(range(-axes, 0))
    return x * jax.lax.rsqrt(jnp.mean(x * x, over, keepdims=True) + EPS) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + EPS)


def short_conv(x, taps):
    """Causal depthwise filter along axis 1: y_t = sum_j taps[j] *
    x_{t - (K-1-j)}, zeros before the first token.  ``x`` (B, T, ...),
    ``taps`` (K, ...)."""
    k = taps.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(taps[j] * xp[:, j:j + x.shape[1]] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The recurrence itself, one token a step.  q, k (B, T, H, dk), v
    (B, T, H, dv), g and beta (B, T, H); S_0 = 0:
    S_t = a_t S_{t-1} + beta_t k_t (v_t - a_t S_{t-1}^T k_t)^T, o_t = S_t^T q_t."""
    b, _t, h, dk = q.shape

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, s, precision=_HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s, precision=_HI)

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def gdn_mixer(p, x, q):
    act = lambda name: jax.nn.silu(short_conv(_mm(x, p[name], q), p["conv_" + name]))
    dk = p["q"].shape[-1]
    qh = l2norm(act("q")) / jnp.sqrt(jnp.float32(dk))
    kh = l2norm(act("k"))
    vh = act("v")
    beta = 2.0 * jax.nn.sigmoid(_mm(x, p["b"], q))  # linear_allow_neg_eigval
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(_mm(x, p["a"], q) + p["dt_bias"])
    if q is not None:
        qh, kh, vh = q[0](qh), q[0](kh), q[0](vh)
    o = delta_rule(qh, kh, vh, g, beta)
    y = rms_norm(o, p["o_norm"]) * jax.nn.silu(_mm(x, p["g"], q))
    return _mm(y, p["o"], q, axes=2)


def attn_mixer(p, x, q):
    t, hd = x.shape[1], p["q"].shape[-1]
    heads = lambda a: jnp.moveaxis(a, 2, 0)  # (B, T, H, hd) -> (H, B, T, hd)
    qh = heads(rms_norm(_mm(x, p["q"], q), p["q_norm"], axes=2))
    kh = heads(rms_norm(_mm(x, p["k"], q), p["k_norm"], axes=2))
    vh = heads(_mm(x, p["v"], q))
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(qkv):
        qq, kk, vv = qkv  # (B, T, hd)
        if q is not None:
            qq, kk = q[0](qq), q[0](kk)
        sc = jnp.einsum("bik,bjk->bij", qq, kk, precision=_HI) / jnp.sqrt(jnp.float32(hd))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        if q is not None:
            pr, vv = q[0](pr), q[0](vv)
        return jnp.einsum("bij,bjk->bik", pr, vv, precision=_HI)

    o = jax.lax.map(head, (qh, kh, vh))  # (H, B, T, hd)
    return _mm(jnp.moveaxis(o, 0, 2), p["o"], q, axes=2)


def ffn(p, x, q):
    return _mm(jax.nn.silu(_mm(x, p["gate"], q)) * _mm(x, p["up"], q), p["down"], q)


def block(p, x, quant=None):
    """One block on (B, T, hidden): ``p`` holds its mixer under ``gdn`` or
    ``attn``, its ``ffn`` and its ``norms``."""
    q = quantizer(quant)
    mix = gdn_mixer(p["gdn"], x, q) if "gdn" in p else attn_mixer(p["attn"], x, q)
    h = x + rms_norm(mix, p["norms"]["mixer"])
    return h + rms_norm(ffn(p["ffn"], h, q), p["norms"]["ffn"])


def block_tree(params, i):
    """Block ``i``'s leaves out of the flat plain layout, the kind of
    layer read off the tree's own names; empty past the last block."""
    return {part: params[f"block_{i}/{part}"] for part in ("gdn", "attn", "ffn", "norms")
            if f"block_{i}/{part}" in params}


def embed(params, ids, quant=None):
    """(B, T) int token ids, every row T true tokens -> (B, hidden)
    unit-norm embeddings: ``stages`` one after another on the whole tree."""
    x = ids
    for tree, fn in stages(params):
        x = fn(tree, x, quant=quant)
    return x


def lookup(p, ids, quant=None):
    """The first stage of ``stages``: (B, T) ids -> (B, T, hidden)."""
    return p["table"][ids].astype(jnp.float32)


def norm_pool(p, x, quant=None):
    """The last stage of ``stages``: the final norm, then (B, T, hidden) ->
    (B, hidden), the mean over the tokens, L2-normalized."""
    x = jnp.mean(rms_norm(x, p["weight"]), axis=1)
    return x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))


def stages(params):
    """The tower as an ordered list of (sub-tree, function): the table,
    then one block a stage, then the final norm and the pooling, so that
    ``run_serve.embed_pool`` holds one stage's float32 weights on the
    device at a time (the table, 1.54 GB, or a block, 0.83 GB, at the
    published widths) where the whole tree is 8.2 GB at eight layers.
    Every block goes through the ONE function ``block``, so a kind of
    layer compiles once a shape, not once a layer."""
    trees = []
    while tree := block_tree(params, len(trees)):
        trees.append(tree)
    return ([(params["embed"], lookup)] + [(tree, block) for tree in trees]
            + [(params["final_norm"], norm_pool)])
