"""Interpret-mode parity tests for the fused stem kernels
(ops.pallas_stem vs the XLA references) — forward AND backward, both LRN
views, ragged shapes included, plus the ConvBlock/GoogLeNet wiring
contracts (parameter-tree interchange with the plain path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from npairloss_tpu.models import layers
from npairloss_tpu.models.layers import (
    ConvBlock,
    local_response_norm,
    local_response_norm_xla,
)
from npairloss_tpu.obs import tracing
from npairloss_tpu.ops import pallas_stem as ps

# ``rows`` view (channels on the lanes, a pixel a row): sub-tile, exact
# and ragged lane counts (24, 64, 130, 192, 200), channels that do not
# divide 128 (48, 200), row counts of one chunk (7), several chunks and
# a ragged last block (300; serving's 3,136 x 1 and x 3).
ROWS_SHAPES = [
    (2, 7, 7, 24),
    (1, 5, 3, 64),
    (2, 3, 9, 130),
    (3, 10, 10, 8),
    (2, 4, 4, 192),
    (2, 3, 3, 200),
    (7, 1, 1, 48),
    (1, 56, 56, 64),
    (3, 56, 56, 64),
]
# ``cols`` view (the batch on the lanes, channels on the sublanes): one
# channel chunk (48, 64), three with real halos (192), a ragged last
# chunk (200), a batch that is not a lane multiple (100), more pixels
# than one block holds (6 x 6 at C = 192).
COLS_SHAPES = [
    (128, 2, 2, 64),
    (100, 1, 3, 192),
    (128, 1, 2, 48),
    (128, 2, 1, 200),
    (256, 6, 6, 192),
]
LRN_SHAPES = ROWS_SHAPES + COLS_SHAPES
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape).astype(dtype))


def _lrn(x, **kw):
    return ps.fused_lrn(x, interpret=True, **kw)


def _f32(a):
    return np.asarray(a, dtype=np.float32)


def _tol(dtype):
    # float32: the reference's own power, summed in another order;
    # bf16: one ulp of the rounded result (the power there is exp2 of log).
    return dict(atol=2e-6, rtol=2e-6) if dtype == jnp.float32 else dict(
        atol=2 ** -8, rtol=2 ** -7)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", LRN_SHAPES)
def test_fused_lrn_forward_parity(shape, dtype):
    x = _rand(shape).astype(dtype)
    out = _lrn(x)
    assert out.dtype == dtype and out.shape == x.shape
    np.testing.assert_allclose(_f32(out), _f32(local_response_norm_xla(x)),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", LRN_SHAPES)
def test_fused_lrn_backward_parity(shape, dtype):
    x = _rand(shape, seed=1).astype(dtype)
    w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape))
    loss = lambda f: lambda v: (f(v).astype(jnp.float32) * w).sum()
    g_ref = jax.grad(loss(local_response_norm_xla))(x)
    g = jax.grad(loss(_lrn))(x)
    assert g.dtype == dtype
    tol = dict(atol=1e-5, rtol=1e-4) if dtype == jnp.float32 else _tol(dtype)
    np.testing.assert_allclose(_f32(g), _f32(g_ref), **tol)


@pytest.mark.parametrize("shape", [(2, 4, 4, 24), (128, 2, 2, 24)],
                         ids=["rows", "cols"])
def test_fused_lrn_generic_beta_and_params(shape):
    """Non-default beta / k, and an EVEN window (lo != hi: the backward
    needs the transposed window, not the same one)."""
    x = _rand(shape, seed=2)
    w = _rand(shape, seed=3)
    for kw in (dict(size=3, alpha=2e-3, beta=0.5, k=2.0),
               dict(size=4, alpha=5e-2, beta=1.25, k=1.5)):
        ref = lambda v: local_response_norm_xla(v, **kw)
        np.testing.assert_allclose(_f32(_lrn(x, **kw)), _f32(ref(x)),
                                   atol=2e-6, rtol=1e-5)
        g = jax.grad(lambda v: (_lrn(v, **kw) * w).sum())(x)
        g_ref = jax.grad(lambda v: (ref(v) * w).sum())(x)
        np.testing.assert_allclose(_f32(g), _f32(g_ref),
                                   atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("batch", [2, 128], ids=["rows", "cols"])
@pytest.mark.parametrize("channel", [0, 63])
def test_fused_lrn_window_stops_at_the_pixel(batch, channel):
    """A one-hot pixel at the first / last channel: the window is zero
    filled at the channel edges, so nothing leaks into the neighbouring
    pixel's channels 0-1 / 62-63 (which sit next to it in a packed row,
    a flattened view or a wrapped rotation), forward or backward."""
    shape = (batch, 1, 4, 64)
    x = jnp.zeros(shape).at[1, 0, 2, channel].set(100.0)
    y = _lrn(x)
    hot = np.zeros(shape, bool)
    hot[1, 0, 2, channel] = True
    assert float(y[1, 0, 2, channel]) > 0
    assert not np.asarray(y)[~hot].any()
    # backward: a cotangent on every OTHER pixel reaches no gradient of
    # the hot one; the hot pixel's own window does.
    x = x + 1.0
    w = jnp.asarray(~hot.any(axis=-1, keepdims=True), jnp.float32)
    g = jax.grad(lambda v: (_lrn(v) * w).sum())(x)
    g_ref = jax.grad(lambda v: (local_response_norm_xla(v) * w).sum())(x)
    assert not np.asarray(g)[1, 0, 2].any()
    np.testing.assert_allclose(_f32(g), _f32(g_ref), atol=1e-6, rtol=1e-5)


def _kernel_instants(shapes):
    """The ``lrn/kernel`` instants of tracing the kernel at ``shapes``
    (bf16).  They fire where ``_lrn_call`` is traced, so its jit cache
    is dropped first: no dependence on which tests ran before."""
    ps._lrn_call.clear_cache()
    tr = tracing.SpanTracer()
    prev = tracing.install(tr)
    try:
        for shape in shapes:
            jax.eval_shape(_lrn, jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    finally:
        tracing.install(prev)
    return [e["args"] for e in tr.to_chrome_trace()["traceEvents"]
            if e["name"] == "lrn/kernel"]


def test_fused_lrn_view_follows_the_lane_padding():
    """The batch goes on the lanes exactly where it pads them less than
    the channels do — XLA's own choice at these shapes (PERF.md PR 26) —
    and the instant says which body a program got."""
    got = _kernel_instants([
        (480, 2, 2, 64), (120, 2, 2, 192), (96, 2, 2, 64), (96, 2, 2, 192),
        (64, 2, 2, 64), (8, 2, 2, 192), (1, 2, 2, 64)])
    assert [a["view"] for a in got] == [
        "cols", "cols", "cols", "rows", "rows", "rows", "rows"]
    assert got[0]["rows"] == 4 and got[0]["lanes"] == 480
    assert got[-1]["rows"] == 4 and got[-1]["lanes"] == 64
    assert all(a["block_rows"] >= 1 and not a["backward"] for a in got)


def test_fused_lrn_blocks_are_sized_from_the_shape():
    """About a megabyte a block at the cell's two sites (no parameter):
    16 and 5 pixels of (C, 480 -> 512 lanes) bf16."""
    a, b, c = _kernel_instants([
        (480, 56, 56, 64), (480, 56, 56, 192), (1, 56, 56, 192)])
    assert (a["rows"], a["block_rows"]) == (3136, 16)
    assert (b["rows"], b["block_rows"]) == (3136, 5)
    assert (c["view"], c["rows"], c["block_rows"]) == ("rows", 3136, 2048)


def test_fused_lrn_vjp_saves_x_alone():
    """The residual of the VJP is x in its own dtype: the denominator is
    recomputed, nothing float32 of the activation's size survives the
    forward (the XLA body saves six such tensors)."""
    from jax._src.ad_checkpoint import saved_residuals

    x = _rand((2, 4, 4, 32)).astype(jnp.bfloat16)
    res = saved_residuals(_lrn, x)
    assert [(a.shape, a.dtype) for a, _ in res] == [(x.shape, x.dtype)]
    ref = saved_residuals(local_response_norm_xla, x)
    assert sum(a.dtype == jnp.float32 and a.shape == x.shape
               for a, _ in ref) >= 2


def test_local_response_norm_routing(monkeypatch):
    """Off the TPU the entry point IS the reduce_window body, bit for
    bit, and never touches the kernel; on a TPU backend it runs the
    kernel, and the two agree."""
    x = _rand((2, 4, 4, 16), seed=9)
    called, kernel = [], ps.fused_lrn
    monkeypatch.setattr(
        ps, "fused_lrn",
        lambda *a: called.append(a) or kernel(*a, interpret=True))
    np.testing.assert_array_equal(
        np.asarray(local_response_norm(x)),
        np.asarray(local_response_norm_xla(x)))
    assert not called
    monkeypatch.setattr(layers.jax, "default_backend", lambda: "tpu")
    np.testing.assert_allclose(
        np.asarray(local_response_norm(x, 3, 2e-3, 0.5, 2.0)),
        np.asarray(local_response_norm_xla(x, 3, 2e-3, 0.5, 2.0)),
        atol=2e-6, rtol=1e-5)
    assert len(called) == 1


@pytest.mark.parametrize("shape,spec", [
    ((8, 4, 4, 64), P("dp")),
    ((512, 2, 2, 64), P("dp")),
    ((4, 4, 4, 64), P("dp", "sp")),
], ids=["rows", "cols", "rows_2d"])
def test_fused_lrn_partitions_by_pixels(shape, spec):
    """Under GSPMD the call keeps whatever shards the pixel dimensions
    (channels whole): no all-gather / all-reduce in the compiled forward
    + backward, each device runs the kernel on its own pixels, and the
    result is the single-device one."""
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs.reshape(2, 2), ("dp", "sp")) if len(spec) > 1 \
        else Mesh(devs, ("dp",))
    x, g = _rand(shape, seed=4), _rand(shape, seed=5)

    def fwd_bwd(x, g):
        y, vjp = jax.vjp(_lrn, x)
        return y, vjp(g)[0]

    want = fwd_bwd(x, g)
    sh = NamedSharding(mesh, spec)
    fn = jax.jit(fwd_bwd, in_shardings=(sh, sh), out_shardings=(sh, sh))
    text = fn.lower(x, g).compile().as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    got = fn(jax.device_put(x, sh), jax.device_put(g, sh))
    for a, b in zip(got, want):
        assert a.sharding.is_equivalent_to(sh, a.ndim)
        np.testing.assert_allclose(_f32(a), _f32(b), atol=1e-6, rtol=1e-6)


def test_fused_bias_relu_parity():
    x = _rand((2, 5, 5, 24), seed=3)
    b = _rand((24,), seed=4)
    ref = jnp.maximum(x + b, 0)
    np.testing.assert_allclose(np.asarray(ps.fused_bias_relu(x, b)),
                               np.asarray(ref), atol=1e-6)
    got = jax.grad(
        lambda xx, bb: (ps.fused_bias_relu(xx, bb) ** 2).sum(),
        argnums=(0, 1))(x, b)
    want = jax.grad(
        lambda xx, bb: (jnp.maximum(xx + bb, 0) ** 2).sum(),
        argnums=(0, 1))(x, b)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("hw", [(8, 8), (7, 9), (5, 5)])
def test_fused_bias_relu_pool_parity(hw):
    """SAME 3x3/s2 pool epilogue vs bias+relu+reduce_window, fwd + bwd,
    even and odd (ragged-pad) spatial sizes."""
    x = _rand((2, *hw, 24), seed=5)
    b = _rand((24,), seed=6)
    ref = ps._reference_bias_relu_pool(x, b, 3, 2)
    np.testing.assert_allclose(np.asarray(ps.fused_bias_relu_pool(x, b)),
                               np.asarray(ref), atol=1e-6)
    got = jax.grad(
        lambda xx: (ps.fused_bias_relu_pool(xx, b) ** 2).sum())(x)
    want = jax.grad(
        lambda xx: (ps._reference_bias_relu_pool(xx, b, 3, 2)
                    .astype(jnp.float32) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Model wiring: the fused path must interchange with the plain one
# ---------------------------------------------------------------------------


def test_convblock_fused_epilogue_param_tree_and_output():
    """fused_epilogue keeps the EXACT nn.Conv parameter tree
    (Conv_0/{kernel,bias}) and computes the same function; fuse_pool
    folds the SAME max-pool the caller would otherwise apply."""
    import jax.tree_util as jtu

    from npairloss_tpu.models.layers import max_pool

    x = _rand((2, 12, 12, 3), seed=7)
    key = jax.random.PRNGKey(0)
    plain = ConvBlock(16, (3, 3), (2, 2))
    fused = ConvBlock(16, (3, 3), (2, 2), fused_epilogue=True)
    pooled = ConvBlock(16, (3, 3), (2, 2), fused_epilogue=True,
                       fuse_pool=(3, 2))
    v = plain.init(key, x)
    paths = lambda t: [jtu.keystr(k) for k, _ in
                       jtu.tree_flatten_with_path(t)[0]]
    assert paths(fused.init(key, x)) == paths(v)
    o_plain = plain.apply(v, x)
    o_fused = fused.apply(v, x)
    np.testing.assert_allclose(np.asarray(o_fused), np.asarray(o_plain),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(pooled.apply(v, x)),
        np.asarray(max_pool(o_plain, 3, 2)), atol=1e-5)


def test_fused_epilogue_nonfp32_bias_cotangent_dtype():
    """custom_vjp requires db.dtype == bias.dtype: a policy rule may
    store a fused-stem conv's params in bf16, and the epilogue VJPs
    must return the bias cotangent in that dtype (a hardcoded fp32 db
    raised at trace time on the first training step)."""
    from npairloss_tpu.models.precision import PrecisionPolicy

    pol = PrecisionPolicy(
        name="bf16params", compute_dtype=jnp.bfloat16,
        rules=((r".*", {"param_dtype": jnp.bfloat16}),),
    )
    x = _rand((2, 8, 8, 3), seed=11)
    for fuse_pool in (None, (3, 2)):
        blk = ConvBlock(8, (3, 3), policy=pol, fused_epilogue=True,
                        fuse_pool=fuse_pool)
        v = blk.init(jax.random.PRNGKey(0), x)
        assert v["params"]["Conv_0"]["bias"].dtype == jnp.bfloat16
        g = jax.grad(
            lambda vv: blk.apply(vv, x).astype(jnp.float32).sum())(v)
        assert g["params"]["Conv_0"]["bias"].dtype == jnp.bfloat16


def test_convblock_fused_epilogue_ignored_under_bn():
    """BN trunks have neither conv bias nor an epilogue to fuse — the
    flag must be a no-op there, not an error."""
    x = _rand((2, 8, 8, 3), seed=8)
    bn = ConvBlock(8, (3, 3), use_bn=True, fused_epilogue=True)
    v = bn.init(jax.random.PRNGKey(0), x)
    ref = ConvBlock(8, (3, 3), use_bn=True)
    np.testing.assert_array_equal(
        np.asarray(bn.apply(v, x)), np.asarray(ref.apply(v, x)))


@pytest.mark.slow
def test_googlenet_pallas_registry_interchange():
    """googlenet_pallas == googlenet_mxu trunk + pallas_stem: identical
    parameter tree, near-identical function on shared params (the
    fused-kernel wiring pin at trunk level).  Slow-marked: two
    GoogLeNet jits (~13s); the ConvBlock-level interchange test above
    plus the ci.sh pallas smoke keep the wiring covered in tier-1
    time."""
    import jax.tree_util as jtu

    from npairloss_tpu.models import get_model, jit_init

    x = _rand((2, 32, 32, 3), seed=10)
    key = jax.random.PRNGKey(0)
    m_mxu = get_model("googlenet_mxu", policy="mxu")
    m_pal = get_model("googlenet_pallas", policy="mxu")
    assert m_pal.pallas_stem and m_pal.stem_s2d and m_pal.fuse_1x1
    v = jit_init(m_mxu, key, x)
    paths = lambda t: [jtu.keystr(k) for k, _ in
                       jtu.tree_flatten_with_path(t)[0]]
    assert paths(jax.eval_shape(
        lambda: m_pal.init(key, x))) == paths(v)
    o_mxu = jax.jit(lambda v_, x_: m_mxu.apply(v_, x_))(v, x)
    o_pal = jax.jit(lambda v_, x_: m_pal.apply(v_, x_))(v, x)
    assert float(jnp.abs(o_pal - o_mxu).max()) < 2e-2
