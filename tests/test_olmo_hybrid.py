"""The Olmo-Hybrid token tower (``models/olmo_hybrid.py``) against the plain
reference (``benchmarks/reference/olmo_hybrid.py``: float32, the recurrence
token by token, nothing of the program imported) on seeded weights at the
tiny preset, through the benchmark's own adapter.

Tolerances, on unit-norm embeddings 64 wide (a coordinate is ~0.125):
float32 against float32 differs by the order of the sums alone (chunked
scan, blocked attention): 5e-6 (measured 7e-7).  bfloat16-resident weights
with bfloat16 operands round every matrix product's inputs to 8 bits down
four layers: 4e-2 a coordinate, cosine over 0.998 (measured 1.2e-2, 0.9996).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.adapters import olmo_hybrid as adapter  # noqa: E402
from benchmarks.harness import weights  # noqa: E402
from npairloss_tpu.models import get_model  # noqa: E402

SEED = 2**31 + 77
LENGTHS = (37, 100, 64)


def _cfg(params_dtype, policy):
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "olmo_hybrid_7b_l8.json")))
    cfg.update(cfg["rehearsal"])
    cfg["precision"] = dict(cfg["precision"], params=params_dtype)
    cfg["program"] = dict(cfg["program"], precision=policy)
    return cfg


def _unit(x):
    x = np.array(x, np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


@pytest.fixture(scope="module", params=[("float32", "fp32_parity"), ("bfloat16", "bf16")],
                ids=["float32", "bfloat16"])
def tower(request):
    """(params dtype, model, program tree, plain float32 tree, documents,
    the padded batch's embeddings)."""
    dtype, policy = request.param
    cfg = _cfg(dtype, policy)
    params = weights.make_params(adapter, cfg, SEED)
    tree = adapter.to_program(params, xp=jnp)
    model = adapter.build_model(cfg)
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, cfg["vocab_size"], size=n).astype(np.int32) for n in LENGTHS]
    ids = np.zeros((4, 128), np.int32)  # a padding row and padded columns
    lens = np.ones((4,), np.int32)
    for i, d in enumerate(docs):
        ids[i, :len(d)], lens[i] = d, len(d)
    # jitted: op by op on the CPU every small primitive compiles by itself
    apply = jax.jit(lambda p, i, n: model.apply({"params": p}, i, n, train=False))
    out = apply(tree, jnp.asarray(ids), jnp.asarray(lens))
    host = jax.tree_util.tree_map(jnp.asarray, weights.widened(params))
    return dtype, apply, params, tree, host, docs, out


def test_tower_matches_the_plain_reference(tower):
    dtype, _apply, _params, _tree, host, docs, out = tower
    assert out.dtype == jnp.float32 and out.shape == (4, 64)
    got = _unit(out)
    embed = jax.jit(adapter.embed)
    for i, d in enumerate(docs):
        want = np.asarray(embed(host, jnp.asarray(d[None])))[0]
        if dtype == "float32":
            np.testing.assert_allclose(got[i], want, atol=5e-6, rtol=0)
        else:
            np.testing.assert_allclose(got[i], want, atol=4e-2, rtol=0)
            assert float(got[i] @ want) > 0.998


def test_padding_changes_no_embedding(tower):
    """A document alone at its own length and the same document padded
    beside longer co-riders (and a padding row) give the same embedding:
    both mixers are causal, the lengths mask the pool.  In float32 to the
    order of a sum (the mean over 37 of 128 rows): 2e-6.  In bfloat16 the
    two shapes are two compiled programs, which fuse, and so round their
    bfloat16 intermediates, in other places: 2e-3 (measured 3e-4, a
    fortieth of bfloat16's own distance from the reference)."""
    dtype, apply, _params, tree, _host, docs, out = tower
    atol = 2e-6 if dtype == "float32" else 2e-3
    for i, d in enumerate(docs):
        alone = apply(tree, jnp.asarray(d[None]), jnp.asarray([len(d)]))
        np.testing.assert_allclose(_unit(alone)[0], _unit(out)[i], atol=atol, rtol=0)


def test_parameters_stay_in_the_dtype_they_are_given(tower):
    """bfloat16-resident weights: the adapter's tree is what the model
    reads (no float32 copy of the tree is an argument of the program)."""
    dtype, apply, _params, tree, _host, _docs, _out = tower
    leaves = jax.tree_util.tree_leaves(tree)
    assert {str(x.dtype) for x in leaves} == {dtype}
    lowered = apply.lower(tree, jnp.zeros((1, 16), jnp.int32), jnp.ones((1,), jnp.int32))
    args = lowered.compile().memory_analysis().argument_size_in_bytes
    assert args <= sum(x.size * x.dtype.itemsize for x in leaves) + 1024


def test_program_and_plain_layouts_round_trip(tower):
    dtype, _apply, params, tree, _host, _docs, _out = tower
    back = adapter.from_program(tree)
    assert sorted(back) == sorted(params)
    for layer, leaves in params.items():
        assert sorted(back[layer]) == sorted(leaves)
        for leaf, v in leaves.items():
            assert back[layer][leaf] is v  # the same arrays: nothing is copied
    # the program's own init makes the same tree: names and shapes
    model = get_model("olmo_hybrid")
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    same = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, init, tree)
    assert all(jax.tree_util.tree_leaves(same))


def test_init_draws_the_gated_deltanet_defaults():
    model = get_model("olmo_hybrid")
    p = jax.jit(model.init)(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    gdn = p["block_0"]["gdn"]
    a = np.exp(np.asarray(gdn["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(gdn["dt_bias"])))  # softplus
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert float(np.asarray(p["block_3"]["attn"]["q_norm"]).min()) == 1.0
    with pytest.raises(ValueError, match="layer type"):
        get_model("olmo_hybrid", layer_types=("sliding_attention",)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_registry_loads_the_tower_only_when_it_is_built():
    """A float-input process (``get_model`` of any other trunk) imports
    neither the tower nor its ops."""
    code = (
        "import sys\n"
        "from npairloss_tpu.models import get_model\n"
        "import npairloss_tpu.serve.server, npairloss_tpu.serve.engine\n"
        "get_model('mlp')\n"
        "mods = ('npairloss_tpu.models.olmo_hybrid', 'npairloss_tpu.ops.gated_delta',\n"
        "        'npairloss_tpu.ops.short_conv', 'npairloss_tpu.ops.causal_attention')\n"
        "assert not [m for m in mods if m in sys.modules], sys.modules.keys()\n"
        "get_model('olmo_hybrid')\n"
        "assert all(m in sys.modules for m in mods)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]


def _dense_causal(q, k, v):
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("t,block", [(50, 16), (64, 16), (10, 512), (128, 32)])
def test_blocked_causal_attention_is_the_dense_softmax(t, block):
    """Blocks of queries against the key blocks at or before them with a
    running maximum and sum: the dense causal softmax to float32 rounding
    (scores of size ~10: 5e-5), T a multiple of the block or not, one
    block or many; and it differentiates (both loops are scans)."""
    from npairloss_tpu.ops.causal_attention import causal_attention

    q, k, v = (3.0 * jax.random.normal(key, (2, t, 3, 8))
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    got = jax.jit(causal_attention, static_argnames="block")(q, k, v, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense_causal(q, k, v)),
                               atol=5e-5, rtol=0)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    grads = jax.jit(jax.grad(loss(lambda *a: causal_attention(*a, block=block)),
                             argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(_dense_causal), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=0)
