"""The seam between the harness and a configuration's adapter, where the
program cannot exercise it yet: requests of different lengths cost what
each requires, the reference embeds a ragged pool group by group, the
weights come in groups and in the configuration's type, and a mix's
``engine`` / ``batcher`` keys all reach the program's dataclasses."""

import bench_path  # noqa: F401  (repo root on sys.path)

import hashlib
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_drive import toy_cell
from benchmarks.harness import loader, run_serve, serve_window, weights

ASKED = ("input_shape", "warm_inputs", "query_pool", "train_batches", "forward_flops",
         "embed", "shapes", "init_scales", "post_init", "build_model",
         "to_program", "from_program")
# googlenet_v1's tree from the seed 2**31 + 5 / 3, taken from the one-call
# make_params of the parent (600e6f7) on this CPU
PINNED = {2**31 + 5: "f63648393efd5087e4f96a22238912310e7738473db2498c25515d582378811b",
          3: "e88af996daa055c4d45759e2cff7337598dd00155accb4d54fd8abccfd2a94d6"}


def tree_digest(tree):
    h = hashlib.sha256()
    for name in sorted(tree):
        for leaf in sorted(tree[name]):
            a = np.ascontiguousarray(np.asarray(tree[name][leaf]))
            h.update(f"{name}.{leaf}:{a.dtype}:{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", [c["name"] for c in loader.manifest()["configs"]])
def test_the_adapter_answers_everything_the_harness_asks(config):
    cell = next(w for w in loader.manifest()["workloads"] if w["config"] == config)
    adapter = loader.Cell(cell["name"]).adapter
    assert [f for f in ASKED if not callable(getattr(adapter, f, None))] == []


def test_the_harness_names_no_family_and_no_input_kind():
    bound = re.compile(r'image_size|num_channels|image_pool|"family"\] ==')
    b, hits = loader.BENCH_DIR, []
    files = [os.path.join(b, "run.py"), os.path.join(b, "calibrate.py")] + [
        os.path.join(b, d, f) for d in ("harness", "readers")
        for f in sorted(os.listdir(os.path.join(b, d))) if f.endswith(".py")]
    for path in files:
        hits += [(path, line) for line in open(path) if bound.search(line)]
    assert hits == []


def _serve_cell(forward_flops):
    mix = {"gallery": {"index": "flat", "rows": 100, "clusters": 4},
           "engine": {"top_k": 2, "probes": 1}}
    return types.SimpleNamespace(
        traffic=mix, config={"embedding_dim": 8},
        adapter=types.SimpleNamespace(forward_flops=forward_flops))


def test_tally_sums_what_each_answered_request_own_input_requires():
    """Keys 0 and 1 map to inputs of 4 and 9 tokens, costing 1,000 a token
    squared; the failed request costs nothing; one evaluation a key."""
    calls = []

    def flops(cfg, x=None):
        calls.append(x.shape)
        return 1000 * x.shape[0] ** 2

    pool = [np.zeros((4,), np.int32), np.zeros((9,), np.int32)]
    keys = [0, 1, 1, 0, 1, 1]
    ledger = serve_window.Ledger(len(keys))
    ledger.key = keys
    ledger.done = [1.0] * len(keys)
    ok = {"neighbors": [{"row": 0, "score": 1.0}, {"row": 1, "score": 0.5}]}
    ledger.answer = [ok] * 5 + [{"error": "boom"}]
    out = run_serve.tally(_serve_cell(flops), ledger, {"t1": 2.0}, 5, None, pool)
    assert out["rows"] == 5
    assert out["required_flops"] - out["search_flops"] == 2 * 16_000 + 3 * 81_000
    assert sorted(calls) == [(4,), (9,)]


def test_the_reference_embeds_a_ragged_pool_group_by_group_in_key_order():
    traced = []

    def embed(params, x, quant=None):
        traced.append(x.shape)  # once a compiled shape
        return jnp.stack([x.sum(-1) * params, jnp.full(x.shape[:1], x.shape[1], jnp.float32)], -1)

    lengths = [3, 5, 3, 5, 5, 3, 3]
    pool = [np.full((n,), i + 1, np.float32) for i, n in enumerate(lengths)]
    pool.append(np.full((3,), 9, np.int32))  # another dtype is another group
    emb = run_serve.embed_pool(types.SimpleNamespace(embed=embed), jnp.float32(2.0), pool, 2)
    assert emb.shape == (8, 2)
    np.testing.assert_array_equal(emb[:, 0], [2.0 * x.sum() for x in pool])
    np.testing.assert_array_equal(emb[:, 1], lengths + [3])
    assert sorted(traced) == [(1, 3), (1, 5), (2, 3), (2, 5)]


@pytest.mark.parametrize("group_bytes", [2 ** 30, 2 ** 21])
@pytest.mark.parametrize("seed", sorted(PINNED))
def test_make_params_is_bit_equal_to_the_one_call_tree_however_grouped(
        monkeypatch, seed, group_bytes):
    monkeypatch.setattr(weights, "GROUP_BYTES", group_bytes)
    cell = loader.Cell("googlenet_train")
    calls = []

    def post_init(group):
        calls.append(sorted(group))
        return cell.adapter.post_init(group)

    adapter = types.SimpleNamespace(shapes=cell.adapter.shapes, post_init=post_init,
                                    init_scales=cell.adapter.init_scales)
    tree = weights.make_params(adapter, cell.config, seed)
    assert tree_digest(tree) == PINNED[seed]
    # whole layers, in sorted order, each in one call; 28 MB stay ONE call
    assert [n for group in calls for n in group] == sorted(tree)
    assert (len(calls) == 1) == (group_bytes == 2 ** 30)


def test_make_params_honours_precision_params_and_the_reference_reads_them_widened(monkeypatch):
    cell = loader.Cell("googlenet_train")
    full = weights.make_params(cell.adapter, cell.config, 3)
    cfg = dict(cell.config, precision=dict(cell.config["precision"], params="bfloat16"))
    monkeypatch.setattr(weights, "GROUP_BYTES", 2 ** 22)
    half = weights.make_params(cell.adapter, cfg, 3)
    wide = weights.widened(half)
    for name, leaves in full.items():
        for leaf, v in leaves.items():
            assert half[name][leaf].dtype == jnp.bfloat16 and wide[name][leaf].dtype == np.float32
            # the cast fuses into the group's program: a value may round
            # from a float32 one ulp off the uncast call's (seen: 1 in 67,584)
            want = np.asarray(v.astype(jnp.bfloat16), np.float32)
            np.testing.assert_allclose(wide[name][leaf], want, rtol=2.0 ** -7, atol=1e-7)
            assert np.mean(wide[name][leaf] != want) < 1e-3


@pytest.mark.parametrize("cell", ["googlenet_serve_ivf_rate", "googlenet_serve_flat_sat"])
def test_every_engine_and_batcher_key_of_the_mix_reaches_the_program(cell):
    mix = toy_cell(cell).traffic
    engine, batcher = serve_window.engine_config(mix["engine"]), serve_window.batcher_config(mix)
    for key, value in mix["engine"].items():
        assert getattr(engine, key) == (tuple(value) if isinstance(value, list) else value)
    for key, value in mix["batcher"].items():
        assert getattr(batcher, key) == value
    assert batcher.max_batch == mix["engine"]["buckets"][-1]


@pytest.mark.parametrize("which", ["engine", "batcher"])
def test_a_key_the_dataclass_lacks_fails_loudly(which):
    mix = toy_cell("googlenet_serve_flat_sat").traffic
    mix[which] = dict(mix[which], token_budget=16384)
    with pytest.raises(TypeError, match="token_budget"):
        serve_window.engine_config(mix["engine"])
        serve_window.batcher_config(mix)


@pytest.fixture
def scratch_mlp(monkeypatch):
    """The scratch family's adapter and reference, imported from its files."""
    import benchmarks.adapters
    import benchmarks.reference
    from bench_drive import SCRATCH

    for pkg in (benchmarks.adapters, benchmarks.reference):
        monkeypatch.setattr(pkg, "__path__", pkg.__path__ + [
            os.path.join(SCRATCH, pkg.__name__.rpartition(".")[2])])
    import importlib
    adapter = importlib.import_module("benchmarks.adapters.scratch_mlp")
    yield adapter
    for name in ("benchmarks.adapters.scratch_mlp", "benchmarks.reference.scratch_mlp"):
        sys.modules.pop(name, None)


def _scratch_tree(adapter, hidden=(32,)):
    cfg = {"input_dim": 24, "hidden": list(hidden), "embedding_dim": 16}
    rng = np.random.default_rng(11)
    tree = {name: {leaf: rng.normal(0, 0.3, shape).astype(np.float32)
                   for leaf, shape in leaves.items()}
            for name, leaves in adapter.shapes(cfg).items()}
    pool = [rng.normal(size=shape).astype(np.float32)
            for shape in [(24,)] * 5 + [(4, 6)] * 3 + [(2, 12)]]  # ragged: three groups
    return tree, pool


@pytest.mark.parametrize("quant", [None, "float8_e4m3fn", "bfloat16"])
def test_a_streamed_reference_equals_the_whole_tree_embed(scratch_mlp, quant):
    """Two stages (a layer each) over a ragged pool in blocks of two,
    against the one-stage path an adapter without ``stages`` takes."""
    tree, pool = _scratch_tree(scratch_mlp)
    assert len(scratch_mlp.stages(tree)) == 2
    whole = types.SimpleNamespace(embed=scratch_mlp.embed)
    want = run_serve.embed_pool(whole, tree, pool, 2, quant=quant)
    got = run_serve.embed_pool(scratch_mlp, tree, pool, 2, quant=quant)
    assert got.shape == want.shape == (9, 16)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    if quant:  # and the control is a different embedding, stage by stage too
        plain = run_serve.embed_pool(scratch_mlp, tree, pool, 2)
        assert np.max(np.abs(got - plain)) > 1e-4


def test_a_stage_is_freed_before_the_next_loads(scratch_mlp):
    """A tree larger than the device budget (here: more than any two of
    its four stages together) is checked stage by stage: when the loop
    asks for the next stage, no earlier stage's weights are alive."""
    tree, pool = _scratch_tree(scratch_mlp, hidden=(256, 256, 256))
    sizes = [sum(v.nbytes for v in leaves.values()) for leaves in tree.values()]
    budget = max(sizes) + 16384  # one stage and the pool's activations
    assert sum(sizes) > 2 * budget
    seen = []

    def stages(host_params):  # the adapter's own, looking at the device between stages
        for sub, fn in scratch_mlp.stages(host_params):
            seen.append(sum(a.nbytes for a in jax.live_arrays()))
            yield sub, fn

    watched = types.SimpleNamespace(embed=scratch_mlp.embed, stages=stages)
    base = sum(a.nbytes for a in jax.live_arrays())
    emb = run_serve.embed_pool(watched, tree, pool, 2)
    assert len(seen) == 4 and emb.shape == (9, 16)
    # no earlier stage's weights are alive (on the CPU the waiting
    # activations, 9 rows x 256 floats, are live arrays too)
    assert max(seen) - base <= 9 * 256 * 4 + 1024 < min(sizes)
    assert sum(a.nbytes for a in jax.live_arrays()) - base < min(sizes)
    whole = types.SimpleNamespace(embed=scratch_mlp.embed)
    np.testing.assert_allclose(emb, run_serve.embed_pool(whole, tree, pool, 2), atol=1e-6)


def test_the_token_tower_streams_a_block_a_stage():
    """``olmo_hybrid``'s stages: the table, one block each (all through ONE
    function, so a kind of layer compiles once a shape), the final norm
    with the pooling; every leaf in exactly one stage; at rehearsal size
    the streamed embedding is the whole tree's."""
    cell = toy_cell("olmo_hybrid_serve_docs_backlog")
    cfg, mix = cell.config, cell.traffic
    tree = weights.widened(weights.make_params(cell.adapter, cfg, 5))
    stages = cell.adapter.stages(tree)
    assert len(stages) == len(cfg["layer_types"]) + 2
    assert len({fn for _, fn in stages[1:-1]}) == 1 and "table" in stages[0][0]
    count = lambda t: len(jax.tree_util.tree_leaves(t))
    assert sum(count(sub) for sub, _ in stages) == count(tree)
    pool = cell.adapter.query_pool(cfg, mix, 5)
    whole = types.SimpleNamespace(embed=cell.adapter.embed)
    got = run_serve.embed_pool(cell.adapter, tree, pool, mix["reference_block"])
    want = run_serve.embed_pool(whole, tree, pool, mix["reference_block"])
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
