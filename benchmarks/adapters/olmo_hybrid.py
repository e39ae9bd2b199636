"""Adapter for the ``olmo_hybrid`` family: the program's token tower
(``npairloss_tpu/models/olmo_hybrid.py``) beside the plain reference
(``reference/olmo_hybrid.py``).  The two parameter layouts hold the same
arrays under other names: ``to_program`` / ``from_program`` move no
number and copy none, so the bfloat16 tree is on the device once.

An input is a DOCUMENT: a 1-D int32 row of token ids.  The pool's
lengths are the mix's (``doc_lengths``: [length, count] pairs, pinned by
the mix's ``canon_seed`` like the rest of its traffic: every seed serves
the same lengths); the ids come from ``--seed``.  Each document has its
own topic: a Zipf(1) over a random ``topic_vocab`` ids of the
vocabulary, in its own order, so that pooled embeddings differ from
document to document (a collapsed pool hides a precision fault).
"""

from __future__ import annotations

import numpy as np

from npairloss_tpu.serve.engine import EngineConfig

from benchmarks.reference import olmo_hybrid as ref

if "length_buckets" not in EngineConfig.__dataclass_fields__:
    # a checkout from before the token path: fail at once, before any
    # weight or gallery is made (the harness would fail minutes later)
    raise ImportError("the program in this checkout has no token engine "
                      "(EngineConfig.length_buckets): the olmo_hybrid family "
                      "cannot run on it")

embed = ref.embed
stages = ref.stages  # optional: the reference a block at a time (run_serve.embed_pool)
gated_delta_cost = ref.gated_delta_cost


def _held(cfg):
    """The reference writes some of the config as constants; hold the
    config to them."""
    if cfg["rms_norm_eps"] != ref.EPS or not cfg["linear_allow_neg_eigval"] \
            or cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("the olmo_hybrid reference is written for rms_norm_eps "
                         f"{ref.EPS}, negative eigenvalues, silu and no bias")
    # layer_types is THIS chip's stage; num_hidden_layers the whole model's
    if cfg["num_hidden_layers"] != len(cfg["layer_types"]) * cfg["pipeline_stages"]:
        raise ValueError("num_hidden_layers is not pipeline_stages x layer_types")
    return cfg


def input_shape(cfg):
    """What ``RetrievalServer`` keeps for a re-warm; a token engine warms
    from its own ``length_buckets`` and reads nothing of it."""
    return None


def warm_inputs(cfg, mix):
    """One ``engine.warmup`` call: it warms every (rows, length) pair
    within the mix's token budget by itself."""
    return [None]


def doc_lengths(mix):
    """The pool's lengths in key order: the mix's [length, count] pairs,
    dealt by its ``canon_seed`` (the same for every ``--seed``)."""
    lengths = [int(n) for n, count in mix["doc_lengths"] for _ in range(count)]
    if len(lengths) != mix["pool_images"]:
        raise ValueError(f"doc_lengths holds {len(lengths)} documents, "
                         f"pool_images says {mix['pool_images']}")
    np.random.default_rng(mix.get("canon_seed", 0)).shuffle(lengths)
    return lengths


def query_pool(cfg, mix, seed):
    """The serving pool: one int32 document for each key."""
    vocab, width = cfg["vocab_size"], min(mix["topic_vocab"], cfg["vocab_size"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    weights = 1.0 / np.arange(1, width + 1)
    weights /= weights.sum()
    pool = []
    for n in doc_lengths(mix):
        topic = rng.choice(vocab, size=width, replace=False)
        pool.append(topic[rng.choice(width, size=n, p=weights)].astype(np.int32))
    return pool


def train_batches(cfg, mix, seed):
    raise NotImplementedError(
        "no training cell: the trainer has no token dataset and no scan "
        "backward at a batch that fits (PERF.md §7)")


def forward_flops(cfg, x=None):
    """Operations ONE document's forward pass requires, from its length."""
    if x is None:
        raise ValueError("a document's cost depends on its length: pass it")
    return ref.forward_flops(cfg, x)


def shapes(cfg):
    return ref.param_shapes(_held(cfg))


def init_scales(cfg):
    """normal(0, 0.02) matrices and table, norm weights 1; the filters
    U(-0.5, 0.5) (a depthwise Conv1d's default at 4 taps); ``A_log`` and
    ``dt_bias`` start as U(-1, 1) and ``post_init`` maps them."""
    kinds = {"A_log": ("uniform", 1.0), "dt_bias": ("uniform", 1.0)}
    out = {}
    for name, leaves in shapes(cfg).items():
        out[name] = {}
        for leaf, shape in leaves.items():
            if leaf in kinds:
                out[name][leaf] = kinds[leaf]
            elif leaf.startswith("conv_"):
                out[name][leaf] = ("uniform", 1.0 / float(np.sqrt(shape[0])))
            elif len(shape) == 1 or leaf.endswith("_norm"):
                out[name][leaf] = ("ones", 1.0)
            else:
                out[name][leaf] = ("normal", 0.02)
    return out


def post_init(params):
    """The Gated DeltaNet defaults from the U(-1, 1) draws: ``A_log`` =
    log U(1, 16); ``dt_bias`` = inverse softplus of dt, log-uniform in
    [0.001, 0.1]."""
    import jax.numpy as jnp

    for name, leaves in params.items():
        if name.endswith("/gdn"):
            leaves["A_log"] = jnp.log(8.5 + 7.5 * leaves["A_log"])
            dt = jnp.exp(jnp.log(1e-3) + (leaves["dt_bias"] + 1.0) / 2.0
                         * (jnp.log(1e-1) - jnp.log(1e-3)))
            leaves["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    return params


def build_model(cfg):
    from npairloss_tpu.models import get_model

    _held(cfg)
    return get_model(
        cfg["program"]["model"], policy=cfg["program"]["precision"],
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        intermediate=cfg["intermediate_size"], layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"], linear_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        conv_taps=cfg["linear_conv_kernel_dim"], eps=cfg["rms_norm_eps"],
        chunk=cfg["program"]["chunk"])


def to_program(params, xp=np):
    """Plain layout -> the program's flax tree (the same arrays)."""
    out = {"table": params["embed"]["table"],
           "final_norm": params["final_norm"]["weight"]}
    for name, leaves in params.items():
        block, _, part = name.partition("/")
        if part == "norms":
            out.setdefault(block, {}).update(
                mixer_norm=leaves["mixer"], ffn_norm=leaves["ffn"])
        elif part:
            out.setdefault(block, {})[part] = dict(leaves)
    return out


def from_program(tree, xp=np):
    """The program's flax tree -> plain layout."""
    out = {"embed": {"table": tree["table"]},
           "final_norm": {"weight": tree["final_norm"]}}
    for block, parts in tree.items():
        if not block.startswith("block_"):
            continue
        out[f"{block}/norms"] = {"mixer": parts["mixer_norm"], "ffn": parts["ffn_norm"]}
        for part in ("gdn", "attn", "ffn"):
            if part in parts:
                out[f"{block}/{part}"] = dict(parts[part])
    return out
