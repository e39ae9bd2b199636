"""The ``olmo_hybrid`` family in the benchmark: its counts by hand, its
documents, its cell at rehearsal size (``correct`` true; the float8
control fails), the two readers it brings, and its entries in the
manifest beside the accepted ones, none of which changed."""

import bench_path  # noqa: F401  (repo root on sys.path)

import hashlib
import json
import os

import numpy as np
import pytest

from bench_drive import drive, grown_manifest, toy_cell
from benchmarks.adapters import olmo_hybrid as adapter
from benchmarks.harness import compare, loader, run_serve, weights
from benchmarks.reference import olmo_hybrid as ref
from benchmarks.reference import retrieval
from npairloss_tpu.obs import tracing

CELL = "olmo_hybrid_serve_docs_backlog"
PUBLISHED = {  # allenai/Olmo-Hybrid-7B config.json
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "model_type": "olmo_hybrid"}


@pytest.fixture(scope="module")
def cfg():
    return json.load(open(os.path.join(loader.BENCH_DIR, "configs", "olmo_hybrid_7b_l8.json")))


def test_the_configuration_keeps_every_published_width(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == period * 2 and cfg["reduced"] == ["layer_types"]
    assert cfg["pipeline_stages"] * len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    assert cfg["embedding_dim"] == cfg["hidden_size"]
    assert cfg["precision"]["params"] == "bfloat16" and cfg["deployment"]
    said = " ".join(cfg["assumed"] + cfg["reduced_how"])
    for item in ("reordered norm", "QK-norm", "rotary", "no bias", "pooling", "head not built",
                 "normal(0, 0.02)"):
        assert item in said, item


def test_counts_by_hand(cfg):
    d, ff, h = 3840, 11008, 30
    linear = 2 * d * h * 96 + 2 * d * h * 192 + h * 192 * d + 2 * d * h  # q k, v g, o, a b
    full, ffn = 4 * d * d, 3 * d * ff
    assert (linear, full, ffn) == (88_704_000, 58_982_400, 126_812_160)
    assert ref.matrix_params(cfg) == 6 * linear + 2 * full + 8 * ffn == 1_664_686_080
    # one token of one linear layer: the decay of S and three products with it
    assert ref.recurrence_flops_per_token(cfg) == 7 * 96 * 192 * 30 == 3_870_720
    t = 8192
    assert ref.forward_flops(cfg, range(t)) == (
        2 * 1_664_686_080 * t + 2 * (4 * t * t // 2) * d + 6 * 3_870_720 * t
    ) == 28_495_262_515_200
    assert adapter.forward_flops(cfg, np.zeros(512, np.int32)) == 1_720_555_929_600
    with pytest.raises(ValueError):
        adapter.forward_flops(cfg)  # a document's cost is its length's
    # q, k (96 each), v, o (192 each), g, beta: 578 numbers a head a token, 2 bytes each
    assert adapter.gated_delta_cost(cfg, t) == (6 * 3_870_720 * t, 6 * 30 * 578 * 2 * t) \
        == (190_253_629_440, 1_704_591_360)
    # the whole tree: what the chip holds in bfloat16 (4.10 GB)
    total = sum(int(np.prod(s)) for leaves in ref.param_shapes(cfg).values()
                for s in leaves.values())
    assert total == 2_050_396_392


def test_documents_keep_their_lengths_and_change_their_ids_with_the_seed(cfg):
    mix = json.load(open(os.path.join(loader.BENCH_DIR, "traffic", "docs_poisson_1p5knee.json")))
    lengths = adapter.doc_lengths(mix)
    assert len(lengths) == mix["pool_images"] == 48 and sum(lengths) == 111_024
    assert sorted(set(lengths)) == [512, 1024, 1900, 3072, 4096, 6000, 8192]
    buckets = mix["engine"]["length_buckets"]
    padded = sum(min(b for b in buckets if b >= n) for n in lengths)
    assert padded == 124_928 and max(lengths) == mix["engine"]["token_budget"]
    small = dict(mix, **mix["rehearsal"])
    a = adapter.query_pool(cfg, small, 2**31 + 9)
    b = adapter.query_pool(cfg, small, 2**31 + 10)
    again = adapter.query_pool(cfg, small, 2**31 + 9)
    assert [len(x) for x in a] == [len(x) for x in b] == adapter.doc_lengths(small)
    assert all(x.dtype == np.int32 and x.ndim == 1 for x in a)
    assert all(0 <= x.min() and x.max() < cfg["vocab_size"] for x in a)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))
    assert not any(np.array_equal(x, y) for x, y in zip(a, b))
    with pytest.raises(ValueError):
        adapter.doc_lengths(dict(small, pool_images=5))


def test_the_adapter_refuses_a_config_the_reference_is_not_written_for(cfg):
    with pytest.raises(ValueError, match="reference is written for"):
        adapter.shapes(dict(cfg, rms_norm_eps=1e-5))
    with pytest.raises(ValueError, match="pipeline_stages"):
        adapter.shapes(dict(cfg, num_hidden_layers=8))
    with pytest.raises(ValueError, match="grouped keys"):
        adapter.shapes(dict(cfg, num_key_value_heads=6))
    with pytest.raises(NotImplementedError):
        adapter.train_batches(cfg, {}, 1)


def test_the_cell_runs_at_rehearsal_size_and_is_correct(capsys):
    line = drive(CELL, capsys, seed=2**31 + 5, seconds=1.0)
    assert list(line)[-1] == "checks" and line["attempted"] == 20
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["checks"]) == {"bad_answers", "refused", "score_gap", "rank_gap"}


def test_the_float8_control_fails_a_limit():
    cell = toy_cell(CELL)
    cfg, mix = cell.config, cell.traffic
    g, k = mix["gallery"], mix["engine"]["top_k"]
    params = weights.make_params(cell.adapter, cfg, 2**31 + 5)
    ctx = {"host_params": weights.widened(params),
           "pool": cell.adapter.query_pool(cfg, mix, 2**31 + 5),
           "gallery": weights.mixture_gallery(g["seed"], g["rows"],
                                              cfg["embedding_dim"], g["centres"])[0]}
    emb = run_serve.embed_pool(cell.adapter, ctx["host_params"], ctx["pool"],
                               mix["reference_block"], quant=cfg["precision"]["control"])
    s, r = retrieval.exact_topk(emb, ctx["gallery"], k)
    numbers = run_serve.serve_numbers(run_serve.as_answers(r, s), ctx, cell, k)
    numbers["refused"] = 0.0
    rows, ok = compare.judge(numbers, mix["limits"])
    assert not ok, rows
    assert numbers["score_gap"] > mix["limits"]["score_gap"]
    assert numbers["rank_gap"] > mix["limits"]["rank_gap"]


@pytest.fixture
def tracer():
    tr = tracing.SpanTracer(max_events=8, clock=lambda: 100.0)
    prev = tracing.install(tr)
    yield tr
    tracing.install(prev)


def _put(tr, name, start_s, end_s, **args):
    tr._append(tr.complete_event(name, tr.to_us(start_s), tr.to_us(end_s), **args))


WINDOW = {"t0": 101.0, "t1": 103.0}


def test_pad_share_reads_the_encode_spans_of_the_window(tracer):
    enc = "serve/encode"
    _put(tracer, enc, 100.2, 100.9, tokens=64, padded_tokens=64)      # warm-up
    _put(tracer, enc, 101.1, 101.2, tokens=1900, padded_tokens=2048, rows=1)
    _put(tracer, enc, 102.0, 102.4, tokens=900, padded_tokens=2048, rows=3)
    _put(tracer, enc, 102.9, 103.3, tokens=512, padded_tokens=512)    # the next window's
    _put(tracer, "serve/topk", 102.5, 102.6, tokens=5, padded_tokens=50)
    read = loader.reader("pad_share")
    ctx = {"serve": {"window": WINDOW}}
    assert read(ctx, span=enc) == pytest.approx((4096 - 2800) / 4096)
    assert read({"serve": {}}, span=enc) is None and read({}, span=enc) is None
    tracing.install(None)
    assert read(ctx, span=enc) is None


def test_pad_share_reads_nothing_of_a_float_engine_or_a_dropped_tracer(tracer):
    read = loader.reader("pad_share")
    ctx = {"serve": {"window": WINDOW}}
    _put(tracer, "serve/encode", 101.1, 101.2, rows=3, bucket=8)  # no token arguments
    assert read(ctx, span="serve/encode") is None
    for i in range(8):
        _put(tracer, "serve/encode", 101.3, 101.4, tokens=8, padded_tokens=16)
    assert tracer.dropped == 1 and read(ctx, span="serve/encode") is None


ROOT = "serve/encode/OlmoHybridEmbedding/"
BY_OP = {
    ROOT + "block_0/gdn/scan/while/body/fusion.3": 0.004,
    ROOT + "block_1/gdn/scan/triangular_solve": 0.002,
    ROOT + "block_6/gdn/scan/while/body/dot_general.7": 0.002,
    ROOT + "block_0/gdn/proj/fusion.1": 0.050,
    ROOT + "block_3/attn/core/while/body/fusion.9": 0.030,
    ROOT + "block_0/ffn/fusion.2": 0.100,
    "serve/score/fusion": 0.010,
}


def test_gdn_scan_roofline_is_the_least_time_over_the_scans_time(tracer, cfg):
    spec = loader.metric_spec("gdn_scan_roofline")
    read = loader.reader(spec["reader"])
    _put(tracer, "serve/encode", 101.1, 101.2, tokens=1900, padded_tokens=2048)
    _put(tracer, "serve/encode", 102.0, 102.4, tokens=2196, padded_tokens=4096)
    _put(tracer, "serve/encode", 100.2, 100.9, tokens=8192, padded_tokens=8192)  # warm-up
    cell = loader.Cell(CELL)
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"cell": cell, "peaks": peaks, "trace": {"by_op": BY_OP},
           "traced": {"window": WINDOW, "batches": 2}}
    flops, nbytes = adapter.gated_delta_cost(cfg, 4096)
    assert nbytes / 819e9 > flops / 197e12  # its floor is bytes
    want = 100.0 * (nbytes / 819e9) / 0.008
    assert read(ctx, **spec["args"]) == pytest.approx(want)
    assert 0 < want < 100
    # nothing, never 0: no trace, no peaks (a rehearsal), no scan region, no tokens
    assert read(dict(ctx, trace=None), **spec["args"]) is None
    assert read(dict(ctx, peaks=None), **spec["args"]) is None
    assert read(dict(ctx, trace={"by_op": {"serve/score/fusion": 0.01}}), **spec["args"]) is None
    assert read(dict(ctx, traced={"window": {"t0": 1.0, "t1": 2.0}}), **spec["args"]) is None
    # and the region metrics split the same table by layer kind
    region = loader.reader("region_ms")
    for name, seconds in (("gdn_scan_ms_batch", 0.008), ("gdn_proj_conv_ms_batch", 0.050),
                          ("full_attn_core_ms_batch", 0.030), ("ffn_ms_batch", 0.100)):
        args = loader.metric_spec(name)["args"]
        assert region(ctx, **args) == pytest.approx(1e3 * seconds / 2), name


# What PR 29 added and what was accepted before it, pinned BY NAME and in
# every field; nothing that FOLLOWS them is pinned: a later PR appends
# configurations, cells, metrics and its cells' names on an accepted
# metric's ``workloads`` list, and edits no entry that is there.
CONFIGS = ["googlenet_v1", "olmo_hybrid_7b_l8"]
CELLS = ["googlenet_train", "googlenet_serve_ivf_rate", "googlenet_serve_flat_sat", CELL]
END_TO_END = ["train_emb_per_s_chip", "serve_answers_per_s", "setup_s"]
BEFORE = 37  # per-layer metrics accepted before PR 29
METRICS = ["docs_step_mfu", "docs_busy_mfu", "docs_idle_share", "docs_compiles_in_window",
           "docs_batch_rows_mean", "docs_encode_ms_batch", "docs_search_ms_batch",
           "gdn_scan_ms_batch", "gdn_proj_conv_ms_batch", "full_attn_core_ms_batch",
           "ffn_ms_batch", "docs_encode_host_ms_batch", "docs_dispatch_host_ms_batch",
           "docs_pad_share", "gdn_scan_roofline"]
# the digest of ``accepted_part``: the manifest of commit 84369ac (PR 32)
# with the bound of serve_answers_per_s at 0.095 (PR 34, this commit)
ACCEPTED = "b1f589a1368ff8bc523e2637353826d8f804d8685d57dd7c2a68016fee51779a"


def accepted_part(man):
    """The accepted entries of ``man`` alone, each ``workloads`` list cut
    to the accepted cells (a later cell's name on it is not a change)."""
    def cut(m):
        return dict(m, workloads=[w for w in m["workloads"] if w in CELLS]) \
            if "workloads" in m else m

    return {"command": man["command"], "paths": man["paths"],
            "run_seconds": man["run_seconds"],
            "configs": man["configs"][:len(CONFIGS)],
            "workloads": man["workloads"][:len(CELLS)],
            "end_to_end": [cut(m) for m in man["end_to_end"][:len(END_TO_END)]],
            "per_layer": [cut(m) for m in man["per_layer"][:BEFORE + len(METRICS)]]}


def check_pin(man):
    part = accepted_part(man)
    assert [c["name"] for c in part["configs"]] == CONFIGS
    assert [w["name"] for w in part["workloads"]] == CELLS
    assert [m["name"] for m in part["end_to_end"]] == END_TO_END
    assert [m["name"] for m in part["per_layer"]][BEFORE:] == METRICS
    for m in part["per_layer"][BEFORE:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_answers_per_s"
    digest = hashlib.sha256(json.dumps(part, sort_keys=True).encode()).hexdigest()
    assert digest == ACCEPTED, digest


def test_the_manifest_keeps_the_family_and_no_accepted_entry_changed():
    man = loader.manifest()
    check_pin(man)
    roof = {m["name"]: m for m in man["per_layer"]}["gdn_scan_roofline"]
    assert roof["unit"] == "%" and roof["source"] == "device_trace"
    cell = loader.Cell(CELL, man)
    assert {m["name"] for m in cell.end_to_end()} == {"serve_answers_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} >= set(METRICS)
    mix = cell.traffic
    assert mix["loop"] == "open" and mix["zipf_s"] == 0 and mix["reference_block"] == 2
    assert mix["rate_qps"] == pytest.approx(mix["knee_factor"] * mix["knee_qps"], rel=0.02)
    assert mix["knee_factor"] == 1.5 and mix["sweep"] and mix["sweep_date"]


def test_the_pin_holds_nothing_that_follows_the_accepted_entries():
    """The scratch family's entries appended (a third configuration, two
    more cells, a per-layer metric, longer end-to-end lists), a fourth
    end-to-end metric and a later cell on an accepted per-layer metric's
    list: the pin passes."""
    man, entries = grown_manifest()
    assert len(man["configs"]) == 3 and len(man["workloads"]) == len(CELLS) + 2
    assert man["per_layer"][-1]["name"] == entries["per_layer"][0]["name"]
    lists = {m["name"]: m["workloads"] for m in man["end_to_end"] if "workloads" in m}
    assert lists["serve_answers_per_s"][-1] == "scratch_serve"
    man["end_to_end"].append({"name": "scratch_p95_ms", "unit": "ms", "better": "lower",
                              "bound": 0.05, "source": "host_clock",
                              "workloads": ["scratch_serve"]})
    man["per_layer"][0]["workloads"] = man["per_layer"][0]["workloads"] + ["scratch_serve"]
    check_pin(man)


def _set(path, value):
    def change(man):
        at = man
        for key in path[:-1]:
            at = at[key]
        at[path[-1]] = value(at[path[-1]]) if callable(value) else value
    return change


@pytest.mark.parametrize("change", [
    pytest.param(_set(("per_layer", 3, "unit"), "us"), id="a-unit"),
    pytest.param(_set(("per_layer", BEFORE + 2, "moves"), "setup_s"), id="a-moves"),
    pytest.param(_set(("per_layer", BEFORE - 1, "workloads"), lambda w: w[:-1]),
                 id="a-per-layer-list-loses-a-cell"),
    pytest.param(_set(("end_to_end", 1, "workloads"), lambda w: [x for x in w if x != CELL]),
                 id="an-end-to-end-list-loses-a-cell"),
    pytest.param(_set(("configs", 1, "reduced"), ["layer_types", "vocab_size"]),
                 id="a-configs-reduced"),
    pytest.param(_set(("end_to_end", 1, "bound"), 0.05), id="a-bound"),
    pytest.param(_set(("workloads", 2, "traffic"), "closed_16"), id="a-cells-traffic"),
    pytest.param(lambda man: man["per_layer"].insert(BEFORE, man["per_layer"].pop()),
                 id="an-entry-put-among-the-accepted"),
])
def test_the_pin_fails_when_a_field_of_an_accepted_entry_changes(change):
    man, _ = grown_manifest()
    check_pin(man)
    change(man)
    with pytest.raises(AssertionError):
        check_pin(man)
