"""The control of ``correct``: the plain reference put in the program's
place, computed in the nearest precision below the configuration's
(float8 for bfloat16), has to come out as NOT correct; the reference in
float32 in its own place reads nought."""

import bench_path  # noqa: F401  (repo root on sys.path)

from bench_drive import toy_cell
from benchmarks.harness import compare, run_serve, train_window, weights
from benchmarks.reference import retrieval


def test_training_control_fails_and_reference_passes():
    cell = toy_cell("googlenet_train")
    cfg, tr = cell.config, cell.traffic
    params = weights.make_params(cell.adapter, cfg, 2**31 + 5)
    host0 = weights.widened(params)
    images, labels = cell.adapter.train_batches(cfg, tr, 2**31 + 5)
    ref = train_window.reference_numbers(cell, host0, images, labels)
    again = train_window.reference_numbers(cell, host0, images, labels)
    low = train_window.reference_numbers(cell, host0, images, labels,
                                         quant=cfg["precision"]["control"])
    _, ok = compare.judge(compare.training_numbers(again, ref)[0], tr["limits"])
    assert ok
    rows, ok = compare.judge(compare.training_numbers(low, ref)[0], tr["limits"])
    assert not ok, rows


def test_serving_control_fails():
    cell = toy_cell("googlenet_serve_flat_sat")
    cfg, mix = cell.config, cell.traffic
    g, k = mix["gallery"], mix["engine"]["top_k"]
    params = weights.make_params(cell.adapter, cfg, 2**31 + 5)
    ctx = {"host_params": weights.widened(params),
           "pool": cell.adapter.query_pool(cfg, mix, 2**31 + 5),
           "gallery": weights.mixture_gallery(g["seed"], g["rows"],
                                              cfg["embedding_dim"], g["centres"])[0]}
    emb = run_serve.embed_pool(cell.adapter, params, ctx["pool"], len(ctx["pool"]),
                               quant=cfg["precision"]["control"])
    s, r = retrieval.exact_topk(emb, ctx["gallery"], k)

    # the lower precision's own ten, read as answers
    numbers = run_serve.serve_numbers(run_serve.as_answers(r, s), ctx, cell, k)
    numbers["refused"] = 0.0
    rows, ok = compare.judge(numbers, mix["limits"])
    assert not ok, rows
    assert numbers["score_gap"] > mix["limits"]["score_gap"]


def test_judge_fails_a_missing_or_nan_number():
    assert compare.judge({"a": 0.1}, {"a": 0.2, "b": 0.2})[1] is False
    assert compare.judge({"a": float("nan")}, {"a": 0.2})[1] is False
    assert compare.judge({"a": 0.1}, {"a": 0.2})[1] is True
