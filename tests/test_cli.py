"""CLI contract tests: the ``caffe train`` counterpart must never train
on data the user did not ask for — a missing/absent data source is a hard
error unless synthetic data was explicitly opted into (--synthetic)."""

import os

import pytest

from npairloss_tpu.cli import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _repo_cwd(monkeypatch):
    # The tiny solver references its net relative to the repo root, as
    # Caffe resolves net paths relative to the CWD.
    monkeypatch.chdir(REPO)


def test_train_without_source_fails_loudly():
    """The tiny net's MultibatchData has no `source`: training it without
    --synthetic must exit with an error, not silently fabricate data."""
    with pytest.raises(SystemExit, match="source|synthetic"):
        main([
            "train", "--solver", "examples/tiny_solver.prototxt",
            "--model", "mlp", "--max_iter", "2",
        ])


def test_train_missing_source_path_fails_loudly(tmp_path):
    """A typo'd source path is a hard error (VERDICT r1: the CLI used to
    silently 'succeed' on random clusters)."""
    net = tmp_path / "net.prototxt"
    net.write_text("""
name: "TinyMLP"
layer {
  name: "d" type: "MultibatchData" top: "d" top: "l"
  include { phase: TRAIN }
  transform_param { crop_size: 8 }
  multi_batch_data_param {
    batch_size: 16 identity_num_per_batch: 8 img_num_per_identity: 2
    source: "/nonexistent/list.txt"
  }
}
""")
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.01\nlr_policy: "fixed"\nmax_iter: 2\n'
        "display: 0\nsnapshot: 0\ntest_interval: 0\ntest_iter: 0\n"
    )
    with pytest.raises(SystemExit, match="does not exist"):
        main(["train", "--solver", str(solver), "--model", "mlp",
              "--max_iter", "2"])


def test_train_synthetic_opt_in_runs():
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "2", "--synthetic",
    ])
    assert rc == 0


def test_train_blockwise_engine_runs():
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "2", "--synthetic",
        "--engine", "blockwise",
    ])
    assert rc == 0


@pytest.mark.slow
def test_train_ring_engine_runs_single_device_mesh():
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "2", "--synthetic",
        "--engine", "ring", "--mesh", "1",
    ])
    assert rc == 0


def test_cli_test_command_blockwise_engine(capsys):
    rc = main([
        "test", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--synthetic", "--iterations", "1",
        "--engine", "blockwise",
    ])
    assert rc == 0
    import json

    m = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "retrieve_top1" in m


def test_cli_test_command(tmp_path, capsys):
    """`test` = caffe test counterpart: TEST phase metrics from a
    (fresh or restored) model, no training."""
    rc = main([
        "test", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--synthetic", "--iterations", "2",
    ])
    assert rc == 0
    import json

    out = capsys.readouterr().out.strip().splitlines()[-1]
    m = json.loads(out)
    assert "loss" in m and "retrieve_top1" in m
    assert all(abs(v) < 1e9 for v in m.values())


def test_cli_extract_command(tmp_path, capsys):
    """`extract` dumps eval-mode embeddings + labels as .npy."""
    out_prefix = str(tmp_path / "feat")
    rc = main([
        "extract", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--synthetic", "--batches", "2",
        "--phase", "TEST", "--out", out_prefix,
    ])
    assert rc == 0
    import json

    import numpy as np

    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    emb = np.load(rec["embeddings"])
    lab = np.load(rec["labels"])
    assert emb.shape[0] == lab.shape[0] > 0
    # L2Normalize head: unit-norm rows (the deployment contract)
    np.testing.assert_allclose(
        np.linalg.norm(emb, axis=1), 1.0, rtol=1e-4
    )


def test_train_weights_finetune_start(tmp_path):
    """--weights starts training from an externally-supplied params
    file (the caffemodel-migration finetune workflow).  The load is
    structure-enforced by Solver.load_params — a tree mismatch fails
    loudly, so rc 0 here means the marked params were accepted and
    loaded."""
    import flax.serialization
    import jax
    import jax.numpy as jnp
    import numpy as np

    from npairloss_tpu import NPairLossConfig
    from npairloss_tpu.models import get_model
    from npairloss_tpu.train import Solver, SolverConfig

    solver = Solver(
        get_model("mlp"),
        NPairLossConfig(),
        SolverConfig(base_lr=0.0, lr_policy="fixed", display=0, snapshot=0),
        input_shape=(8, 8, 3),
    )
    solver.init()
    rng = np.random.default_rng(9)
    marked = jax.tree_util.tree_map(
        lambda a: rng.standard_normal(a.shape).astype(np.float32),
        solver.state["params"],
    )
    wfile = tmp_path / "pre.msgpack"
    wfile.write_bytes(flax.serialization.msgpack_serialize(
        {"params": marked, "batch_stats": {}}
    ))

    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "1", "--synthetic",
        "--weights", str(wfile),
    ])
    assert rc == 0


def test_bench_is_not_a_subcommand(capsys):
    """The benchmark is `python3 benchmarks/run.py` (BENCHMARK.json's
    command); the CLI has no `bench` to forward to."""
    with pytest.raises(SystemExit) as e:
        main(["bench"])
    assert e.value.code == 2
    assert "invalid choice: 'bench'" in capsys.readouterr().err


def test_a_path_named_bench_keeps_the_arguments_after_it(
        tmp_path, monkeypatch, capsys):
    """An argv in which `bench` is a VALUE is parsed whole: the
    forwarding that once cut every argv at that word, on any
    subcommand, is gone with the subcommand."""
    import json
    import shutil

    monkeypatch.chdir(tmp_path)
    shutil.copy(os.path.join(REPO, "examples", "tiny_net.prototxt"),
                "bench")
    assert main(["parse", "bench", "--json"]) == 0
    assert isinstance(json.loads(capsys.readouterr().out), dict)


def test_cli_time_command(capsys):
    """`npairloss_tpu time --net X` — the `caffe time -model X` surface:
    no solver prototxt required, stage timings + derived deltas emitted
    as one JSON record."""
    import json

    argv = ["time", "--net", "examples/tiny_net.prototxt", "--model",
            "mlp", "--iterations", "2"]
    # A measuring command refuses a CPU it was not pointed at by name.
    assert main(argv) == 2
    assert capsys.readouterr().out == ""
    rc = main(["--platform", "cpu", *argv])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for key in ("trunk_forward_ms", "forward_ms", "loss_forward_ms",
                "forward_backward_ms", "backward_ms", "emb_per_sec"):
        assert key in rec, key
        assert rec[key] >= 0
    assert rec["device"].startswith("cpu:") and "fetch_floor_ms" not in rec
    assert rec["batch"] == 16  # tiny_net.prototxt: 8 ids x 2 imgs
    assert rec["iterations"] == 2


@pytest.mark.slow
def test_cli_time_forward_only_engines(capsys):
    """--forward-only skips the backward stage; the streaming engines
    must both time through the same entrypoint, and the emitted record
    must prove which engine/mesh actually ran (ring on an explicit
    2-device mesh — the multi-chip shard_map timing path; blockwise
    single-device by contract)."""
    import json

    for engine, extra, mesh_devices in (
        ("ring", ["--mesh", "2"], 2),
        ("blockwise", [], 1),
    ):
        rc = main([
            "--platform", "cpu",
            "time", "--net", "examples/tiny_net.prototxt", "--model",
            "mlp", "--iterations", "2", "--forward-only",
            "--engine", engine, *extra,
        ])
        assert rc == 0
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "forward_backward_ms" not in rec
        assert rec["forward_ms"] >= 0
        assert rec["engine"] == engine
        assert rec["mesh_devices"] == mesh_devices


def test_cli_device_query(capsys):
    """`device-query` — the `caffe device_query` surface: topology plus
    one record per device."""
    import json

    rc = main(["device-query"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["device_count"] >= 1
    assert len(rec["devices"]) == rec["device_count"]
    for d in rec["devices"]:
        assert "platform" in d and "device_kind" in d


def test_cli_train_log_json(tmp_path, capsys):
    """--log-json appends structured display/test events the Caffe text
    log only renders as prose."""
    import json

    path = tmp_path / "metrics.jsonl"
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "10", "--synthetic",
        "--log-json", str(path),
    ])
    assert rc == 0
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    events = {r["event"] for r in recs}
    assert "display" in events
    displays = [r for r in recs if r["event"] == "display"]
    assert all("loss_avg" in r and "iteration" in r for r in displays)
    assert displays[-1]["iteration"] == 10


def test_time_stage_bodies_resist_dce():
    """The timed stage programs must contain the work they claim to time:
    forward+backward FLOPs well above forward FLOPs (grad leaves all
    consumed), forward above trunk (loss+metrics consumed).  If an
    anchor regresses, XLA dead-code-eliminates the missing subgraph and
    these ratios collapse toward 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from npairloss_tpu.cli import _time_stage_bodies
    from npairloss_tpu.data import synthetic_identity_batches
    from npairloss_tpu.models import get_model
    from npairloss_tpu.ops.npair_loss import NPairLossConfig
    from npairloss_tpu.train import Solver, SolverConfig
    from npairloss_tpu.utils.profiling import cost_flops

    # Tiny trunk + larger batch/embedding so the loss+metrics subgraph
    # (O(N^2 D)) is a visible share of forward FLOPs.
    solver = Solver(
        get_model("mlp", hidden=(8,), embedding_dim=64),
        NPairLossConfig(),
        SolverConfig(display=0, snapshot=0),
        input_shape=(16,),
    )
    images, labels = next(synthetic_identity_batches(32, 16, 2, (16,)))
    solver.init(np.asarray(images[:2]))
    trunk, fwd, fb, init = _time_stage_bodies(solver, images, labels)

    def flops(body):
        lowered = jax.jit(
            lambda c: body(c, jnp.float32(0.0))
        ).lower(init)
        return cost_flops(lowered)

    f_trunk, f_fwd, f_fb = flops(trunk), flops(fwd), flops(fb)
    assert f_trunk and f_fwd and f_fb
    assert f_fwd > f_trunk * 1.2, (f_trunk, f_fwd)  # loss+metrics present
    assert f_fb > f_fwd * 1.7, (f_fwd, f_fb)        # full backward present


def test_train_caffe_solverstate_resume_conflict(tmp_path):
    """--caffe-solverstate and --resume are mutually exclusive snapshot
    sources; the conflict errors out before any restore runs."""
    f = tmp_path / "x.solverstate"
    f.write_bytes(b"")
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "1", "--synthetic",
        "--caffe-solverstate", str(f), "--resume", "/nonexistent",
    ])
    assert rc == 2


def test_train_caffe_solverstate_requires_weights(tmp_path):
    """A solverstate resume over random-init weights is a corrupt
    trajectory; the CLI demands the paired .caffemodel via --weights."""
    f = tmp_path / "x.solverstate"
    f.write_bytes(b"")
    rc = main([
        "train", "--solver", "examples/tiny_solver.prototxt",
        "--model", "mlp", "--max_iter", "1", "--synthetic",
        "--caffe-solverstate", str(f),
    ])
    assert rc == 2
