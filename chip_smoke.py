#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the two programs this repo IS, through the entry points a user
would call, at the full width of the flagship model, one leg after
another (a chip belongs to one process at a time — this parent never
imports jax, every leg is a ``python -m npairloss_tpu`` child):

  train         flagship recipe (examples/flagship*.prototxt): GoogLeNet
                ``--model flagship --precision mxu``, batch 120 = 60 ids
                x 2 at 224x224, REFERENCE_CONFIG mining, 10 steps on
                ``--synthetic`` data with ``--health-metrics``, ending
                in a committed snapshot
  index         ``index`` over a seeded 1,000,000 x 128 gallery, flat
                and IVF (1,024 clusters)
  serve_flat    ``serve`` answers gallery-row queries (exact scan)
  serve_ivf     the same queries, 32 probes, ``--probe-impl auto``
                (the fused Pallas kernel on a TPU)
  serve_ivf_all probes >= clusters: must equal the flat answers
  serve_snap    ``serve --snapshot`` on the trainer's snapshot: raw
                inputs encode through the restored trunk, then scan

Every serve leg runs under ``NPAIRLOSS_SERVE_COMPILE_GUARD=strict`` and
every leg under ``NPAIRLOSS_PALLAS_INTERPRET=forbid`` and ``--platform
tpu`` (no chip -> the first leg dies at start-up, nothing is printed as
a result).  Uses whatever devices the machine has: one chip runs
mesh-less, several get the CLI's default mesh.

The last stdout line on success is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only if every leg passed on a TPU.  ``--cpu-rehearsal``
runs the same legs at a tiny size on the CPU (stamped ``platform:
cpu``) to debug the script itself; it is never what happens when no
chip is found.
"""

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SNAPSHOT_FORMAT = "npairloss-snapshot-v1"
LEG_TIMEOUT_S = 900
TRAIN_STEPS = 10
TOP_K = 10
BUCKETS = "1,8"
MODEL, MODEL_DIM = "flagship", 1024  # GoogLeNet pool5 embedding width

FULL = dict(gallery=1_000_000, dim=128, centers=4096, clusters=1024,
            probes=32, queries=48, snap_gallery=2048, snap_queries=4,
            input_size=224, train_sample=131072)
TINY = dict(gallery=4096, dim=32, centers=64, clusters=16, probes=4,
            queries=12, snap_gallery=256, snap_queries=3,
            input_size=64, train_sample=4096)


class LegFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise LegFailed(msg)


def run_leg(name, argv, platform, work, stdin_text=None):
    """One ``python -m npairloss_tpu`` child in its own process group,
    killed as a group at the time limit.  Returns (stdout, stderr,
    wall seconds, cache stats or None)."""
    env = dict(os.environ)
    env["NPAIRLOSS_SERVE_COMPILE_GUARD"] = "strict"
    if platform == "tpu":
        env["NPAIRLOSS_PALLAS_INTERPRET"] = "forbid"
        env.pop("JAX_PLATFORMS", None)
    cmd = [sys.executable, "-m", "npairloss_tpu", "--platform", platform,
           *argv]
    err_path = os.path.join(work, f"{name}.stderr")
    t0 = time.perf_counter()
    with open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=err_f, text=True,
            start_new_session=True)
        try:
            out, _ = proc.communicate(stdin_text, timeout=LEG_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise LegFailed(f"timed out after {LEG_TIMEOUT_S}s")
        finally:
            if proc.poll() is None:  # parent interrupted: leave nothing
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    wall = time.perf_counter() - t0
    with open(err_path) as f:
        err = f.read()
    if proc.returncode != 0:
        raise LegFailed(f"exit code {proc.returncode}\n--- stderr tail "
                        f"---\n{err[-3000:]}")
    cache = None
    for line in err.splitlines():
        if line.startswith("compile_cache "):
            cache = json.loads(line[len("compile_cache "):])
    return out, err, wall, cache


def leg_manifest(tel_dir):
    """The leg's run manifest and (platform, kind, count, mesh) as it
    recorded them from jax."""
    with open(os.path.join(tel_dir, "manifest.json")) as f:
        man = json.load(f)
    devs = man["topology"]["devices"]
    return man, (devs[0]["platform"], devs[0]["device_kind"], len(devs),
                 man.get("mesh"))


def span_seconds(trace_path, name):
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    return sum(e.get("dur", 0.0) for e in events if e["name"] == name) / 1e6


# -- legs ---------------------------------------------------------------------


def rehearsal_recipe(work, size):
    """The flagship pair cut to a CPU-sized batch and crop (same trunk,
    same mining) — rehearsal only."""
    with open(os.path.join(REPO, "examples", "flagship.prototxt")) as f:
        net = f.read()
    for old, new in (("crop_size: 224", f"crop_size: {size['input_size']}"),
                     ("batch_size: 120", "batch_size: 8"),
                     ("identity_num_per_batch: 60",
                      "identity_num_per_batch: 4"),
                     ("new_height: 256", "new_height: 72"),
                     ("new_width: 256", "new_width: 72")):
        assert old in net, old
        net = net.replace(old, new)
    net_path = os.path.join(work, "rehearsal_net.prototxt")
    with open(net_path, "w") as f:
        f.write(net)
    with open(os.path.join(REPO, "examples",
                           "flagship_solver.prototxt")) as f:
        solver = f.read()
    solver = re.sub(r'net: "[^"]*"', f'net: "{net_path}"', solver)
    solver_path = os.path.join(work, "rehearsal_solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(solver)
    return solver_path


def leg_train(platform, work, size):
    solver = (os.path.join("examples", "flagship_solver.prototxt")
              if platform == "tpu" else rehearsal_recipe(work, size))
    tel = os.path.join(work, "train_tel")
    prefix = os.path.join(work, "snap", "flagship_")
    _, _, wall, cache = run_leg("train", [
        "train", "--solver", solver, "--model", MODEL,
        "--precision", "mxu", "--synthetic",
        "--max_iter", str(TRAIN_STEPS), "--snapshot_prefix", prefix,
        "--telemetry-dir", tel, "--health-metrics",
    ], platform, work)
    rows = []
    with open(os.path.join(tel, "metrics.jsonl")) as f:
        for line in f:
            row = json.loads(line)
            if row.get("phase") == "train":
                rows.append(row)
    check(len(rows) >= TRAIN_STEPS,
          f"{len(rows)} train rows, want >= {TRAIN_STEPS}")
    losses = [r["loss"] for r in rows]
    check(all(math.isfinite(v) for v in losses),
          f"non-finite loss: {losses}")
    for key in ("retrieve_top1", "retrieve_top5", "retrieve_top10",
                "feature_asum", "grad_norm", "update_ratio"):
        check(all(key in r and math.isfinite(r[key]) for r in rows),
              f"metric {key} missing or non-finite")
    # The run must MOVE: a BN-free GoogLeNet at random init sits near
    # loss = log(N - 1) (every embedding aligned), so the loss alone
    # may change only in its last digits — the batches (feature_asum)
    # and the parameters (gradient and update norms) must change too.
    asums = [r["feature_asum"] for r in rows]
    check(len(set(losses)) > 1 or len(set(asums)) > 1,
          f"loss and feature_asum are both constant: {losses}")
    check(all(r["grad_norm"] > 0 and r["update_ratio"] > 0 for r in rows),
          "a step with a zero gradient or a zero update")
    snap = f"{prefix}iter_{TRAIN_STEPS}.ckpt"
    with open(os.path.join(snap, "manifest.json")) as f:
        man = json.load(f)
    check(man.get("format") == SNAPSHOT_FORMAT
          and man.get("step") == TRAIN_STEPS
          and isinstance(man.get("arrays"), dict) and man["arrays"],
          f"snapshot manifest invalid: format={man.get('format')!r} "
          f"step={man.get('step')!r}")
    return {
        "wall_s": wall, "cache": cache, "snapshot": snap,
        "compile_s": span_seconds(os.path.join(tel, "trace.json"),
                                  "step/compile"),
        "device": leg_manifest(tel)[1],
        "loss_first_last": (losses[0], losses[-1]),
        "distinct_losses": len(set(losses)),
    }


def write_gallery(work, size):
    """Seeded unit-norm gallery: ``centers`` Gaussian blobs, so the IVF
    clustering has structure to find.  numpy only."""
    import numpy as np

    rng = np.random.default_rng(0)
    n, d = size["gallery"], size["dim"]
    centers = rng.standard_normal((size["centers"], d), np.float32)
    lab = rng.integers(0, size["centers"], n).astype(np.int32)
    emb = centers[lab] + 0.5 * rng.standard_normal((n, d), np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    np.save(os.path.join(work, "g.emb.npy"), emb)
    np.save(os.path.join(work, "g.labels.npy"), lab)
    rows = rng.choice(n, size["queries"], replace=False)
    queries = "".join(
        json.dumps({"id": int(r), "embedding": emb[r].tolist()}) + "\n"
        for r in rows)
    snap = rng.standard_normal(
        (size["snap_gallery"], MODEL_DIM), np.float32)
    np.save(os.path.join(work, "s.emb.npy"), snap)
    np.save(os.path.join(work, "s.labels.npy"),
            np.arange(size["snap_gallery"], dtype=np.int32))
    side = size["input_size"]
    imgs = rng.standard_normal(
        (size["snap_queries"] - 1, side, side, 3), np.float32)
    imgs = np.round(imgs, 3)
    recs = [{"id": i, "input": im.tolist()} for i, im in enumerate(imgs)]
    recs.append({"id": len(recs), "input": imgs[0].tolist()})  # a repeat
    return queries, "".join(json.dumps(r) + "\n" for r in recs)


def leg_index(platform, work, size):
    out = {}
    wall = 0.0
    cache = None  # summed over the three children
    for kind, prefix, extra in (
        ("flat", "g", []),
        ("ivf", "g", ["--kind", "ivf", "--clusters",
                      str(size["clusters"]), "--train-sample",
                      str(size["train_sample"]), "--parity-sample", "0"]),
        ("snap", "s", []),
    ):
        path = os.path.join(work, f"{kind}.gidx")
        stdout, _, w, c = run_leg(f"index_{kind}", [
            "index", "--prefix", os.path.join(work, prefix),
            "--out", path, *extra,
        ], platform, work)
        cache = c if cache is None else {
            **c, "hits": cache["hits"] + c["hits"],
            "misses": cache["misses"] + c["misses"],
            "entries_before": cache["entries_before"]}
        summary = json.loads(stdout.strip().splitlines()[-1])
        want_rows = size["snap_gallery" if kind == "snap" else "gallery"]
        check(summary["rows"] == want_rows,
              f"{kind}: {summary['rows']} rows, want {want_rows}")
        if kind == "ivf":
            check(summary["clusters"] == size["clusters"],
                  f"ivf: {summary['clusters']} clusters")
            out["cap"] = summary["cap"]
        out[kind] = path
        wall += w
    return {"wall_s": wall, "cache": cache, **out}


def leg_serve(name, platform, work, index, queries, extra,
              self_match=True):
    tel = os.path.join(work, f"{name}_tel")
    stdout, err, wall, cache = run_leg(name, [
        "serve", "--index", index, "--top-k", str(TOP_K),
        "--buckets", BUCKETS, "--telemetry-dir", tel, *extra,
    ], platform, work, stdin_text=queries)
    with open(os.path.join(work, f"{name}.stdout"), "w") as f:
        f.write(stdout)  # kept with --keep: the answers themselves
    lines = [json.loads(ln) for ln in stdout.splitlines() if ln.strip()]
    check(lines and lines[-1].get("event") == "serve_drain",
          "no drain summary on stdout")
    drain, answers = lines[-1], lines[:-1]
    n = len(queries.splitlines())
    check(len(answers) == n, f"{len(answers)} answers for {n} queries")
    bad = [a for a in answers if "error" in a or not a.get("neighbors")]
    check(not bad, f"unanswered/errored: {bad[:2]}")
    check(drain["errors"] == 0 and drain["answered"] == n,
          f"drain: answered {drain['answered']} errors {drain['errors']}")
    check(drain["queries"] == drain["answered"] + drain["errors"]
          + drain["rejected"], f"drain invariant broken: {drain}")
    check(drain["compiles_after_warmup"] == 0,
          f"compiles_after_warmup = {drain['compiles_after_warmup']}")
    for a in answers:
        scores = [nb["score"] for nb in a["neighbors"]]
        check(len(scores) == TOP_K and all(map(math.isfinite, scores)),
              f"query {a['id']}: bad scores {scores}")
        if self_match:
            top = a["neighbors"][0]
            check(top["row"] == a["id"] and top["score"] > 0.999,
                  f"query {a['id']}: top-1 is row {top['row']} "
                  f"score {top['score']}")
    warm = re.search(r"serve warmup: \d+ bucket\(s\) compiled in "
                     r"([0-9.]+)s", err)
    man, device = leg_manifest(tel)
    return {
        "wall_s": wall, "cache": cache, "answers": answers,
        "compile_s": float(warm.group(1)) if warm else None,
        "device": device,
        "probe_impl": man["config"].get("probe_impl_resolved"),
    }


def same_answers(a, b):
    """IVF with every cluster probed is the flat scan: same rows (ties
    may swap, so equal scores also pass)."""
    for x, y in zip(a, b):
        for nx, ny in zip(x["neighbors"], y["neighbors"]):
            check(nx["row"] == ny["row"]
                  or abs(nx["score"] - ny["score"]) <= 1e-5,
                  f"query {x['id']}: ivf row {nx['row']} "
                  f"({nx['score']}) vs flat row {ny['row']} "
                  f"({ny['score']})")


# -- driver -------------------------------------------------------------------


def fmt_cache(c):
    if not c:
        return "cache n/a"
    return (f"cache hits {c['hits']} misses {c['misses']} entries "
            f"{c['entries_before']}->{c['entries_after']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, stamped platform: cpu "
                    "(debugs this script; proves nothing about a chip)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (.smoke_work/)")
    args = ap.parse_args()
    assert "jax" not in sys.modules

    for need in ("npairloss_tpu/__main__.py",
                 "examples/flagship_solver.prototxt",
                 "examples/flagship.prototxt"):
        if not os.path.exists(os.path.join(REPO, need)):
            print(f"chip_smoke: {need} not found beside this script — "
                  "run it from a checkout of the repo", file=sys.stderr)
            return 2

    platform = "cpu" if args.cpu_rehearsal else "tpu"
    size = TINY if args.cpu_rehearsal else FULL
    work = os.path.join(REPO, ".smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    t_start = time.perf_counter()
    report = {"legs": {}}
    failed = None
    try:
        legs = report["legs"]

        def run(name, fn, *a, **kw):
            print(f"leg {name}: running ...", flush=True)
            try:
                legs[name] = res = fn(*a, **kw)
            except (LegFailed, OSError, KeyError, ValueError) as e:
                print(f"leg {name}: FAIL — {e}", flush=True)
                legs[name] = {"ok": False, "error": str(e)[:4000]}
                raise LegFailed(name)
            comp = res.get("compile_s")
            print(f"leg {name}: PASS  wall {res['wall_s']:.1f}s  "
                  f"compile {'n/a' if comp is None else f'{comp:.1f}s'}"
                  f"  {fmt_cache(res.get('cache'))}", flush=True)
            return res

        train = run("train", leg_train, platform, work, size)
        plat, kind, count, mesh = train["device"]
        print(f"platform: {plat}\ndevice_kind: {kind}\n"
              f"device_count: {count}\n"
              f"mesh: {json.dumps(mesh) if mesh else 'none'}", flush=True)
        check(plat == platform, f"train ran on {plat}, want {platform}")
        queries, snap_queries = write_gallery(work, size)
        idx = run("index", leg_index, platform, work, size)

        def serve(name, index, stdin, extra, **kw):
            return run(name, leg_serve, name, platform, work, index,
                       stdin, extra, **kw)

        flat = serve("serve_flat", idx["flat"], queries,
                     ["--index-kind", "flat"])
        ivf_args = ["--index-kind", "ivf", "--probe-impl", "auto"]
        ivf = serve("serve_ivf", idx["ivf"], queries,
                    [*ivf_args, "--probes", str(size["probes"])])
        ivf_all = serve("serve_ivf_all", idx["ivf"], queries,
                        [*ivf_args, "--probes", str(size["clusters"])])
        same_answers(ivf_all["answers"], flat["answers"])
        snap = serve("serve_snap", idx["snap"], snap_queries,
                     ["--index-kind", "flat", "--snapshot",
                      train["snapshot"], "--model", MODEL,
                      "--input-size", str(size["input_size"])],
                     self_match=False)
        first, repeat = snap["answers"][0], snap["answers"][-1]
        check(first["neighbors"] == repeat["neighbors"],
              "the same input encoded to different answers")
        for name in ("serve_flat", "serve_ivf", "serve_ivf_all",
                     "serve_snap"):
            check(legs[name]["device"][:3] == (plat, kind, count),
                  f"{name} saw {legs[name]['device'][:3]}")
        if platform == "tpu":
            check(ivf["probe_impl"] == "fused"
                  and ivf_all["probe_impl"] == "fused",
                  f"--probe-impl auto resolved to {ivf['probe_impl']!r}"
                  " on a TPU")
        print(f"ivf: cap {idx['cap']}, probe impl {ivf['probe_impl']}",
              flush=True)
    except LegFailed as e:
        failed = str(e)
    finally:
        for leg in report["legs"].values():
            leg.pop("answers", None)
        report["wall_s"] = round(time.perf_counter() - t_start, 1)
        out_dir = os.path.join(REPO, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1, default=str)
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)
    if failed is not None:
        print(f"chip_smoke: FAILED at {failed} after "
              f"{report['wall_s']}s", file=sys.stderr)
        return 1
    print(f"all legs passed in {report['wall_s']}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": plat, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
