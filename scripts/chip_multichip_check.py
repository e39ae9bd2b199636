"""The main path on several chips, beyond what chip_smoke.py checks.

    chiprun --chips 4 -- python scripts/chip_multichip_check.py

``chip_smoke.py`` already runs on whatever devices it finds (several
chips: the CLI's default mesh for ``train``, the gallery sharded for
``serve``).  This script runs it with ``--keep`` and then adds what a
multi-chip bring-up must show on real ICI:

  * ``train --engine ring`` for the same 10 steps on the same data:
    per-step loss agrees with the default (dense, all_gather) run to
    1e-4 relative;
  * ``serve --mesh 1`` (one device) answers the same queries as the
    sharded flat tier: recall is equal — and the sharded IVF tier with
    every cluster probed equals both;
  * placement: the batch, the loss pool, the flat gallery and the IVF
    slab each have addressable shards on every device, every device's
    ``memory_stats()["bytes_in_use"]`` is non-trivial, ``plan_for_mesh``
    resolves the device kind to a table row on the real topology, and
    where ``--replicas N`` engines live (the same devices — recorded,
    not fixed here).

Like chip_smoke.py the parent never imports jax; every leg is a child
process, one after another.  Exit 0 only if everything held;
``--cpu-rehearsal`` runs tiny on virtual CPU devices (set
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402  (stdlib-only, jax-free)

PLACEMENT = r'''
import json, sys
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from npairloss_tpu.parallel import build_mesh, plan_for_mesh
from npairloss_tpu.serve.index import load_index

work, batch = sys.argv[1], int(sys.argv[2])
devs = jax.devices()
mesh = build_mesh(devs)
plan = plan_for_mesh(mesh, batch, 1024, requested="auto")
out = {"devices": [str(d) for d in devs], "plan": plan.to_dict()}

def shard_devices(x):
    return sorted({str(s.device) for s in x.addressable_shards})

x = jax.device_put(np.zeros((batch, 8), np.float32),
                   NamedSharding(mesh, P("dp")))
out["batch"] = shard_devices(x)
# The loss pool: every shard's rows gathered onto every device.
pool = jax.jit(jax.shard_map(
    lambda v: jax.lax.all_gather(v, "dp", axis=0, tiled=True),
    mesh=mesh, in_specs=P("dp"), out_specs=P(), check_vma=False))(x)
out["pool"] = shard_devices(pool)
out["pool_rows"] = int(pool.shape[0])
flat = load_index(work + "/flat.gidx", mesh=mesh)
out["flat_gallery"] = shard_devices(flat.emb)
ivf = load_index(work + "/ivf.gidx", mesh=mesh)
out["ivf_slab"] = shard_devices(ivf.layout.packed)
out["bytes_in_use"] = {
    str(d): int((d.memory_stats() or {}).get("bytes_in_use", 0))
    for d in devs}
# --replicas N: engines share the primary's index and programs.
from npairloss_tpu.serve import EngineConfig, QueryEngine
primary = QueryEngine(flat, EngineConfig(top_k=10, buckets=(8,)))
replica = QueryEngine(flat, EngineConfig(top_k=10, buckets=(8,)),
                      share_compiled_with=primary)
out["replica_devices"] = shard_devices(replica.index.emb)
print(json.dumps(out))
'''


def train_losses(tel_dir):
    losses = []
    with open(os.path.join(tel_dir, "metrics.jsonl")) as f:
        for line in f:
            row = json.loads(line)
            if row.get("phase") == "train":
                losses.append(row["loss"])
    return losses


def answers_of(path):
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    return [a for a in lines if a.get("event") != "serve_drain"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    assert "jax" not in sys.modules
    platform = "cpu" if args.cpu_rehearsal else "tpu"
    size = cs.TINY if args.cpu_rehearsal else cs.FULL
    work = os.path.join(REPO, ".smoke_work")

    smoke = [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--keep"]
    if args.cpu_rehearsal:
        smoke.append("--cpu-rehearsal")
    proc = subprocess.run(smoke, cwd=REPO, stdout=subprocess.PIPE,
                          text=True)
    print(proc.stdout, end="", flush=True)
    if proc.returncode != 0:
        return proc.returncode
    device = json.loads(proc.stdout.strip().splitlines()[-1])["device"]
    if device["count"] < 2:
        print(f"multichip: only {device['count']} device(s) here",
              file=sys.stderr)
        return 1
    record = {"device": device}
    try:
        # -- ring vs dense, same data, same steps ---------------------------
        solver = (os.path.join("examples", "flagship_solver.prototxt")
                  if platform == "tpu"
                  else os.path.join(work, "rehearsal_solver.prototxt"))
        ring_tel = os.path.join(work, "train_ring_tel")
        _, _, wall, _ = cs.run_leg("train_ring", [
            "train", "--solver", solver, "--model", cs.MODEL,
            "--precision", "mxu", "--synthetic", "--engine", "ring",
            "--max_iter", str(cs.TRAIN_STEPS), "--snapshot_prefix",
            os.path.join(work, "snap_ring", "flagship_"),
            "--telemetry-dir", ring_tel,
        ], platform, work)
        dense = train_losses(os.path.join(work, "train_tel"))
        ring = train_losses(ring_tel)
        cs.check(len(ring) == len(dense) >= cs.TRAIN_STEPS,
                 f"{len(dense)} dense vs {len(ring)} ring steps")
        rel = [abs(a - b) / max(abs(a), 1e-12)
               for a, b in zip(dense, ring)]
        record["ring_vs_dense"] = {
            "dense": dense, "ring": ring, "max_rel": max(rel),
            "ring_wall_s": round(wall, 1)}
        print(f"ring vs dense: max relative loss gap {max(rel):.2e} "
              f"over {len(rel)} steps", flush=True)
        cs.check(max(rel) <= 1e-4, f"ring vs dense loss gap {max(rel):.2e}")

        # -- one device answers like the sharded tier -----------------------
        sharded = answers_of(os.path.join(work, "serve_flat.stdout"))
        one = cs.leg_serve(
            "serve_flat_one", platform, work,
            os.path.join(work, "flat.gidx"),
            smoke_queries(work, [a["id"] for a in sharded]),
            ["--index-kind", "flat", "--mesh", "1"])
        cs.same_answers(sharded, one["answers"])
        cs.same_answers(
            answers_of(os.path.join(work, "serve_ivf_all.stdout")),
            one["answers"])
        print("serve: sharded flat == one-device flat == sharded IVF "
              "(all clusters probed)", flush=True)

        # -- placement ------------------------------------------------------
        batch = 120 if platform == "tpu" else 8
        env = dict(os.environ)
        if platform == "tpu":
            env.pop("JAX_PLATFORMS", None)
        child = subprocess.run(
            [sys.executable, "-c", PLACEMENT, work, str(batch)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=cs.LEG_TIMEOUT_S)
        cs.check(child.returncode == 0,
                 f"placement child failed:\n{child.stderr[-3000:]}")
        place = json.loads(child.stdout.strip().splitlines()[-1])
        record["placement"] = place
        n = device["count"]
        for key in ("batch", "pool", "flat_gallery", "ivf_slab"):
            cs.check(len(place[key]) == n,
                     f"{key} has shards on {place[key]}, want {n} devices")
        print(f"placement: batch/pool/gallery/ivf shards on all {n} "
              f"devices; pool rows {place['pool_rows']}", flush=True)
        if platform == "tpu":
            low = {d: b for d, b in place["bytes_in_use"].items()
                   if b < (1 << 20)}
            cs.check(not low, f"devices holding < 1 MiB: {low}")
        print("bytes_in_use: " + json.dumps(place["bytes_in_use"]),
              flush=True)
        plan = place["plan"]
        cs.check(plan["peak_known"] or platform == "cpu",
                 f"plan used an unknown-device spec: {plan}")
        print(f"plan_for_mesh: {plan['device_kind']} -> engine "
              f"{plan['engine']} over {plan['link']} ({plan['reason']})",
              flush=True)
        print(f"--replicas: replica engines' gallery lives on "
              f"{place['replica_devices']} (the primary's devices)",
              flush=True)
    except cs.LegFailed as e:
        print(f"multichip: FAILED — {e}", file=sys.stderr)
        return 1
    finally:
        out_dir = os.path.join(REPO, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "multichip_check.json"), "w") as f:
            json.dump(record, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def smoke_queries(work, rows):
    """The smoke's own query embeddings for ``rows`` (numpy only)."""
    import numpy as np

    emb = np.load(os.path.join(work, "g.emb.npy"), mmap_mode="r")
    return "".join(
        json.dumps({"id": int(r), "embedding": emb[r].tolist()}) + "\n"
        for r in rows)


if __name__ == "__main__":
    sys.exit(main())
