"""Stretch-pool parity oracle on the virtual CPU mesh (no TPU needed).

STRETCH.json times the 32k-pool blockwise engine on hardware, but no
artifact pins CORRECTNESS at that scale: the CPU test suite tops out at
a few hundred rows, and the hardware stretch has no dense oracle to
compare against (the whole point of the streaming engines is that the
dense pair matrix is HBM-impossible on-chip).  On the host, 125 GB of
RAM makes the dense 32k graph possible — so this script computes, at
the full stretch pool:

    dense  : ``npair_loss`` value+grad on all N rows, single device
    ring   : ``parallel.ring`` over the 8-shard virtual mesh
             (N/8 rows per shard, ppermute streaming, grad rotation)

with the FLAGSHIP mining config (GLOBAL/RELATIVE_HARD AP + LOCAL/HARD
AN, usage/def.prototxt:137-146) — at N=32k the RELATIVE rank population
is ~1e9 pairs, exercising the radix-selection count arithmetic at a
scale no unit test reaches — and asserts loss + gradient parity.

Writes STRETCH_PARITY.json.  Runtime: tens of minutes on one CPU core
(three ~1.1-TFLOP gemms plus full-matrix sweeps); pass --pool to
shrink.

Usage: python scripts/stretch_parity_virtual.py [--pool 32768]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg):
    print(f"[stretch-parity t={time.time() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.time()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pool", type=int, default=32768)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument(
        "--blockwise-pool", type=int, default=0,
        help="also check the Pallas blockwise engine (interpret mode on "
        "CPU) against the single-rank dense oracle at this pool — "
        "interpret is slow, so this uses a smaller pool than the ring "
        "check (8192 is ~4x the hardware parity pool)",
    )
    ap.add_argument(
        "--skip-ring", action="store_true",
        help="only run the blockwise section (merge into existing out)",
    )
    ap.add_argument(
        "--out", default=os.path.join(REPO, "STRETCH_PARITY.json")
    )
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.shards}"
    ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from npairloss_tpu import REFERENCE_CONFIG
    from npairloss_tpu.ops.npair_loss import npair_loss
    from jax import shard_map
    from npairloss_tpu.parallel.mesh import data_parallel_mesh
    from npairloss_tpu.parallel.ring import ring_npair_loss_and_metrics

    n, d, g = args.pool, args.dim, args.shards
    assert n % g == 0
    rng = np.random.default_rng(0)
    f = rng.standard_normal((n, d)).astype(np.float32)
    f /= np.linalg.norm(f, axis=1, keepdims=True)
    labels_np = np.repeat(np.arange(n // 2), 2).astype(np.int32)
    mesh = data_parallel_mesh(jax.devices()[:g])
    shard = NamedSharding(mesh, P("dp"))
    feats = jax.device_put(jnp.asarray(f), shard)
    labels = jax.device_put(jnp.asarray(labels_np), shard)
    cfg = REFERENCE_CONFIG

    log(f"pool {n} x dim {d}, {g} virtual shards, flagship config")

    # Both engines run per-rank semantics on the SAME mesh (the
    # reference is per-MPI-rank: GLOBAL thresholds are per-rank
    # N x N*G block statistics, cu:327-334 — a G=1 dense run would be a
    # DIFFERENT math, not an oracle).  Composition mirrors
    # tests/test_ring.py::_dense_fns/_ring_fns, scaled to the full pool.
    def ring_shard(pos_topk):
        def fn(xs, ls):
            loss = ring_npair_loss_and_metrics(
                xs, ls, cfg, "dp", top_ks=(), pos_topk=pos_topk)[0]
            grad = jax.grad(
                lambda x_: ring_npair_loss_and_metrics(
                    x_, ls, cfg, "dp", top_ks=(), pos_topk=pos_topk
                )[0]
            )(xs)
            return loss[None], grad
        return fn

    def dense_shard(xs, ls):
        # npair_loss(axis_name=...) all-gathers the pool in-graph and
        # materializes this rank's (N/g x N) pair matrix — the full
        # dense-path oracle at stretch scale (~0.5 GB per shard).
        loss = npair_loss(xs, ls, cfg, axis_name="dp")
        grad = jax.grad(
            lambda x_: npair_loss(x_, ls, cfg, axis_name="dp")
        )(xs)
        return loss[None], grad

    def run(name, shard_fn):
        fn = jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("dp"), P("dp")), out_specs=(P("dp"), P("dp")),
        ))
        log(f"compiling + running {name}...")
        loss, grad = fn(feats, labels)
        loss = np.asarray(loss)
        grad = np.asarray(grad)
        log(f"{name} per-rank loss mean {loss.mean():.6f}")
        return loss, grad

    def parity(name_a, name_b, la, ga, lb, gb):
        """(delta summary, ok) at the test_ring elementwise bar."""
        loss_delta = float(np.max(np.abs(la - lb)))
        grad_max_delta = float(np.max(np.abs(gb - ga)))
        grad_scale = float(np.max(np.abs(gb)))
        grad_ok = bool(np.allclose(ga, gb, rtol=3e-5, atol=1e-6))
        sec_ok = (
            loss_delta <= 1e-4 * max(1.0, abs(float(np.mean(lb))))
            and grad_ok
            and bool(np.isfinite(ga).all())
        )
        return {
            f"loss_{name_a}": float(np.mean(la)),
            f"loss_{name_b}": float(np.mean(lb)),
            "loss_delta": loss_delta,
            "grad_max_delta": grad_max_delta,
            "grad_scale": grad_scale,
            "ok": bool(sec_ok),
        }, sec_ok

    record = {
        "what": ("dense-oracle parity for the streaming engines at "
                 "stretch-scale pools on the virtual CPU mesh — "
                 "correctness at the scale STRETCH.json only times "
                 "(radix RELATIVE selection over ~1e9 pairs included)"),
        "config": "flagship (usage/def.prototxt:137-146)",
        "backend": "cpu (virtual mesh)",
        "command": f"python scripts/stretch_parity_virtual.py --pool {n}"
                   + (f" --blockwise-pool {args.blockwise_pool}"
                      if args.blockwise_pool else ""),
    }
    if os.path.exists(args.out):
        try:
            with open(args.out) as fo:
                prev = json.load(fo)
            for key in ("ring", "ring_radix", "blockwise",
                        "blockwise_radix"):
                if key in prev:
                    record[key] = prev[key]
        except Exception:
            pass

    ok = True
    if not args.skip_ring:
        dense_losses, gd = run(
            "dense oracle (per-rank pair matrices)", dense_shard)
        # Both AP-threshold machineries at the full stretch pool: the
        # sparse-positive fast path (default, round 4) and the radix
        # selection it falls back to (pos_topk=0; rank population ~1e9
        # pairs — the count-arithmetic scale no unit test reaches).
        for key, pos_topk, label in (
            ("ring", None, "ring (sparse-positive fast path)"),
            ("ring_radix", 0, "ring (radix selection, pos_topk=0)"),
        ):
            ring_losses, gr = run(label, ring_shard(pos_topk))
            sec, sec_ok = parity(
                "ring", "dense", ring_losses, gr, dense_losses, gd)
            ok = ok and sec_ok
            record[key] = {
                "pool": n, "dim": d, "shards": g, "pos_topk": pos_topk,
                **sec,
                "note": "per-rank semantics on the 8-shard mesh, both sides",
            }
            log(f"{key} section {'OK' if sec_ok else 'FAIL'}: "
                f"loss d={sec['loss_delta']:.2e}, "
                f"grad max d={sec['grad_max_delta']:.2e}")

    if args.blockwise_pool:
        from npairloss_tpu.ops.pallas_npair import blockwise_npair_loss

        nb = args.blockwise_pool
        fb = rng.standard_normal((nb, d)).astype(np.float32)
        fb /= np.linalg.norm(fb, axis=1, keepdims=True)
        feats_b = jnp.asarray(fb)
        labels_b = jnp.asarray(
            np.repeat(np.arange(nb // 2), 2).astype(np.int32))
        log(f"blockwise section: pool {nb} (interpret mode on CPU)...")
        ld_, gd_ = jax.jit(jax.value_and_grad(
            lambda x: npair_loss(x, labels_b, cfg)))(feats_b)
        ld_, gd_ = np.asarray(ld_), np.asarray(gd_)
        for key, pos_topk in (("blockwise", None), ("blockwise_radix", 0)):
            t0 = time.time()
            lb_, gb_ = jax.jit(jax.value_and_grad(
                lambda x: blockwise_npair_loss(
                    x, labels_b, cfg, pos_topk=pos_topk)))(feats_b)
            lb_, gb_ = np.asarray(lb_), np.asarray(gb_)
            log(f"{key} loss {float(lb_):.6f} ({time.time() - t0:.0f}s)")
            sec, sec_ok = parity(
                "blockwise", "dense",
                np.asarray([lb_]), gb_, np.asarray([ld_]), gd_)
            ok = ok and sec_ok
            record[key] = {
                "pool": nb, "dim": d, "block": 512,
                "interpret": True, "pos_topk": pos_topk, **sec,
                "note": ("single-rank semantics (the blockwise engine is "
                         "the single-chip path); Pallas interpret mode — "
                         "the Mosaic-compiled twin is PALLAS_CHECK.json"),
            }
            log(f"{key} section {'OK' if sec_ok else 'FAIL'}: "
                f"loss d={sec['loss_delta']:.2e}, "
                f"grad max d={sec['grad_max_delta']:.2e}")

    record["ok"] = bool(ok)
    record["elapsed_s"] = round(time.time() - T0, 1)
    with open(args.out, "w") as fo:
        json.dump(record, fo, indent=1)
        fo.write("\n")
    log(f"{'OK' if ok else 'FAIL'} -> {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
