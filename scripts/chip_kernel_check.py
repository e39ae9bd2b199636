"""Mosaic-compile and run every Pallas kernel at production geometry on
the chip, and check each against its XLA reference.

    chiprun -- python scripts/chip_kernel_check.py [--only SUBSTR ...]

The cases are ``npairloss_tpu.testing.pallas_cases`` (blockwise pool
4096x512 fwd+grad x4 configs, LRN at the benchmark's batch 480 and
batch 1, the conv epilogues at batch 120, the IVF probe at the 1M
geometry x3 dtypes + its shard-local form).  Exits
non-zero when the backend is not a TPU or any case fails to compile or
misses parity; one JSON line per case, a summary line last, and the
whole record under ``chiprun_out/kernel_check.json``.

``--sharded`` (several chips) instead runs LRN forward + backward with
the batch sharded over every device: no collective in the compiled
program, every device its own kernel, the one-device result.

``--aot TOPOLOGY`` (e.g. ``v5e:2x2``) needs no chip: it compiles every
case against libtpu's description of that topology, so Mosaic accepts
or refuses each kernel on a CPU-only box.  Nothing executes — parity
still needs the chip.
"""

import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def sharded_lrn(seed: int) -> dict:
    """LRN under GSPMD on every device: the custom_partitioning rule of
    ``ops.pallas_stem`` on real chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from npairloss_tpu.ops.pallas_stem import fused_lrn

    devs = jax.devices()
    sh = NamedSharding(Mesh(np.asarray(devs), ("dp",)), P("dp"))

    def fwd_bwd(x, g):
        y, vjp = jax.vjp(lambda v: fused_lrn(v, interpret=False), x)
        return y, vjp(g)[0]

    out = {}
    rng = np.random.default_rng(seed)
    for shape in ((480, 56, 56, 64), (8 * len(devs), 56, 56, 192)):
        x, g = (rng.standard_normal(shape, dtype=np.float32)
                .astype(jnp.bfloat16) for _ in range(2))
        fn = jax.jit(fwd_bwd, in_shardings=(sh, sh), out_shardings=(sh, sh))
        text = fn.lower(x, g).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel"
        for op in ("all-gather", "all-reduce", "all-to-all"):
            assert op not in text, f"{op} around the sharded LRN"
        got = fn(jax.device_put(x, sh), jax.device_put(g, sh))
        want = jax.jit(fwd_bwd)(x, g)
        err = max(float(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)).max())
                  for a, b in zip(got, want))
        assert err <= 2 ** -5, (shape, err)  # a bf16 ulp
        out["x".join(map(str, shape))] = err
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sharded", action="store_true",
                    help="LRN with the batch sharded over every device")
    ap.add_argument("--only", nargs="*", default=[],
                    help="run cases whose name contains any of these")
    ap.add_argument("--aot", metavar="TOPOLOGY", default=None,
                    help="compile-only against a libtpu topology "
                    "description (no chip needed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np

    from npairloss_tpu.pipeline.compile_cache import enable_compile_cache
    from npairloss_tpu.testing.pallas_cases import kernel_cases

    cases = [c for c in kernel_cases()
             if not args.only or any(s in c.name for s in args.only)]
    record = {"mode": "aot" if args.aot else "run", "cases": {}}
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        dev = topologies.get_topology_desc(args.aot, "tpu").devices[0]
        sharding = SingleDeviceSharding(dev)
        record["device"] = {"platform": dev.platform,
                            "kind": dev.device_kind, "topology": args.aot}
    else:
        enable_compile_cache()
        dev = jax.devices()[0]
        record["device"] = {"platform": dev.platform,
                            "kind": dev.device_kind,
                            "count": len(jax.devices())}
        if dev.platform != "tpu":
            print(json.dumps({"ok": False, "error": "no TPU found",
                              **record["device"]}))
            return 1

    if args.sharded:
        if args.aot or len(jax.devices()) < 2:
            sys.exit("--sharded needs several chips (and no --aot)")
        print(json.dumps({"sharded_lrn_max_abs": sharded_lrn(args.seed),
                          **record["device"]}))
        return 0

    failed = 0
    for case in cases:
        row = {}
        t0 = time.perf_counter()
        try:
            if args.aot:
                specs = [jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=sharding)
                         for s in case.specs]
                text = jax.jit(case.fn).lower(*specs).compile().as_text()
                assert "tpu_custom_call" in text, "no Mosaic kernel"
            else:
                host = case.make_args(np.random.default_rng(args.seed))
                dev_args = [jax.device_put(a) for a in host]
                compiled = jax.jit(case.fn).lower(*dev_args).compile()
                assert "tpu_custom_call" in compiled.as_text(), \
                    "no Mosaic kernel"
                row["compile_s"] = round(time.perf_counter() - t0, 2)
                got = jax.block_until_ready(compiled(*dev_args))
                want = jax.block_until_ready(jax.jit(case.ref)(*dev_args))
                row["err"] = case.check(got, want)
            row["ok"] = True
        except Exception as e:  # a failed case is reported, not fatal
            failed += 1
            row.update(ok=False, error=f"{type(e).__name__}: "
                       f"{str(e)[:2000]}")
            traceback.print_exc(file=sys.stderr)
        row["wall_s"] = round(time.perf_counter() - t0, 2)
        record["cases"][case.name] = row
        print(json.dumps({"case": case.name, **row}), flush=True)

    record["ok"] = failed == 0
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.json"), "w") as fh:
        json.dump(record, fh, indent=1)
    print(json.dumps({"ok": record["ok"], "failed": failed,
                      "cases": len(cases), **record["device"]}))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
