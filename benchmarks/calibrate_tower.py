"""The upper reading of a serving cell's limits where the float32
reference fills the chip by itself (a tower of billions of parameters):
``calibrate.py`` keeps the program's server beside the reference's
stage and activations; here no server is built.  A seed at a time: the
plain reference embeds the cell's pool in float32 and again with every
matrix product's operands rounded to a narrower type
(``precision.control``, or ``--quants a,b``); the narrower embedding's
own ten are read as answers and held to the float32 reference by
``run_serve.serve_numbers``, the comparison that decides ``correct``.
Beside them the pool's pairwise cosines under the reference: a pool
collapsed onto one direction hides a precision fault.  The LOWER reading
(the program over many seeds) is the ``numbers`` of the cell's own runs.
Not part of a benchmark run.

    python3 benchmarks/calibrate_tower.py --workload NAME --seeds 3
        [--quants float8_e4m3fn,bfloat16] [--out chiprun_out/FILE.jsonl]
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3000000019)
    ap.add_argument("--quants", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness import device, loader

    cell = loader.Cell(args.workload)
    if args.cpu_rehearsal:
        cell.config.update(cell.config.get("rehearsal", {}))
        cell.traffic.update(cell.traffic.get("rehearsal", {}))
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    device.require_devices(cell.chips, args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        from npairloss_tpu.pipeline.compile_cache import enable_compile_cache

        enable_compile_cache()
    import numpy as np

    from benchmarks.harness import run_serve, weights
    from benchmarks.reference import retrieval

    cfg, mix, adapter = cell.config, cell.traffic, cell.adapter
    g, top_k = mix["gallery"], mix["engine"]["top_k"]
    block = mix.get("reference_block", 32)
    quants = args.quants.split(",") if args.quants else [cfg["precision"]["control"]]
    gallery = weights.mixture_gallery(g["seed"], g["rows"], cfg["embedding_dim"],
                                      g["centres"])[0]
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        ctx = {"host_params": weights.widened(weights.make_params(adapter, cfg, seed)),
               "pool": adapter.query_pool(cfg, mix, seed), "gallery": gallery}
        ref = run_serve.embed_pool(adapter, ctx["host_params"], ctx["pool"], block)
        low = {q: run_serve.embed_pool(adapter, ctx["host_params"], ctx["pool"], block,
                                       quant=q) for q in quants}
        cos = (ref @ ref.T)[~np.eye(len(ref), dtype=bool)]
        row = {"workload": cell.name, "seed": seed, "limits": mix["limits"],
               "pool_cosine": {"min": float(cos.min()), "mean": float(cos.mean()),
                               "max": float(cos.max())}}
        for q, emb in low.items():
            s, r = retrieval.exact_topk(emb, gallery, top_k)
            row[q] = dict(run_serve.serve_numbers(run_serve.as_answers(r, s), ctx,
                                                  cell, top_k),
                          embedding_err=float(np.max(np.linalg.norm(emb - ref, axis=1))))
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
