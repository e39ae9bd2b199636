"""How the limits of ``correct`` were read: the program against the plain
reference over many seeds (the lower reading), and the low-precision
control and the planted faults against it (the upper reading), all in
one process at the cell's own size.  Not part of a benchmark run.

    python3 benchmarks/calibrate.py --workload NAME --seeds 12 --controls 3
        [--window 5] [--witness 3] [--probes 1,4,16]
        [--program-precision fp32_parity --identities 120]
        [--out chiprun_out/calibrate_NAME.jsonl]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _emit(out, row):
    line = json.dumps(row)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def _groups(per_leaf):
    """{layer group: median error} down the trunk: conv1, conv2, 3a .. 5b."""
    import numpy as np

    out = {}
    for leaf, v in per_leaf.items():
        layer = leaf.split("/")[0].split(".")[0]
        out.setdefault(layer.replace("inception_", "").replace("_reduce", ""), []).append(v)
    return {g: float(np.median(v)) for g, v in out.items()}


def witness(cfg, tr, adapter, host0, x, lab, prog_grad):
    """Where the gap between the bfloat16 program and the float32
    reference enters.  The reference is put in the program's place with
    its trunk in bfloat16 (conv operands and cotangents rounded,
    float32 accumulation), whole and in parts: only the cotangent that
    the loss hands back taken from the bfloat16 embeddings
    (``ct_only``), only the trunk's backward in bfloat16
    (``trunk_only``), only the loss's own products on bfloat16-rounded
    embeddings (``loss_bf16_only``, as the mxu policy runs them).  Each
    reads the median leaf of ||g - g_ref|| over max(||g_ref||, median),
    the number ``correct`` holds, and the same by layer down the trunk."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import compare
    from benchmarks.reference.npair import Trainer

    mk = lambda q: Trainer(adapter.embed, host0, cfg["mining"], cfg["solver"],
                           block=tr["reference_block"], quant=q)
    t32, t16 = mk(None), mk("bfloat16")
    p = t32.params
    blocks = [(i, min(i + t32.block, len(x))) for i in range(0, len(x), t32.block)]
    fwd = lambda t: jnp.concatenate([t._fwd(p, jnp.asarray(x[a:b])) for a, b in blocks])
    emb32, emb16 = fwd(t32), fwd(t16)
    lab = jnp.asarray(lab)
    ct = {"f32": t32._loss(emb32, lab)[1], "bf16": t32._loss(emb16, lab)[1],
          "loss_bf16": t32._loss(emb32.astype(jnp.bfloat16).astype(jnp.float32), lab)[1]}

    def pull(t, c):
        acc = jax.tree_util.tree_map(jnp.zeros_like, p)
        for a, b in blocks:
            acc = t._pull(p, jnp.asarray(x[a:b]), c[a:b], acc)
        return jax.tree_util.tree_map(np.asarray, acc)

    ref = pull(t32, ct["f32"])
    sides = {"program": prog_grad, "reference_bf16": pull(t16, ct["bf16"]),
             "ct_only": pull(t32, ct["bf16"]), "trunk_only": pull(t16, ct["f32"]),
             "loss_bf16_only": pull(t32, ct["loss_bf16"])}
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    gram = np.asarray(emb32 @ emb32.T)
    out = {"embedding_err": rel(emb16, emb32), "cotangent_err": rel(ct["bf16"], ct["f32"]),
           "cotangent_err_loss_bf16": rel(ct["loss_bf16"], ct["f32"]),
           "cosine_mean": float((gram.sum() - len(gram)) / (len(gram) * (len(gram) - 1)))}
    for name, g in sides.items():
        per_leaf = compare.error_norms(g, ref)
        out[name] = {"grad_err_median": float(np.median(list(per_leaf.values()))),
                     "by_layer": _groups(per_leaf)}
    return out


def train(cell, devices, args):
    import jax
    import numpy as np

    from benchmarks.harness import compare, train_window
    from benchmarks.reference.npair import Trainer

    cfg, tr, adapter = cell.config, cell.traffic, cell.adapter
    solver = train_window.build_solver(cell, devices)
    control = cfg["precision"]["control"]
    trainers = {}

    def follow(kind, host0, images, labels):
        if kind not in trainers:
            trainers[kind] = Trainer(
                adapter.embed, host0, cfg["mining"], cfg["solver"],
                block=tr["reference_block"],
                quant=control if kind == "control" else None)
        t = trainers[kind]
        t.reset(host0)
        cut = images[0].shape[0] // 2 if kind == "half_batch" else None
        losses = [t.step(images[i % len(images)][:cut], labels[i % len(images)][:cut])
                  for i in range(tr["check_steps"])]
        after = jax.tree_util.tree_map(np.asarray, t.params)
        return {"losses": losses, "grad": t.first_grads,
                "delta": compare.tree_sub(after, host0)}

    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.time()
        params0, feed = train_window.seeded_inputs(cell, seed)
        images, labels = feed.inputs, feed.labels
        host0, prog = train_window.first_steps(solver, cell, params0, feed)
        solver.state = None
        t1 = time.time()
        ref = follow("reference", host0, images, labels)
        t2 = time.time()
        numbers, notes = compare.training_numbers(prog, ref)
        row = {"workload": cell.name, "seed": seed, "rows": len(labels[0]),
               "policy": cfg["program"]["precision"], "program": numbers,
               "notes": notes, "losses": [prog["losses"], ref["losses"]],
               "program_s": t1 - t0, "reference_s": t2 - t1}
        if k < args.controls:
            for kind in ("control", "half_batch"):
                row[kind] = compare.training_numbers(
                    follow(kind, host0, images, labels), ref)[0]
        if k < args.witness:
            row["witness"] = witness(cfg, tr, adapter, host0, images[0], labels[0],
                                     prog["grad"])
        _emit(args.out, row)


def serve(cell, devices, args):
    import jax.numpy as jnp
    import numpy as np

    from npairloss_tpu.serve.engine import QueryEngine

    from benchmarks.harness import run_serve, serve_window, weights
    from benchmarks.reference import retrieval

    cfg, mix, adapter = cell.config, cell.traffic, cell.adapter
    server, ctx = serve_window.build_server(cell, args.first_seed, False)
    e = mix["engine"]
    top_k = e["top_k"]
    # the program with fewer probes than the configuration states
    fewer = {}
    if mix["gallery"]["index"] == "ivf" and args.controls:
        for probes in args.probes:
            fewer[probes] = QueryEngine(ctx["index"], serve_window.engine_config(
                dict(e, buckets=[32], probes=probes)))
            fewer[probes].warmup()
    loop = serve_window.open_window if mix["loop"] == "open" \
        else serve_window.closed_window
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        params = weights.make_params(adapter, cfg, seed)
        ctx["host_params"] = weights.widened(params)
        ctx["pool"] = pool = adapter.query_pool(cfg, mix, seed)
        server.engine.state = {"params": adapter.to_program(params, xp=jnp),
                               "batch_stats": {}}
        ledger, win = loop(server, ctx, mix, seed, args.window)
        numbers = run_serve.serve_numbers(ledger, ctx, cell, top_k)
        row = {"workload": cell.name, "seed": seed, "program": numbers,
               "answers": len(ledger.answer), "refused": ledger.refused}
        if k < args.controls:
            # the control need not serve: the reference in the lower
            # precision puts its own ten first; read them as answers
            emb = run_serve.embed_pool(adapter, ctx["host_params"], pool, 32,
                                       quant=cfg["precision"]["control"])
            s, r = retrieval.exact_topk(emb, ctx["gallery"], top_k)
            row["control"] = run_serve.serve_numbers(
                run_serve.as_answers(r, s), ctx, cell, top_k)
            if fewer:
                emb = np.concatenate([server.engine.encode(np.stack(pool[i:i + 32]))
                                      for i in range(0, len(pool), 32)])
            for probes, engine in fewer.items():
                out = engine.query(emb)
                row[f"probes_{probes}"] = run_serve.serve_numbers(
                    run_serve.as_answers(out["rows"], out["scores"]), ctx, cell, top_k)
        _emit(args.out, row)
    server.replicaset.close(drain=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3000000019)
    ap.add_argument("--window", type=float, default=5.0)
    ap.add_argument("--witness", type=int, default=0,
                    help="training: seeds on which the bf16 gap is taken apart")
    ap.add_argument("--probes", type=lambda t: [int(v) for v in t.split(",")],
                    default=[1], help="IVF serving: the fewer-probes controls")
    ap.add_argument("--program-precision", default="",
                    help="training: run the program under another policy")
    ap.add_argument("--identities", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness import device, loader

    cell = loader.Cell(args.workload)
    if args.cpu_rehearsal:
        cell.config.update(cell.config.get("rehearsal", {}))
        cell.traffic.update(cell.traffic.get("rehearsal", {}))
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.program_precision:
        cell.config["program"]["precision"] = args.program_precision
    if args.identities:
        cell.traffic["identities"] = args.identities
    devices = device.require_devices(cell.chips, args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        from npairloss_tpu.pipeline.compile_cache import enable_compile_cache

        enable_compile_cache()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    (train if cell.traffic["kind"] == "train" else serve)(cell, devices, args)


if __name__ == "__main__":
    main()
