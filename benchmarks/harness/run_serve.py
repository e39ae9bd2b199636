"""A run of a serving cell: set-up, window, reference, result."""

from __future__ import annotations

import collections
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import (compare, counts, device, peaks, run_train,
                                serve_window, tracing, traffic)


def as_answers(rows, scores):
    """(rows, scores) of one answer per pool image, in the ledger's form:
    how the low-precision control's own ten are read as answers."""
    return types.SimpleNamespace(
        key=list(range(len(rows))),
        answer=[{"neighbors": [{"row": int(a), "score": float(b)}
                               for a, b in zip(rr, ss)]}
                for rr, ss in zip(rows, scores)])


def embed_pool(adapter, host_params, pool, block, quant=None):
    """The plain reference's embedding of every pool entry, rows in key
    order.  Entries may differ in shape and dtype: they are grouped by
    both, and each group is stacked in blocks of ``block``.

    The reference's weights stream a STAGE at a time.  An adapter may
    answer one optional question, ``stages(host_params)``: the reference
    as an ordered list of (sub-tree of ``host_params``, function), each
    function taking its sub-tree and the activations to the next
    activations (the first stage's are the stacked inputs, the last's
    the embeddings), with ``quant`` as ``embed`` takes it.  One stage's
    weights go to the device, every block of the pool goes through it,
    the activations wait on the host in float32, and the stage is freed
    before the next is loaded: the device holds the largest stage and a
    block's activations, never the whole tree.  The host holds the whole
    pool's activations between stages, which bounds the pool (cell 5: 48
    documents, 111k tokens x 3840 x 4 B = 1.7 GB).  An adapter without
    ``stages`` is one stage: the whole tree through ``adapter.embed``.
    This loop is the one place where reference weights reach the device."""
    stages = getattr(adapter, "stages", None)
    stages = stages(host_params) if stages else [(host_params, adapter.embed)]
    groups = {}
    for i, x in enumerate(pool):
        groups.setdefault((x.shape, str(x.dtype)), []).append(i)
    parts = [keys[lo:lo + block] for keys in groups.values()
             for lo in range(0, len(keys), block)]
    acts = [np.stack([pool[i] for i in part]) for part in parts]
    programs = {}
    for tree, fn in stages:
        if fn not in programs:
            programs[fn] = jax.jit(lambda p, x, fn=fn: fn(p, x, quant=quant))
        weights = jax.tree_util.tree_map(jnp.asarray, tree)
        for k, x in enumerate(acts):  # in place: one copy of the pool's activations
            acts[k] = np.asarray(programs[fn](weights, jnp.asarray(x)))
        del weights  # freed before the next stage loads
    rows = [None] * len(pool)
    for part, emb in zip(parts, acts):
        for i, row in zip(part, emb):
            rows[i] = row
    return np.stack(rows)


def serve_numbers(ledger, ctx, cell, top_k):
    """Every answer of the window against the plain reference: the
    reference embeds the pool in float32 (``embed_pool``, its weights a
    stage at a time) and scores it exactly
    against the whole gallery; each served neighbour's score
    (``score_gap``) and rank (``rank_gap``: how far the k-th served score
    lies under the reference's k-th) are held to that, and so is the
    share of the reference's own ten that the answers miss
    (``recall_miss``, the mean over all answers)."""
    from benchmarks.reference import retrieval

    emb = embed_pool(cell.adapter, ctx["host_params"], ctx["pool"],
                     cell.traffic.get("reference_block", 32))
    ref_s, ref_r = retrieval.exact_topk(emb, ctx["gallery"], top_k)
    rows_n = ctx["gallery"].shape[0]
    bad, keys, ids, scores = 0, [], [], []
    for key, ans in zip(ledger.key, ledger.answer):
        nb = ans.get("neighbors") if isinstance(ans, dict) else None
        if not nb or len(nb) != top_k:
            bad += 1
            continue
        r = [n["row"] for n in nb]
        s = [n["score"] for n in nb]
        if len(set(r)) != top_k or min(r) < 0 or max(r) >= rows_n or \
                any(a < b - 1e-6 for a, b in zip(s, s[1:])):
            bad += 1
            continue
        keys.append(key)
        ids.append(r)
        scores.append(s)
    numbers = {"bad_answers": float(bad)}
    if keys:
        keys, ids = np.asarray(keys), np.asarray(ids)
        true = retrieval.dots(emb[keys], ctx["gallery"], ids)
        numbers["score_gap"] = float(np.max(np.abs(np.asarray(scores) - true)))
        under = np.maximum(ref_s[keys] - true, 0.0)
        numbers["rank_gap"] = float(np.max(under))
        numbers["rank_gap_mean"] = float(np.mean(np.max(under, axis=1)))
        hit = (ids[:, :, None] == ref_r[keys][:, None, :]).any(-1)
        numbers["recall_miss"] = float(1.0 - hit.mean())
    return numbers


def tally(cell, ledger, win, batches, cap, pool):
    """What one window did, for the readers: answers, batches, the work
    they required (each answered request's own input by the adapter's
    ``forward_flops``, the search by ``harness/counts.py``) and the
    host-clock numbers."""
    mix, g = cell.traffic, cell.traffic["gallery"]
    top_k = mix["engine"]["top_k"]
    ok = {i for i, a in enumerate(ledger.answer)
          if isinstance(a, dict) and len(a.get("neighbors") or ()) == top_k}
    # a request that failed or was refused misses every latency limit
    lat = [(ledger.done[i] - ledger.due[i]) * 1e3 if i in ok else float("inf")
           for i in range(len(ledger.answer))]
    rows = len(ok)
    dim = cell.config["embedding_dim"]
    asked = collections.Counter(ledger.key[i] for i in ok)
    enc = sum(n * cell.adapter.forward_flops(cell.config, pool[key])
              for key, n in asked.items())
    if g["index"] == "ivf":
        s_flops, s_bytes = counts.probe_cost(
            rows, mix["engine"]["probes"], g["rows"] / g["clusters"], dim, 0)
    else:
        s_flops, _ = counts.scan_cost(rows, g["rows"], dim)
        s_bytes = batches * g["rows"] * dim * 4
    qwait = [(q.t_picked - q.t_admitted) / 1e3 for q in ledger.qt if q is not None]
    late = [(s - u) * 1e3 for s, u in zip(ledger.sent, ledger.due)]
    return {"ok": ok, "batches": batches, "rows": rows,
            "in_window": sum(1 for i in ok if ledger.done[i] <= win["t1"]),
            "rows_per_batch": rows / batches if batches else None,
            "required_flops": enc + s_flops,
            "search_flops": s_flops, "search_bytes": s_bytes,
            "queue_wait_ms_p50": traffic.percentile(qwait, 50),
            "gen_late_ms_p99": traffic.percentile(late, 99),
            "p50_ms": traffic.percentile(lat, 50),
            "p95_ms": traffic.percentile(lat, 95), "cap": cap}


def run(cell, devices, args, process_start) -> int:
    """``--trace 0``: one window of ``--seconds``.  ``--trace 1``: a
    short window under the profiler, which the device metrics read.  An
    open loop runs below its knee, where the profiler's own work on the
    host would push it above: there a whole untraced window runs first,
    and the host-clock and queue metrics are read from that one."""
    mix = cell.traffic
    server, ctx = serve_window.build_server(cell, args.seed, bool(args.trace))
    setup_s = time.perf_counter() - process_start
    loop = serve_window.open_window if mix["loop"] == "open" \
        else serve_window.closed_window
    runs, traced = [], {}
    with tracing.CompileCounter() as compiles:
        if not args.trace or mix["loop"] == "open":
            ledger, win = loop(server, ctx, mix, args.seed, args.seconds)
            runs.append((ledger, win, server.replicaset.batches))
        if args.trace:
            seconds = min(args.seconds, mix.get("trace_seconds", 4.0))
            before = server.replicaset.batches
            with tracing.traced(True, cell.name) as traced:
                ledger, win = loop(server, ctx, mix, args.seed + 1, seconds)
            runs.append((ledger, win, server.replicaset.batches - before))
    compiles_after = server.engine.compiles_after_warmup
    server.replicaset.close(drain=True)
    dev = device.device_report(devices)
    tallies = [dict(tally(cell, *r, ctx["cap"], ctx["pool"]), window=dict(r[1], steps=r[2]))
               for r in runs]
    host = tallies[0]
    metrics_all = {
        "setup_s": setup_s,
        "serve_answers_per_s": host["in_window"] / host["window"]["seconds"],
        "serve_p50_ms": host["p50_ms"],
        "serve_p95_ms": host["p95_ms"],
    }
    ctx_m = {
        "cell": cell, "window": host["window"], "serve": host,
        "traced": tallies[-1],
        "compiles_in_window": compiles.count + compiles_after, "device": dev,
        "peaks": None if args.cpu_rehearsal else peaks.peaks_for(dev["kind"]),
    }
    # free the program's side before the reference runs
    gallery, host_params, pool = ctx["gallery"], ctx["host_params"], ctx["pool"]
    server = None
    ctx.clear()
    gc.collect()
    ctx.update(gallery=gallery, host_params=host_params, pool=pool)
    every = types.SimpleNamespace(
        key=[k for r in runs for k in r[0].key],
        answer=[a for r in runs for a in r[0].answer])
    numbers = serve_numbers(every, ctx, cell, mix["engine"]["top_k"])
    numbers["refused"] = float(sum(r[0].refused for r in runs))
    checks, correct = compare.judge(numbers, mix["limits"])
    attempted = len(every.answer)
    return run_train.finish(cell, args, ctx_m, metrics_all, traced, dev, checks,
                            correct, attempted=attempted,
                            failed=attempted - sum(len(t["ok"]) for t in tallies),
                            extra={"numbers": numbers, "host_clock": metrics_all,
                                   "window": host["window"]})
