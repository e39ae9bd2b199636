"""The sweep that fixes a fixed-rate cell's rate.  One process, one
set-up: offered rates on a geometric ladder until completions fall
behind offers or a request is refused; the knee is the highest rung
sustained.  Then repeats of the real window at fractions of the knee,
with the spread of p50 and p95 over the repeats, and one traced slice a
fraction for the device's idle share.  Not part of a benchmark run.

    python3 benchmarks/sweep.py --workload NAME --window 20 [--out FILE]

With ``--knee Q`` the ladder is skipped; ``--seeds a,a,b`` names the
repeats' seeds (one named twice shows what the arrival ORDER does).
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--window", type=float, default=20.0)
    ap.add_argument("--rung-seconds", type=float, default=12.0)
    ap.add_argument("--start", type=float, default=25.0)
    ap.add_argument("--factor", type=float, default=1.5)
    ap.add_argument("--fractions", default="0.5,0.65,0.8")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2500000001)
    ap.add_argument("--knee", type=float, default=None)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness import (device, loader, serve_window, trace_reduce,
                                    tracing, traffic)

    cell = loader.Cell(args.workload)
    if args.cpu_rehearsal:
        cell.config.update(cell.config.get("rehearsal", {}))
        cell.traffic.update(cell.traffic.get("rehearsal", {}))
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    device.require_devices(cell.chips, args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        from npairloss_tpu.pipeline.compile_cache import enable_compile_cache

        enable_compile_cache()
    mix = dict(cell.traffic)
    server, ctx = serve_window.build_server(cell, args.seed, True)

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")

    def one(rate, seconds, seed, traced=False):
        m = dict(mix, rate_qps=rate)
        b0 = server.replicaset.batches
        with tracing.traced(traced, "sweep") as tr:
            ledger, win = serve_window.open_window(server, ctx, m, seed, seconds)
        done = [d for d in ledger.done if d is not None]
        lat = sorted((d - u) * 1e3 for d, u in zip(ledger.done, ledger.due))
        n = len(ledger.answer)
        last_due = max(ledger.due)
        # answers that came back by the time the last request was due,
        # against the offers of the whole rung
        kept_up = sum(1 for d in done if d <= last_due + 0.25) / max(n, 1)
        half = [i for i in range(n) if ledger.due[i] <= win["t0"] + seconds / 2]
        mid_backlog = sum(1 for i in half if ledger.done[i] > win["t0"] + seconds / 2)
        end_backlog = sum(1 for i in range(n) if ledger.done[i] > last_due)
        qwait = [(q.t_picked - q.t_admitted) / 1e3 for q in ledger.qt if q is not None]
        late = [(s - u) * 1e3 for s, u in zip(ledger.sent, ledger.due)]
        batches = server.replicaset.batches - b0
        row = {"rate_qps": rate, "seconds": seconds, "seed": seed, "offered": n,
               "refused": ledger.refused, "kept_up": kept_up,
               "backlog_mid": mid_backlog, "backlog_end": end_backlog,
               "p50_ms": traffic.percentile(lat, 50), "p95_ms": traffic.percentile(lat, 95),
               "p99_ms": traffic.percentile(lat, 99),
               "queue_wait_ms_p50": traffic.percentile(qwait, 50),
               "rows_per_batch": n / batches if batches else None,
               "gen_late_ms_p99": traffic.percentile(late, 99)}
        if traced and tr.get("trace"):
            red = trace_reduce.reduce(tr["trace"], mix.get("trace_groups"))
            if red:
                row["idle_share"] = red["idle_share"]
                row["group_s"] = red["group_s"]
        return row

    knee, rate, k = args.knee, args.start, 0
    while args.knee is None:
        row = one(rate, args.rung_seconds, args.seed + k)
        sustained = (row["refused"] == 0 and row["kept_up"] >= 0.98
                     and row["backlog_end"] <= max(row["backlog_mid"], 64))
        emit(dict(row, phase="ladder", sustained=sustained))
        if not sustained or k > 14:
            break
        knee, rate, k = rate, rate * args.factor, k + 1
    emit({"phase": "knee", "knee_qps": knee})
    if knee is None:
        return
    for frac in [float(x) for x in args.fractions.split(",")]:
        rows = []
        seeds = [int(x) for x in args.seeds.split(",")] if args.seeds \
            else [args.seed + 100 + r for r in range(args.repeats)]
        for seed in seeds:
            row = one(knee * frac, args.window, seed)
            rows.append(row)
            emit(dict(row, phase="repeat", fraction=frac))
        spread = {}
        for key in ("p50_ms", "p95_ms"):
            vals = [r[key] for r in rows]
            spread[key] = (max(vals) - min(vals)) / statistics.median(vals)
        t = {} if args.no_trace else \
            one(knee * frac, min(args.window, 4.0), args.seed + 200, traced=True)
        emit({"phase": "fraction", "fraction": frac, "rate_qps": knee * frac,
              "range_over_median": spread, "idle_share": t.get("idle_share"),
              "group_s": t.get("group_s")})
    server.replicaset.close(drain=True)


if __name__ == "__main__":
    main()
