"""Two sets of runs of a cell, the same seeds in both, and the spread of
every end-to-end metric by the contract's rule: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median, the wider of the two sets.  The parent stays off
JAX; every run is a process of its own.  Not part of a benchmark run.

    python3 benchmarks/spreads.py --workload NAME [--runs 6] [--traced 3]
        [--seconds S] [--out FILE]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--first-seed", type=int, default=2200000033)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    seconds = args.seconds or man["run_seconds"]
    rows = []

    def one(seed, trace, label):
        t0 = time.time()
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             "--workload", args.workload, "--seed", str(seed),
             "--seconds", str(seconds), "--trace", str(trace)],
            capture_output=True, text=True, cwd=ROOT)
        lines = p.stdout.strip().splitlines()
        row = {"workload": args.workload, "set": label, "seed": seed, "trace": trace,
               "rc": p.returncode, "wall_s": time.time() - t0,
               "line": json.loads(lines[-1]) if p.returncode == 0 and lines else None,
               "stderr_tail": p.stderr.strip().splitlines()[-8:]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        return row

    seeds = [args.first_seed + 104729 * k for k in range(args.runs)]
    for label in ("A", "B"):
        for seed in seeds:
            one(seed, 0, label)
    for k in range(args.traced):
        one(args.first_seed + 104729 * (args.runs + k), 1, "T")
    summary = {"workload": args.workload, "seconds": seconds, "spread": {}, "median": {}}
    names = {n for r in rows if r["line"] and not r["trace"] for n in r["line"]["metrics"]}
    for name in sorted(names):
        per_set = {}
        for label in ("A", "B"):
            vals = [r["line"]["metrics"][name]["value"] for r in rows
                    if r["set"] == label and r["line"]]
            if name == "setup_s":
                vals = vals[1:] if label == "A" else vals  # the first run compiles
            if len(vals) >= 2:
                per_set[label] = (spread(vals), statistics.median(vals))
        if per_set:
            summary["spread"][name] = max(v[0] for v in per_set.values())
            summary["median"][name] = {k: v[1] for k, v in per_set.items()}
    summary["all_correct"] = all(r["line"] and r["line"]["correct"] for r in rows)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(summary) + "\n")


if __name__ == "__main__":
    main()
