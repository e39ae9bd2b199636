#!/usr/bin/env python
"""Sample a process's threads four times a second until it ends.

Run beside a benchmark run on the chip machine to tell a stall of the
program from a stall of the machine: this process only sleeps, so a gap
in ITS rows at the moment the server's window stalls means the whole
sandbox stood still (PERF.md section 6, PR 31 finding 3; section 7,
first).  Stdlib and /proc only; never imports jax, so it cannot take
the chip.

Usage: stall_sampler.py PID OUT.json.gz

OUT holds ``tick`` (clock ticks a second), ``rows`` (wall time, load
average, the host's cpu counters, cpu pressure, and state / utime /
stime of every thread of PID) and ``worst_gap_s`` (the longest this
sampler itself slept beyond its 0.25 s, over the WHOLE run: a
benchmark's set-up alone holds gaps of 4-5 s on the chip machine, so
for a measured window compare the rows' ``t`` with the window's).
"""

import gzip
import json
import os
import sys
import time

PERIOD_S = 0.25


def _read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def threads(pid):
    """{tid: [name, state, utime, stime]} in ticks; None once PID is gone."""
    try:
        tids = os.listdir(f"/proc/{pid}/task")
    except OSError:
        return None
    got = {}
    for tid in tids:
        stat = _read(f"/proc/{pid}/task/{tid}/stat")
        if stat is None:
            continue
        # The name may hold spaces and parentheses: cut at the last ")".
        name = stat[stat.index("(") + 1:stat.rindex(")")]
        fields = stat[stat.rindex(")") + 2:].split()
        got[tid] = [name, fields[0], int(fields[11]), int(fields[12])]
    if any(state != "Z" for _, state, _, _ in got.values()):
        return got
    return None


def sample(pid):
    rows = []
    while True:
        th = threads(pid)
        if th is None:
            return rows
        rows.append({
            "t": time.time(),
            "load": (_read("/proc/loadavg") or "").split()[:3],
            # user nice system idle iowait irq softirq steal
            "cpu": (_read("/proc/stat") or "\n").splitlines()[0].split()[1:9],
            "psi": (_read("/proc/pressure/cpu") or "\n").splitlines()[0]
            or None,
            "th": th,
        })
        time.sleep(PERIOD_S)


def main(argv):
    if len(argv) != 3:
        sys.stderr.write(__doc__)
        return 2
    rows = sample(int(argv[1]))
    gaps = [b["t"] - a["t"] - PERIOD_S for a, b in zip(rows, rows[1:])]
    with gzip.open(argv[2], "wt") as f:
        json.dump({"tick": os.sysconf("SC_CLK_TCK"), "rows": rows,
                   "worst_gap_s": max(gaps, default=0.0)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
