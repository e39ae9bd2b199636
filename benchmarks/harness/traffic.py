"""Traffic from the seed.  One general generator reads a mix's data file.

The arrival arithmetic is copied from the program's
``gameday/traffic.py`` (exponential gaps at the local rate; Zipf keys by
bisection on the cumulative harmonic weights), with one change that the
benchmark needs: every seed gets the SAME multiset of gaps and of keys,
drawn once from the mix's own ``canon_seed``, in another order.  A seed
then changes which request meets which neighbour, never how much work a
window holds.
"""

from __future__ import annotations

import bisect
import random


def zipf_keys(count: int, catalog: int, s: float, rng: random.Random):
    acc, cum = 0.0, []
    for k in range(1, catalog + 1):
        acc += 1.0 / (k ** s)
        cum.append(acc)
    return [bisect.bisect_left(cum, rng.random() * acc) for _ in range(count)]


def burst_windows(duration_s, bursts, burst_s):
    """Evenly spaced burst centres, clear of the window's edges."""
    return [(duration_s * (i + 1) / (bursts + 1) - burst_s / 2.0,
             duration_s * (i + 1) / (bursts + 1) + burst_s / 2.0)
            for i in range(bursts)]


def open_loop(mix: dict, seed: int, seconds: float):
    """[(due_s, key)] for an open loop at ``mix['rate_qps']``: Poisson
    arrivals (optionally with burst windows at ``burst_qps``), Zipf keys.
    Due times are exact for the seed; the count is the same for every seed."""
    canon = random.Random(mix.get("canon_seed", 0))
    rate = float(mix["rate_qps"])
    windows = burst_windows(seconds, mix.get("bursts", 0), mix.get("burst_s", 0.0))
    if windows:
        # inhomogeneous: step at the local rate, as gameday does; the
        # schedule is canonical and the seed only re-deals the keys.
        due, t = [], 0.0
        while True:
            local = mix["burst_qps"] if any(a <= t < b for a, b in windows) else rate
            t += canon.expovariate(local)
            if t >= seconds:
                break
            due.append(t)
    else:
        n = int(rate * seconds)
        gaps = [canon.expovariate(rate) for _ in range(n)]
        random.Random(seed).shuffle(gaps)
        scale = seconds * n / (n + 1.0) / sum(gaps) if gaps else 1.0
        due, t = [], 0.0
        for g in gaps:
            t += g * scale
            due.append(t)
    keys = zipf_keys(len(due), mix["pool_images"], mix["zipf_s"], canon)
    random.Random(seed + 1).shuffle(keys)
    return list(zip(due, keys))


def closed_loop(mix: dict, seed: int, count: int):
    """``count`` keys for the callers of a closed loop to take in turn."""
    canon = random.Random(mix.get("canon_seed", 0))
    keys = zipf_keys(count, mix["pool_images"], mix["zipf_s"], canon)
    random.Random(seed + 1).shuffle(keys)
    return keys


def percentile(values, q):
    """Nearest-rank percentile of all the values given (no interpolation,
    no trimming): a tail is the tail of all requests."""
    if not values:
        return None
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, int(-(-q * len(ordered) // 100)) - 1))
    return ordered[idx]
