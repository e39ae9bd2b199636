"""Traffic from the seed, and timing from when a request was due."""

import bench_path  # noqa: F401  (repo root on sys.path)

import concurrent.futures
import gc
import json
import os
import threading
import time

import pytest

from bench_drive import SCRATCH
from benchmarks.harness import serve_window, traffic

MIX = {"rate_qps": 200.0, "pool_images": 16, "zipf_s": 1.1, "canon_seed": 24}


def test_same_seed_same_schedule():
    a = traffic.open_loop(MIX, 2**31 + 7, 2.0)
    assert a == traffic.open_loop(MIX, 2**31 + 7, 2.0)
    assert a != traffic.open_loop(MIX, 2**31 + 8, 2.0)


def test_every_seed_gets_the_same_work():
    a = traffic.open_loop(MIX, 1, 2.0)
    b = traffic.open_loop(MIX, 99, 2.0)
    assert len(a) == len(b) == 400
    assert sorted(k for _, k in a) == sorted(k for _, k in b)
    gaps = lambda plan: sorted(round(y[0] - x[0], 9) for x, y in zip(plan, plan[1:]))
    # the same multiset of gaps in another order (the first gap aside)
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 0.05
    assert 0.0 < a[0][0] and a[-1][0] < 2.0


def test_closed_loop_keys_are_a_permutation():
    mix = dict(MIX, callers=4)
    assert sorted(traffic.closed_loop(mix, 3, 50)) == sorted(traffic.closed_loop(mix, 4, 50))


def test_percentile_is_nearest_rank_of_all():
    vals = list(range(1, 101))
    assert traffic.percentile(vals, 50) == 50 and traffic.percentile(vals, 95) == 95
    assert traffic.percentile([5.0], 95) == 5.0 and traffic.percentile([], 50) is None


class StallingServer:
    """Stand-in for the served system: one worker answers in order, 1 ms a
    request, and sleeps ``stall`` seconds once in the middle."""

    def __init__(self, stall, at):
        self.q, self.stall, self.at, self.n = [], stall, at, 0
        self.cv = threading.Condition()
        self.stop = False
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    def submit(self, rec):
        fut = concurrent.futures.Future()
        with self.cv:
            self.q.append((rec, fut))
            self.cv.notify()
        return fut, time.perf_counter()

    def _work(self):
        while True:
            with self.cv:
                while not self.q and not self.stop:
                    self.cv.wait(0.05)
                if self.stop and not self.q:
                    return
                rec, fut = self.q.pop(0)
            self.n += 1
            if self.n == self.at:
                time.sleep(self.stall)
            time.sleep(0.001)
            fut.set_result({"id": rec["id"], "neighbors": [{}] * 10})


def _window(stall, at=60):
    server = StallingServer(stall, at=at)
    ctx = {"pool": list(range(16)), "qtracer": None}
    ledger, win = serve_window.open_window(server, ctx, MIX, 5, 1.0)
    server.stop = True
    lat = [(d - u) * 1e3 for d, u in zip(ledger.done, ledger.due)]
    in_time = sum(1 for d in ledger.done if d <= win["t0"] + 1.0)
    return traffic.percentile(lat, 95), in_time / 1.0, lat, win


def test_a_stall_raises_p95_and_lowers_the_rate():
    calm_p95, calm_rate, _, _ = _window(0.0)
    p95, rate, lat, _ = _window(0.6)
    # requests that were DUE during the stall wait it out: timed from when
    # they were due, the tail shows it; timed from when they were sent
    # by a generator that stalled with the server, it would not.
    assert p95 > calm_p95 + 300.0
    assert max(lat) >= 550.0
    assert rate < calm_rate - 30


def test_an_open_window_runs_to_its_last_answer():
    """All the work over all the time: a server that falls behind near
    the close stretches the window's seconds, so the rate of answers
    falls; one that keeps up closes with its time."""
    _, _, lat, calm = _window(0.0)
    assert 1.0 <= calm["seconds"] < 1.1 and calm["offered"] == len(lat) == 200
    _, _, lat, late = _window(0.6, at=190)
    assert late["seconds"] > 1.4 and late["t1"] - late["t0"] == late["seconds"]
    assert len(lat) / late["seconds"] < 200 / 1.3


@pytest.mark.parametrize("chunk", [16384, 64, 7])
def test_a_closed_window_runs_to_the_clock_whatever_the_server_sustains(monkeypatch, chunk):
    """The scratch family's closed mix (4 callers) against a server of
    ~1,000 answers/s: the callers run until the clock and the window is
    ``seconds`` and the drain of what was in flight, whether its records
    were all made before it (one chunk of 16,384) or most of them inside
    it (chunks of 64 or 7); every chunk is the same keys in an order of
    its own."""
    with open(os.path.join(SCRATCH, "traffic", "scratch_closed_4.json")) as f:
        mix = json.load(f)
    monkeypatch.setattr(serve_window, "_CHUNK", chunk)
    server = StallingServer(0.0, at=-1)
    ctx = {"pool": list(range(mix["pool_images"])), "qtracer": None}
    seed = 2**31 + 11
    try:
        ledger, win = serve_window.closed_window(server, ctx, mix, seed, 0.4)
    finally:
        gc.unfreeze()
        server.stop = True
    assert 0.4 <= win["seconds"] < 0.5 and win["offered"] > 100 > mix["callers"]
    assert len(ledger.answer) == len(ledger.key) == win["offered"] and None not in ledger.done
    assert [a["id"] for a in ledger.answer] == list(range(win["offered"]))
    chunks = [traffic.closed_loop(mix, seed + lo, chunk)
              for lo in range(0, max(win["offered"], 2 * chunk), chunk)]
    assert ledger.key == sum(chunks, [])[:win["offered"]]
    assert sorted(chunks[0]) == sorted(chunks[1]) and (chunk < 64 or chunks[0] != chunks[1])
