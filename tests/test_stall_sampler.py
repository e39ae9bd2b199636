"""scripts/stall_sampler.py: rows while the process lives, a file when
it ends.  Stdlib-only, no jax."""

import gzip
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLER = os.path.join(REPO, "scripts", "stall_sampler.py")


def test_the_sampler_follows_a_process_to_its_end(tmp_path):
    out = tmp_path / "rows.json.gz"
    watched = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(0.8)"])
    try:
        # The child stays a zombie until waited for: the sampler must
        # read that as the end, not sample it for ever.
        rc = subprocess.run([sys.executable, SAMPLER, str(watched.pid),
                             str(out)], timeout=30).returncode
    finally:
        watched.wait()
    assert rc == 0
    with gzip.open(out, "rt") as f:
        got = json.load(f)
    assert got["tick"] > 0 and len(got["rows"]) >= 2
    assert all(str(watched.pid) in row["th"] for row in got["rows"])
    gaps = [b["t"] - a["t"] for a, b in zip(got["rows"], got["rows"][1:])]
    assert got["worst_gap_s"] == max(gaps) - 0.25


def test_the_sampler_wants_a_pid_and_a_file():
    done = subprocess.run([sys.executable, SAMPLER], capture_output=True,
                          text=True, timeout=30)
    assert done.returncode == 2 and "Usage:" in done.stderr
