"""The last line: one JSON object on standard output, and each number
compared beside its limit as the last lines on standard error."""

from __future__ import annotations

import json
import sys


def emit(correct, attempted, failed, metrics, device, checks, breakdown=None,
         extra=None):
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["checks"] = {name: {"value": value, "limit": limit, "ok": ok}
                      for name, value, limit, ok in checks}
    sys.stdout.flush()
    for name, value, limit, ok in checks:
        print(f"check {name}: value {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
