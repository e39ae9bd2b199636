"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload NAME --seed N --seconds S --trace 0|1

Prints the result as the last line of standard output (see
``harness/result.py``).  Without a TPU it exits non-zero, except under
``--cpu-rehearsal``, which runs the same code at toy size and reports no
device metric.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "npairloss_tpu")):
        sys.exit("the system under test (npairloss_tpu/) is not in this checkout")

    from benchmarks.harness import loader

    cell = loader.Cell(args.workload)
    if args.cpu_rehearsal:
        cell.config.update(cell.config.get("rehearsal", {}))
        cell.traffic.update(cell.traffic.get("rehearsal", {}))
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell.chips > 1:
            flag = f"--xla_force_host_platform_device_count={cell.chips}"
            if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    from benchmarks.harness import device

    devices = device.require_devices(cell.chips, args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        from npairloss_tpu.pipeline.compile_cache import enable_compile_cache

        enable_compile_cache()
    kind = cell.traffic["kind"]
    if kind == "train":
        from benchmarks.harness import run_train as runner
    elif kind == "serve":
        from benchmarks.harness import run_serve as runner
    else:
        sys.exit(f"traffic kind {kind!r} has no window loop")
    return runner.run(cell, devices, args, _PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
