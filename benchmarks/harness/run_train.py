"""A run of a training cell: set-up, window, reference, result."""

from __future__ import annotations

import gc
import time

import jax

from benchmarks.harness import (compare, device, loader, peaks, result,
                                tracing, train_window)


def run(cell, devices, args, process_start) -> int:
    tr = cell.traffic
    telemetry = None
    if args.trace:
        from npairloss_tpu.obs.run import RunTelemetry

        telemetry = RunTelemetry(tracing.WORK_DIR + "/telemetry", metrics=False)
    solver, feed, host0, prog = train_window.setup(
        cell, devices, args.seed, telemetry)
    seconds = min(args.seconds, tr.get("trace_seconds", 4.0)) if args.trace \
        else args.seconds
    setup_s = time.perf_counter() - process_start
    span0 = telemetry.tracer.num_events if telemetry else 0
    with tracing.CompileCounter() as compiles, \
            tracing.traced(bool(args.trace), cell.name) as traced:
        win = train_window.window(solver, feed, seconds)
    dev = device.device_report(devices)
    rows = tr["identities"] * tr["per_identity"]
    metrics_all = {
        "train_emb_per_s_chip": win["steps"] * rows / win["seconds"] / cell.chips,
        "setup_s": setup_s,
    }
    spans = telemetry.tracer.events_since(span0)[0] if telemetry else []
    inputs, labels = feed.inputs, feed.labels
    # free the program's state before the reference runs
    solver.state = None
    solver = None
    gc.collect()
    ref = train_window.reference_numbers(cell, host0, inputs, labels)
    numbers, notes = compare.training_numbers(prog, ref)
    checks, correct = compare.judge(numbers, tr["limits"])
    correct = correct and win["steps"] > 0
    ctx = {"cell": cell, "window": win, "traced": {"window": win},
           "rows_per_step": rows, "spans": spans,
           "compiles_in_window": compiles.count, "device": dev,
           "peaks": None if args.cpu_rehearsal else peaks.peaks_for(dev["kind"])}
    return finish(cell, args, ctx, metrics_all, traced, dev, checks, correct,
                  attempted=win["steps"], failed=0, extra={"notes": notes, "numbers": numbers})


def finish(cell, args, ctx, metrics_all, traced, dev, checks, correct,
           attempted, failed, extra=None):
    """Shared tail of every runner: choose the line's metrics, read the
    per-layer metrics off the trace, print."""
    from benchmarks.harness import trace_reduce

    # a CPU rehearsal prints counts only: never a device metric's name
    shown = lambda m: not args.cpu_rehearsal or m["source"] == "program_counter"
    breakdown = None
    if args.trace:
        red = trace_reduce.reduce(traced["trace"], cell.traffic.get("trace_groups")) \
            if traced.get("trace") else None
        ctx["trace"] = red
        metrics = {}
        for m in filter(shown, cell.per_layer()):
            spec = loader.metric_spec(m["name"])
            value = loader.reader(spec["reader"])(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if red is not None:
            dev = dict(dev, busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = red["breakdown"]
    else:
        metrics = {m["name"]: {"value": metrics_all[m["name"]], "unit": m["unit"]}
                   for m in filter(shown, cell.end_to_end())}
    result.emit(correct, attempted, failed, metrics, dev, checks, breakdown, extra)
    return 0
