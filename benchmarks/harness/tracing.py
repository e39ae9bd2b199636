"""The traced run's profiler session and compile counter."""

from __future__ import annotations

import contextlib
import os
import shutil

from benchmarks.harness import loader, trace_reduce

WORK_DIR = os.path.join(loader.ROOT, ".bench_work")


class CompileCounter:
    """Counts XLA compilations (JAX's own monitoring events) while open."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.open = 0, False

    def _on(self, event, duration, **_kw):
        if self.open and event == self._EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False


def annotate(name):
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(enabled: bool, tag: str):
    """Profile the block; yields a dict that gets ``trace`` (the loaded
    events) once the block has closed."""
    out = {}
    if not enabled:
        yield out
        return
    import jax.profiler

    path = os.path.join(WORK_DIR, "trace_" + tag)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    # the Python call tracer off: it costs the host more than anything
    # the window does, and the reduction reads none of its events
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=options)
    try:
        with annotate(trace_reduce.WINDOW_SPAN):
            yield out
    finally:
        jax.profiler.stop_trace()
    out["trace"] = trace_reduce.load(path)
    shutil.rmtree(path, ignore_errors=True)
