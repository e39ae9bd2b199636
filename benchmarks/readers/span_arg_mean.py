"""Mean of one argument over the program's spans of the given name that
END inside the serving window the queue metrics come from
(``ctx["serve"]``): ``inflight`` over ``serve/batch`` is the share of
batches launched while the one before them was not yet answered.
Nothing (never 0) without a tracer, a window or its batches, without
such a span, when no such span carries the argument (a program from
before it), or when the tracer's cap has dropped events."""


def read(ctx, span, arg):
    from npairloss_tpu.obs import tracing

    tracer = getattr(tracing, "current", lambda: None)()
    serve = ctx.get("serve") or {}
    win, batches = serve.get("window"), serve.get("batches")
    if tracer is None or not win or not batches or tracer.dropped:
        return None
    lo, hi = tracer.to_us(win["t0"]), tracer.to_us(win["t1"])
    events, _next, _dropped = tracer.events_since(0)
    values = [ev["args"][arg] for ev in events
              if ev.get("ph") == "X" and ev.get("name") == span
              and lo <= ev["ts"] + ev["dur"] <= hi
              and arg in ev.get("args", {})]
    if not values:
        return None
    return sum(values) / len(values)
