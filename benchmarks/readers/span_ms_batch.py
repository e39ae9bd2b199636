"""Host milliseconds a dispatched batch spends in the program's own
spans of the given name(s): the events of the process's tracer
(``npairloss_tpu.obs.tracing.current()``) that END inside the serving
window the queue metrics come from (``ctx["serve"]``), summed, over that
window's batches.  Nothing (never 0) without a tracer, without such a
span, or when the tracer's cap has dropped events."""


def read(ctx, span):
    from npairloss_tpu.obs import tracing

    tracer = getattr(tracing, "current", lambda: None)()
    serve = ctx.get("serve") or {}
    win, batches = serve.get("window"), serve.get("batches")
    if tracer is None or not win or not batches or tracer.dropped:
        return None
    names = {span} if isinstance(span, str) else set(span)
    lo, hi = tracer.to_us(win["t0"]), tracer.to_us(win["t1"])
    events, _next, _dropped = tracer.events_since(0)
    durs = [ev["dur"] for ev in events
            if ev.get("ph") == "X" and ev.get("name") in names
            and lo <= ev["ts"] + ev["dur"] <= hi]
    if not durs:
        return None
    return sum(durs) / 1e3 / batches
