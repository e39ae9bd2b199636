"""Share of the tokens a token engine ran that were padding: (padded -
true) over padded, summed over the program's ``serve/encode`` spans that
END inside the serving window the queue metrics come from
(``ctx["serve"]``).  The spans carry both counts as arguments
(``tokens``, ``padded_tokens``: rows bucket x length bucket).  Nothing
(never 0) without a tracer, without such a span, when the spans carry
no such arguments (a float-input engine; a program from before the
token path), or when the tracer's cap has dropped events."""


def span_args(window, span, keys):
    """{key: sum of that argument} over the process tracer's ``span``
    events that end inside ``window`` (``t0`` .. ``t1`` in
    ``perf_counter`` seconds) and carry every key; None when there is no
    tracer, it dropped events, or no such event is there."""
    from npairloss_tpu.obs import tracing

    tracer = getattr(tracing, "current", lambda: None)()
    if tracer is None or not window or tracer.dropped:
        return None
    lo, hi = tracer.to_us(window["t0"]), tracer.to_us(window["t1"])
    events, _next, _dropped = tracer.events_since(0)
    rows = [ev["args"] for ev in events
            if ev.get("ph") == "X" and ev.get("name") == span
            and lo <= ev["ts"] + ev["dur"] <= hi
            and all(k in ev.get("args", {}) for k in keys)]
    if not rows:
        return None
    return {k: sum(r[k] for r in rows) for k in keys}


def read(ctx, span):
    got = span_args((ctx.get("serve") or {}).get("window"), span,
                    ("tokens", "padded_tokens"))
    if not got or not got["padded_tokens"]:
        return None
    return (got["padded_tokens"] - got["tokens"]) / got["padded_tokens"]
