"""Answers that came back inside the window (``ctx[of]["in_window"]``), for
the files-only test: a per-layer metric is a reader file, a metric file
and a manifest entry.  Nothing (never 0) without such a window."""


def read(ctx, of):
    return (ctx.get(of) or {}).get("in_window") or None
