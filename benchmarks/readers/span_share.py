"""Share of the window that the program's host spans of one name cover."""


def read(ctx, span):
    win = ctx["window"]
    if not ctx.get("spans") or not win["seconds"]:
        return None
    total_us = sum(ev.get("dur", 0.0) for ev in ctx["spans"]
                   if ev.get("name") == span and ev.get("ph") == "X")
    return total_us / 1e6 / win["seconds"]
