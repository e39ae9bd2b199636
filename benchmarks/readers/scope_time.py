"""Device milliseconds of one group of ``named_scope`` regions (see the
traffic file's ``trace_groups``), per step or per dispatched batch of
the traced window."""


def read(ctx, group, per):
    red = ctx.get("trace")
    if not red or group not in red["group_s"]:
        return None
    n = ctx["traced"]["window"]["steps"] if per == "step" else ctx["traced"]["batches"]
    seconds = red["group_s"][group]
    if not n or not seconds:
        return None
    return 1e3 * seconds / n
