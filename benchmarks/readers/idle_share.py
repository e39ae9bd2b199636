"""1 - (union of device operation intervals) / traced window, on the
fullest-loaded device."""


def read(ctx):
    red = ctx.get("trace")
    return None if not red else red["idle_share"]
