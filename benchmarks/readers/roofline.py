"""A kernel's share of its roofline, in percent: the least time the chip
could take for the search work the traced window's answers required (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
``harness/counts.py``) over the device time of the named regions.
Returns nothing when the regions ran no operation: never 0."""

from benchmarks.harness import counts


def read(ctx, groups):
    red, serve = ctx.get("trace"), ctx.get("traced")
    if not red or not serve or ctx.get("peaks") is None:
        return None
    seconds = sum(red["group_s"].get(g, 0.0) for g in groups)
    if not seconds or not serve["search_flops"]:
        return None
    least, _bound = counts.roofline_seconds(
        serve["search_flops"], serve["search_bytes"], ctx["peaks"])
    return 100.0 * least / seconds
