"""The chunked gated-delta-rule scan's share of its roofline, in percent:
the least time the chip could take for what the recurrence REQUIRES of
the traced window's true tokens in every linear layer (the adapter's
``gated_delta_cost``: the recurrence's operations, and q, k, v, g, beta
read once and o written once in the compute type; the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the device
time of the ``named_scope`` regions under ``prefixes`` (each layer's
``gdn/scan/``).  The true tokens are the ``tokens`` arguments of the
program's ``serve/encode`` spans that end inside the traced window:
padding is device time and no required work.  Nothing (never 0) when the
regions ran no operation, the spans carry no tokens or the adapter has
no such cost."""

from benchmarks.harness import counts
from benchmarks.readers.pad_share import span_args


def read(ctx, span, prefixes):
    red, traced = ctx.get("trace"), ctx.get("traced") or {}
    cost = getattr(ctx["cell"].adapter, "gated_delta_cost", None)
    if not red or not red.get("by_op") or cost is None or ctx.get("peaks") is None:
        return None
    seconds = sum(s for label, s in red["by_op"].items()
                  if (label.rpartition("/")[0] + "/").startswith(tuple(prefixes)))
    got = span_args(traced.get("window"), span, ("tokens",))
    if not seconds or not got or not got["tokens"]:
        return None
    flops, bytes_ = cost(ctx["cell"].config, got["tokens"])
    least, _bound = counts.roofline_seconds(flops, bytes_, ctx["peaks"])
    return 100.0 * least / seconds
