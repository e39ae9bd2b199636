"""Share of the flat scan's turns (a group of gallery blocks) whose merge
ran: turns merged over turns walked, summed over the program's
``serve/topk/scan`` spans
that END inside the serving window the queue metrics come from
(``ctx["serve"]``).  The spans carry both counts as arguments
(``scan_blocks``, ``scan_blocks_merged``: what the jitted top-k returned
beside the answer).  Nothing (never 0) without a tracer, without such a
span or such arguments (an IVF engine; a program whose scan merges every
block and counts none), or when the tracer's cap has dropped events."""

from benchmarks.readers.pad_share import span_args


def read(ctx, span):
    got = span_args((ctx.get("serve") or {}).get("window"), span,
                    ("scan_blocks", "scan_blocks_merged"))
    if not got or not got["scan_blocks"]:
        return None
    return got["scan_blocks_merged"] / got["scan_blocks"]
