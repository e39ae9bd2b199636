"""Whole-step share of the chip's peak, in percent: required operations
of the work done in the window (``harness/counts.py`` with the
adapter's ``forward_flops``) over seconds x chips x peak FLOP/s.
``train`` and ``serve_window`` take the host clock's window;
``serve_busy`` takes the traced window's work over the seconds in which
the device ran an operation."""

from benchmarks.harness import counts


def read(ctx, kind="train"):
    if ctx.get("peaks") is None:
        return None
    cell, win = ctx["cell"], ctx["window"]
    if kind == "train":
        rows = ctx["rows_per_step"]
        flops = win["steps"] * rows * counts.train_flops_per_image(
            cell.adapter, cell.config, rows)
        seconds = win["seconds"]
    elif kind == "serve_window":
        flops, seconds = ctx["serve"]["required_flops"], win["seconds"]
    else:
        if not ctx.get("trace"):
            return None
        flops = ctx["traced"]["required_flops"]
        seconds = ctx["trace"]["busy_s_fullest"]
    if not flops or not seconds:
        return None
    return 100.0 * flops / (seconds * cell.chips * ctx["peaks"]["flops_bf16"])
