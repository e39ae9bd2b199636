"""Device milliseconds, per step or per dispatched batch of the traced
window, of the operations whose ``named_scope`` region starts with one of
``prefixes``.  A prefix is given from the model's root and ends in ``/``
(``GoogLeNetEmbedding/conv2/`` does not take ``conv2_reduce/``, nor a
stem name an inception branch); a backward operation keeps its forward
region (``trace_reduce.region_of`` drops the transform wrappers)."""


def read(ctx, prefixes, per):
    red = ctx.get("trace")
    if not red or not red.get("by_op"):
        return None
    n = ctx["traced"]["window"]["steps"] if per == "step" else ctx["traced"]["batches"]
    # a label is "<region>/<operation>"
    seconds = sum(s for label, s in red["by_op"].items()
                  if (label.rpartition("/")[0] + "/").startswith(tuple(prefixes)))
    if not n or not seconds:
        return None
    return 1e3 * seconds / n
