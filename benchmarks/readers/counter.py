"""A count the harness or the program kept: ``ctx[key]`` or, dotted,
``ctx[a][b]``."""


def read(ctx, key):
    node = ctx
    for part in key.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node
