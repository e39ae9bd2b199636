"""Operations and bytes that the algorithms REQUIRE, from shapes alone:
whatever implements them, and with nothing recomputed counted twice.
A multiply-add is two operations.  What one input's forward pass
requires is the configuration's adapter's to say (``forward_flops``)."""

from __future__ import annotations


def train_flops_per_image(adapter, config, pool_rows):
    """Forward + backward (twice the forward) of the trunk, plus the
    loss's similarity product and its two gradient products per row."""
    dim = config["embedding_dim"]
    return 3 * adapter.forward_flops(config) + 3 * 2 * pool_rows * dim


def probe_cost(batch, probes, cap, dim, clusters, bytes_per=4):
    """IVF probe of ``batch`` queries: the centroid scan plus ``probes``
    clusters of ``cap`` slab rows each a query."""
    flops = 2 * batch * clusters * dim + 2 * batch * probes * cap * dim
    bytes_ = (clusters * dim + batch * probes * cap * dim) * bytes_per
    return flops, bytes_


def scan_cost(batch, rows, dim, bytes_per=4):
    """Exact scan: the gallery read once a batch, 2 B N D operations."""
    return 2 * batch * rows * dim, rows * dim * bytes_per


def roofline_seconds(flops, bytes_, peaks):
    """(least seconds, which bound): the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    t_f = flops / peaks["flops_bf16"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
