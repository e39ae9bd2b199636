"""Operations and bytes that the algorithms REQUIRE, from shapes alone:
whatever implements them, and with nothing recomputed counted twice.
A multiply-add is two operations."""

from __future__ import annotations

from benchmarks.reference.googlenet import INCEPTION


def _same(size, stride):
    return -(-size // stride)


def googlenet_forward_flops(image_size=224, in_ch=3):
    """One image through GoogLeNet v1 to pool5 (convolutions only; the
    paper's stem is 7x7/2)."""
    total = 0

    def conv(hw, k, cin, cout):
        nonlocal total
        total += 2 * hw * hw * k * k * cin * cout

    hw = _same(image_size, 2)
    conv(hw, 7, in_ch, 64)
    hw = _same(hw, 2)
    conv(hw, 1, 64, 64)
    conv(hw, 3, 64, 192)
    hw = _same(hw, 2)
    c = 192
    for key, (p1, p3r, p3, p5r, p5, pp) in INCEPTION.items():
        if key in ("4a", "5a"):
            hw = _same(hw, 2)
        conv(hw, 1, c, p1)
        conv(hw, 1, c, p3r)
        conv(hw, 3, p3r, p3)
        conv(hw, 1, c, p5r)
        conv(hw, 5, p5r, p5)
        conv(hw, 1, c, pp)
        c = p1 + p3 + p5 + pp
    return total


def forward_flops(config):
    if config["family"] == "googlenet_v1":
        return googlenet_forward_flops(config["image_size"], config["num_channels"])
    raise KeyError(config["family"])


def train_flops_per_image(config, pool_rows):
    """Forward + backward (twice the forward) of the trunk, plus the
    loss's similarity product and its two gradient products per row."""
    dim = config["embedding_dim"]
    return 3 * forward_flops(config) + 3 * 2 * pool_rows * dim


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
