"""The flat scan's jitted top-k alone, at the benchmark's shapes, on the chip.

Times ``serve.engine._stream_topk`` of whichever tree is first on
``PYTHONPATH`` (so a parent checkout and the working tree are compared by
running this file twice, one process each: a chip belongs to one process)
around ``block_until_ready``, and prints one JSON line a case with the
median and the least of ``--reps`` calls and, where the program returns
it, the scan's own count of turns walked and merged.

Cases (``--shape cell4``: 2,000,000 x 1024, buckets 1 / 8 / 32;
``--shape cell5``: 262,144 x 3840, buckets 1 / 4): real rows 1 .. bucket
in a gallery in random order, the rest of the bucket zero rows as
``_query_bucketed`` pads it; then the same gallery with a trend along
the first query added in place, rising (every turn holds a new best:
every turn merges) and falling (for that query only the first does).

    PYTHONPATH=. python scripts/chip_stream_topk_timing.py --shape cell4
"""

import argparse
import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from npairloss_tpu.serve.engine import EngineConfig, _stream_topk

SHAPES = {
    "cell4": dict(rows=2_000_000, dim=1024, cases=((1, 1), (8, 1), (8, 2), (8, 8),
                                                   (32, 1), (32, 8), (32, 32))),
    "cell5": dict(rows=262_144, dim=3840, cases=((1, 1), (4, 1), (4, 4))),
    "toy": dict(rows=4_000, dim=64, cases=((1, 1), (8, 2))),
}
CHUNK = 131_072


def gallery(rows, dim, seed):
    """(rows, dim) float32 of N(0, 1/dim) entries, filled in place a chunk
    at a time: the whole of it is most of the chip."""
    @functools.partial(jax.jit, donate_argnums=0)
    def fill(buf, key, lo):
        x = jax.random.normal(key, (min(CHUNK, rows), dim), jnp.float32) * dim ** -0.5
        return jax.lax.dynamic_update_slice_in_dim(buf, x, lo, axis=0)

    buf = jnp.zeros((rows, dim), jnp.float32)
    key = jax.random.PRNGKey(seed)
    for i, lo in enumerate(range(0, rows, CHUNK)):
        buf = fill(buf, jax.random.fold_in(key, i), min(lo, rows - min(CHUNK, rows)))
    return buf


@functools.partial(jax.jit, donate_argnums=0)
def add_trend(emb, q0, slope):
    """Row i gains ``slope * i / rows`` of the first query's direction: a
    turn's scores against it then differ from the next turn's by far
    more than the rows' own spread."""
    ramp = jnp.arange(emb.shape[0], dtype=jnp.float32) / emb.shape[0]
    return emb + slope * ramp[:, None] * q0[None, :]


def queries(bucket, real, dim, seed):
    # row i is the same whatever the bucket: row 0 is the trend's direction
    key = jax.random.PRNGKey(seed)
    q = jnp.stack([jax.random.normal(jax.random.fold_in(key, i), (dim,), jnp.float32)
                   for i in range(bucket)])
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    return q.at[real:].set(0.0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="cell4")
    ap.add_argument("--blocks", default=str(EngineConfig().gallery_block),
                    help="comma-separated gallery_block values")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=30)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    shape = SHAPES[args.shape]
    rows, dim, k = shape["rows"], shape["dim"], EngineConfig().top_k
    dev = jax.devices()[0]
    emb = gallery(rows, dim, args.seed)
    valid = jnp.ones((rows,), bool)
    fns = {b: jax.jit(functools.partial(_stream_topk, k=k, block=b, scoring="fp32"))
           for b in (int(x) for x in args.blocks.split(","))}

    def measure(order, cases):
        for bucket, real in cases:
            q = queries(bucket, real, dim, 1000 + args.seed)
            for block, fn in fns.items():
                out = jax.block_until_ready(fn(q, emb, None, valid))  # compiles
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(fn(q, emb, None, valid))
                    times.append(1e3 * (time.perf_counter() - t0))
                scan = [int(x) for x in out[2]] if len(out) > 2 else None
                print(json.dumps({
                    "tag": args.tag, "device": dev.device_kind, "platform": dev.platform,
                    "shape": args.shape, "order": order, "block": block,
                    "bucket": bucket, "real_rows": real,
                    "ms_median": round(statistics.median(times), 4),
                    "ms_min": round(min(times), 4),
                    "turns": scan and scan[0], "turns_merged": scan and scan[1],
                    "top1_row": int(out[1][0, 0]), "top1_score": float(out[0][0, 0]),
                }), flush=True)

    ends = (shape["cases"][0], shape["cases"][-1])
    measure("random", shape["cases"])
    q0 = queries(1, 1, dim, 1000 + args.seed)[0]
    emb = add_trend(emb, q0, 1000.0)
    measure("rising", ends)
    emb = add_trend(emb, q0, -2000.0)
    measure("falling", ends)


if __name__ == "__main__":
    sys.exit(main())
