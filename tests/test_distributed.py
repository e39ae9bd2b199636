"""Distributed parity on a virtual 8-device CPU mesh.

Validates the TPU-native replacements for the reference's MPI collectives
(SURVEY.md §2.3): all_gather negative pooling (cu:17-43), the per-rank loss
over the pod-wide pool (cu:218-388), and the allreduced 0.5/0.5-merged
gradient (cu:462-497) — against the G-rank NumPy oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import make_identity_batch
from npairloss_tpu import MiningMethod, MiningRegion, NPairLossConfig
from npairloss_tpu.ops.npair_loss import npair_loss, npair_loss_with_aux
from npairloss_tpu.parallel import (
    DEFAULT_AXIS,
    data_parallel_mesh,
    shard_batch,
    shard_map,
    sharded_npair_loss_fn,
)
from npairloss_tpu.testing import oracle

G = 8

CFG = NPairLossConfig(  # the shipped config, def.prototxt:137-146
    margin_diff=-0.05,
    identsn=-0.0,
    diffsn=-0.3,
    ap_mining_region=MiningRegion.GLOBAL,
    ap_mining_method=MiningMethod.RELATIVE_HARD,
    an_mining_region=MiningRegion.LOCAL,
    an_mining_method=MiningMethod.HARD,
)


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= G, "conftest must force 8 CPU devices"
    return data_parallel_mesh(jax.devices()[:G])


def _global_batch(rng, num_ids=3, imgs_per_id=2, dim=8):
    feats, labs = make_identity_batch(rng, num_ids, imgs_per_id, dim, num_shards=G)
    return feats, labs, np.concatenate(feats), np.concatenate(labs)


@pytest.mark.slow
def test_forward_parity_vs_oracle(mesh, rng):
    feats, labs, gf, gl = _global_batch(rng)
    want = oracle.forward(feats, labs, CFG)
    fn = jax.jit(sharded_npair_loss_fn(mesh, CFG))
    losses, aux = fn(*shard_batch(mesh, (gf, gl)))
    losses = np.asarray(losses)
    for r in range(G):
        np.testing.assert_allclose(losses[r], want[r].loss, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(aux["sim_exp"])[r], want[r].sim_exp, rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(aux["pos_threshold"])[r], want[r].pos_thr, rtol=1e-6
        )


@pytest.mark.slow
def test_grad_parity_vs_oracle(mesh, rng):
    """Mean-of-rank-losses gradient == per-rank oracle grads / G.

    The reference optimizes each rank's own loss with allreduced db-side
    grads; the JAX equivalent differentiates mean_r(loss_r), whose cotangent
    to each rank's loss is 1/G — so oracle grads (loss_weight=1) divided by G.
    """
    feats, labs, gf, gl = _global_batch(rng)
    res = oracle.forward(feats, labs, CFG)
    # Each rank's loss gets cotangent 1/G; the oracle's allreduce already
    # sums every rank's db-side contribution.
    want = oracle.backward(feats, res, loss_weight=1.0 / G)

    def mean_loss(features, labels):
        loss = npair_loss(features, labels, CFG, axis_name=DEFAULT_AXIS)
        return jax.lax.pmean(loss, DEFAULT_AXIS)

    grad_fn = shard_map(
        jax.grad(mean_loss),
        mesh=mesh,
        in_specs=(P(DEFAULT_AXIS), P(DEFAULT_AXIS)),
        out_specs=P(DEFAULT_AXIS),
    )
    got = np.asarray(jax.jit(grad_fn)(*shard_batch(mesh, (gf, gl))))
    for r in range(G):
        np.testing.assert_allclose(
            got[r * len(labs[0]) : (r + 1) * len(labs[0])],
            want[r],
            rtol=1e-5,
            atol=1e-8,
        )


@pytest.mark.slow
def test_local_mining_sharded_equals_oracle_not_single_device(mesh, rng):
    """G shards != one shard on the concat batch for the *loss* (each rank
    mines per its own query rows), but LOCAL/RAND absolute mining with a
    shared pool means the gathered sim matrix rows must agree with a
    single-device run on the concatenated batch."""
    feats, labs, gf, gl = _global_batch(rng)
    cfg = NPairLossConfig()  # LOCAL/RAND: selection = all non-self pairs
    fn = jax.jit(sharded_npair_loss_fn(mesh, cfg))
    losses, aux = fn(*shard_batch(mesh, (gf, gl)))
    # Single device on the concatenated batch:
    loss1, aux1 = jax.jit(lambda f, l: npair_loss_with_aux(f, l, cfg))(gf, gl)
    # Row blocks of the gathered sim matrix line up rank-by-rank:
    sims = np.concatenate([np.asarray(aux["sim"])[r] for r in range(G)])
    np.testing.assert_allclose(sims, np.asarray(aux1["sim"]), rtol=1e-6)
    # And with selection == all pairs, mean of rank losses == concat loss.
    np.testing.assert_allclose(
        np.asarray(losses).mean(), float(loss1), rtol=1e-5, atol=1e-7
    )


def test_rank_blocks_ordered_like_mpi_allgather(mesh):
    """Gathered rows land at [r*N, (r+1)*N) exactly as MPI_Allgather's
    recvbuf ordering (cu:31-38) — pinned via per-rank labels."""
    n, d = 4, 8
    gf = np.tile(np.eye(d, dtype=np.float32)[:1], (G * n, 1))
    gl = np.arange(G * n, dtype=np.int32)  # all distinct

    def get_total(features, labels):
        tl = jax.lax.all_gather(labels, DEFAULT_AXIS, axis=0, tiled=True)
        return tl[None]

    fn = shard_map(
        get_total, mesh=mesh, in_specs=(P(DEFAULT_AXIS), P(DEFAULT_AXIS)),
        out_specs=P(DEFAULT_AXIS),
    )
    total = np.asarray(jax.jit(fn)(*shard_batch(mesh, (gf, gl))))
    for r in range(G):
        np.testing.assert_array_equal(total[r], gl)


def test_parallel_imports_clean_under_deprecation_errors():
    """The package speaks the installed JAX: importing it with
    DeprecationWarning promoted to an error (a fresh interpreter, so
    nothing is cached) must succeed — no jax.lax.pvary, no
    experimental.shard_map."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c",
         "import npairloss_tpu.parallel, npairloss_tpu.parallel.ring"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
