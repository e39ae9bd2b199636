"""Device-mesh plumbing for the global negative pool.

The reference's distribution model is one MPI rank per GPU with
MPI_Allgather'd embeddings (npair_multi_class_loss.cu:17-43) and an
MPI_Allreduce of database-side gradients (cu:462-489) — collectives on CPU
buffers, serialized against compute.  Here the same semantics ride the TPU
interconnect: a 1-D ``jax.sharding.Mesh`` over the data-parallel axis, the
loss body wrapped in ``shard_map`` so ``jax.lax.all_gather``/``psum`` become
ICI (or DCN, multi-slice) collectives fused into the step graph by XLA.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from npairloss_tpu.ops.npair_loss import NPairLossConfig, npair_loss_with_aux

DEFAULT_AXIS = "dp"


def data_parallel_mesh(
    devices: Optional[Sequence[jax.Device]] = None, axis: str = DEFAULT_AXIS
) -> Mesh:
    """A 1-D mesh over all (or the given) devices, in process-major
    (ring) order: one ``ppermute`` rotation then crosses the DCN once
    per host boundary — the minimum — instead of on arbitrary hops
    (``parallel.plan.ring_device_order``)."""
    from npairloss_tpu.parallel.plan import ring_device_order

    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(ring_device_order(devices)), (axis,))


def build_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    mp: int = 1,
    axis: str = DEFAULT_AXIS,
    mp_axis: str = "mp",
) -> Mesh:
    """The pod mesh: 1-D data-parallel (``mp=1`` — byte-identical to
    :func:`data_parallel_mesh`), or 2-D ``dp x mp`` when a partition
    ruleset shards parameters.

    The ``mp`` axis is the INNER (fastest-varying) one over the
    process-major device order, so model-parallel groups land on
    adjacent chips of one host whenever ``mp`` divides the per-host
    device count — parameter collectives ride ICI, and only the
    data-parallel axis (batch all_gather, grad all-reduce) ever
    crosses the DCN.  That is the TPU-v4 paper's placement rule
    (PAPERS.md): spend the cheap wires on the chatty axis.
    """
    from npairloss_tpu.parallel.plan import ring_device_order

    devices = ring_device_order(
        list(devices) if devices is not None else jax.devices())
    mp = int(mp) if mp else 1
    if mp <= 1:
        return Mesh(np.array(devices), (axis,))
    if len(devices) % mp:
        raise ValueError(
            f"--mp {mp} does not divide the {len(devices)}-device mesh")
    arr = np.array(devices).reshape(len(devices) // mp, mp)
    return Mesh(arr, (axis, mp_axis))


def mesh_topology(mesh: Mesh, axis: str = DEFAULT_AXIS) -> dict:
    """JSON-able description of a mesh for run manifests (the fleet
    observatory's "what topology produced these streams?" record):
    axes/sizes plus the device→process placement, so an offline reader
    can tell which shards were local to which rank without a live
    backend.

    ``process_count`` prefers the multi-controller runtime's own
    ``jax.process_count()`` when one is initialized, then the declared
    fleet stamp (``NPAIRLOSS_FLEET_PROCESS`` — under that harness every
    device *attribute* claims process 0, so inferring the count from
    per-device ``process_index`` attrs under-reports the fleet), and
    only then the per-device attrs."""
    from npairloss_tpu.obs.fleet.stamp import resolved_process

    devices = list(mesh.devices.flatten())
    attr_count = len({getattr(d, "process_index", 0) for d in devices})
    process_index, resolved_count = resolved_process()
    process_count = max(resolved_count, attr_count)
    return {
        "axis": axis,
        "axes": {str(a): int(s)
                 for a, s in zip(mesh.axis_names, mesh.devices.shape)},
        "devices": len(devices),
        "device_ids": [d.id for d in devices],
        "device_process": [getattr(d, "process_index", 0) for d in devices],
        "process_count": process_count,
        "process_index": process_index,
    }


def shard_batch(mesh: Mesh, batch, axis: str = DEFAULT_AXIS):
    """Place a host batch with its leading dim sharded over ``axis``."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)


def sharded_npair_loss_fn(
    mesh: Mesh,
    cfg: NPairLossConfig = NPairLossConfig(),
    axis: str = DEFAULT_AXIS,
) -> Callable:
    """Build ``f(features, labels) -> (loss, aux)`` running under shard_map.

    ``features``/``labels`` are globally-sharded arrays (leading dim split
    over ``axis``); each shard computes the reference's per-rank loss over the
    all-gathered pool.  Outputs gain a leading per-rank axis of size G —
    ``loss`` comes back as shape (G,) (each MPI rank of the reference reports
    its own loss; their mean is the pod-level monitor).
    """

    def per_shard(features, labels):
        loss, aux = npair_loss_with_aux(features, labels, cfg, axis_name=axis)
        stack = lambda x: jnp.asarray(x)[None]
        return stack(loss), jax.tree_util.tree_map(stack, aux)

    return jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    )
