"""Distribution: multi-process runtime, device-mesh plumbing +
ring-blockwise negative pooling."""

from jax import shard_map

from npairloss_tpu.parallel.distributed import (
    initialize_distributed,
    process_local_batch,
    process_topology,
)
from npairloss_tpu.parallel.mesh import (
    DEFAULT_AXIS,
    build_mesh,
    data_parallel_mesh,
    mesh_topology,
    shard_batch,
    sharded_npair_loss_fn,
)
from npairloss_tpu.parallel.partition import (
    PartitionRuleError,
    load_partition_rules,
    match_partition_rules,
    match_partition_shardings,
    model_parallel_rules,
    partition_summary,
    partition_table,
    place_tree,
    render_partition_table,
    replicated_rules,
)
from npairloss_tpu.parallel.plan import (
    EnginePlan,
    plan_engine,
    plan_for_mesh,
    ring_device_order,
)
from npairloss_tpu.parallel.ring import (
    ring_npair_loss_and_metrics,
    ring_supported,
)

__all__ = [
    "DEFAULT_AXIS",
    "EnginePlan",
    "PartitionRuleError",
    "build_mesh",
    "data_parallel_mesh",
    "initialize_distributed",
    "load_partition_rules",
    "match_partition_rules",
    "match_partition_shardings",
    "mesh_topology",
    "model_parallel_rules",
    "partition_summary",
    "partition_table",
    "place_tree",
    "plan_engine",
    "plan_for_mesh",
    "process_local_batch",
    "process_topology",
    "render_partition_table",
    "replicated_rules",
    "ring_device_order",
    "shard_batch",
    "sharded_npair_loss_fn",
    "ring_npair_loss_and_metrics",
    "ring_supported",
    "shard_map",
]
