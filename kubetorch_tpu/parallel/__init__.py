"""TPU-native parallelism: device meshes, sharding rules, collectives.

This package is the TPU answer to the reference's parallelism story. The
reference is an orchestrator — it bootstraps torchrun/NCCL env vars and leaves
TP/PP/SP/EP to user code (SURVEY.md §2.7). On TPU, parallelism *is* the
framework: a `MeshSpec` names the axes (pp/dp/fsdp/sp/tp/ep), `ShardingRules`
map logical array axes onto mesh axes, and XLA inserts the ICI/DCN collectives.
"""

from kubetorch_tpu.parallel.mesh import (
    AXIS_ORDER,
    MeshSpec,
    best_spec_for,
    local_mesh,
)
from kubetorch_tpu.parallel.sharding import (
    LOGICAL_AXIS_RULES,
    ShardingRules,
    logical_to_pspec,
    named_sharding,
    shard_constraint,
)

__all__ = [
    "AXIS_ORDER",
    "MeshSpec",
    "best_spec_for",
    "local_mesh",
    "ShardingRules",
    "LOGICAL_AXIS_RULES",
    "logical_to_pspec",
    "named_sharding",
    "shard_constraint",
]
