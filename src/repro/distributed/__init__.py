"""Distribution layer: sharding rules, tiered collectives, pipeline."""

from repro.distributed.collectives import (
    flat_grad_allreduce,
    hierarchical_grad_allreduce,
    make_grad_sync,
)
from repro.distributed.pipeline import pipeline_apply
from repro.distributed.sharding import (
    BASELINE_RULES,
    ShardingRules,
    batch_spec,
    cache_specs,
    mesh_axis_sizes,
    param_shardings,
    param_specs,
)

__all__ = [
    "flat_grad_allreduce", "hierarchical_grad_allreduce", "make_grad_sync",
    "pipeline_apply",
    "BASELINE_RULES", "ShardingRules", "batch_spec", "cache_specs",
    "mesh_axis_sizes", "param_shardings", "param_specs",
]
