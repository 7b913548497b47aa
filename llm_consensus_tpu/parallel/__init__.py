"""Parallelism layer: device meshes, sharding specs, and collectives.

This package is the TPU-native replacement for the reference's concurrency
story. The reference fans out goroutines over remote HTTP APIs
(/root/reference/internal/runner/runner.go:60-115); here "parallelism" is
physical: `jax.sharding.Mesh` slices carved out of the chip topology, with
panel models pinned to disjoint slices and the judge TP/EP-sharded over a
bigger one, XLA inserting collectives over ICI.

Modules:
  mesh        — topology: build meshes, carve disjoint per-model slices
  distributed — multi-host: jax.distributed init, hybrid DCN×ICI meshes
  sharding    — PartitionSpec trees for params/caches (TP + EP), shard fns
  pipeline    — GPipe-style pipeline parallelism via shard_map + ppermute
  ring        — ring attention (sequence/context parallelism) via ppermute
"""

from llm_consensus_tpu.parallel.distributed import hybrid_mesh, initialize
from llm_consensus_tpu.parallel.mesh import (
    MeshPlan,
    best_tp,
    carve_slices,
    make_mesh,
    plan_panel,
)
from llm_consensus_tpu.parallel.pipeline import pipeline_forward
from llm_consensus_tpu.parallel.ring import ring_attention
from llm_consensus_tpu.parallel.sharding import (
    cache_specs,
    make_shard_fn,
    param_shardings,
    param_specs,
    shard_pytree,
)

__all__ = [
    "MeshPlan",
    "hybrid_mesh",
    "initialize",
    "best_tp",
    "carve_slices",
    "make_mesh",
    "plan_panel",
    "cache_specs",
    "make_shard_fn",
    "param_shardings",
    "param_specs",
    "pipeline_forward",
    "ring_attention",
    "shard_pytree",
]
